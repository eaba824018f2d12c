"""setup_s: seconds from the process's start to the first timed job: making
the inputs, loading them into the library, compiling and the warm-up job."""


def read(run):
    return run.setup_s
