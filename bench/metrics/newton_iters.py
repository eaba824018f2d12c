"""newton_iters: Newton iterations per fit (``FitResult.iterations``), as a
mean over the window's fits."""


def read(run):
    return run.per_job("iterations")
