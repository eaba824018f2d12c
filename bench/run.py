"""Run one cell of the on-chip benchmark once and print its result line.

    python bench/run.py --workload logreg_higgs.newton --seed 7 --seconds 10 --trace 0

Runs from the root of a checkout that holds ``src/repro`` (the system under
test), ``BENCHMARK.json`` and ``bench/``, in this one process, on the chips
of the machine it is started on.  With ``--trace 0`` the result line carries
the cell's end-to-end metrics; with ``--trace 1`` the window runs under the
JAX profiler and the line carries the per-layer metrics.  The last line of
standard output is the result; the last lines of standard error give each
compared number beside its limit.  The exit code is 0 only for a correct
run; 2, with no result line, where JAX's first device is not a TPU, there
are fewer devices than the cell asks for, the device is not in
``bench/peaks.json`` or the checkout holds no ``src/repro``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # JAX's persistent compilation cache lives at one fixed path inside the
    # checkout, and the program's helper takes it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import harness

    try:
        cell = harness.Cell.load(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"unknown cell {args.workload!r}: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = harness.load_peaks(ROOT, devices[0].device_kind)
        import repro
        from repro.launch.persistent_cache import enable_persistent_cache
    except (KeyError, ImportError) as e:
        print(f"cannot run: {e}", file=sys.stderr)
        return 2
    if not Path(repro.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"repro was imported from {repro.__file__}, not from this "
              "checkout", file=sys.stderr)
        return 2
    enable_persistent_cache()
    result, checks = harness.run_cell(ROOT, args.workload, args.seed,
                                      args.seconds, bool(args.trace), T_START,
                                      peaks)
    harness.report(result, checks)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
