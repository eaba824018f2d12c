"""Readings that a cell's limits are set from, over many seeds in one process.

    python bench/limits.py --workload logreg_higgs.newton --seeds 1-12 --control 1-3

For each seed this makes the cell's inputs, runs its warm-up job and one job
more through the cell's own job code and sizes, and compares them with the
plain reference, as a benchmark run does after its window.  For the seeds
of ``--control`` it also puts the control in the program's place (the
reference in the precision below the configuration's) and sends it through
the same comparison and verdict.  It prints one JSON line per seed, then the
largest reading of the program and the smallest of the control for each
compared number, and writes them to ``--out`` when given.  It exits 1 where
a program run is not correct or a control run is.  Benchmark runs do not
call it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_list(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True,
                    help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--control", type=seed_list, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness
    from repro.launch.persistent_cache import enable_persistent_cache

    enable_persistent_cache()
    cell = harness.Cell.load(ROOT, args.workload)
    traffic = cell.workload["traffic"]
    program, control = {}, {}
    verdicts = {"program": [], "control": []}
    for seed in args.seeds:
        t0 = time.perf_counter()
        job = cell.kind.setup(cell.config, traffic, seed)
        records = [job.run() for _ in range(traffic["warmup_jobs"] + 1)]
        t1 = time.perf_counter()
        job.collect()
        checks, failed = job.check(records[-1:])
        t2 = time.perf_counter()
        line = {"seed": seed, "failed": failed,
                "correct": harness.is_correct(checks, failed),
                "setup_and_jobs_s": t1 - t0, "reference_s": t2 - t1,
                "program": {c.name: c.value for c in checks},
                "records": [{k: v for k, v in r.items() if k not in ("beta", "rows")}
                            for r in records]}
        verdicts["program"].append(line["correct"])
        for c in checks:
            program[c.name] = max(program.get(c.name, c.value), c.value)
        if seed in args.control:
            checks, failed = job.check(job.control())
            line["control"] = {c.name: c.value for c in checks}
            line["control_correct"] = harness.is_correct(checks, failed)
            line["control_s"] = time.perf_counter() - t2
            verdicts["control"].append(line["control_correct"])
            for c in checks:
                control[c.name] = min(control.get(c.name, c.value), c.value)
        print(json.dumps(line), flush=True)
        del job
    summary = {"workload": args.workload, "seeds": len(args.seeds),
               "program_max": program, "control_min": control,
               "program_correct": sum(verdicts["program"]),
               "control_correct": sum(verdicts["control"])}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary) + "\n")
    return 0 if all(verdicts["program"]) and not any(verdicts["control"]) else 1


if __name__ == "__main__":
    sys.exit(main())
