"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
                                            [--pipeline] [--json PATH]
    PYTHONPATH=src python -m benchmarks.run --smoke --json smoke.json

Emits ``name,us_per_call,derived`` CSV (paper timing protocol: repeats with
best/worst dropped).  ``--pipeline`` runs every suite with the pipelined
(queued, overlap-aware) executor instead of eager sync dispatch — the
sync-vs-pipelined x scheduler ablation is this one flag.  ``--smoke`` runs a
tiny-grid subset (CI's bench-smoke job) and ``--json`` writes the rows plus
dispatch counts as a machine-readable artifact so per-PR regressions in
n_rfc/makespan are visible.  The roofline section reads the dry-run artifact
(benchmarks/artifacts/dryrun.jsonl) produced by ``repro.launch.dryrun``.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.launch.persistent_cache import enable_persistent_cache

from . import (
    bench_bounds,
    bench_calibration,
    bench_chaos,
    bench_serving,
    bench_datasci,
    bench_dgemm,
    bench_linalg,
    bench_logreg,
    bench_memory,
    bench_micro,
    bench_overhead,
    bench_qr,
    bench_roofline,
    bench_tensor,
    bench_trace,
    common,
)
from .common import header

SUITES = {
    "micro": bench_micro,        # Fig. 9
    "overhead": bench_overhead,  # Fig. 8
    "dgemm": bench_dgemm,        # Fig. 10 / Table 2
    "qr": bench_qr,              # Fig. 11 / 12a
    "linalg": bench_linalg,      # §8 comm-avoiding Cholesky/rSVD + ratios
    "tensor": bench_tensor,      # Fig. 13
    "logreg": bench_logreg,      # Fig. 12b / 14 / 15
    "datasci": bench_datasci,    # Table 3 / Fig. 16
    "bounds": bench_bounds,      # Appendix A
    "serving": bench_serving,    # beyond-paper: continuous batching
    "roofline": bench_roofline,  # §Roofline (reads dry-run artifact)
    "chaos": bench_chaos,        # beyond-paper: fault-injection robustness
    "memory": bench_memory,      # beyond-paper: budgets + bounded recovery
    "trace": bench_trace,        # beyond-paper: flight recorder + crit path
    "calibration": bench_calibration,  # beyond-paper: measured-cost fit +
                                       # observed-load controller
}


def _write_json(path: str, payload: dict) -> None:
    payload["rows"] = [
        dict(zip(("name", "us_per_call", "derived"), r.split(",", 2)))
        for r in common.ROWS
    ]
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"# wrote {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale repeats")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined executor (queued dispatch, overlap drain)")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax", "pallas"),
                    help="block-kernel backend for measured contexts "
                         "(repro.backend); each runs at its natural dtype — "
                         "f64 numpy reference vs f32 compiled jax/pallas")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-grid CI subset (micro pipeline ablation)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact")
    args = ap.parse_args()
    enable_persistent_cache()
    common.set_pipeline(args.pipeline)
    common.set_backend(args.backend)
    meta = {"pipeline": args.pipeline, "smoke": args.smoke,
            "backend": args.backend}
    t0 = time.time()
    if args.smoke:
        smoke = bench_micro.smoke()
        print(json.dumps(smoke, indent=2, default=float))
        # dispatch-count regression gate: the logreg graph's RFC count is a
        # stable function of the grid; flag drift loudly in the CI log
        for sched, row in smoke["pipeline_ablation"].items():
            print(f"# smoke n_rfc[{sched}]={row['n_rfc']} "
                  f"overlap={row['overlap_speedup']:.3f}x", flush=True)
        pc = smoke["plan_cache"]
        print(f"# smoke plan_cache sched_overhead_speedup="
              f"{pc['overhead_speedup']:.2f}x hit_rate={pc['hit_rate']:.3f} "
              f"(cold={pc['off']['sched_overhead_s'] * 1e3:.1f}ms "
              f"cached={pc['on']['sched_overhead_s'] * 1e3:.1f}ms)", flush=True)
        rs = smoke["reshard"]
        print(f"# smoke reshard moved={rs['reshard_moved']:.0f} "
              f"naive={rs['naive_moved']:.0f} "
              f"cpals moved={rs['cpals_reshard_moved']:.0f} "
              f"naive={rs['cpals_naive_moved']:.0f}", flush=True)
        be = smoke["backend"]
        fc = be["fused_chain"]
        print(f"# smoke backend jax add={be['jax']['measured_add_us']:.0f}us "
              f"numpy add={be['numpy']['measured_add_us']:.0f}us "
              f"compile_hit_rate={be['jax']['compile_hit_rate']:.3f} "
              f"fused_dispatches={fc['fused_dispatches']} "
              f"interp_dispatches={fc['interp_dispatches']}", flush=True)
        ch = smoke["chaos"]
        print(f"# smoke chaos ratio={ch['makespan_ratio']:.3f} "
              f"identical={ch['identical']} "
              f"deterministic={ch['deterministic']} "
              f"retries={ch['chaos_retries']} "
              f"replayed={ch['chaos_blocks_replayed']} "
              f"spec_wins={ch['chaos_spec_wins']}", flush=True)
        mem = smoke["memory"]
        print(f"# smoke memory gc_peak_ratio={mem['gc']['gc_peak_ratio']:.2f} "
              f"budget_violations="
              f"{sum(x.get('violations', 0) for x in mem['budget'].values())} "
              f"recovery_depth_ratio={mem['recovery']['depth_ratio']:.2f} "
              f"oom_ratio={mem['oom']['makespan_ratio']:.3f} "
              f"oom_events={mem['oom']['mem_oom_events']}", flush=True)
        tr = smoke["trace"]
        print(f"# smoke trace overhead={tr['overhead_ratio']:.3f}x "
              f"clocks_equal={tr['makespan_pipelined_equal']} "
              f"bit_identical={tr['bit_identical']} "
              f"chaos_top_stall={tr['chaos']['top_stall']} "
              f"chaos_total_pct={tr['chaos']['decomposition_total_pct']:.2f}",
              flush=True)
        if args.json:
            _write_json(args.json, {**meta, "smoke_result": smoke})
        print(f"# total {time.time() - t0:.1f}s", flush=True)
        return
    header()
    for name, mod in SUITES.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            mod.run(quick=not args.full)
        except Exception as ex:  # keep the suite going; record the failure
            print(f"{name}.ERROR,0.0,{type(ex).__name__}:{ex}", flush=True)
    if args.json:
        _write_json(args.json, meta)
    print(f"# total {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
