"""Deterministic floors of the block runtime: counts, simulated makespans, bit
identity and determinism that no other test asserts at these thresholds.

Each case runs the configuration its threshold was set on, so a case that
fails here is a change in what the runtime does, not timer noise: nothing
below reads a wall clock.  Correctness of each mechanism at other sizes is
in the module's own test file (``test_chaos``, ``test_memory``,
``test_calibration``, ``test_obs``, ``test_plan_cache``, ``test_linalg_ca``).
"""
import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec, FlightRecorder
from repro.launch.chaos import run_chaos_scenario
from repro.launch.workloads import cpals_loop, logreg_newton_loop

# the composed chaos scenario: 8 nodes, one dies, two straggle 4x, and 2 % of
# dispatches fail transiently
CHAOS = dict(nodes=8, workers=2, backend="numpy", iters=3, d=32,
             fail_nodes=1, stragglers=2, slowdown=4.0, fault_prob=0.02)


def plan_cache_hit_rate(tmp_path):
    """Ten Newton iterations replay their plans: at least half the computes
    hit, and the scheduler's stats carry the plan-cache timings."""
    ctx = ArrayContext(cluster=ClusterSpec(16, 4), node_grid=(16, 1),
                       backend="sim", seed=0, plan_cache=True)
    logreg_newton_loop(ctx, n=1 << 15, d=32, q=64, iters=10)
    ctx.flush()
    st = ctx.sched_stats
    assert st.hit_rate() >= 0.5
    stats = st.as_dict()
    for key in ("sched_overhead_s", "dispatch_s", "plan_hits", "plan_misses",
                "fingerprint_s"):
        assert key in stats


def jax_compile_cache_hits(tmp_path):
    """A block op repeated on the jax backend reuses its compiled kernels."""
    ctx = ArrayContext(cluster=ClusterSpec(2, 2), node_grid=(2, 1),
                       backend="jax")
    X = ctx.random((1 << 10, 64), grid=(64, 1))
    Y = ctx.random((1 << 10, 64), grid=(64, 1))
    for _ in range(4):
        (X + Y).compute().wait()
    assert ctx.loads()["compile_hit_rate"] > 0.5


def chaos_degraded_makespan(tmp_path):
    """Faults cost at most 1.5x the fault-free makespan, change no bits, and
    the retry and replay paths both ran."""
    r = run_chaos_scenario(**CHAOS)
    assert r["identical"] and r["deterministic"]
    assert r["makespan_ratio"] <= 1.5
    assert r["chaos_retries"] > 0
    assert r["chaos_blocks_replayed"] > 0


def traced_chaos_attribution(tmp_path):
    """With the flight recorder on, the chaos scenario keeps its bits and its
    determinism, and the critical path accounts for the whole makespan."""
    r = run_chaos_scenario(**CHAOS, trace_path=str(tmp_path / "chaos.json"))
    assert r["identical"] and r["deterministic"]
    assert abs(r["trace"]["decomposition_total_pct"] - 100.0) <= 1.0
    assert r["trace"]["top_stall"] != ""


def oom_backpressure_makespan(tmp_path):
    """A budget at 0.6x the peak, halved on node 0 mid-run: backpressure
    costs at most 2x the unbudgeted makespan and the budget is never
    exceeded."""
    r = run_chaos_scenario(nodes=8, workers=2, backend="numpy", iters=3, d=32,
                           fail_nodes=0, stragglers=0, slowdown=1.0,
                           fault_prob=0.0, mem_budget=0.6, oom_at=0.5)
    assert r["identical"] and r["deterministic"]
    assert r["makespan_ratio"] <= 2.0
    assert r["mem_oom_events"] >= 1
    assert r["mem_violations"] == 0


def controller_grow_shrink(tmp_path):
    """The observed-load controller resizes the cluster on its own at least
    once, within 2x the fault-free makespan."""
    r = run_chaos_scenario(**CHAOS, controller=True)
    assert r["identical"] and r["deterministic"]
    assert sum(a["kind"] in ("grow", "shrink")
               for a in r["controller_actions"]) >= 1
    assert r["makespan_ratio"] <= 2.0


def budgeted_cpals(tmp_path):
    """CP-ALS under a budget of 0.6x its peak: the budget evicts, is never
    exceeded, and the factors keep their bits."""
    def run(**kw):
        ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                           backend="numpy", pipeline=True, **kw)
        f0 = cpals_loop(ctx, dim=16, rank=8, q=4, iters=2, reset_loads=False)
        ctx.flush()
        return f0.to_numpy(), ctx.executor.memory.stats

    ref, st_ref = run()
    got, st = run(mem_capacity=max(0.6 * st_ref.peak_live_elements, 1.0))
    assert st.violations == 0
    assert st.gc_freed_blocks + st.spills + st.recompute_drops > 0
    assert got.tobytes() == ref.tobytes()


def linalg_makespans(tmp_path):
    """TSQR, Cholesky and rSVD each place work on the simulated clocks."""
    from repro.linalg import cholesky, rsvd, tsqr_indirect

    def makespan(workers, shape, grid, op):
        ctx = ArrayContext(cluster=ClusterSpec(4, workers), node_grid=(4, 1),
                           backend="sim")
        A = ctx.random(shape, grid=grid)
        ctx.reset_loads()
        op(ctx, A)
        return ctx.loads()["makespan"]

    assert makespan(4, (16 * 1024, 64), (16, 1), tsqr_indirect) > 0
    assert makespan(2, (256, 256), (4, 4), cholesky) > 0
    assert makespan(2, (8 * 256, 32), (8, 1),
                    lambda c, A: rsvd(c, A, rank=8, oversample=8,
                                      power_iters=1)) > 0


def traced_newton_ring(tmp_path):
    """The recorder's default ring holds a traced Newton run whole, and its
    critical path accounts for the whole makespan."""
    from repro.obs import analyze

    ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                       backend="sim", pipeline=True, seed=0, trace=True)
    logreg_newton_loop(ctx, n=1 << 13, d=32, q=16, iters=3, reset_loads=False)
    ctx.flush()
    a = analyze(ctx.export_trace())
    assert a["dropped"] == 0
    assert abs(a["decomposition_total_pct"] - 100.0) <= 1.0


def calibrated_jax_matches_oracle(tmp_path):
    """A profile fitted on the live jax backend changes clocks and placement,
    not the answer: the calibrated float64 Newton run matches numpy to 1e-6,
    and the profiled run timed its ops."""
    from repro.obs.calibrate import run_calibration
    from repro.obs.critical_path import drift_report

    def newton(backend, calibration=None, dtype=None):
        rec = FlightRecorder()
        ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                           backend=backend, dtype=dtype, pipeline=True, seed=0,
                           trace=rec, calibration=calibration)
        ctx.executor.profile_sync = True
        _g, _h, beta = logreg_newton_loop(ctx, 1 << 10, 32, 8, iters=2,
                                          reset_loads=False)
        ctx.flush()
        return drift_report(rec), beta.to_numpy()

    _report, oracle = newton("numpy")
    profile = run_calibration(backend="jax", nodes=4, workers=2, n=1 << 10,
                              d=32, q=8, iters=2, seed=0)
    report, beta = newton("jax", calibration=profile, dtype="float64")
    assert report["n_ops"] > 0
    assert np.abs(beta - oracle).max() <= 1e-6 * np.abs(oracle).max()


FLOORS = [plan_cache_hit_rate, jax_compile_cache_hits, chaos_degraded_makespan,
          traced_chaos_attribution, oom_backpressure_makespan,
          controller_grow_shrink, budgeted_cpals, linalg_makespans,
          traced_newton_ring, calibrated_jax_matches_oracle]


@pytest.mark.parametrize("floor", FLOORS, ids=lambda f: f.__name__)
def test_floor(floor, tmp_path):
    floor(tmp_path)
