"""The CP-ALS cell ``cpals_dense.sweep`` at a tiny size on the CPU: a sound
run is correct through the harness, the control and each planted fault are
not, and its three per-layer metrics read numbers."""
import json
import time

import numpy as np
import pytest

from bench import harness

CELL = "cpals_dense.sweep"
TINY_CELL = f"tiny_{CELL}"
# every setting but the tensor's size and the rank is the cell's own,
# limits included
TINY = {"n": 32, "rank": 8}
NEW_METRICS = ("cpals_roofline", "reshard_ms_per_fit", "layout_gb_per_fit")


@pytest.fixture
def cpals_root(tmp_path, make_checkout):
    return make_checkout(tmp_path, {CELL: TINY})


@pytest.fixture
def run_cpals(cpals_root):
    from repro.backend import GLOBAL_COMPILE_CACHE

    def run(seconds=0.3, trace=False, seed=2**31 + 11):
        # a planted fault must not meet kernels compiled before it
        GLOBAL_COMPILE_CACHE.clear()
        result, _ = harness.run_cell(cpals_root, TINY_CELL, seed, seconds,
                                     trace, time.perf_counter())
        return result

    yield run
    GLOBAL_COMPILE_CACHE.clear()


def test_sound_run_is_correct(run_cpals):
    result = run_cpals()
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["checks"]) == {"factor_rel_err", "fit_gap",
                                     "normal_eq_residual"}
    for check in result["checks"].values():
        assert check["value"] <= check["limit"]
    assert set(result["metrics"]) >= {"solve_s", "setup_s"}


def test_control_fails_the_limit(cpals_root):
    """The reference computed in bfloat16, in the program's place, comes
    out not correct through the cell's own comparison, on three seeds."""
    c = harness.Cell.load(cpals_root, TINY_CELL)
    for seed in (1, 2, 3):
        job = c.kind.setup(c.config, c.workload["traffic"], seed)
        checks, failed = job.check(job.control())
        assert failed == 1, checks
        assert not harness.is_correct(checks, failed), checks


def sweep_dropped(monkeypatch):
    """Every fit runs one sweep fewer than it counts: the first sweep after
    a fit builds its unfoldings does nothing.  It holds no reference to
    them, so they are freed with the fit as in a sound run."""
    from repro.factor import cpals

    unfoldings, sweep, fresh = cpals._unfoldings, cpals._sweep, [False]

    def marked(X, move):
        fresh[0] = True
        return unfoldings(X, move)

    def dropped(factors, xmats, move):
        if fresh[0]:
            fresh[0] = False
            return
        sweep(factors, xmats, move)

    monkeypatch.setattr(cpals, "_unfoldings", marked)
    monkeypatch.setattr(cpals, "_sweep", dropped)


def _patch_jax_op(monkeypatch, name, lowering):
    """Replace the jax lowering of block op ``name``: ``lowering(meta, fn)``."""
    from repro.backend.jax_backend import JaxBackend

    build = JaxBackend._build

    def patched(self, op, meta):
        fn = build(self, op, meta)
        return lowering(meta, fn) if op == name else fn

    monkeypatch.setattr(JaxBackend, "_build", patched)


def one_gram(monkeypatch):
    """The normal equations use one Gram matrix, not the Hadamard product
    of the other two factors' Grams."""
    _patch_jax_op(monkeypatch, "mul", lambda meta, fn: lambda a, b: a)


def unfolding_axes_swapped(monkeypatch):
    """The mode-1 unfolding orders its columns (k, i) instead of (i, k)."""
    import jax.numpy as jnp

    def lowering(meta, fn):
        if meta["mode"] != 1:
            return fn
        return lambda x: jnp.swapaxes(jnp.moveaxis(x, 1, 0), 1, 2).reshape(
            x.shape[1], -1)

    _patch_jax_op(monkeypatch, "matricize", lowering)


ALL = {"factor_rel_err", "fit_gap", "normal_eq_residual"}


@pytest.mark.parametrize("fault, over", [
    pytest.param(f, over, id=f.__name__) for f, over in (
        (sweep_dropped, {"factor_rel_err", "fit_gap"}),
        (one_gram, ALL),
        (unfolding_axes_swapped, {"factor_rel_err", "fit_gap"}))])
def test_fault_is_not_correct(run_cpals, monkeypatch, fault, over):
    """Each fault fails the run, on the readings ``over``: the last
    update's residual given the other two factors does not see a sweep
    too few or columns in the wrong order, the factors and the fit do."""
    fault(monkeypatch)
    result = run_cpals()
    assert result["correct"] is False, result
    assert result["failed"] >= 1
    assert {name for name, c in result["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]} == over, result


def test_traced_run_reports_the_new_metrics(run_cpals, cpals_root):
    """Every metric the cell lists that the CPU can give, each non-null;
    the layout bytes are the analytic count of the block ops that write
    them: per fit two reshards of the tensor (slices, then concatenations),
    three unfoldings and 3 x 10 factor gathers."""
    result = run_cpals(trace=True)
    assert result["correct"], result
    spec = json.loads((cpals_root / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in spec["per_layer"]
              if TINY_CELL in m["workloads"]}
    assert set(NEW_METRICS) <= set(listed)
    expected = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert set(result["metrics"]) == expected
    m = {k: v["value"] for k, v in result["metrics"].items()}
    n, F = TINY["n"], TINY["rank"]
    assert m["layout_gb_per_fit"] * 1e9 == pytest.approx(
        4 * (7 * n ** 3 + 30 * n * F), rel=1e-12)
    assert m["reshard_ms_per_fit"] > 0
    assert m["dispatches_per_fit"] == 95 and m["lowered_ops_per_fit"] == 798
    assert m["window_compiles.solve"] == 0


def test_roofline_share_is_the_fits_least_time(cpals_root):
    """``cpals_roofline`` over a device trace: each fit's least time, the
    larger of its operations at the bf16 peak and its bytes at the HBM
    peak, over the busy time."""
    from bench.trace_reduce import TraceSummary

    cell = harness.Cell.load(cpals_root, TINY_CELL)
    job = cell.kind.setup(cell.config, cell.workload["traffic"], 5)
    records = [job.run() for _ in range(2)]
    peaks = harness.load_peaks(cpals_root, "TPU v5 lite")
    run = harness.Run(cell, job, 0.0, 1.0, records, {}, {}, 0, None, peaks,
                      TraceSummary(window_s=1.0, busy_s={0: 1e-6}))
    reader = harness.load_module(cpals_root / "bench" / "metrics"
                                 / "cpals_roofline.py")
    n, F, S = TINY["n"], TINY["rank"], 10
    least = max(3 * S * 2 * n ** 3 * F / peaks["bf16_flops_per_s"],
                4 * n ** 3 * (3 * S + 4) / peaks["hbm_bytes_per_s"])
    assert reader.read(run) == pytest.approx(100.0 * 2 * least / 1e-6)
    assert np.shape(records[0]["rows"]) == (3 * n, F)
    job.collect()
