"""Lowered plans: a pipelined jax or pallas context with the plan cache on
runs each cached plan as one compiled program per device segment
(``Executor._lowered_outputs``/``_run_segments``, ``JaxBackend.run_program``)
instead of one call per block op.  The device does the per-op path's work op
for op, so results and every simulated count match the per-op run bit for
bit; the per-op path stays wherever the executor cannot see that lowering is
safe."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec
from repro.core.chaos import ChaosPlan
from repro.glm import GLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKENDS = ["jax", "pallas"]
# loads() fields the simulated clocks and the memory manager give: a lowered
# run must reproduce them exactly
SIMULATED = ("makespan", "makespan_sync", "makespan_pipelined", "transfers",
             "total_net", "max_mem", "mem_peak_live_elements",
             "mem_peak_store_blocks", "mem_peak_store_bytes",
             "mem_gc_freed_blocks", "mem_gc_freed_elements", "n_rfc")


def _ctx(backend, nodes=1, **kw):
    kw.setdefault("pipeline", True)
    kw.setdefault("plan_cache", True)
    kw.setdefault("gc", True)
    return ArrayContext(cluster=ClusterSpec(nodes, 4), node_grid=(nodes, 1),
                        backend=backend, **kw)


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape)


def _newton(ctx):
    rng = np.random.default_rng(3)
    y = (rng.random((2_000, 1)) < 0.5).astype(np.float64)
    X = rng.standard_normal((2_000, 5)) + 0.5 * (y - 0.5)
    model = GLM(ctx, max_iter=3, tol=1e-9, reg=1e-6)
    model.fit(ctx.from_numpy(X, grid=(4, 1)), ctx.from_numpy(y, grid=(4, 1)))
    return model.beta


def _matmul_add(ctx):
    A = ctx.from_numpy(_rand((64, 64), 1), grid=(2, 2))
    B = ctx.from_numpy(_rand((64, 64), 2), grid=(2, 2))
    return ((A @ B) + A).to_numpy()


def _reshard(ctx):
    X = ctx.from_numpy(_rand((64, 32), 4), grid=(4, 1))
    return (X.reshard(grid=(2, 2)) * 2.0).to_numpy()


PROGRAMS = {"newton": _newton, "matmul_add": _matmul_add, "reshard": _reshard}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_lowered_run_is_the_per_op_run_bit_for_bit(backend, program):
    run = PROGRAMS[program]
    lowered_ctx, per_op_ctx = _ctx(backend), _ctx(backend, trace=True)
    lowered, per_op = run(lowered_ctx), run(per_op_ctx)
    assert lowered.tobytes() == per_op.tobytes()
    lo, po = lowered_ctx.loads(), per_op_ctx.loads()
    assert lo["backend_lowered_ops"] == lowered_ctx.executor.stats.n_queued > 0
    assert po["backend_lowered_ops"] == po["backend_programs"] == 0
    assert {k: lo[k] for k in SIMULATED} == {k: po[k] for k in SIMULATED}
    # same values as the synchronous executor, which never lowers
    assert lowered.tobytes() == run(_ctx(backend, pipeline=False)).tobytes()


@pytest.mark.parametrize("backend", BACKENDS)
def test_one_program_per_plan_on_one_device(backend):
    ctx = _ctx(backend)
    ex = ctx.executor
    enqueued = []
    end_plan = ex.end_plan

    def spy(plan):
        enqueued.append(len(ex._plan_ops or ()))
        end_plan(plan)

    ex.end_plan = spy
    _newton(ctx)
    loads = ctx.loads()
    assert loads["backend_programs"] == sum(1 for n in enqueued if n) > 0
    assert loads["backend_lowered_ops"] == sum(enqueued) == ex.stats.n_queued
    # every host call of the fit was a program
    assert loads["backend_dispatches"] == loads["backend_programs"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_second_identical_fit_compiles_nothing(backend):
    ctx = _ctx(backend)
    first = _newton(ctx)
    before = ctx.loads()
    second = _newton(ctx)
    after = ctx.loads()
    assert after["compiles"] == before["compiles"]
    assert after["backend_programs"] > before["backend_programs"]
    assert second.tobytes() == first.tobytes()
    # program keys are structural: a fresh context reuses the compilations
    _newton(_ctx(backend))
    assert ctx.loads()["compiles"] == before["compiles"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_lowered_results_live_as_long_as_per_op_ones(backend):
    """Device bytes alive after each fit, with the cyclic collector off: a
    lowered drain holds no block past the point where the per-op drain
    frees it (the device's peak memory rests on this)."""
    import gc

    import jax

    def live_after_fits(trace):
        ctx = _ctx(backend, trace=trace)
        _newton(ctx)  # compiles and fills the plan cache
        gc.collect()
        gc.disable()
        try:
            base = sum(a.nbytes for a in jax.live_arrays())
            grown = []
            for _ in range(2):
                _newton(ctx)
                grown.append(sum(a.nbytes for a in jax.live_arrays()) - base)
        finally:
            gc.enable()
        return grown

    assert live_after_fits(False) == live_after_fits(True)


def _chaos(ctx):
    ctx.enable_chaos(ChaosPlan(stragglers={0: 2.0}), seed=1)


def _drain_hook(ctx):
    ctx.executor.drain_hook = lambda vid: None


def _profile_sync(ctx):
    ctx.executor.profile_sync = True


PER_OP = {
    "chaos": ({}, _chaos),
    "memory_budget": ({"mem_capacity": 1e9}, None),
    "flight_recorder": ({"trace": True}, None),
    "drain_hook": ({}, _drain_hook),
    "profile_sync": ({}, _profile_sync),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(PER_OP))
def test_per_op_where_lowering_is_not_safe(backend, case):
    kw, setup = PER_OP[case]
    ctx = _ctx(backend, **kw)
    if setup is not None:
        setup(ctx)
    beta = _newton(ctx)
    loads = ctx.loads()
    assert loads["backend_lowered_ops"] == loads["backend_programs"] == 0
    assert loads["backend_dispatches"] == ctx.executor.stats.n_queued
    assert beta.tobytes() == _newton(_ctx(backend)).tobytes()


@pytest.mark.parametrize("backend", BACKENDS)
def test_recover_after_a_lowered_drain_is_bit_identical(backend):
    ctx = _ctx(backend, nodes=2)
    ex = ctx.executor
    A = ctx.from_numpy(_rand((64, 64), 1), grid=(2, 2))
    B = ctx.from_numpy(_rand((64, 64), 2), grid=(2, 2))
    C = ((A @ B) + A).compute()
    ex.flush()
    assert ctx.loads()["backend_lowered_ops"] > 0
    blocks = {idx: np.asarray(ex.get(C.block(idx).vid)).tobytes()
              for idx in C.grid.iter_indices()}
    lost = ex.fail_node(1)
    assert lost
    assert ex.recover(lost) > 0
    for idx, bits in blocks.items():
        assert np.asarray(ex.get(C.block(idx).vid)).tobytes() == bits, idx


@pytest.fixture(scope="module")
def four_devices():
    """A Newton fit on four virtual CPU devices, lowered and per op, for each
    backend: bits, counts, and the ``device_put``s seen between devices."""
    code = f"""
        import json, sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        import jax
        import numpy as np
        from repro.core import ArrayContext, ClusterSpec, weighted_gram
        from repro.glm import GLM

        moved = []
        put = jax.device_put

        def counting_put(x, device=None, *args, **kwargs):
            if isinstance(x, jax.Array):  # device to device, not from_host
                moved.append(x.nbytes)
            return put(x, device, *args, **kwargs)

        jax.device_put = counting_put
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(0)
        X = rng.standard_normal((8_000, 5))
        y = (rng.random((8_000, 1)) < 0.5).astype(np.float64)
        out = {{}}
        for backend in ("jax", "pallas"):
            res = {{}}
            for name, trace in (("lowered", False), ("per_op", True)):
                ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                                   backend=backend, pipeline=True,
                                   plan_cache=True, gc=True, trace=trace)
                Xg = ctx.from_numpy(X, grid=(8, 1))
                yg = ctx.from_numpy(y, grid=(8, 1))
                n0 = len(moved)
                beta = GLM(ctx, max_iter=3, tol=1e-9, reg=1e-6).fit(Xg, yg).beta
                loads = ctx.loads()
                res[name] = {{"beta": beta.tobytes().hex(),
                             "seen": len(moved) - n0,
                             **{{k: loads[k] for k in (
                                 "backend_programs", "backend_lowered_ops",
                                 "backend_gram_ops", "backend_device_moves",
                                 "makespan")}}}}
            ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                               backend=backend)
            w = rng.random((8_000, 1))
            H = weighted_gram(ctx.from_numpy(X, grid=(8, 1)),
                              ctx.from_numpy(w, grid=(8, 1))).to_numpy()
            res["gram_err"] = float(np.abs(H - (X * w).T @ X).max()
                                    / np.abs((X * w).T @ X).max())
            res["gram_ops"] = ctx.loads()["backend_gram_ops"]
            out[backend] = res
        print("RESULT", json.dumps(out))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    import json

    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("backend", BACKENDS)
def test_four_devices_lowered_is_bitwise_with_fewer_calls(four_devices, backend):
    lowered, per_op = four_devices[backend]["lowered"], four_devices[backend]["per_op"]
    assert lowered["beta"] == per_op["beta"]
    assert lowered["makespan"] == per_op["makespan"]
    assert 0 < lowered["backend_programs"] < lowered["backend_lowered_ops"]
    assert per_op["backend_programs"] == 0
    # each program moves an operand once, however many of its ops read it
    for run in (lowered, per_op):
        assert run["backend_device_moves"] == run["seen"] > 0
    assert lowered["backend_device_moves"] < per_op["backend_device_moves"]
    # the Hessian is one weighted-Gram op per row block and iteration
    assert lowered["backend_gram_ops"] == per_op["backend_gram_ops"] == 8 * 3


@pytest.mark.parametrize("backend", BACKENDS)
def test_four_devices_weighted_gram_matches_numpy(four_devices, backend):
    res = four_devices[backend]
    assert res["gram_err"] < 1e-5  # float32 blocks
    assert res["gram_ops"] == 8
