"""repro.backend: compiled block-kernel execution backends.

Covers the backend subsystem end to end:

* an op-level parity sweep — every block op in ``_UNARY``/``_BINARY`` plus
  ``scalar``, ``matmul`` (all transpose-flag combos and the vector forms),
  ``reduce_axis``, reduce trees, ``slice``/``concat_blocks``, linalg/tensor
  ops, and fused chains — on all three backends against the numpy reference;
* end-to-end parity on the paper workloads (logreg-Newton, CP-ALS, DGEMM)
  at ≤1e-6 relative tolerance with *identical* schedules and loads
  (placement never reads block values, so backends must not perturb LSHS);
* the structural compile cache (hits, invalidation by shape/dtype/meta,
  LRU eviction, counters in ``ctx.loads``);
* fused-chain lowering: a chain of ≥3 elementwise ops is exactly one
  compiled dispatch per block on the jax backend;
* the no-host-round-trip property of device-resident execution (h2d/d2h
  counters flat across op execution);
* fault-tolerance lineage replay on the compiled backend;
* placement over four devices: node i's blocks on ``jax.devices()[i]``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.backend import (
    GLOBAL_COMPILE_CACHE,
    CompileCache,
    available_backends,
    make_backend,
)
from repro.core import ArrayContext, ClusterSpec
from repro.core.graph_array import _BINARY, _UNARY, execute_block_op
from repro.launch.workloads import dgemm_graph, logreg_newton_loop

RTOL = 1e-6  # acceptance tolerance; f64 backends land many orders below


def _ctx(backend: str, k: int = 2, r: int = 2, ng=(2, 1), **kw):
    kw.setdefault("dtype", "float64")
    return ArrayContext(cluster=ClusterSpec(k, r), node_grid=ng,
                        backend=backend, seed=0, **kw)


def _rel(a, b):
    a, b = np.asarray(a, dtype=np.float64), np.asarray(b, dtype=np.float64)
    denom = max(np.abs(b).max(), 1e-12)
    return np.abs(a - b).max() / denom


# ---------------------------------------------------------------------------
# op-level parity sweep
# ---------------------------------------------------------------------------

def _op_cases():
    """(op, meta, input arrays) covering every block-level op kind."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((6, 5))
    ypos = rng.random((6, 5)) + 0.5       # strictly positive (log/sqrt/rsqrt)
    y = rng.standard_normal((6, 5))
    v = rng.standard_normal(6)
    cases = []
    for op in _UNARY:
        arg = ypos if op in ("log", "sqrt", "rsqrt") else x
        cases.append((op, {}, [arg]))
    for op in _BINARY:
        b = ypos if op == "pow" else y
        a = ypos if op == "pow" else x
        cases.append((op, {}, [a, b]))
    cases.append(("add", {"expand_b": True}, [x, v]))
    cases.append(("mul", {"expand_a": True}, [v, x]))
    for sop in ("add", "mul", "sub", "div"):
        cases.append(("scalar", {"op": sop, "scalar": 1.75, "reverse": False}, [x]))
        cases.append(("scalar", {"op": sop, "scalar": 1.75, "reverse": True}, [x]))
    a23, b35 = rng.standard_normal((2, 3)), rng.standard_normal((3, 5))
    for ta in (False, True):
        for tb in (False, True):
            aa = a23.T if ta else a23
            bb = b35.T if tb else b35
            cases.append(("matmul", {"ta": ta, "tb": tb}, [aa, bb]))
    cases.append(("matmul", {"ta": False, "tb": False}, [v, v]))       # dot
    cases.append(("matmul", {"ta": False, "tb": False},
                  [rng.standard_normal((6, 4)), rng.standard_normal(4)]))
    for axis in (None, 0, 1):
        for rop in ("add", "maximum", "minimum"):
            cases.append(("reduce_axis", {"axis": axis, "op": rop}, [x]))
    t = rng.standard_normal((3, 4, 2))
    cases.append(("transpose", {"perm": (2, 0, 1)}, [t]))
    cases.append(("transpose", {"perm": None}, [x]))
    cases.append(("tensordot", {"axes": 1},
                  [rng.standard_normal((3, 4)), rng.standard_normal((4, 2))]))
    cases.append(("einsum", {"spec": "ijk,jf,kf->if"},
                  [t, rng.standard_normal((4, 3)), rng.standard_normal((2, 3))]))
    chain = [("unary", "exp"), ("scalar", "mul", 0.5, False),
             ("unary", "tanh"), ("unary", "square")]
    cases.append(("fused", {"chain": chain}, [x]))
    tall = rng.standard_normal((8, 3))
    cases.append(("qr_r", {}, [tall]))
    cases.append(("qr_q", {}, [tall]))
    cases.append(("qr_stackr", {}, [np.triu(rng.standard_normal((3, 3))),
                                    np.triu(rng.standard_normal((3, 3)))]))
    cases.append(("stack", {}, [rng.standard_normal((2, 3)),
                                rng.standard_normal((4, 3))]))
    cases.append(("slice_rows", {"start": 1, "stop": 4}, [x]))
    cases.append(("slice", {"starts": (1, 0), "stops": (5, 3)}, [x]))
    cases.append(("concat_blocks",
                  {"shape": (4, 4), "offsets": [(0, 0), (0, 2), (2, 0), (2, 2)]},
                  [rng.standard_normal((2, 2)) for _ in range(4)]))
    cases.append(("matricize", {"mode": 1}, [t]))
    cases.append(("khatri_rao", {}, [rng.standard_normal((3, 4)),
                                     rng.standard_normal((2, 4))]))
    cases.append(("wgram", {}, [x, rng.random((6, 1)) + 0.1]))
    spd = rng.standard_normal((4, 4))
    spd = spd @ spd.T + 4.0 * np.eye(4)
    cases.append(("solve", {}, [spd, rng.standard_normal((4, 2))]))
    cases.append(("rsolve", {}, [rng.standard_normal((5, 4)), spd]))
    return cases


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_op_parity_sweep(backend):
    be = make_backend(backend, dtype="float64")
    for op, meta, inputs in _op_cases():
        ref = execute_block_op(op, dict(meta), [np.asarray(i) for i in inputs])
        res = be.execute(op, dict(meta),
                         [be.from_host(np.asarray(i), (0, 0)) for i in inputs],
                         (0, 0))
        got = be.to_host(res)
        assert got.shape == np.asarray(ref).shape, (op, meta)
        if op in ("qr_q", "qr_r", "qr_stackr"):
            # QR is unique only up to column signs across LAPACK drivers;
            # compare magnitudes (and exact shape above)
            assert _rel(np.abs(got), np.abs(ref)) < 1e-8, (op, meta)
        else:
            assert _rel(got, ref) < 1e-8, (op, meta)


def test_numpy_backend_is_bit_exact():
    be = make_backend("numpy")
    for op, meta, inputs in _op_cases():
        ref = execute_block_op(op, dict(meta), [np.asarray(i) for i in inputs])
        got = be.execute(op, dict(meta), list(inputs), (0, 0))
        assert np.array_equal(np.asarray(got), np.asarray(ref)), op


def test_registry():
    assert {"numpy", "jax", "pallas"} <= set(available_backends())
    with pytest.raises(ValueError):
        make_backend("no-such-backend")


# ---------------------------------------------------------------------------
# end-to-end workload parity + schedule identity
# ---------------------------------------------------------------------------

def _schedule_signature(ctx, out):
    return {
        "S": ctx.state.S.copy(),
        # vertex ids are process-global, so compare transfer *structure*
        "transfers": [(t.src, t.dst, t.elements) for t in ctx.state.transfers],
        "placements": out.placements(),
        "n_rfc": ctx.executor.stats.n_rfc,
    }


def _assert_same_schedule(sig_a, sig_b):
    assert np.array_equal(sig_a["S"], sig_b["S"])
    assert sig_a["transfers"] == sig_b["transfers"]
    assert sig_a["n_rfc"] == sig_b["n_rfc"]
    assert list(sig_a["placements"].values()) == list(sig_b["placements"].values())


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_dgemm_end_to_end_parity(backend):
    ref_ctx = _ctx("numpy", k=4, r=2, ng=(2, 2))
    C_ref = dgemm_graph(ref_ctx, 64, 4)
    ctx = _ctx(backend, k=4, r=2, ng=(2, 2))
    C = dgemm_graph(ctx, 64, 4)
    assert _rel(C.to_numpy(), C_ref.to_numpy()) < RTOL
    _assert_same_schedule(_schedule_signature(ref_ctx, C_ref),
                          _schedule_signature(ctx, C))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_logreg_newton_end_to_end_parity(backend):
    ref_ctx = _ctx("numpy", k=4, r=2, ng=(2, 2))
    g_ref, H_ref, beta_ref = logreg_newton_loop(ref_ctx, 128, 8, 4, iters=3)
    ctx = _ctx(backend, k=4, r=2, ng=(2, 2))
    g, H, beta = logreg_newton_loop(ctx, 128, 8, 4, iters=3)
    assert _rel(beta.to_numpy(), beta_ref.to_numpy()) < RTOL
    assert _rel(g.to_numpy(), g_ref.to_numpy()) < RTOL
    assert _rel(H.to_numpy(), H_ref.to_numpy()) < RTOL
    _assert_same_schedule(_schedule_signature(ref_ctx, H_ref),
                          _schedule_signature(ctx, H))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_cpals_end_to_end_parity(backend):
    from repro.factor import cp_als

    ref_ctx = _ctx("numpy", k=2, r=2, ng=(2, 1, 1))
    X_ref = ref_ctx.random((8, 8, 8), grid=(2, 1, 1))
    res_ref = cp_als(X_ref, rank=3, iters=2, track_fit=False)
    ctx = _ctx(backend, k=2, r=2, ng=(2, 1, 1))
    X = ctx.random((8, 8, 8), grid=(2, 1, 1))
    res = cp_als(X, rank=3, iters=2, track_fit=False)
    for f_ref, f in zip(res_ref.factors, res.factors):
        assert _rel(f.to_numpy(), f_ref.to_numpy()) < RTOL
    assert np.array_equal(ref_ctx.state.S, ctx.state.S)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_pipelined_matches_sync_on_compiled_backend(backend):
    outs = {}
    for pipeline in (False, True):
        ctx = _ctx(backend, k=4, r=2, ng=(2, 2), pipeline=pipeline)
        A = ctx.random((32, 32), grid=(4, 4))
        B = ctx.random((32, 32), grid=(4, 4))
        outs[pipeline] = ((A @ B) + A).compute().to_numpy()
    assert np.array_equal(outs[False], outs[True])


def test_pallas_matmul_non_tile_multiple_blocks():
    """Block dims between one and two kernel tiles (e.g. a 600-row
    contraction dim padding to 640 with bk=512) must not trip the kernel's
    divisibility guard — the tile clamps to a divisor of the padded dim."""
    ctx = _ctx("pallas", k=2, r=2)
    X = ctx.random((1200, 64), grid=(2, 1))        # blocks of 600 rows
    out = (X.T @ X).compute().to_numpy()
    ref = X.to_numpy()
    assert _rel(out, ref.T @ ref) < RTOL


# ---------------------------------------------------------------------------
# fused-chain lowering: one compiled dispatch per block
# ---------------------------------------------------------------------------

def _chain_jit_calls(fuse: bool) -> int:
    ctx = _ctx("jax", fuse=fuse)
    x = ctx.random((16, 16), grid=(2, 2))
    stats = ctx.executor.backend.stats
    before = stats.jit_calls
    (x.exp().relu().sqrt()).compute()
    return stats.jit_calls - before


def test_fused_chain_is_single_jit_dispatch():
    n_blocks = 4
    assert _chain_jit_calls(fuse=True) == n_blocks          # 1 per block
    assert _chain_jit_calls(fuse=False) == 3 * n_blocks     # per-op dispatch


def test_fused_chain_value_parity():
    for backend in ("jax", "pallas"):
        ref = _ctx("numpy", fuse=True)
        ctx = _ctx(backend, fuse=True)
        xr = ref.random((16, 16), grid=(2, 2))
        xc = ctx.random((16, 16), grid=(2, 2))
        a = (xr.square().exp().reciprocal() * 2.0).compute().to_numpy()
        b = (xc.square().exp().reciprocal() * 2.0).compute().to_numpy()
        assert _rel(b, a) < 1e-12


# ---------------------------------------------------------------------------
# device residency: no host round-trips between ops
# ---------------------------------------------------------------------------

def test_no_host_transfers_between_ops():
    ctx = _ctx("jax", k=4, r=2, ng=(2, 2))
    A = ctx.random((32, 32), grid=(2, 2))
    B = ctx.random((32, 32), grid=(2, 2))
    stats = ctx.executor.backend.stats
    h2d0, d2h0 = stats.h2d, stats.d2h
    out = ((A @ B).sum(axis=0) + 1.0).compute()
    # many ops executed; none crossed the host boundary
    assert ctx.executor.stats.n_rfc > 8
    assert stats.h2d == h2d0
    assert stats.d2h == d2h0
    assert stats.fallbacks == 0
    out.to_numpy()  # the gather is where device->host happens
    assert stats.d2h > d2h0


def test_op_without_lowering_raises():
    # no silent numpy round-trip through the host for an unknown op
    from repro.backend.jax_backend import JaxBackend

    be = JaxBackend("float32", cache=CompileCache())
    x = be.from_host(np.ones((4, 4)), (0, 0))
    with pytest.raises(NotImplementedError, match="no_such_op"):
        be.execute("no_such_op", {}, [x], (0, 0))
    assert be.stats.fallbacks == 0


def test_blocks_stay_jax_arrays():
    import jax

    ctx = _ctx("jax")
    A = ctx.random((16, 16), grid=(2, 2))
    out = (A + A).compute()
    for idx in out.grid.iter_indices():
        assert isinstance(ctx.executor.get(out.block(idx).vid), jax.Array)


# ---------------------------------------------------------------------------
# structural compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_hits_on_repeat_structure():
    cache = CompileCache()
    from repro.backend.jax_backend import JaxBackend

    be = JaxBackend("float64", cache=cache)
    x = be.from_host(np.random.default_rng(0).standard_normal((8, 8)), (0, 0))
    be.execute("exp", {}, [x], (0, 0))
    assert (cache.hits, cache.misses, cache.compiles) == (0, 1, 1)
    for _ in range(5):
        be.execute("exp", {}, [x], (0, 0))
    assert (cache.hits, cache.misses, cache.compiles) == (5, 1, 1)
    assert cache.compile_s > 0.0


def test_compile_cache_invalidates_on_shape_dtype_meta():
    cache = CompileCache()
    from repro.backend.jax_backend import JaxBackend

    be = JaxBackend("float64", cache=cache)
    rng = np.random.default_rng(0)
    x88 = be.from_host(rng.standard_normal((8, 8)), (0, 0))
    x44 = be.from_host(rng.standard_normal((4, 4)), (0, 0))
    be.execute("scalar", {"op": "mul", "scalar": 2.0, "reverse": False}, [x88], (0, 0))
    be.execute("scalar", {"op": "mul", "scalar": 2.0, "reverse": False}, [x44], (0, 0))
    be.execute("scalar", {"op": "mul", "scalar": 3.0, "reverse": False}, [x88], (0, 0))
    be.execute("scalar", {"op": "add", "scalar": 2.0, "reverse": False}, [x88], (0, 0))
    assert cache.misses == 4 and cache.hits == 0          # all distinct keys
    be32 = JaxBackend("float32", cache=cache)
    y88 = be32.from_host(rng.standard_normal((8, 8)), (0, 0))
    be32.execute("scalar", {"op": "mul", "scalar": 2.0, "reverse": False}, [y88], (0, 0))
    assert cache.misses == 5                               # dtype is in the key


def test_compile_cache_lru_eviction():
    cache = CompileCache(max_entries=2)
    from repro.backend.jax_backend import JaxBackend

    be = JaxBackend("float64", cache=cache)
    x = be.from_host(np.random.default_rng(0).standard_normal((4, 4)), (0, 0))
    for op in ("exp", "tanh", "square"):                   # 3 entries, cap 2
        be.execute(op, {}, [x], (0, 0))
    assert cache.evictions == 1 and len(cache) == 2
    be.execute("exp", {}, [x], (0, 0))                     # evicted: recompile
    assert cache.misses == 4


def test_compile_counters_surface_in_loads():
    ctx = _ctx("jax")
    A = ctx.random((16, 16), grid=(2, 2))
    (A + A).compute()
    d = ctx.loads()
    for key in ("compile_hits", "compile_misses", "compiles", "compile_s",
                "compile_hit_rate", "backend_jit_calls", "backend_h2d",
                "backend_d2h"):
        assert key in d, key
    assert d["backend_jit_calls"] >= 4
    sd = ctx.sched_stats.as_dict()
    for key in ("backend_compiles", "backend_compile_hits",
                "backend_compile_misses", "backend_compile_hit_rate",
                "backend_compile_s", "backend_jit_calls"):
        assert key in sd, key
    assert ctx.sched_stats.backend_jit_calls == d["backend_jit_calls"]


def test_global_cache_shared_across_contexts():
    ctx1 = _ctx("jax")
    A = ctx1.random((24, 24), grid=(2, 2))
    (A.exp()).compute()
    misses0 = GLOBAL_COMPILE_CACHE.misses
    hits0 = GLOBAL_COMPILE_CACHE.hits
    ctx2 = _ctx("jax")
    B = ctx2.random((24, 24), grid=(2, 2))
    (B.exp()).compute()
    # second context re-uses the first one's compilations: hits, no compiles
    assert GLOBAL_COMPILE_CACHE.misses == misses0
    assert GLOBAL_COMPILE_CACHE.hits > hits0
    assert ctx2.loads()["compile_hit_rate"] > 0


# ---------------------------------------------------------------------------
# dtype threading
# ---------------------------------------------------------------------------

def test_natural_dtypes(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_DTYPE", raising=False)
    assert ArrayContext(backend="numpy").dtype == "float64"
    assert ArrayContext(backend="jax").dtype == "float32"
    assert ArrayContext(backend="jax", dtype="float64").dtype == "float64"
    assert ArrayContext().backend == "numpy"
    monkeypatch.setenv("REPRO_BACKEND", "jax")
    monkeypatch.setenv("REPRO_DTYPE", "float64")
    ctx = ArrayContext()
    assert ctx.backend == "jax" and ctx.dtype == "float64"


def test_dtype_flows_to_blocks_and_assembly():
    ctx32 = ArrayContext(cluster=ClusterSpec(2, 2), node_grid=(2, 1),
                         backend="jax", dtype="float32", seed=0)
    A = ctx32.random((16, 8), grid=(2, 1))
    out = (A * 2.0).compute().to_numpy()
    assert out.dtype == np.float32
    ctx64 = _ctx("jax")
    B = ctx64.random((16, 8), grid=(2, 1))
    assert (B * 2.0).compute().to_numpy().dtype == np.float64


def test_f32_backend_matches_reference_with_dtype_tolerance():
    ref = ArrayContext(cluster=ClusterSpec(2, 2), node_grid=(2, 1),
                       backend="numpy", seed=0)
    ctx = ArrayContext(cluster=ClusterSpec(2, 2), node_grid=(2, 1),
                       backend="jax", dtype="float32", seed=0)
    Xr = ref.random((64, 16), grid=(4, 1))
    Xc = ctx.random((64, 16), grid=(4, 1))
    a = (Xr.T @ Xr).compute().to_numpy()
    b = (Xc.T @ Xc).compute().to_numpy()
    assert _rel(b, a) < 1e-5  # f32-appropriate tolerance


# ---------------------------------------------------------------------------
# fault tolerance on the compiled backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_fail_node_recover_parity(backend):
    ctx = _ctx(backend, k=4, r=2, ng=(2, 2), pipeline=True)
    A = ctx.random((32, 32), grid=(4, 4))
    B = ctx.random((32, 32), grid=(4, 4))
    out = ((A @ B) + A).compute()
    before = out.to_numpy()
    lost = ctx.executor.fail_node(1)
    assert lost
    replayed = ctx.executor.recover(
        [out.block(i).vid for i in out.grid.iter_indices()])
    assert replayed > 0
    after = out.to_numpy()
    # recovery re-executes through the same backend's cached kernels:
    # recovered blocks are bit-identical, not merely close
    assert np.array_equal(before, after)
    # replays run through the backend and its counter records them
    assert ctx.executor.backend.stats.replays == replayed


def test_chaos_kill_mid_flush_replays_through_jax_backend():
    """Node death injected *while the pipelined drain is running* on the
    compiled backend: the chaos engine kills the node between retirements,
    lost device-resident blocks replay from lineage on survivors through the
    same jitted kernels, and the output stays bit-identical to a fault-free
    jax run."""
    from repro.core import ChaosPlan

    def graph(ctx):
        A = ctx.random((32, 32), grid=(4, 4))
        B = ctx.random((32, 32), grid=(4, 4))
        return ((A @ B) + A).compute().to_numpy()

    ref = graph(_ctx("jax", k=4, r=2, ng=(2, 2), pipeline=True))
    ctx = _ctx("jax", k=4, r=2, ng=(2, 2), pipeline=True)
    eng = ctx.enable_chaos(ChaosPlan(node_failures={1: 0.0}))
    out = graph(ctx)  # compute() drains; the kill fires mid-flush
    assert out.tobytes() == ref.tobytes()
    assert eng.dead == {1}
    assert eng.stats.blocks_lost > 0
    assert eng.stats.blocks_replayed > 0
    # the replay counter on the *backend* moved: recovery executed compiled
    # kernels, not the interpreter
    assert ctx.executor.backend.stats.replays == eng.stats.blocks_replayed
    assert ctx.executor.backend.stats.as_dict()["backend_replays"] > 0


# ---------------------------------------------------------------------------
# contraction precision
# ---------------------------------------------------------------------------

# block ops with a product inside: (meta, operand shapes)
_CONTRACTIONS = {
    "matmul": ({"tb": True}, [(6, 4), (5, 4)]),
    "tensordot": ({"axes": 1}, [(6, 4), (4, 5)]),
    "einsum": ({"spec": "ij,jk->ik"}, [(6, 4), (4, 5)]),
    "syrk_update": ({}, [(6, 6), (6, 4), (6, 4)]),
    "wgram": ({}, [(6, 4), (6, 1)]),
    "rsolve": ({}, [(6, 4), (4, 4)]),
}


def _traced_lowering(dtype, op, plain=False):
    """The jaxpr and the lowered HLO text of the jax backend's lowering of
    ``op`` on ``dtype`` operands (``plain``: of its ``_build`` alone)."""
    import jax

    meta, shapes = _CONTRACTIONS[op]
    be = make_backend("jax", dtype=dtype)
    rng = np.random.default_rng(0)
    xs = [jax.numpy.asarray(rng.standard_normal(s) + 4.0 * np.eye(*s), dtype=dtype)
          for s in shapes]
    fn = be._build(op, meta) if plain else be._lowering(op, meta, xs)
    return str(jax.make_jaxpr(fn)(*xs)), jax.jit(fn).lower(*xs).as_text()


@pytest.fixture
def x64_restored():
    import jax

    was = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", was)


@pytest.mark.parametrize("op", sorted(_CONTRACTIONS))
def test_float32_contractions_lower_at_highest(op):
    """Every product of a float32 block op is traced at HIGHEST; on the CPU
    ``rsolve`` calls LAPACK, so its products show in the jaxpr only."""
    jaxpr, hlo = _traced_lowering("float32", op)
    dots = jaxpr.count("dot_general")
    assert dots >= 1
    assert jaxpr.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") == dots
    if op != "rsolve":
        assert "precision = [HIGHEST, HIGHEST]" in hlo


@pytest.mark.parametrize("op", sorted(_CONTRACTIONS))
def test_float64_contractions_keep_their_lowering(x64_restored, op):
    assert (_traced_lowering("float64", op)
            == _traced_lowering("float64", op, plain=True))


def test_sim_mode_has_no_backend():
    from repro.core.executor import Executor

    ex = Executor(mode="sim")
    assert ex.backend is None
    with pytest.raises(ValueError):
        Executor(mode="bogus")


def test_four_chip_layout_on_virtual_devices():
    """Four nodes on four (virtual CPU) devices: a Newton fit over 32 row
    blocks and a 2 x 2 node-grid matmul on the jax and pallas backends keep
    every block of node i on ``jax.devices()[i]``, spread over all four
    nodes, move operands between devices, and match numpy."""
    code = """
        import json
        import jax
        import numpy as np
        from repro.core import ArrayContext, ClusterSpec
        from repro.glm import LogisticRegression, overlapping_gaussians

        def layout(ctx):
            ex, devices = ctx.executor, jax.devices()
            misplaced, nodes = 0, set()
            for vid, value in ex.store.items():
                if value is None:
                    continue
                node = ex.block_home[vid][0]
                nodes.add(node)
                misplaced += (not isinstance(value, jax.Array)
                              or value.devices() != {devices[node]})
            return {"misplaced": misplaced, "nodes": sorted(nodes),
                    "moves": ctx.loads()["backend_device_moves"]}

        def rel_err(got, ref):
            return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))

        out = {}
        X, y = overlapping_gaussians(32_000, d=28, seed=1, sep=0.25)
        X, y = X.astype(np.float32), y.astype(np.float32)
        # gc stays off, so the store keeps every block the fit made
        ctx = ArrayContext(cluster=ClusterSpec(4, 8), node_grid=(4, 1),
                           backend="jax", pipeline=True, plan_cache=True)
        model = LogisticRegression(ctx, solver="newton", reg=1e-6,
                                   max_iter=10)
        model.fit_numpy(X, y, row_blocks=32)
        X64, y64 = X.astype(np.float64), y.astype(np.float64).ravel()
        ref = np.zeros(X.shape[1])
        for _ in range(25):
            mu = 1.0 / (1.0 + np.exp(-(X64 @ ref)))
            g = X64.T @ (mu - y64) + 1e-6 * ref
            H = X64.T @ (X64 * (mu * (1.0 - mu))[:, None]) + 1e-6 * np.eye(28)
            ref = ref - np.linalg.solve(H, g)
        out["newton"] = {"rel_err": rel_err(model.beta.ravel(), ref),
                         **layout(ctx)}

        for backend in ("jax", "pallas"):
            ctx = ArrayContext(cluster=ClusterSpec(4, 4), node_grid=(2, 2),
                               backend=backend, seed=1)
            A = ctx.random((512, 512), grid=(4, 4))
            B = ctx.random((512, 512), grid=(4, 4))
            C = (A @ B).compute().wait()
            ref = (A.to_numpy().astype(np.float64)
                   @ B.to_numpy().astype(np.float64))
            out[backend] = {"rel_err": rel_err(C.to_numpy(), ref), **layout(ctx)}
        print("RESULT", json.dumps(out))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    res = json.loads(line.split(" ", 1)[1])
    # float32 blocks against float64 numpy
    assert res["newton"]["rel_err"] <= 1e-6
    assert res["jax"]["rel_err"] <= 1e-4 and res["pallas"]["rel_err"] <= 1e-4
    for run in res.values():
        assert run["misplaced"] == 0
        assert run["nodes"] == [0, 1, 2, 3]
        assert run["moves"] > 0
