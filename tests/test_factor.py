"""Full CP-ALS on the reshard subsystem vs the pure-numpy reference."""
import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec
from repro.factor import cp_als, cp_als_reference, khatri_rao, matricize


def _ctx(backend="numpy", k=4, r=2, **kw):
    return ArrayContext(cluster=ClusterSpec(k, r), node_grid=(k, 1, 1),
                        backend=backend, seed=0, **kw)


class TestBuildingBlocks:
    def test_khatri_rao_matches_numpy(self):
        ctx = _ctx()
        rng = np.random.default_rng(3)
        Bn, Cn = rng.standard_normal((6, 4)), rng.standard_normal((5, 4))
        B = ctx.from_numpy(Bn, grid=(1, 1))
        C = ctx.from_numpy(Cn, grid=(1, 1))
        got = khatri_rao(B, C).to_numpy()
        want = np.einsum("jf,kf->jkf", Bn, Cn).reshape(30, 4)
        assert np.array_equal(got, want)

    def test_khatri_rao_rejects_partitioned(self):
        ctx = _ctx()
        B = ctx.random((8, 4), grid=(4, 1))
        C = ctx.random((6, 4), grid=(1, 1))
        with pytest.raises(ValueError):
            khatri_rao(B, C)

    @pytest.mark.parametrize("mode", [0, 1, 2])
    def test_matricize_matches_unfold(self, mode):
        ctx = _ctx()
        X = ctx.random((16, 12, 8), grid=(4, 1, 1))
        ref = X.to_numpy()
        Xi = X if mode == 0 else X.reshard(
            grid=tuple(4 if a == mode else 1 for a in range(3)))
        got = matricize(Xi, mode).to_numpy()
        want = np.moveaxis(ref, mode, 0).reshape(ref.shape[mode], -1)
        assert np.array_equal(got, want)

    def test_matricize_rejects_wrong_partitioning(self):
        ctx = _ctx()
        X = ctx.random((16, 12, 8), grid=(4, 1, 1))
        with pytest.raises(ValueError):
            matricize(X, 1)

    @pytest.mark.parametrize("mode", [0, 1, 2])
    def test_mttkrp_mode_matches_unfolded(self, mode):
        """The reduce-based any-mode MTTKRP (einsum over the original
        layout) agrees with the matricization + Khatri-Rao formulation."""
        from repro.tensor import mttkrp_mode

        ctx = _ctx()
        X = ctx.random((16, 12, 8), grid=(4, 1, 1))
        rng = np.random.default_rng(9)
        f_np = [rng.standard_normal((d, 3)) for d in X.shape]
        factors = [ctx.from_numpy(f, grid=(1, 1)) for f in f_np]
        got = mttkrp_mode(X, factors, mode).to_numpy()
        rest = [m for m in range(3) if m != mode]
        kr = np.einsum("jf,kf->jkf", f_np[rest[0]], f_np[rest[1]]).reshape(-1, 3)
        want = np.moveaxis(X.to_numpy(), mode, 0).reshape(X.shape[mode], -1) @ kr
        assert np.allclose(got, want, atol=1e-10)


class TestCPALS:
    def test_matches_reference_1e8(self):
        """Acceptance: full CP-ALS (3 mode updates, 3 iterations) on a
        (4,1,1)-partitioned tensor matches pure-numpy ALS to 1e-8."""
        rng = np.random.default_rng(7)
        Xn = rng.standard_normal((16, 12, 8))
        ctx = _ctx(plan_cache=True)
        X = ctx.from_numpy(Xn, grid=(4, 1, 1))
        res = cp_als(X, rank=3, iters=3, seed=1)
        ref = cp_als_reference(Xn, rank=3, iters=3, seed=1)
        assert res.iterations == 3
        for f, r in zip(res.factors, ref):
            assert np.allclose(f.to_numpy(), r, atol=1e-8, rtol=1e-8)

    def test_naive_method_matches_reference_too(self):
        rng = np.random.default_rng(11)
        Xn = rng.standard_normal((12, 10, 8))
        ctx = _ctx()
        X = ctx.from_numpy(Xn, grid=(4, 1, 1))
        res = cp_als(X, rank=2, iters=2, method="naive", seed=2)
        ref = cp_als_reference(Xn, rank=2, iters=2, seed=2)
        for f, r in zip(res.factors, ref):
            assert np.allclose(f.to_numpy(), r, atol=1e-8, rtol=1e-8)

    def test_reshard_moves_less_than_naive(self):
        moved = {}
        for method in ("reshard", "naive"):
            ctx = _ctx(backend="sim")
            X = ctx.random((24, 24, 24), grid=(4, 1, 1))
            ctx.reset_loads()
            res = cp_als(X, rank=4, iters=2, method=method, seed=1)
            moved[method] = res.moved_elements
        assert 0 < moved["reshard"] < moved["naive"]

    def test_fit_improves(self):
        """On a genuinely low-rank tensor, ALS sweeps increase the fit."""
        rng = np.random.default_rng(2)
        A0, B0, C0 = (rng.standard_normal((d, 2)) for d in (16, 12, 8))
        Xn = np.einsum("if,jf,kf->ijk", A0, B0, C0)
        ctx = _ctx()
        X = ctx.from_numpy(Xn, grid=(4, 1, 1))
        res = cp_als(X, rank=2, iters=8, seed=0)
        assert res.fit_history[-1] > 0.99
        assert res.fit_history[-1] >= res.fit_history[0]

    def test_plan_cache_amortizes_inner_loop(self):
        ctx = _ctx(backend="sim", plan_cache=True)
        X = ctx.random((24, 24, 24), grid=(4, 1, 1))
        ctx.reset_loads()
        cp_als(X, rank=4, iters=4, seed=1)
        assert ctx.sched_stats.hit_rate() >= 0.5

    def test_works_on_sim_backend(self):
        ctx = _ctx(backend="sim")
        X = ctx.random((24, 24, 24), grid=(4, 1, 1))
        res = cp_als(X, rank=4, iters=1, seed=1)
        assert [f.shape for f in res.factors] == [(24, 4), (24, 4), (24, 4)]
        assert res.fit_history == []  # no data to assemble on sim

    def test_launch_workload_smoke(self):
        from repro.launch.blocks import build_workload

        ctx = _ctx(backend="sim")
        A = build_workload(ctx, "cpals", scale=1, iters=2)
        assert A.shape[0] == 32


def _cell_ctx(**kw):
    """The one-chip CP-ALS cell's context: one node of 8 workers, jax
    backend in float32, pipelined, plan cache and refcount GC on."""
    kw.setdefault("trace", False)
    return ArrayContext(cluster=ClusterSpec(1, 8), node_grid=(1, 1, 1),
                        backend="jax", dtype="float32", seed=0,
                        pipeline=True, plan_cache=True, gc=True, **kw)


def _planted(n=16, rank=3, seed=4):
    """A rank-``rank`` tensor plus noise at 10 % of its norm, and inits."""
    rng = np.random.default_rng(seed)
    A, B, C = (rng.standard_normal((n, rank)) for _ in range(3))
    Xn = np.einsum("if,jf,kf->ijk", A, B, C)
    Xn += 0.1 * np.linalg.norm(Xn) / Xn.size ** 0.5 * rng.standard_normal(Xn.shape)
    Xn = Xn.astype(np.float32)
    inits = [rng.standard_normal((n, rank)).astype(np.float32) for _ in range(3)]
    return Xn, inits


def _fit(ctx, Xn, inits, iters=4):
    X = ctx.from_numpy(Xn, grid=(8, 1, 1))
    res = cp_als(X, rank=inits[0].shape[1], iters=iters, inits=inits,
                 track_fit=False)
    return [f.to_numpy() for f in res.factors]


class TestCPALSOnJax:
    """CP-ALS as the one-chip cell runs it: every cached plan a segment
    program (``core/plan.py``), float32 contractions at HIGHEST."""

    def test_matches_reference_1e5(self):
        Xn, inits = _planted()
        ctx = _cell_ctx()
        got = _fit(ctx, Xn, inits)
        ref = cp_als_reference(Xn, rank=3, iters=4,
                               inits=[f.astype(np.float64) for f in inits])
        assert ctx.loads()["backend_lowered_ops"] > 0
        for f, r in zip(got, ref):
            assert np.linalg.norm(f - r) <= 1e-5 * np.linalg.norm(r)

    def test_segment_programs_are_the_per_op_path_bit_for_bit(self):
        Xn, inits = _planted(seed=5)
        lowered_ctx, per_op_ctx = _cell_ctx(), _cell_ctx(trace=True)
        lowered, per_op = _fit(lowered_ctx, Xn, inits), _fit(per_op_ctx, Xn, inits)
        assert lowered_ctx.loads()["backend_programs"] > 0
        assert per_op_ctx.loads()["backend_programs"] == 0
        for a, b in zip(lowered, per_op):
            assert a.tobytes() == b.tobytes()

    def test_a_fit_frees_its_unfoldings_as_it_returns(self):
        """Device bytes alive after each fit, with the cyclic collector
        off: the second fit leaves what the first left, so no fit's
        unfoldings or resharded copies wait for a collection."""
        import gc

        import jax

        Xn, inits = _planted(seed=6)
        ctx = _cell_ctx()
        X = ctx.from_numpy(Xn, grid=(8, 1, 1))

        def fit():
            res = cp_als(X, rank=3, iters=2, inits=inits, track_fit=False)
            return [f.to_numpy() for f in res.factors]

        gc.collect()
        gc.disable()
        try:
            base = sum(a.nbytes for a in jax.live_arrays())
            live = []
            for _ in range(2):
                fit()
                live.append(sum(a.nbytes for a in jax.live_arrays()) - base)
        finally:
            gc.enable()
        assert live == [0, 0]
        assert ctx.executor.memory.total_live == Xn.size

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_layout_bytes_are_the_layout_ops_output(self, backend):
        """Per fit: two reshards of the tensor, each writing it once as
        slices and once as concatenated blocks; three unfoldings; one factor
        gather (a concatenation) per mode and sweep."""
        n, F, iters = 16, 3, 2
        Xn, inits = _planted(n, F)
        ctx = (_cell_ctx() if backend == "jax" else
               ArrayContext(cluster=ClusterSpec(1, 8), node_grid=(1, 1, 1),
                            backend="numpy"))
        X = ctx.from_numpy(Xn, grid=(8, 1, 1))
        before = ctx.loads()["layout_bytes"]
        res = cp_als(X, rank=F, iters=iters, inits=inits, track_fit=False)
        [f.to_numpy() for f in res.factors]
        itemsize = np.dtype(ctx.dtype).itemsize
        expect = itemsize * (7 * n ** 3 + 3 * iters * n * F)
        assert ctx.loads()["layout_bytes"] - before == expect
