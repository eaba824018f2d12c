"""Communication-avoiding linalg (§8): blocked Cholesky + triangular solve
and sketch-based randomized SVD — combinatorial numpy-oracle parity across
uneven grids, f32/f64, and all three backends (cf. NumS test_np_linalg),
plan-cache replay on an iterative Cholesky solve loop, comm-bound ratio
accounting, and validation-error quality."""
import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec
from repro.linalg import (
    cholesky,
    cholesky_solve,
    rsvd,
    tsqr_direct,
    tsqr_indirect,
)

BACKENDS = ["numpy", "jax", "pallas"]
DTYPES = ["float32", "float64"]
# relative-error ceilings per dtype (factorizations accumulate ~n rounding
# steps, so f32 sits well above eps=1.2e-7; f64 ceilings include the 1e-6
# acceptance bound with margin)
RTOL = {"float32": 2e-4, "float64": 1e-9}


def make_ctx(k=4, r=2, ng=None, **kw):
    return ArrayContext(cluster=ClusterSpec(k, r), node_grid=ng or (k, 1),
                        seed=0, **kw)


def spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return m @ m.T + n * np.eye(n)


def low_rank(m, d, svals, seed=0):
    rng = np.random.default_rng(seed)
    r = len(svals)
    u = np.linalg.qr(rng.standard_normal((m, r)))[0]
    v = np.linalg.qr(rng.standard_normal((d, r)))[0]
    return u @ np.diag(np.asarray(svals, dtype=float)) @ v.T


def rel(err, ref):
    return np.abs(err).max() / max(np.abs(ref).max(), 1.0)


class TestCholeskyParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("n,q", [(50, 3), (64, 4), (40, 1)])
    def test_oracle_parity(self, backend, dtype, n, q):
        a_np = spd(n)
        ctx = make_ctx(backend=backend, dtype=dtype)
        L = cholesky(ctx, ctx.from_numpy(a_np, grid=(q, q))).to_numpy()
        assert np.array_equal(L, np.tril(L)), "strict upper must be zero"
        assert rel(L @ L.T - a_np, a_np) <= RTOL[dtype]
        if dtype == "float64":
            assert rel(L - np.linalg.cholesky(a_np), L) <= 1e-9

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("n,q,cols", [(50, 3, 2), (64, 4, 1)])
    def test_solve_oracle_parity(self, backend, dtype, n, q, cols):
        a_np, b_np = spd(n), np.random.default_rng(1).standard_normal((n, cols))
        ctx = make_ctx(backend=backend, dtype=dtype)
        L = cholesky(ctx, ctx.from_numpy(a_np, grid=(q, q)))
        x = cholesky_solve(ctx, L, ctx.from_numpy(b_np, grid=(q, 1)))
        assert rel(x.to_numpy() - np.linalg.solve(a_np, b_np), 1) <= RTOL[dtype]

    def test_solve_1d_rhs(self):
        n, q = 48, 3
        a_np, b_np = spd(n), np.random.default_rng(2).standard_normal(n)
        ctx = make_ctx(backend="numpy")
        L = cholesky(ctx, ctx.from_numpy(a_np, grid=(q, q)))
        x = cholesky_solve(ctx, L, ctx.from_numpy(b_np, grid=(q,)))
        assert np.allclose(x.to_numpy(), np.linalg.solve(a_np, b_np))

    def test_validation(self):
        ctx = make_ctx(backend="sim")
        with pytest.raises(ValueError, match=r"square 2-D"):
            cholesky(ctx, ctx.random((32, 16), grid=(2, 1)))
        with pytest.raises(ValueError, match=r"square block grid.*\(2, 4\)"):
            cholesky(ctx, ctx.random((32, 32), grid=(2, 4)))
        A = ctx.random((32, 32), grid=(2, 2))
        L = cholesky(ctx, A)
        with pytest.raises(ValueError, match=r"row grid"):
            cholesky_solve(ctx, L, ctx.random((32, 1), grid=(4, 1)))


class TestRsvdParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("m,q", [(200, 3), (256, 4), (96, 1)])
    def test_exact_rank_reconstruction(self, backend, dtype, m, q):
        svals = [10.0, 5.0, 2.0, 1.0, 0.5]
        x_np = low_rank(m, 24, svals)
        ctx = make_ctx(backend=backend, dtype=dtype)
        U, S, V = rsvd(ctx, ctx.from_numpy(x_np, grid=(q, 1)),
                       rank=len(svals), oversample=0, seed=1)
        Un, Sn, Vn = U.to_numpy(), S.to_numpy(), V.to_numpy()
        assert rel(Un @ np.diag(Sn) @ Vn.T - x_np, x_np) <= RTOL[dtype]
        assert np.all(np.diff(Sn) <= 1e-6), "singular values must descend"
        r = len(svals)
        assert rel(Un.T @ Un - np.eye(r), 1) <= RTOL[dtype]
        assert rel(Vn.T @ Vn - np.eye(r), 1) <= RTOL[dtype]
        assert np.abs(Sn - np.asarray(svals)).max() <= 10 * RTOL[dtype]

    def test_full_rank_with_oversampling_jax_f64(self):
        """The 1e-6-rel acceptance case: full-numerical-rank input, sketch
        covering all d directions, compiled jax backend at f64."""
        m, d = 160, 10
        x_np = np.random.default_rng(3).standard_normal((m, d))
        ctx = make_ctx(backend="jax", dtype="float64")
        U, S, V = rsvd(ctx, ctx.from_numpy(x_np, grid=(4, 1)),
                       rank=6, oversample=8, seed=2)  # l = min(14, d) = d
        recon = U.to_numpy() @ np.diag(S.to_numpy()) @ V.to_numpy().T
        assert rel(recon - x_np, x_np) <= 1e-6
        sv = np.linalg.svd(x_np, compute_uv=False)
        assert np.allclose(S.to_numpy(), sv, rtol=1e-8)

    def test_power_iterations_sharpen_decay(self):
        d, r = 30, 4
        rng = np.random.default_rng(4)
        svals = np.concatenate([[8.0, 4.0, 2.0, 1.0], 1e-3 * rng.random(d - r)])
        u = np.linalg.qr(rng.standard_normal((200, d)))[0]
        v = np.linalg.qr(rng.standard_normal((d, d)))[0]
        x_np = u @ np.diag(svals) @ v.T
        ctx = make_ctx(backend="numpy")
        _, S, _ = rsvd(ctx, ctx.from_numpy(x_np, grid=(4, 1)),
                       rank=r, oversample=4, power_iters=2, seed=5)
        assert np.abs(S.to_numpy()[:r] - svals[:r]).max() <= 1e-8

    def test_validation(self):
        ctx = make_ctx(backend="sim")
        with pytest.raises(ValueError, match="single column partition"):
            rsvd(ctx, ctx.random((64, 16), grid=(2, 2)), rank=4)
        with pytest.raises(ValueError, match="rank"):
            rsvd(ctx, ctx.random((64, 16), grid=(4, 1)), rank=0)


class TestCommRatio:
    """Measured moved elements vs the bounds.py floors (the CI-gated
    metric), on deterministic sim clusters."""

    def test_cholesky_ratio_within_gate(self):
        ctx = make_ctx(backend="sim")
        cholesky(ctx, ctx.random((256, 256), grid=(4, 4)))
        loads = ctx.loads()
        assert loads["comm_lower_cholesky"] > 0
        assert loads["comm_ratio_cholesky"] <= 2.0

    def test_tsqr_ratio_within_gate(self):
        ctx = make_ctx(backend="sim")
        tsqr_indirect(ctx, ctx.random((16 * 1024, 64), grid=(16, 1)))
        assert ctx.loads()["comm_ratio_tsqr"] <= 1.5

    def test_rsvd_ratio_within_gate(self):
        ctx = make_ctx(backend="sim")
        rsvd(ctx, ctx.random((2048, 32), grid=(8, 1)),
             rank=8, oversample=8, power_iters=1)
        assert ctx.loads()["comm_ratio_rsvd"] <= 2.5

    def test_note_comm_accumulates(self):
        ctx = make_ctx(backend="sim")
        ctx.sched_stats.note_comm("x", 10.0, 4.0)
        ctx.sched_stats.note_comm("x", 2.0, 4.0)
        assert ctx.sched_stats.comm_ratios["x"] == pytest.approx(1.5)
        d = ctx.sched_stats.as_dict()
        assert d["comm_moved_x"] == 12.0 and d["comm_ratio_x"] == 1.5
        ctx.sched_stats.reset()
        assert not ctx.sched_stats.comm_ratios

    def test_zero_lower_bound_single_node(self):
        ctx = make_ctx(k=1, r=2, ng=(1, 1), backend="sim")
        tsqr_indirect(ctx, ctx.random((512, 16), grid=(4, 1)))
        assert ctx.loads()["comm_ratio_tsqr"] == 1.0


class TestCholeskyPlanCache:
    def _loop(self, plan_cache, iters=3):
        n, q = 64, 4
        a_np, b_np = spd(n), np.random.default_rng(6).standard_normal((n, 2))
        ctx = make_ctx(backend="numpy", plan_cache=plan_cache)
        xs = []
        for _ in range(iters):
            A = ctx.from_numpy(a_np, grid=(q, q))
            L = cholesky(ctx, A)
            xs.append(cholesky_solve(
                ctx, L, ctx.from_numpy(b_np, grid=(q, 1))).to_numpy())
        return ctx, xs

    def test_iterative_solve_hits_cache(self):
        ctx, xs = self._loop(plan_cache=True)
        assert ctx.sched_stats.plan_hits > 0
        for x in xs[1:]:
            assert np.array_equal(x, xs[0])

    def test_cache_on_off_bitwise_identical(self):
        _, cold = self._loop(plan_cache=False)
        ctx, cached = self._loop(plan_cache=True)
        assert ctx.sched_stats.plan_hits > 0
        for a, b in zip(cold, cached):
            assert np.array_equal(a, b)


class TestTsqrValidationErrors:
    def test_column_partition_error_states_grid(self):
        ctx = make_ctx(backend="sim")
        X = ctx.random((64, 8), grid=(4, 2))
        with pytest.raises(ValueError, match=r"got grid \(4, 2\)"):
            tsqr_direct(ctx, X)
        with pytest.raises(ValueError, match=r"got grid \(4, 2\)"):
            tsqr_indirect(ctx, X)

    def test_short_block_error_states_shape(self):
        ctx = make_ctx(backend="sim")
        X = ctx.random((24, 8), grid=(6, 1))  # 4-row blocks, d=8
        with pytest.raises(ValueError, match=r"block \(0, 0\) has shape \(4, 8\)"):
            tsqr_direct(ctx, X)
