"""The weighted Gram ``X^T diag(w) X``: the ``wgram`` block op, its
``weighted_gram`` constructor, and Newton's Hessian built from it.

One ``wgram`` op per row block replaces the pair ``C = w * X`` and
``X.T @ C``, so no n x d array is formed; ``BackendStats.gram_ops`` counts
the ops on the per-op and the lowered drain alike."""
import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec, weighted_gram
from repro.core.graph_array import execute_block_op
from repro.glm import GLM


@pytest.fixture
def x64_restored():
    import jax

    was = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", was)


def _xw(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)), rng.random((n, 1)) + 0.1


@pytest.mark.parametrize("d", [1, 28, 33])
@pytest.mark.parametrize("dtype, rtol", [("float32", 1e-5), ("float64", 1e-12)])
def test_wgram_lowering_matches_the_numpy_reference(x64_restored, dtype, rtol, d):
    from repro.backend import make_backend

    x, w = (a.astype(dtype) for a in _xw(300, d, seed=d))
    ref = execute_block_op("wgram", {}, [x.astype(np.float64), w.astype(np.float64)])
    be = make_backend("jax", dtype=dtype)
    got = be.to_host(be.execute("wgram", {}, [be.from_host(x, (0, 0)),
                                              be.from_host(w, (0, 0))], (0, 0)))
    assert got.shape == ref.shape == (d, d) and got.dtype == np.dtype(dtype)
    assert np.abs(got - ref).max() <= rtol * np.abs(ref).max()
    np.testing.assert_array_equal(execute_block_op("wgram", {}, [x, w]),
                                  (x * w).T @ x)
    assert be.stats.gram_ops == 1


def _ctx(backend, nodes=1, **kw):
    return ArrayContext(cluster=ClusterSpec(nodes, 4), node_grid=(nodes, 1),
                        backend=backend, **kw)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("blocks", [1, 3, 8])
def test_weighted_gram_matches_numpy(backend, blocks):
    x, w = _xw(240, 7, seed=blocks)
    ctx = _ctx(backend, dtype="float64" if backend == "numpy" else "float32")
    H = weighted_gram(ctx.from_numpy(x, grid=(blocks, 1)),
                      ctx.from_numpy(w, grid=(blocks, 1)))
    assert H.shape == (7, 7) and H.grid.grid == (1, 1)
    ref = (x * w).T @ x
    rtol = 1e-12 if backend == "numpy" else 1e-5
    got = H.to_numpy()
    assert np.abs(got - ref).max() <= rtol * np.abs(ref).max()
    assert ctx.loads()["backend_gram_ops"] == blocks


def test_weighted_gram_needs_row_blocks_and_matching_weights():
    ctx = _ctx("numpy")
    x, w = _xw(40, 6)
    with pytest.raises(ValueError):
        weighted_gram(ctx.from_numpy(x, grid=(2, 2)), ctx.from_numpy(w, grid=(2, 1)))
    with pytest.raises(ValueError):
        weighted_gram(ctx.from_numpy(x, grid=(4, 1)), ctx.from_numpy(w, grid=(2, 1)))
    with pytest.raises(ValueError):
        weighted_gram(ctx.from_numpy(x, grid=(2, 1)),
                      ctx.from_numpy(np.hstack([w, w]), grid=(2, 1)))


# -- Newton through the weighted Gram -------------------------------------------

def _data(model, n=1_200, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0.0, 0.5, size=(n, d))
    beta = rng.normal(0.0, 0.5, size=(d, 1))
    z = X @ beta
    if model == "logistic":
        y = (rng.random((n, 1)) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    elif model == "poisson":
        y = rng.poisson(np.exp(z)).astype(np.float64)
    else:
        y = z + 0.1 * rng.standard_normal((n, 1))
    return X, y


def _numpy_newton(model, X, y, reg, iters):
    """Plain float64 Newton: g = X^T (mu - y) + reg beta,
    H = X^T diag(w) X + reg I, from beta = 0."""
    d = X.shape[1]
    beta = np.zeros((d, 1))
    for _ in range(iters):
        z = X @ beta
        if model == "logistic":
            mu = 1.0 / (1.0 + np.exp(-z))
            w = mu * (1.0 - mu)
        elif model == "poisson":
            mu = np.exp(z)
            w = mu
        else:
            mu, w = z, np.ones_like(z)
        g = X.T @ (mu - y) + reg * beta
        H = (X * w).T @ X + reg * np.eye(d)
        beta = beta - np.linalg.solve(H, g)
    return beta


@pytest.mark.parametrize("backend, dtype", [("numpy", "float64"),
                                            ("jax", "float32")])
@pytest.mark.parametrize("model", ["logistic", "linear", "poisson"])
def test_newton_fits_match_a_float64_numpy_newton(backend, dtype, model):
    X, y = _data(model)
    reg, iters = 1e-6, 8
    ref = _numpy_newton(model, X, y, reg, iters)
    ctx = _ctx(backend, dtype=dtype, pipeline=True, plan_cache=True, gc=True)
    fit = GLM(ctx, model=model, max_iter=iters, tol=0.0, reg=reg).fit(
        ctx.from_numpy(X, grid=(8, 1)), ctx.from_numpy(y, grid=(8, 1)))
    assert fit.result.iterations == iters
    beta = fit.beta
    assert np.linalg.norm(beta - ref) / np.linalg.norm(ref) < 1e-6
    assert ctx.loads()["backend_gram_ops"] == 8 * iters


@pytest.mark.parametrize("drain", ["lowered", "per_op"])
def test_gram_ops_count_eight_an_iteration_on_eight_row_blocks(drain):
    X, y = _data("logistic", seed=3)
    ctx = _ctx("jax", pipeline=True, plan_cache=True, gc=True,
               trace=drain == "per_op")
    GLM(ctx, max_iter=3, tol=0.0, reg=1e-6).fit(
        ctx.from_numpy(X, grid=(8, 1)), ctx.from_numpy(y, grid=(8, 1)))
    loads = ctx.loads()
    assert loads["backend_gram_ops"] == 8 * 3
    assert (loads["backend_lowered_ops"] > 0) == (drain == "lowered")
