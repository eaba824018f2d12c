"""Example-script smoke tests (subprocess) + remaining GLM model coverage."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec
from repro.glm import GLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable] + args, capture_output=True, text=True,
                       env=env, timeout=timeout, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


class TestExamples:
    def test_quickstart(self):
        out = run_example(["examples/quickstart.py"])
        assert "A + B moved 0 elements" in out
        assert "numerics match numpy: True" in out

    def test_tensor_factorization(self):
        out = run_example(["examples/tensor_factorization.py"])
        assert "double contraction matches numpy: True" in out

    def test_serve_lm_one_arch(self):
        out = run_example(["examples/serve_lm.py", "--arch", "gemma3-4b",
                           "--gen", "4"])
        assert "generated" in out

    def test_train_lm_tiny(self):
        out = run_example(["examples/train_lm.py", "--tiny", "--steps", "12",
                           "--batch", "2", "--seq", "32"])
        assert "loss=" in out


class TestPoissonGLM:
    def test_poisson_recovers_rate(self):
        rng = np.random.default_rng(0)
        n, d = 2048, 4
        X = rng.normal(0, 0.3, size=(n, d))
        beta_true = np.array([[0.5], [-0.3], [0.2], [0.1]])
        lam = np.exp(X @ beta_true)
        y = rng.poisson(lam).astype(np.float64)
        ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1), seed=0)
        m = GLM(ctx, model="poisson", solver="newton", max_iter=8, reg=1e-8)
        m.fit_numpy(X, y, row_blocks=8)
        assert np.allclose(m.beta, beta_true, atol=0.1)

    def test_poisson_matches_numpy_newton(self):
        rng = np.random.default_rng(1)
        X = rng.normal(0, 0.3, size=(512, 3))
        y = rng.poisson(np.exp(X @ np.array([[0.4], [0.1], [-0.2]]))).astype(float)
        ctx = ArrayContext(cluster=ClusterSpec(2, 2), node_grid=(2, 1), seed=0)
        m = GLM(ctx, model="poisson", solver="newton", max_iter=5, reg=0.0)
        m.fit_numpy(X, y, row_blocks=4)

        beta = np.zeros((3, 1))
        for _ in range(5):
            mu = np.exp(X @ beta)
            g = X.T @ (mu - y)
            H = X.T @ (mu * X)
            beta -= np.linalg.solve(H, g)
        assert np.allclose(m.beta, beta, atol=1e-8)
