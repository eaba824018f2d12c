"""Job kind ``newton_fit``: L2-regularised logistic regression fitted by
Newton's method through ``repro.glm.GLM.fit``, from beta = 0 each time, on
GraphArrays that stay on the device between fits.

The plain reference is a float64 Newton in numpy on the same host arrays.
Each fit of the window is compared with it: the relative error of its
coefficients, and the float64 gradient norm at its coefficients against the
solver's tolerance.  The control is the same reference on X rounded to
bfloat16, the precision below the configuration's float32; its coefficients
go through the same comparison.
"""
from __future__ import annotations

import gc
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.datagen import overlapping_gaussians
from bench.harness import Check
from bench.trace_reduce import span

REF_CHUNK_ROWS = 1 << 17


def setup(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> "NewtonFit":
    X, y = overlapping_gaussians(config["rows"], config["features"],
                                 config["sep"], seed)
    return NewtonFit(config, X, y)


def context(config: Dict[str, Any]):
    """The ``ArrayContext`` the configuration states, every flag given."""
    from repro.core import ArrayContext, ClusterSpec

    layout = config["layout"]
    flags = dict(config["context"])
    flags["mem_watermarks"] = tuple(flags["mem_watermarks"])
    return ArrayContext(cluster=ClusterSpec(*layout["cluster"]),
                        node_grid=tuple(layout["node_grid"]), **flags)


def _over_chunks(X: np.ndarray, y: np.ndarray, part,
                 bfloat16: bool = False) -> Tuple[np.ndarray, ...]:
    """``part(Xc, yc)`` on every chunk of rows, in float64 on threads, each
    result summed over the chunks.  With ``bfloat16`` every element of X is
    first rounded to bfloat16."""
    import ml_dtypes
    from threadpoolctl import threadpool_limits

    def one(start: int):
        Xc = X[start:start + REF_CHUNK_ROWS]
        if bfloat16:
            Xc = Xc.astype(ml_dtypes.bfloat16)
        return part(Xc.astype(np.float64),
                    y[start:start + REF_CHUNK_ROWS, 0].astype(np.float64))

    with threadpool_limits(1, user_api="blas"), \
            ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        parts = list(pool.map(one, range(0, X.shape[0], REF_CHUNK_ROWS)))
    return tuple(np.sum([p[i] for p in parts], axis=0)
                 for i in range(len(parts[0])))


def _sigmoid(z: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return 1.0 / (1.0 + np.exp(-z))


def _newton_part(Xc: np.ndarray, yc: np.ndarray, beta: np.ndarray):
    mu = _sigmoid(Xc @ beta)
    return Xc.T @ (mu - yc), (Xc * (mu * (1.0 - mu))[:, None]).T @ Xc


def newton_reference(X: np.ndarray, y: np.ndarray, reg: float,
                     bfloat16: bool = False, max_iter: int = 50) -> np.ndarray:
    """Plain float64 Newton for L2-regularised logistic regression from
    beta = 0: g = X^T (mu - y) + reg beta, H = X^T diag(mu (1 - mu)) X + reg I,
    summed over chunks of rows on threads."""
    d = X.shape[1]
    beta = np.zeros(d)
    for _ in range(max_iter):
        g, H = _over_chunks(X, y, partial(_newton_part, beta=beta), bfloat16)
        step = np.linalg.solve(H + reg * np.eye(d), g + reg * beta)
        beta = beta - step
        if np.linalg.norm(step) <= 1e-12 * (1.0 + np.linalg.norm(beta)):
            return beta
    raise RuntimeError(f"reference Newton did not converge in {max_iter} steps")


def gradient_norms(X: np.ndarray, y: np.ndarray, reg: float,
                   betas: np.ndarray) -> np.ndarray:
    """The float64 norm of X^T (mu - y) + reg beta at each column of
    ``betas`` (d x k), all columns in one pass over X."""
    def part(Xc, yc):
        return (Xc.T @ (_sigmoid(Xc @ betas) - yc[:, None]),)

    (G,) = _over_chunks(X, y, part)
    return np.linalg.norm(G + reg * betas, axis=0)


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


class NewtonFit:
    def __init__(self, config: Dict[str, Any], X: np.ndarray, y: np.ndarray):
        self.config = config
        self.X, self.y = X, y
        self.ctx = context(config)
        q = config["layout"]["row_blocks"]
        with span("load"):
            self.Xg = self.ctx.from_numpy(X, grid=(q, 1))
            self.yg = self.ctx.from_numpy(y, grid=(q, 1))
        self._ref: Optional[np.ndarray] = None

    def run(self) -> Dict[str, Any]:
        from repro.glm import GLM

        s = self.config["solver"]
        model = GLM(self.ctx, model=s["model"], solver=s["solver"],
                    max_iter=s["max_iter"], tol=s["tol"], reg=s["reg"])
        with span("fit"):
            model.fit(self.Xg, self.yg)
        with span("read_beta"):
            beta = model.beta.ravel()
        res = model.result
        return {"beta": beta, "iterations": res.iterations}

    def loads(self) -> Dict[str, float]:
        return self.ctx.loads()

    def counts(self, record: Dict[str, Any]) -> Tuple[float, float]:
        """Per Newton iteration: X beta, X^T (mu - y), w X and X^T (w X) take
        2 n d^2 + 5 n d operations; one pass reads X and y once, at their
        unpadded float32 size."""
        n, d = self.X.shape
        it = record["iterations"]
        return it * (2.0 * n * d * d + 5.0 * n * d), it * 4.0 * n * (d + 1)

    def collect(self) -> None:
        del self.Xg, self.yg, self.ctx
        gc.collect()

    def reference(self) -> np.ndarray:
        if self._ref is None:
            self._ref = newton_reference(self.X, self.y, self.config["solver"]["reg"])
        return self._ref

    def check(self, records: List[Dict[str, Any]]) -> Tuple[List[Check], int]:
        """Each fit's coefficients against the reference's, and the float64
        gradient norm at them against the solver's tolerance (a fit that
        stopped on ``max_iter`` ends above it).  Fits that gave the same
        coefficients are compared once."""
        limit = self.config["limits"]["beta_rel_err"]
        tol = self.config["solver"]["tol"]
        ref = self.reference()
        betas, which = np.unique(np.stack([np.ravel(r["beta"]) for r in records]),
                                 axis=0, return_inverse=True)
        errs = np.array([rel_err(b, ref) for b in betas])
        norms = gradient_norms(self.X, self.y, self.config["solver"]["reg"],
                               betas.T.astype(np.float64))
        failed = sum(1 for i in np.ravel(which)
                     if not (errs[i] <= limit and norms[i] <= tol))
        return [Check("beta_rel_err", float(np.max(errs)), limit),
                Check("grad_norm", float(np.max(norms)), tol)], failed

    def control(self) -> List[Dict[str, Any]]:
        """A record in the program's form, with the coefficients of the
        reference run on X rounded to bfloat16."""
        return [{"beta": newton_reference(self.X, self.y, self.config["solver"]["reg"],
                                          bfloat16=True)}]
