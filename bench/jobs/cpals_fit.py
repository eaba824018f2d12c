"""Job kind ``cpals_fit``: rank-F CP decompositions of a dense 3-way tensor by
alternating least squares through ``repro.factor.cp_als``, a whole fit of a
fixed number of sweeps each time, from the same initial factors, on a
tensor that stays on the device between fits.

The tensor is a planted rank-F tensor [[A, B, C]] (A, B and C standard
normal) plus Gaussian noise at a stated share of its norm, all made from the
seed.  The plain reference is its own float64 ALS in numpy, in the same
update order: each mode's factor from the mode's unfolding times the
Khatri-Rao product of the other two factors, over the Hadamard product of
their Grams.  It unfolds the tensor once per mode and runs each product in
chunks of rows on threads.  Each fit of the window is compared with it: the
largest relative error of the three factors, and the gap between the two
relative fits 1 - ||X - [[A, B, C]]|| / ||X||, both computed in float64.
The factors' distance grows with how far ten sweeps from random factors
carry a rounding error, which differs from seed to seed by orders of
magnitude; so the precision is also held by a reading that does not: the
residual of the fit's last update, C, in its normal equations given A and
B (``normal_eq_residual``).

The control is the same reference computed in bfloat16, the precision below
the configuration's float32, as one bfloat16 pass of the MXU computes it:
the operands of every product (X, the Khatri-Rao product, the factors in
the Grams) rounded to bfloat16, the sums in float64.  Its factors go
through the same comparison.

A record keeps the three factors stacked row-wise under ``rows`` (A, then
B, then C), a key that ``bench/limits.py`` leaves out of what it prints.
"""
from __future__ import annotations

import gc
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

from bench.datagen import fill_rows, standard_normal
from bench.harness import Check
from bench.jobs.newton_fit import context, rel_err
from bench.trace_reduce import span

REF_CHUNK_ROWS = 16  # rows of an unfolding per reference task
OTHERS = {0: (1, 2), 1: (0, 2), 2: (0, 1)}  # the other modes, in unfolding order


# The reference's threads, made once, and each thread's buffers, made once:
# a chunk in float64 is 75 MB at n = 768, and with a fresh one for each of
# the thousands of chunks a check reads, the memory in use on a TPU v5e
# host grew past its 40 GiB while the process's resident set stayed near
# 20 GB.
_POOL: Optional[ThreadPoolExecutor] = None
_LOCAL = threading.local()


def _pool() -> ThreadPoolExecutor:
    """One worker for each CPU this process may run on."""
    global _POOL
    if _POOL is None:
        _POOL = ThreadPoolExecutor(len(os.sched_getaffinity(0)))
    return _POOL


def _buffer(name: str, shape: Tuple[int, ...]) -> np.ndarray:
    """This thread's float64 buffer ``name``, of ``shape``; kept for the
    next call."""
    size = int(np.prod(shape))
    buf = getattr(_LOCAL, name, None)
    if buf is None or buf.size < size:
        buf = np.empty(size)
        setattr(_LOCAL, name, buf)
    return buf[:size].reshape(shape)


def mttkrp_rows(c: np.ndarray, o1: np.ndarray, o2: np.ndarray) -> np.ndarray:
    """Rows of X_(n) KR(o1, o2) for the rows ``c`` of an unfolding, whose
    columns run over (o1's mode, o2's mode) in C order: contracted with o2
    over the last mode, then with o1."""
    r, a = c.shape[0], o1.shape[0]
    t = _buffer("t", (r * a, o2.shape[1]))
    np.matmul(c.reshape(r * a, o2.shape[0]), o2, out=t)
    return np.einsum("raf,af->rf", t.reshape(r, a, -1), o1)


def planted_tensor(n: int, rank: int, noise: float, seed: int) -> np.ndarray:
    """float32 n x n x n tensor [[A, B, C]] + noise, A, B and C standard
    normal (n x rank), the noise Gaussian with a norm of ``noise`` times the
    planted part's; the planted part is computed in float64, by rows."""
    from threadpoolctl import threadpool_limits

    A, B, C = (standard_normal((n, rank), seed, s).astype(np.float64)
               for s in (1, 2, 3))
    X = np.empty((n, n, n), np.float32)
    squares: Dict[int, float] = {}

    def planted(rng, s, e):
        X[s:e] = ((A[s:e, None, :] * B[None]).reshape(-1, rank) @ C.T).reshape(
            e - s, n, n)
        squares[s] = float(np.sum(np.square(X[s:e], dtype=np.float64)))

    with threadpool_limits(1, user_api="blas"):
        fill_rows(n, n * n, seed, 0, planted)
    sigma = np.float32(noise * np.sqrt(sum(squares.values()) / X.size))

    def noisy(rng, s, e):
        X[s:e] += sigma * rng.standard_normal((e - s, n, n), dtype=np.float32)

    fill_rows(n, n * n, seed, 4, noisy)
    return X


def unfold(X: np.ndarray, mode: int) -> np.ndarray:
    """The mode-``mode`` unfolding, rows indexed by that mode, columns in
    the C order of the other two axes (a view for mode 0, else a copy)."""
    return np.ascontiguousarray(np.moveaxis(X, mode, 0)).reshape(X.shape[mode], -1)


def _bf16(a: np.ndarray) -> np.ndarray:
    """``a`` rounded to bfloat16, held in float64."""
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def _by_rows(Xn: np.ndarray, part, bfloat16: bool = False) -> List[Any]:
    """``part(rows, start)`` on every chunk of rows of ``Xn``, each chunk
    in float64 (rounded to bfloat16 first with ``bfloat16``), on threads.
    ``part`` gets a buffer that the thread reuses: it keeps no reference to
    it."""
    from threadpoolctl import threadpool_limits

    def one(start: int):
        c = Xn[start:start + REF_CHUNK_ROWS]
        buf = _buffer("chunk", c.shape)
        np.copyto(buf, c.astype(ml_dtypes.bfloat16) if bfloat16 else c)
        return part(buf, start)

    with threadpool_limits(1, user_api="blas"):
        return list(_pool().map(one, range(0, Xn.shape[0], REF_CHUNK_ROWS)))


def als_reference(unfoldings: Sequence[np.ndarray], inits: Sequence[np.ndarray],
                  sweeps: int, bfloat16: bool = False) -> List[np.ndarray]:
    """Plain float64 ALS: per sweep, for modes 0, 1, 2 in turn,
    F_n = X_(n) KR(o1, o2) ((o1^T o1) * (o2^T o2))^-1.  With ``bfloat16``
    the operands of each product are rounded to bfloat16 first: the
    unfolding, the Khatri-Rao product (formed whole) and the Grams'
    factors."""
    factors = [np.asarray(f, np.float64) for f in inits]
    for _ in range(sweeps):
        for mode in range(3):
            o1, o2 = (factors[m] for m in OTHERS[mode])
            if bfloat16:
                kr = _buffer("kr", (o1.shape[0] * o2.shape[0], o1.shape[1]))
                np.multiply(o1[:, None, :], o2[None, :, :],
                            out=kr.reshape(o1.shape[0], o2.shape[0], -1))
                np.copyto(kr, kr.astype(ml_dtypes.bfloat16))
                M = np.concatenate(_by_rows(unfoldings[mode],
                                            lambda c, s: c @ kr, bfloat16))
                o1, o2 = _bf16(o1), _bf16(o2)
            else:
                M = np.concatenate(_by_rows(
                    unfoldings[mode], lambda c, s: mttkrp_rows(c, o1, o2)))
            G = (o1.T @ o1) * (o2.T @ o2)
            factors[mode] = np.linalg.solve(G.T, M.T).T
    return factors


def relative_fits(X0: np.ndarray, factor_sets: Sequence[Sequence[np.ndarray]]
                  ) -> np.ndarray:
    """1 - ||X - [[A, B, C]]|| / ||X|| in float64 for each (A, B, C), all
    in one pass over the mode-0 unfolding ``X0``: ||X - [[A, B, C]]||^2 =
    ||X||^2 - 2 <X, [[A, B, C]]> + sum((A^T A) * (B^T B) * (C^T C)), the
    inner product from the mode-0 MTTKRP."""
    sets = [[np.asarray(f, np.float64) for f in fs] for fs in factor_sets]

    def part(c, s):
        rows = slice(s, s + c.shape[0])
        return [float(np.vdot(c, c))] + [
            float(np.vdot(A[rows], mttkrp_rows(c, B, C))) for A, B, C in sets]

    sums = np.sum(_by_rows(X0, part), axis=0)
    norms = [np.sum((A.T @ A) * (B.T @ B) * (C.T @ C)) for A, B, C in sets]
    residuals = sums[0] - 2.0 * sums[1:] + np.array(norms)
    return 1.0 - np.sqrt(np.maximum(residuals, 0.0) / sums[0])


def normal_eq_residual(X2: np.ndarray, factors: Sequence[np.ndarray]) -> float:
    """||X_(2) KR(A, B) - C G|| / ||X_(2) KR(A, B)||, G = (A^T A) * (B^T B),
    in float64 over the mode-2 unfolding ``X2``: how far C, the factor a
    sweep updates last, is from solving its normal equations given A and B.
    A backward error of the last update alone, so it does not grow with how
    far the sweeps before it carried a rounding error, as the factors'
    distance from the reference's does."""
    A, B, C = (np.asarray(f, np.float64) for f in factors)
    M = np.concatenate(_by_rows(X2, lambda c, s: mttkrp_rows(c, A, B)))
    return float(np.linalg.norm(M - C @ ((A.T @ A) * (B.T @ B)))
                 / np.linalg.norm(M))


def setup(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> "CPALSFit":
    n, rank = config["n"], config["rank"]
    X = planted_tensor(n, rank, config["noise"], seed)
    inits = [standard_normal((n, rank), seed, s) for s in (5, 6, 7)]
    return CPALSFit(config, X, inits)


class CPALSFit:
    def __init__(self, config: Dict[str, Any], X: np.ndarray,
                 inits: List[np.ndarray]):
        self.config = config
        self.X = X
        self.inits = inits
        self.ctx = context(config)
        with span("load"):
            self.Xg = self.ctx.from_numpy(X, grid=(config["layout"]["slabs"], 1, 1))
        self._ref: Optional[Tuple[List[np.ndarray], float]] = None

    def run(self) -> Dict[str, Any]:
        from repro.factor import cp_als

        c = self.config
        with span("fit"):
            res = cp_als(self.Xg, rank=c["rank"], iters=c["sweeps"],
                         inits=self.inits, method=c["method"], track_fit=False)
        with span("read_factors"):
            rows = np.concatenate([f.to_numpy() for f in res.factors])
        return {"rows": rows, "sweeps": res.iterations}

    def loads(self) -> Dict[str, float]:
        return self.ctx.loads()

    def counts(self, record: Dict[str, Any]) -> Tuple[float, float]:
        """Per sweep, three MTTKRPs of 2 n^3 F operations, each reading the
        float32 tensor once; per fit, two layout changes that each read and
        write it once (the mode-0 unfolding is a reshape)."""
        n, F = self.config["n"], self.config["rank"]
        sweeps = record["sweeps"]
        return 3.0 * sweeps * 2.0 * n ** 3 * F, 4.0 * n ** 3 * (3 * sweeps + 4)

    def collect(self) -> None:
        del self.Xg, self.ctx
        gc.collect()

    def _unfoldings(self) -> List[np.ndarray]:
        return [unfold(self.X, mode) for mode in range(3)]

    def reference(self) -> Tuple[List[np.ndarray], float]:
        """The reference's factors and their relative fit."""
        if self._ref is None:
            factors = als_reference(self._unfoldings(), self.inits,
                                    self.config["sweeps"])
            (fit,) = relative_fits(unfold(self.X, 0), [factors])
            self._ref = factors, float(fit)
        return self._ref

    def _split(self, rows: np.ndarray) -> List[np.ndarray]:
        n = self.config["n"]
        return [rows[m * n:(m + 1) * n].astype(np.float64) for m in range(3)]

    def check(self, records: List[Dict[str, Any]]) -> Tuple[List[Check], int]:
        """Each fit's factors against the reference's, its relative fit
        against the reference's, and the residual of its last update.  Fits
        that gave the same factors are compared once."""
        limits = self.config["limits"]
        ref, ref_fit = self.reference()
        first: Dict[bytes, int] = {}  # a record's factors -> their set
        sets, which = [], []
        for r in records:
            rows = np.asarray(r["rows"])
            key = rows.tobytes()
            if key not in first:
                first[key] = len(sets)
                sets.append(self._split(rows))
            which.append(first[key])
        X2 = unfold(self.X, 2)
        readings = {
            "factor_rel_err": [max(rel_err(f, r) for f, r in zip(fs, ref))
                               for fs in sets],
            "fit_gap": np.abs(relative_fits(unfold(self.X, 0), sets) - ref_fit),
            "normal_eq_residual": [normal_eq_residual(X2, fs) for fs in sets],
        }
        failed = sum(1 for i in which
                     if not all(v[i] <= limits[name] for name, v in readings.items()))
        return [Check(name, float(np.max(v)), limits[name])
                for name, v in readings.items()], failed

    def control(self) -> List[Dict[str, Any]]:
        """A record in the program's form, with the factors of the reference
        computed in bfloat16."""
        factors = als_reference(self._unfoldings(), self.inits,
                                self.config["sweeps"], bfloat16=True)
        return [{"rows": np.concatenate(factors), "sweeps": self.config["sweeps"]}]
