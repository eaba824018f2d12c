"""Job kind ``dgemm``: C = A @ B on a block grid, ``(A @ B).compute().wait()``,
with A and B resident on the device and the previous C dropped before the
next product.

The comparison reads sampled rows of two products of the window: one whose
index is drawn from the seed and the last.  The seed draws the rows too.
Only those rows are kept: they are gathered on the device, block by block,
when the product is ready, and the product itself is dropped as any other.
The plain reference is those rows of A times B in float64 with numpy.  The
control is the reference in the precision below float32 at HIGHEST: three
bfloat16 passes (``a_hi b_hi + a_hi b_lo + a_lo b_hi``, summed in float64),
in place of the sampled rows.
"""
from __future__ import annotations

import gc
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.datagen import generator, standard_normal
from bench.harness import Check
from bench.jobs.newton_fit import context, rel_err
from bench.trace_reduce import span


def setup(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> "Dgemm":
    n = config["n"]
    A = standard_normal((n, n), seed, 1)
    B = standard_normal((n, n), seed, 2)
    return Dgemm(config, traffic, seed, A, B)


def _bf16_split(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    import ml_dtypes

    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi.astype(np.float64), lo.astype(np.float64)


class Dgemm:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, A: np.ndarray, B: np.ndarray):
        self.config = config
        self.A, self.B = A, B
        n = A.shape[0]
        rng = generator(seed, 3)
        self.rows = np.sort(rng.choice(n, size=config["check"]["rows"],
                                       replace=False))
        # the product sampled for the comparison, counted from the first
        # warm-up product; every warm-up product is sampled too, so that the
        # gathers compile before the window
        self.warmup = traffic["warmup_jobs"]
        self.keep = self.warmup + int(rng.integers(traffic["keep_one_of_first"]))
        self.ctx = context(config)
        g = config["layout"]["grid"]
        with span("load"):
            self.Ag = self.ctx.from_numpy(A, grid=(g, g))
            self.Bg = self.ctx.from_numpy(B, grid=(g, g))
        self.done = 0  # products so far
        self.C = None
        self.last: Dict[str, Any] = {}
        self.sampled: List[Dict[str, Any]] = []
        self._ref: Optional[np.ndarray] = None

    def sample(self, C) -> List[Tuple[np.ndarray, slice, Any]]:
        """The sampled rows of C, gathered on the device block by block:
        (positions among the sampled rows, columns, gathered block rows)."""
        import jax.numpy as jnp

        parts = []
        for idx in C.grid.iter_indices():
            rs, cs = C.grid.block_slices(idx)
            at = np.flatnonzero((self.rows >= rs.start) & (self.rows < rs.stop))
            if at.size:
                block = self.ctx.executor.get(C.block(idx).vid)
                parts.append((at, cs, jnp.take(block, jnp.asarray(self.rows[at] - rs.start),
                                               axis=0)))
        return parts

    def run(self) -> Dict[str, Any]:
        self.C = None
        with span("schedule"):
            C = (self.Ag @ self.Bg).compute()
        with span("wait"):
            C.wait()
        self.C = C
        index, self.done = self.done, self.done + 1
        record: Dict[str, Any] = {"product": index}
        if index < self.warmup or index == self.keep:
            with span("sample"):
                record["rows"] = self.sample(C)
            self.sampled.append(record)
        self.last = record
        return record

    def loads(self) -> Dict[str, float]:
        return self.ctx.loads()

    def counts(self, record: Dict[str, Any]) -> Tuple[float, float]:
        """2 n^3 operations; A and B read once and C written once."""
        n = self.A.shape[0]
        return 2.0 * n ** 3, 3.0 * 4.0 * n * n

    def collect(self) -> None:
        """Samples the last product, brings every sample to the host and
        frees the library's state."""
        if "rows" not in self.last:
            self.last["rows"] = self.sample(self.C)
            self.sampled.append(self.last)
        n = self.A.shape[0]
        for record in self.sampled:
            rows = np.zeros((len(self.rows), n), np.float32)
            for at, cs, part in record["rows"]:
                rows[at, cs] = np.asarray(part)
            record["rows"] = rows
        del self.Ag, self.Bg, self.C, self.ctx
        gc.collect()

    def reference(self) -> np.ndarray:
        if self._ref is None:
            self._ref = (self.A[self.rows].astype(np.float64)
                         @ self.B.astype(np.float64))
        return self._ref

    def check(self, records: List[Dict[str, Any]]) -> Tuple[List[Check], int]:
        """The sampled rows of every record that holds them."""
        limit = self.config["limits"]["c_rel_err"]
        ref = self.reference()
        errs = [rel_err(r["rows"].astype(np.float64), ref)
                for r in records if "rows" in r]
        if not errs:
            return [], 0
        failed = sum(1 for e in errs if not e <= limit)
        return [Check("c_rel_err", float(np.max(errs)), limit)], failed

    def control(self) -> List[Dict[str, Any]]:
        """A record in the program's form, with the sampled rows computed in
        three bfloat16 passes."""
        a_hi, a_lo = _bf16_split(self.A[self.rows])
        b_hi, b_lo = _bf16_split(self.B)
        return [{"product": 0, "rows": a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)}]
