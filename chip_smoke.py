"""Smoke test of the block runtime on TPU chips, through its user entry points.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: both phases on the multi-node path

Phase A fits Newton logistic regression at the UCI HIGGS shape used by the
NumS paper's logistic-regression study (11 000 000 rows x 28 features) with
``LogisticRegression(solver="newton").fit_numpy`` on the ``jax`` backend, and
compares the coefficients with a plain numpy float64 Newton on the same
arrays.  The data is made from ``--seed`` (two overlapping Gaussian classes,
so the optimum is finite).

Phase B multiplies two 16384 x 16384 float32 arrays on a 4 x 4 block grid
through the ``pallas`` backend, compares 256 seeded rows of the product with
numpy float64, and shows that the block matmul lowers to a Mosaic kernel
(``tpu_custom_call``), not to the Pallas interpreter.

With ``--chips 4`` both phases run on four nodes, one per chip, and the
script also checks that node i's blocks live on ``jax.devices()[i]`` and that
operands crossed chips.

Everything runs in this one process.  The script exits non-zero, and prints
no result line, unless JAX's first device is a TPU and every check passes.
Its last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.backend import GLOBAL_COMPILE_CACHE  # noqa: E402
from repro.core import ArrayContext, ClusterSpec  # noqa: E402
from repro.glm import LogisticRegression  # noqa: E402
from repro.glm.data import overlapping_gaussians  # noqa: E402

HIGGS_ROWS, HIGGS_FEATURES = 11_000_000, 28
# Class-mean separation per feature: the classes overlap (Bayes accuracy about
# 75 %), so the maximum-likelihood coefficients are finite.
SEP = 0.25
REG = 1e-6
NEWTON_ITERS = 10
# Relative error of the float32 fit's coefficients against the float64
# reference.  The float32 CPU rehearsal gave 3.2e-8 to 3.7e-8 (200 000 and
# 1 100 000 rows, seeds 0 and 1); the limit leaves a factor of 27 for another
# summation order on the chip.  On a TPU v5e, a sigmoid built on float32
# log1p gave 2.3e-6 and fails it.
BETA_RTOL = 1e-6
# The gradient norm must fall every iteration until it is below this fraction
# of its first value, and stay below it: float32 round-off sets the floor.
GRAD_FLOOR = 1e-6
DGEMM_N, DGEMM_GRID, DGEMM_ROWS = 16384, 4, 256
# Relative Frobenius error of the sampled rows of C.  float32 products
# accumulated in float32 over K = 16384 give about 1e-7; one bfloat16 MXU
# pass per product gave 2.3e-3 on a TPU v5e.
DGEMM_RTOL = 1e-4


class Checks:
    """Named pass/fail results, printed as they come in."""

    def __init__(self) -> None:
        self.failed: List[str] = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})", flush=True)
        if not ok:
            self.failed.append(name)


def newton_reference(X: np.ndarray, y: np.ndarray, reg: float,
                     max_iter: int = 25) -> np.ndarray:
    """Plain numpy float64 Newton for L2-regularized logistic regression:
    g = X^T (mu - y) + reg*beta, H = X^T diag(mu (1 - mu)) X + reg*I."""
    X = X.astype(np.float64)
    y = y.astype(np.float64).ravel()
    d = X.shape[1]
    beta = np.zeros(d)
    for _ in range(max_iter):
        mu = 1.0 / (1.0 + np.exp(-(X @ beta)))
        g = X.T @ (mu - y) + reg * beta
        H = X.T @ (X * (mu * (1.0 - mu))[:, None]) + reg * np.eye(d)
        step = np.linalg.solve(H, g)
        beta -= step
        if np.linalg.norm(step) <= 1e-14 * (1.0 + np.linalg.norm(beta)):
            break
    return beta


class HomeCheck:
    """Drain hook that checks every block an op reads or writes: it must be a
    ``jax.Array`` on the device of the node that holds it (node i ->
    ``devices[i]``)."""

    def __init__(self, executor) -> None:
        self._ex = executor
        self._devices = jax.devices()
        self._seen: set = set()
        self.bad: List[Tuple[int, str]] = []
        self.nodes: set = set()

    def __call__(self, out_id: int) -> None:
        self.check((out_id,) + tuple(self._ex.lineage[out_id].in_ids))

    def check(self, vids) -> None:
        ex = self._ex
        for vid in vids:
            vid = ex.resolve(vid)
            if vid in self._seen:
                continue
            value = ex.store.get(vid)
            if value is None:
                continue
            self._seen.add(vid)
            node = ex.block_home[vid][0]
            self.nodes.add(node)
            want = self._devices[node % len(self._devices)]
            if not isinstance(value, jax.Array):
                self.bad.append((vid, type(value).__name__))
            elif value.devices() != {want}:
                self.bad.append((vid, str(value.devices())))

    @property
    def checked(self) -> int:
        return len(self._seen)


def _peak_bytes() -> str:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        peaks.append("not reported" if stats is None
                     else str(stats.get("peak_bytes_in_use")))
    return ", ".join(peaks)


def _cross_chip(check: Checks, name: str, ctx: ArrayContext, home: HomeCheck,
                chips: int) -> None:
    moves = ctx.executor.backend.stats.device_moves
    check(f"{name}.nodes_on_devices", home.nodes == set(range(chips)),
          f"nodes holding blocks {sorted(home.nodes)}")
    check(f"{name}.device_moves", moves > 0, f"{moves} device-to-device moves")


def phase_newton(check: Checks, *, n: int = HIGGS_ROWS, d: int = HIGGS_FEATURES,
                 nodes: int = 1, row_blocks: int = 8, seed: int = 0,
                 chips: int = 1) -> Dict[str, float]:
    """Phase A: Newton logistic regression through ``fit_numpy``."""
    t0 = time.perf_counter()
    X, y = overlapping_gaussians(n, d=d, seed=seed, sep=SEP)
    X, y = X.astype(np.float32), y.astype(np.float32)
    print(f"A: data {n} x {d} float32 made in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    # gc=True frees each intermediate block once its last consumer has run;
    # without it the store keeps every iteration's w*X, which at this shape
    # is more than one chip's HBM.
    ctx = ArrayContext(cluster=ClusterSpec(nodes, 8), node_grid=(nodes, 1),
                       backend="jax", pipeline=True, plan_cache=True, gc=True)
    home = HomeCheck(ctx.executor)
    ctx.executor.drain_hook = home
    compile_s0 = GLOBAL_COMPILE_CACHE.compile_s
    t0 = time.perf_counter()
    model = LogisticRegression(ctx, solver="newton", reg=REG,
                               max_iter=NEWTON_ITERS)
    model.fit_numpy(X, y, row_blocks=row_blocks)
    beta = model.beta.ravel()
    fit_s = time.perf_counter() - t0
    loads = ctx.loads()
    compile_s = GLOBAL_COMPILE_CACHE.compile_s - compile_s0
    print(f"A: fit_s={fit_s:.3f} compile_s={compile_s:.3f} "
          f"plan_hits={loads['plan_hits']} plan_misses={loads['plan_misses']} "
          f"dispatches={loads['backend_dispatches']} "
          f"peak_bytes_in_use=[{_peak_bytes()}]", flush=True)

    t0 = time.perf_counter()
    ref = newton_reference(X, y, REG)
    print(f"A: numpy float64 reference in {time.perf_counter() - t0:.3f} s",
          flush=True)
    err = float(np.linalg.norm(beta - ref) / np.linalg.norm(ref))
    check("A.beta", err <= BETA_RTOL,
          f"relative error {err:.3e}, limit {BETA_RTOL:.0e}")

    norms = model.result.grad_norms
    floor = GRAD_FLOOR * norms[0]
    falls = all(b < a or (a <= floor and b <= floor)
                for a, b in zip(norms, norms[1:]))
    check("A.grad_norm", falls and norms[-1] <= floor,
          "norms " + " ".join(f"{g:.3e}" for g in norms)
          + f", floor {floor:.3e}")
    fallbacks = ctx.executor.backend.stats.fallbacks
    check("A.fallbacks", fallbacks == 0, f"{fallbacks} host fallbacks")
    check("A.blocks_on_device", home.checked > 0 and not home.bad,
          f"{home.checked} blocks checked, misplaced {home.bad[:3]}")
    if chips > 1:
        _cross_chip(check, "A", ctx, home, chips)
    return {"beta_rel_err": err, "fit_s": fit_s, "compile_s": compile_s}


def phase_dgemm(check: Checks, *, n: int = DGEMM_N, grid: int = DGEMM_GRID,
                rows: int = DGEMM_ROWS, nodes: int = 1,
                node_grid: Optional[Sequence[int]] = None, seed: int = 0,
                chips: int = 1) -> Dict[str, float]:
    """Phase B: blocked DGEMM through the Pallas matmul kernel."""
    ctx = ArrayContext(cluster=ClusterSpec(nodes, 4), node_grid=node_grid,
                       backend="pallas", seed=seed)
    A = ctx.random((n, n), grid=(grid, grid))
    B = ctx.random((n, n), grid=(grid, grid))
    compile_s0 = GLOBAL_COMPILE_CACHE.compile_s
    t0 = time.perf_counter()
    C = (A @ B).compute().wait()
    matmul_s = time.perf_counter() - t0
    compile_s = GLOBAL_COMPILE_CACHE.compile_s - compile_s0
    home = HomeCheck(ctx.executor)
    home.check(ga.block(idx).vid for ga in (A, B, C)
               for idx in ga.grid.iter_indices())
    print(f"B: matmul_s={matmul_s:.3f} compile_s={compile_s:.3f} "
          f"dispatches={ctx.loads()['backend_dispatches']} "
          f"peak_bytes_in_use=[{_peak_bytes()}]", flush=True)

    pick = np.sort(np.random.default_rng(seed).choice(n, size=rows,
                                                      replace=False))
    got = C.to_numpy()[pick].astype(np.float64)
    ref = A.to_numpy()[pick].astype(np.float64) @ B.to_numpy().astype(np.float64)
    err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    check("B.sampled_rows", err <= DGEMM_RTOL,
          f"{rows} rows, relative error {err:.3e}, limit {DGEMM_RTOL:.0e}")

    bm = n // grid
    block = jax.ShapeDtypeStruct((bm, bm), jnp.float32)
    matmul = ctx.executor.backend._build_pallas_matmul("matmul", {})
    hlo = jax.jit(matmul).lower(block, block).as_text()
    on_tpu = jax.devices()[0].platform == "tpu"
    check("B.mosaic_kernel", ("tpu_custom_call" in hlo) == on_tpu,
          f"tpu_custom_call in the {bm}x{bm} block matmul's HLO: "
          f"{'tpu_custom_call' in hlo}")
    check("B.blocks_on_device", home.checked > 0 and not home.bad,
          f"{home.checked} blocks checked, misplaced {home.bad[:3]}")
    if chips > 1:
        _cross_chip(check, "B", ctx, home, chips)
    return {"dgemm_rel_err": err, "matmul_s": matmul_s, "compile_s": compile_s}


# Keyword arguments of phases A and B for each --chips value: on four chips
# every phase spreads its blocks over four nodes, one per chip.
LAYOUTS = {
    1: ({}, {}),
    4: ({"nodes": 4, "row_blocks": 32, "chips": 4},
        {"nodes": 4, "node_grid": (2, 2), "chips": 4}),
}


def run_phases(chips: int, seed: int = 0, newton_sizes: Optional[dict] = None,
               dgemm_sizes: Optional[dict] = None) -> List[str]:
    """Run phases A and B laid out for ``chips`` chips; return the names of
    the checks that failed.  The sizes default to the full ones."""
    check = Checks()
    newton_kw, dgemm_kw = LAYOUTS[chips]
    for name, phase, kw in (
            ("A", phase_newton, {**newton_kw, **(newton_sizes or {})}),
            ("B", phase_dgemm, {**dgemm_kw, **(dgemm_sizes or {})})):
        t0 = time.perf_counter()
        result = phase(check, seed=seed, **kw)
        print(f"phase {name}: {result} in {time.perf_counter() - t0:.3f} s",
              flush=True)
    return check.failed


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(LAYOUTS), default=1,
                    help="1: both phases on one chip; 4: both phases on four "
                         "nodes, one per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print(f"no TPU: JAX's first device is {device['platform']}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.persistent_cache import enable_persistent_cache

    print(f"persistent compilation cache: {enable_persistent_cache()}",
          flush=True)
    failed = run_phases(args.chips, args.seed)
    if failed:
        print(f"failed checks: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
