"""Blocked MXU matmul kernel (the paper's DGEMM hot-spot, §8.2, on TPU).

Grid (M/bm, N/bn, K/bk) with K innermost; partial products accumulate in an
f32 VMEM scratch tile and are written once on the last K step.  Block shapes
default to (512, 1024, 512) — MXU-aligned (multiples of 128).  The dot runs
at HIGHEST precision, so float32 blocks keep float32 accuracy on the MXU;
its working set, double-buffered inputs plus the bfloat16 splits of the
operands, fits a 32 MiB scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, acc_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: for float32 operands Mosaic's default is one bfloat16 MXU
    # pass, which left a 16384^2 product 2.3e-3 off in relative norm on a
    # TPU v5e
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_dtype,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 512,
    bn: int = 1024,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.  Dims must divide block shapes
    (ops.py pads otherwise)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk
    out_dtype = out_dtype or a.dtype
    # accumulator dtype: f32 matches the MXU's native accumulation; f64
    # inputs (CPU interpret runs, backend parity tests under x64) accumulate
    # in f64 so the kernel is bit-comparable to a float64 reference matmul
    acc_dtype = jnp.float64 if jnp.dtype(a.dtype) == jnp.float64 else jnp.float32
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps, acc_dtype=acc_dtype),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the HIGHEST-precision dot splits its operands into bfloat16
            # parts in VMEM: the default tiles need a little over the
            # default 16 MiB scoped limit (a v5e core has 128 MiB)
            vmem_limit_bytes=32 * 1024 * 1024,
        ),
        interpret=interpret,
    )(a, b)
