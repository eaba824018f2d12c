"""Chunked selective-scan kernel (Mamba-1 recurrence) for TPU Pallas.

h_t = dA_t * h_{t-1} + dBx_t ;  y_t = <h_t, C_t>

The CUDA selective-scan kernel keeps h in registers and streams the sequence;
the TPU adaptation keeps h as a (bd, N) VMEM-resident tile and walks the
sequence in chunks: grid (B, DI/bd, S/chunk) with the time dimension
innermost ("arbitrary"), a fori_loop over the chunk's steps, and the carry
persisting in scratch across chunk steps.  The (DI) channel dimension is the
vectorized lane axis — channels are independent, which is what makes the
recurrence TPU-friendly despite being sequential in time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dA_ref, dBx_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        da = dA_ref[0, t]        # (bd, N)
        dbx = dBx_ref[0, t]      # (bd, N)
        c = c_ref[0, t]          # (1, N) -> broadcast over channels
        h = da * h + dbx
        y_ref[0, t] = jnp.sum(h * c, axis=1).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan_pallas(
    dA: jax.Array,     # (B, S, DI, N) float32
    dBx: jax.Array,    # (B, S, DI, N) float32
    C: jax.Array,      # (B, S, N)     float32
    *,
    bd: int = 512,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    B, S, DI, N = dA.shape
    bd = min(bd, DI)
    chunk = min(chunk, S)
    assert DI % bd == 0 and S % chunk == 0, (DI, S, bd, chunk)
    kernel = functools.partial(_scan_kernel, chunk=chunk)
    c4 = C[:, :, None, :]  # (B, S, 1, N)
    return pl.pallas_call(
        kernel,
        grid=(B, DI // bd, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda b, d, c: (b, c, d, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda b, d, c: (b, c, d, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, d, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, DI), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dA, dBx, c4)
