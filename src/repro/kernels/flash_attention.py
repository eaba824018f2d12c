"""Flash attention forward kernel (TPU Pallas): online-softmax over KV blocks
with causal and sliding-window masking, GQA via head->kv-head index mapping.

Layout: q (B, H, Sq, hd), k/v (B, KV, Skv, hd).  Grid is
(B*H, Sq/bq, Skv/bk) with the KV dimension innermost ("arbitrary" semantics);
running max m, denominator l and the output accumulator live in VMEM scratch
and persist across KV steps.  hd is padded to the 128-lane register width by
ops.py; bq/bk default to 512/512 so the live tiles
(bq*hd + 2*bk*hd + bq*bk f32) fit VMEM comfortably.

The TPU adaptation of the CUDA flash algorithm: instead of warp-level
softmax reductions, whole (bq, bk) score tiles are produced on the MXU and
reduced on the VPU; block-level masking (causal / window) prunes entire
tiles via pl.when, which is where the sliding-window sub-quadratic win
comes from on long_500k shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, bq: int, bk: int, scale: float,
                  causal: bool, window: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # whole-tile pruning: skip KV tiles fully masked out
    tile_min_q = iq * bq + q_offset
    tile_max_q = tile_min_q + bq - 1
    tile_min_k = ik * bk
    live = True
    if causal:
        live = tile_min_k <= tile_max_q
    if window > 0:
        live = jnp.logical_and(live, (ik * bk + bk - 1) > (tile_min_q - window))

    @pl.when(live)
    def _compute():
        q = q_ref[0]                       # (bq, hd)
        k = k_ref[0]                       # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                          # (bq, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                # (bq, 128) broadcast storage
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])      # (bq, bk)
        l_new = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], m_prev.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == kv_steps - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,            # (B, H, Sq, hd)
    k: jax.Array,            # (B, KV, Skv, hd)
    v: jax.Array,            # (B, KV, Skv, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    _, KV, Skv, _ = k.shape
    rep = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    kv_steps = Skv // bk
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _flash_kernel, kv_steps=kv_steps, bq=bq, bk=bk, scale=scale,
        causal=causal, window=window or 0, q_offset=q_offset,
    )
    qf = q.reshape(B * H, Sq, hd)
    grid = (B * H, Sq // bq, kv_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, iq, ik, rep=rep, KV=KV:
                         ((bh // rep) % KV + (bh // (rep * KV)) * KV, ik, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, iq, ik, rep=rep, KV=KV:
                         ((bh // rep) % KV + (bh // (rep * KV)) * KV, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, k.reshape(B * KV, Skv, hd), v.reshape(B * KV, Skv, hd)).reshape(B, H, Sq, hd)
