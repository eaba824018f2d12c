"""Flash attention backward kernel (TPU Pallas) + custom_vjp wiring.

Standard flash-style backward with recomputation: the forward saves only the
output O and the softmax log-normalizer L = m + log(l); the backward kernel
re-materializes P tile-by-tile and accumulates

    dv += P^T dO
    dP  = dO V^T ;  dS = P * (dP - delta),  delta = rowsum(dO * O)
    dq += dS K ;  dk += dS^T Q

Grid is (B*KV, Skv/bk, Sq/bq) with the *query* dimension innermost so dk/dv
accumulate in VMEM scratch across q-tiles (one pass over Q per KV tile);
dq is accumulated via a second pass in the dq kernel with (B*H, Sq/bq,
Skv/bk).  Two kernels keep every accumulator race-free without atomics —
the TPU-idiomatic replacement for the CUDA kernel's shared-memory dq
atomics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, flash_attention_pallas


def _masks(iq, ik, bq, bk, q_offset, causal, window):
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    return mask


def _recompute_p(q, k, lse, mask, scale):
    """lse: (bq, 1) f32 log-normalizer column."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    return jnp.exp(s - lse)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                q_steps, bq, bk, scale, causal, window, q_offset, rep):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # sum over the rep query-head group mapped to this kv head
    for r in range(rep):
        q = q_ref[0, r]
        do = do_ref[0, r]
        o = o_ref[0, r]
        lse = lse_ref[0, r][:, None].astype(jnp.float32)
        mask = _masks(iq, ik, bq, bk, q_offset, causal, window)
        p = _recompute_p(q, k_ref[0], lse, mask, scale)      # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, hd)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=1, keepdims=True)               # (bq, 1)
        ds = p * (dp - delta) * scale                        # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, hd)

    @pl.when(iq == q_steps - 1)
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_acc, *,
               kv_steps, bq, bk, scale, causal, window, q_offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    mask = _masks(iq, ik, bq, bk, q_offset, causal, window)
    p = _recompute_p(q_ref[0], k_ref[0],
                     lse_ref[0][:, None].astype(jnp.float32), mask, scale)
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq_acc[...] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == kv_steps - 1)
    def _store():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fwd_with_lse(q, k, v, causal, window, q_offset, bq, bk, interpret):
    """Forward returning (out, lse) — lse recomputed cheaply via jnp (the
    kernel stores only O; lse = logsumexp of scores row-wise, computed
    blockwise in f32 without materializing the full score matrix)."""
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, bq=bq, bk=bk,
                                 interpret=interpret)
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    qg = q.reshape(B, KV, rep, Sq, hd).astype(jnp.float32)
    s = jnp.einsum("bkrqd,bksd->bkrqs", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[2])
    mask = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)            # (B,KV,rep,Sq)
    return out, lse.reshape(B, H, Sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_vjp(q, k, v, causal=True, window=None, q_offset=0,
                        bq=512, bk=512, interpret=False):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, bq=min(bq, q.shape[2]),
                                  bk=min(bk, k.shape[2]), interpret=interpret)


def _vjp_fwd(q, k, v, causal, window, q_offset, bq, bk, interpret):
    out, lse = _fwd_with_lse(q, k, v, causal, window, q_offset,
                             min(bq, q.shape[2]), min(bk, k.shape[2]), interpret)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, window, q_offset, bq, bk, interpret, res, dout):
    q, k, v, out, lse = res
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    rep = H // KV
    bq_, bk_ = min(bq, Sq), min(bk, Skv)
    scale = 1.0 / math.sqrt(hd)
    w = window or 0

    # heads-grouped layouts: q-side tensors as (B*KV, rep, Sq, hd)
    qg = q.reshape(B, KV, rep, Sq, hd).reshape(B * KV, rep, Sq, hd)
    dog = dout.reshape(B, KV, rep, Sq, hd).reshape(B * KV, rep, Sq, hd)
    og = out.reshape(B, KV, rep, Sq, hd).reshape(B * KV, rep, Sq, hd)
    lseg = lse.reshape(B, KV, rep, Sq).reshape(B * KV, rep, Sq)
    kf = k.reshape(B * KV, Skv, hd)
    vf = v.reshape(B * KV, Skv, hd)

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, q_steps=Sq // bq_, bq=bq_, bk=bk_,
                          scale=scale, causal=causal, window=w,
                          q_offset=q_offset, rep=rep),
        grid=(B * KV, Skv // bk_, Sq // bq_),
        in_specs=[
            pl.BlockSpec((1, rep, bq_, hd), lambda b, ik, iq: (b, 0, iq, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, rep, bq_, hd), lambda b, ik, iq: (b, 0, iq, 0)),
            pl.BlockSpec((1, rep, bq_, hd), lambda b, ik, iq: (b, 0, iq, 0)),
            pl.BlockSpec((1, rep, bq_), lambda b, ik, iq: (b, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk_, hd), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, ik, iq: (b, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B * KV, Skv, hd), k.dtype),
                   jax.ShapeDtypeStruct((B * KV, Skv, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk_, hd), jnp.float32),
                        pltpu.VMEM((bk_, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kf, vf, dog, og, lseg)
    dk = dkv[0].reshape(B, KV, Skv, hd)
    dv = dkv[1].reshape(B, KV, Skv, hd)

    qf = q.reshape(B * H, Sq, hd)
    dof = dout.reshape(B * H, Sq, hd)
    of = out.reshape(B * H, Sq, hd)
    lsef = lse.reshape(B * H, Sq)

    def kv_map(bh, iq, ik, rep=rep, KV=KV):
        return ((bh // rep) % KV + (bh // (rep * KV)) * KV, ik, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, kv_steps=Skv // bk_, bq=bq_, bk=bk_,
                          scale=scale, causal=causal, window=w,
                          q_offset=q_offset),
        grid=(B * H, Sq // bq_, Skv // bk_),
        in_specs=[
            pl.BlockSpec((1, bq_, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk_, hd), kv_map),
            pl.BlockSpec((1, bk_, hd), kv_map),
            pl.BlockSpec((1, bq_, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq_, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq_), lambda b, iq, ik: (b, iq)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, of, lsef)
    return dq.reshape(B, H, Sq, hd), dk, dv


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
