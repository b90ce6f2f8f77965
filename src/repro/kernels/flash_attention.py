"""Pallas TPU flash attention (prefill): online-softmax, causal + sliding
window, GQA-aware (KV blocks indexed by q_head // group — no KV repeat is
materialized).

Grid (B, Hq, Sq/bq, Sk/bk), KV innermost/sequential; the running max `m`,
denominator `l` (lane-replicated [bq, 128]) and fp32 accumulator [bq, D]
live in VMEM scratch across KV steps. Fully-masked KV blocks are skipped
via pl.when on the block indices (causal/window block bounds).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: Optional[int],
               bq: int, bk: int, sq: int, sk: int, nk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: any (q,k) pair in this tile may be live?
    q_lo = (sk - sq) + iq * bq                  # right-aligned positions
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        live = jnp.logical_and(live, (ik + 1) * bk - 1 > q_lo - window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)      # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)      # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk                         # padding
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_ref[:, :1]                    # [bq, 1]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)              # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)          # [bq, 1]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.where(denom == 0.0, 1.0, denom)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    """q [B,Hq,Sq,D], k/v [B,Hkv,Sk,D] -> [B,Hq,Sq,D]."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale_v = float(d ** -0.5 if scale is None else scale)
    bq = min(bq, max(sq, 8))
    bk = min(bk, max(sk, 8))
    sqp, skp = -(-sq // bq) * bq, -(-sk // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    nq, nk = sqp // bq, skp // bk
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale_v, causal=causal,
                          window=window, bq=bq, bk=bk, sq=sq, sk=sk, nk=nk),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :sq]
