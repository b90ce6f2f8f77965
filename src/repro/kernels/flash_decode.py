"""Pallas TPU flash decoding: one new query token against a long KV cache.

The GQA trick: all `G = Hq/Hkv` query heads sharing a KV head form the
rows of the MXU op — Q[G, D] @ K[D, bk] — so decode attention stays a
matmul even at batch 1. Grid (B, Hkv, Sk/bk) with the KV scan innermost;
online-softmax state (m, l lane-replicated; fp32 acc [G, D]) in VMEM
scratch. Valid-length masking reads `length[b]` from an SMEM-style block.
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


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, bk: int, sk: int, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0, 0]
    live = ik * bk < length

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, bk]
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(kpos < jnp.minimum(length, sk), logits, _NEG)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
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


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 length: Optional[jax.Array] = None, *,
                 scale: Optional[float] = None, bk: int = 256,
                 interpret: bool = False) -> jax.Array:
    """q [B,Hq,D], k/v [B,Hkv,Sk,D], length [B] -> [B,Hq,D]."""
    b, hq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale_v = float(d ** -0.5 if scale is None else scale)
    if length is None:
        length = jnp.full((b,), sk, dtype=jnp.int32)
    bk = min(bk, max(sk, 8))
    skp = -(-sk // bk) * bk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    qg = q.reshape(b, hkv, g, d)
    nk = skp // bk
    out = pl.pallas_call(
        functools.partial(_fd_kernel, scale=scale_v, bk=bk, sk=sk, nk=nk),
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b_, h, ik: (b_, 0)),
            pl.BlockSpec((1, 1, g, d), lambda b_, h, ik: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ik: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, ik: (b_, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, ik: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length.reshape(b, 1).astype(jnp.int32), qg, kp, vp)
    return out.reshape(b, hq, d)
