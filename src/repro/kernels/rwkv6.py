"""Pallas TPU RWKV-6 (Finch) WKV kernel: data-dependent-decay recurrence.

Per head, state S [Dk, Dv]:
    o_t = r_t . (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t

Grid (B, H, T/bt), time innermost; S persists in VMEM scratch across time
blocks (initialized from the optional s0). The inner fori_loop performs
rank-1 outer-product updates [Dk, Dv] — VPU work with Dk*Dv elements per
step, matching the head sizes (64x64) of rwkv6-1.6b.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                  s_ref, *, bt: int, nt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                  # [Dk]

    def step(i, _):
        r_t = r_ref[0, 0, i, :].astype(jnp.float32)   # [Dk]
        k_t = k_ref[0, 0, i, :].astype(jnp.float32)
        v_t = v_ref[0, 0, i, :].astype(jnp.float32)   # [Dv]
        w_t = w_ref[0, 0, i, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]              # [Dk, Dv]
        s = s_ref[...]
        o_t = jnp.sum((s + u[:, None] * kv) * r_t[:, None], axis=0)  # [Dv]
        s_ref[...] = w_t[:, None] * s + kv
        o_ref[0, 0, i, :] = o_t.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bt, step, ())

    @pl.when(it == nt - 1)
    def _finish():
        sT_ref[0, 0] = s_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
          u: jax.Array, s0: jax.Array | None = None, *,
          bt: int = 128, interpret: bool = False):
    """r,k,w [B,H,T,Dk], v [B,H,T,Dv], u [H,Dk] -> (o [B,H,T,Dv], S_T)."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, dv), dtype=jnp.float32)
    bt = min(bt, t)
    tp = -(-t // bt) * bt
    pad4 = ((0, 0), (0, 0), (0, tp - t), (0, 0))
    rp, kp_, vp = (jnp.pad(x, pad4) for x in (r, k, v))
    # pad decay with ones so padded steps keep the state unchanged
    wp = jnp.pad(w, pad4, constant_values=1.0)
    nt = tp // bt
    o, sT = pl.pallas_call(
        functools.partial(_rwkv6_kernel, bt=bt, nt=nt),
        grid=(b, h, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, dk), lambda ib, ih, it: (ib, ih, it, 0)),
            pl.BlockSpec((1, 1, bt, dk), lambda ib, ih, it: (ib, ih, it, 0)),
            pl.BlockSpec((1, 1, bt, dv), lambda ib, ih, it: (ib, ih, it, 0)),
            pl.BlockSpec((1, 1, bt, dk), lambda ib, ih, it: (ib, ih, it, 0)),
            pl.BlockSpec((1, dk), lambda ib, ih, it: (ih, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt, dv), lambda ib, ih, it: (ib, ih, it, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tp, dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rp, kp_, vp, wp, u, s0)
    return o[:, :, :t], sT
