"""Pallas TPU RG-LRU scan (RecurrentGemma's gated linear recurrence).

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t

Grid (B/bb, T/bt) with time innermost/sequential; the carry h [bb, D]
persists in VMEM scratch across time blocks (re-initialized — from the
optional h0 — whenever a new batch block starts). Inside a block the
recurrence runs as a fori_loop over bt steps of fully-vectorized [bb, D]
VPU ops: batch/feature parallel, time sequential — the TPU-native layout
for this memory-bound scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_ref, h0_ref, y_ref, hT_ref, h_ref, *,
                  bt: int, nt: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    def step(i, _):
        a_t = a_ref[:, i, :].astype(jnp.float32)
        x_t = x_ref[:, i, :].astype(jnp.float32)
        g_t = jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 0.0)) * x_t
        h = a_t * h_ref[...] + g_t
        h_ref[...] = h
        y_ref[:, i, :] = h.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bt, step, ())

    @pl.when(it == nt - 1)
    def _finish():
        hT_ref[...] = h_ref[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bt", "interpret"))
def rglru(x: jax.Array, a: jax.Array, h0: jax.Array | None = None, *,
          bb: int = 8, bt: int = 128, interpret: bool = False):
    """x, a: [B, T, D] -> (y [B, T, D], h_T [B, D])."""
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), dtype=jnp.float32)
    bb = min(bb, b)
    bt = min(bt, t)
    bp, tp = -(-b // bb) * bb, -(-t // bt) * bt
    xp = jnp.pad(x, ((0, bp - b), (0, tp - t), (0, 0)))
    # pad decay with ones so padded steps keep the carry unchanged
    ap = jnp.pad(a, ((0, bp - b), (0, tp - t), (0, 0)), constant_values=1.0)
    h0p = jnp.pad(h0, ((0, bp - b), (0, 0)))
    nt = tp // bt
    y, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, bt=bt, nt=nt),
        grid=(bp // bb, nt),
        in_specs=[
            pl.BlockSpec((bb, bt, d), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec((bb, bt, d), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec((bb, d), lambda ib, it: (ib, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bt, d), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec((bb, d), lambda ib, it: (ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp, d), x.dtype),
            jax.ShapeDtypeStruct((bp, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, ap, h0p)
    return y[:b, :t], hT[:b]
