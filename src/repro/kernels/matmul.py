"""Pallas TPU tiled matmul (MXU-aligned, VMEM-blocked, fp32 accumulate).

Grid (M/bm, N/bn, K/bk) with the K loop innermost (sequential) so the
accumulator lives in VMEM scratch across K steps. Block sizes default to
(128, 128, 128): MXU-native tiles; the fp32 accumulator (bm x bn) plus the
two input tiles fit comfortably in ~16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = False) -> jax.Array:
    """x [M, K] @ y [K, N] -> [M, N]. Pads to block multiples."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    mp, kp, np_ = (-(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    k_steps = kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(mp // bm, np_ // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]
