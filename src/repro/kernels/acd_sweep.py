"""Pallas kernel for the ACD kept-prefix sweep (scheduler hot spot #1).

One program per queue row: a sequential scan over the priority queue
carrying the running *kept* demand sum. A masked job is evicted exactly
when the kept prefix ahead of it already exceeds its slack threshold;
kept jobs add their demand to the prefix. A single pass computes the
same evict set as the DES's iterated remove-first-violator-and-resweep
loop: removing the first violator never changes the prefix sums of
earlier positions, so the re-sweep re-derives the identical keeps and
the iteration telescopes into one left-to-right scan.

The row is the whole queue ([1, J] block, J a few hundred): the scan is
inherently sequential (kept-sum recurrence is non-associative), so the
win over XLA is dispatch count — one kernel launch instead of J
scalar-op thunks — not parallelism.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _acd_kernel(p_ref, t_ref, m_ref, e_ref):
    J = p_ref.shape[-1]

    def body(i, s):
        mi = m_ref[0, i]
        ev = mi & (s > t_ref[0, i])
        e_ref[0, i] = ev
        return s + jnp.where(mi & ~ev, p_ref[0, i], jnp.zeros((), s.dtype))

    jax.lax.fori_loop(0, J, body, jnp.zeros((), p_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def acd_evict(P: jax.Array, thresh: jax.Array, mask: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """Greedy ACD evict set per queue row.

    ``P`` [B, J] per-job demand, ``thresh`` [B, J] slack thresholds
    (already reduced to a single per-job float by the caller), ``mask``
    [B, J] sweep eligibility (in-queue & ACD-enabled). Returns the
    [B, J] bool evict mask; dtype of the running sum follows ``P``.
    """
    B, J = P.shape
    return pl.pallas_call(
        _acd_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, J), lambda b: (b, 0)),
            pl.BlockSpec((1, J), lambda b: (b, 0)),
            pl.BlockSpec((1, J), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, J), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, J), jnp.bool_),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(P, thresh, mask)
