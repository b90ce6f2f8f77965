"""Pallas kernel for the capped FIFO pop/dispatch chain (scheduler hot
spot #2).

Replays public dispatches of one stage in the DES's chronological event
order: each job takes every provider's earliest-free FIFO slot
(replica-clock argmin over the [P, C] slot pool), prices its queueing
wait — and, under the cold-start model, the warm-up of a slot idle past
the keep-alive window — into the placement argmin as occupancy $/s,
then advances the chosen provider's slot clock to its end time. The
chain is inherently sequential (each dispatch moves the clocks the next
one reads), so the slot clocks live in VMEM scratch and the kernel wins
by collapsing the per-job op-dispatch storm into one launch.

Expression-for-expression the ``slot_step`` body of the vector engine
(`core/vectorsim.py`), which is itself ``_start_public_capped`` of the
DES — gathers, argmins and float association are kept identical so the
three agree bitwise in f64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(order_ref, pub_ref, n_ref, ready_ref, dur_ref,
                     selc_ref, occ_ref, seg_ref, cap_ref, wu_ref,
                     sclk0_ref, sidle0_ref, ka_ref,
                     prov_ref, sego_ref, wait_ref, cold_ref, start_ref,
                     end_ref, extra_ref, sclk, sidle, *, cold: bool):
    # untouched (private / absent) jobs keep the engine's zero fill
    prov_ref[...] = jnp.zeros_like(prov_ref)
    sego_ref[...] = jnp.zeros_like(sego_ref)
    wait_ref[...] = jnp.zeros_like(wait_ref)
    cold_ref[...] = jnp.zeros_like(cold_ref)
    start_ref[...] = jnp.zeros_like(start_ref)
    end_ref[...] = jnp.zeros_like(end_ref)
    extra_ref[...] = jnp.zeros_like(extra_ref)
    sclk[...] = sclk0_ref[...]
    sidle[...] = sidle0_ref[...]
    cap_p = cap_ref[0, :]
    wu_p = wu_ref[0, :]
    ka = ka_ref[0, 0]

    def body(i, _):
        j = order_ref[0, i]
        ready_p = ready_ref[:, pl.ds(j, 1)][:, 0]              # [P]
        clk = sclk[...]
        si = jnp.argmin(clk, axis=1)                           # [P]
        sc_sel = jnp.min(clk, axis=1)                          # == clk[p, si]
        wait_p = jnp.where(cap_p, jnp.maximum(0.0, sc_sel - ready_p), 0.0)
        if cold:
            idle_sel = jnp.take_along_axis(sidle[...], si[:, None],
                                           axis=1)[:, 0]
            cold_p = cap_p & ((ready_p + wait_p - idle_sel > ka)
                              | jnp.isneginf(idle_sel))
        else:
            cold_p = jnp.zeros_like(cap_p)
        pen = occ_ref[:, pl.ds(j, 1)][:, 0] * (wait_p + cold_p * wu_p)
        prov = jnp.argmin(selc_ref[:, pl.ds(j, 1)][:, 0] + pen)
        start = ready_p[prov] + wait_p[prov] + cold_p[prov] * wu_p[prov]
        end = start + dur_ref[:, pl.ds(j, 1)][prov, 0]
        prov_ref[0, j] = prov.astype(prov_ref.dtype)
        sego_ref[0, j] = seg_ref[:, pl.ds(j, 1)][prov, 0]
        wait_ref[0, j] = wait_p[prov]
        cold_ref[0, j] = cold_p[prov]
        start_ref[0, j] = start
        end_ref[0, j] = end
        extra_ref[0, j] = pen[prov]

        @pl.when(cap_p[prov])
        def _():
            sclk[prov, si[prov]] = end
            sidle[prov, si[prov]] = end

        return 0

    # the caller orders public jobs first, so the chain stops at n_pub
    jax.lax.fori_loop(0, n_ref[0, 0], body, 0)


@functools.partial(jax.jit, static_argnames=("cold", "interpret"))
def fifo_dispatch(order: jax.Array, locpub: jax.Array, n_pub: jax.Array,
                  ready: jax.Array, dur: jax.Array, selc: jax.Array,
                  occ: jax.Array, seg: jax.Array, capped_p: jax.Array,
                  wu_p: jax.Array, sclk0: jax.Array, sidle0: jax.Array,
                  keep_alive, *, cold: bool = False,
                  interpret: bool = False):
    """Capped FIFO dispatch chain for one stage.

    ``order`` [J] visits jobs in DES event order (public jobs first,
    ``n_pub`` of them); ``ready``/``dur``/``selc``/``occ``/``seg`` are
    [P, J] per-(provider, job) epochs / durations / selection costs /
    occupancy rates / price segments; ``capped_p`` [P] marks providers
    with finite caps, ``sclk0``/``sidle0`` [P, C] the initial slot
    clocks / idle stamps. Returns (prov, seg, wait, cold, start, end,
    extra), each [J] — provider pick, its segment, queue wait, cold
    flag, start/end instants and the occupancy surcharge.
    """
    J = order.shape[-1]
    P, C = sclk0.shape
    f = ready.dtype
    def as_row(v, dt=None):
        if dt is None:
            return v.reshape(1, -1)
        return v.reshape(1, -1).astype(dt)

    outs = pl.pallas_call(
        functools.partial(_dispatch_kernel, cold=cold),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 13,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 7,
        out_shape=[
            jax.ShapeDtypeStruct((1, J), jnp.int32),   # prov
            jax.ShapeDtypeStruct((1, J), jnp.int32),   # seg
            jax.ShapeDtypeStruct((1, J), f),           # wait
            jax.ShapeDtypeStruct((1, J), jnp.bool_),   # cold
            jax.ShapeDtypeStruct((1, J), f),           # start
            jax.ShapeDtypeStruct((1, J), f),           # end
            jax.ShapeDtypeStruct((1, J), f),           # extra
        ],
        scratch_shapes=[pltpu.VMEM((P, C), f), pltpu.VMEM((P, C), f)],
        interpret=interpret,
    )(as_row(order, jnp.int32), as_row(locpub),
      jnp.asarray(n_pub, jnp.int32).reshape(1, 1),
      ready, dur, selc, occ, seg.astype(jnp.int32),
      as_row(capped_p), as_row(wu_p, f), sclk0, sidle0,
      jnp.asarray(keep_alive, f).reshape(1, 1))
    return tuple(o[0] for o in outs)
