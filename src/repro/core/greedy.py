"""Greedy scheduling math (Alg. 1) — vectorized, jit-able.

Two mechanisms:
  * initialization offload: capacity prefix rule over T_max = sum_k I_k*C_max
  * apparent-closeness-to-deadline (ACD) sweep over a stage queue

Both are pure array programs (sort / cumsum / masks). The discrete-event
loop in ``simulator.py`` calls the numpy twins; the jnp versions power the
on-device serving control loop (fixed-size, masked) in ``serving/hybrid.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis, as the engine computes it.

    On CPU this is ``jnp.cumsum``, a sequential left fold that matches
    ``np.cumsum`` in the DES bit for bit. On other backends it is a
    log-depth ``associative_scan``: XLA:TPU lowers a float64 ``cumsum``
    over a 4096-wide queue to a program that takes minutes to compile,
    where the scan compiles in seconds. Float64 is emulated there with
    float32 pairs, so neither order matches numpy's rounding on a TPU.
    """
    if jax.default_backend() == "cpu":
        return jnp.cumsum(x, axis=-1)
    return jax.lax.associative_scan(jnp.add, x, axis=x.ndim - 1)


# -- initialization phase (Alg. 1 lines 2-10) -----------------------------

def t_max(replicas: np.ndarray, c_max: float) -> float:
    """T_max = sum_k I_k * C_max: total private compute capacity."""
    return float(np.sum(replicas) * c_max)


def init_offload(C_total: np.ndarray, keys: np.ndarray, capacity: float) -> np.ndarray:
    """Capacity prefix rule.

    ``C_total[j]`` = estimated whole-job private runtime; ``keys[j]`` = the
    priority key (ascending = head first); jobs are kept in priority order
    while the running sum of C stays <= capacity, the rest (the tail) are
    offloaded.  Returns a boolean offload mask [J].
    """
    C_total = np.asarray(C_total, dtype=np.float64)
    order = np.argsort(np.asarray(keys), kind="stable")        # head first
    csum = np.cumsum(C_total[order])
    keep_sorted = csum <= capacity + 1e-12
    offload = np.ones(C_total.shape[0], dtype=bool)
    offload[order[keep_sorted]] = False
    return offload


@partial(jax.jit, static_argnames=())
def init_offload_jax(C_total: jax.Array, keys: jax.Array, capacity) -> jax.Array:
    """jnp twin of :func:`init_offload` (stable sort, mask output)."""
    order = jnp.argsort(keys, stable=True)
    csum = jnp.cumsum(C_total[order])
    keep_sorted = csum <= capacity + 1e-12
    offload = jnp.ones_like(C_total, dtype=bool).at[order].set(~keep_sorted)
    return offload


# -- ACD (Sec. III-B) ------------------------------------------------------

def acd_sweep(
    queue_P_stage: np.ndarray,
    path_remaining: np.ndarray,
    t: float,
    deadline: float,
    replicas: int,
) -> np.ndarray:
    """ACD for every job currently in one stage queue, in queue order.

    ACD_{l,j}(t) = D - ( t + sum_{y<j in Q_l} P^priv_{l,y} / I_l
                           + sum_{k in Gamma(l)} P^priv_{k,j} )

    ``queue_P_stage[i]`` = P^private of the i-th queued job *at this stage*;
    ``path_remaining[i]`` = critical-path latency from this stage (incl.)
    to the sink for that job.  Returns ACD values [Q].
    """
    P = np.asarray(queue_P_stage, dtype=np.float64)
    excl_prefix = np.concatenate([[0.0], np.cumsum(P)[:-1]])
    return deadline - (t + excl_prefix / max(replicas, 1)
                       + np.asarray(path_remaining, dtype=np.float64))


def acd_sweep_jax(queue_P_stage, path_remaining, t, deadline, replicas, mask=None):
    """jnp twin; ``mask`` marks real entries in a fixed-size padded queue.

    Padded entries contribute no queue delay and return ACD=+inf.

    The arithmetic dtype follows the inputs (no forced float32): under
    ``enable_x64`` a float64 queue reproduces the numpy twin bit-for-bit,
    so near-tie ACD values cannot flip the offload decision between the
    serving control loop and the DES.
    """
    P = jnp.asarray(queue_P_stage)
    if not jnp.issubdtype(P.dtype, jnp.floating):
        P = P.astype(jnp.result_type(float))  # ints promote, floats keep
    if mask is not None:
        P = P * mask
    csum = jnp.cumsum(P)
    excl_prefix = csum - P
    acd = deadline - (t + excl_prefix / jnp.maximum(replicas, 1)
                      + jnp.asarray(path_remaining, dtype=P.dtype))
    if mask is not None:
        acd = jnp.where(mask.astype(bool), acd, jnp.inf)
    return acd


def offload_negative_acd(acd: np.ndarray) -> np.ndarray:
    """Alg. 1 line 17: mask of queue positions to dispatch to public."""
    return np.asarray(acd) < 0.0


# -- provider selection (multi-cloud eviction target) ----------------------

def select_provider(selection_costs: np.ndarray) -> np.ndarray:
    """Cheapest feasible provider per (job, stage).

    ``selection_costs``: [P, ...] predicted billed cost per provider, +inf
    where infeasible (see ``ProviderPortfolio.np_selection_costs``). The
    eviction target is the argmin along the provider axis, ties broken by
    the lowest provider index.
    """
    return np.argmin(np.asarray(selection_costs), axis=0)


def select_provider_jax(selection_costs: jax.Array) -> jax.Array:
    """jnp twin of :func:`select_provider` (same first-min tie-break)."""
    return jnp.argmin(selection_costs, axis=0)
