"""Batched, jit-compiled scenario-sweep engine for Alg. 1 (``engine="vector"``).

The discrete-event reference in :mod:`.simulator` replays one (app, order,
C_max, latency-draw) point at a time; every headline figure of the paper is
a *grid* of such points. This module runs the same algorithm — capacity
prefix initialization offload, per-stage priority queues, the adaptive ACD
kept-prefix sweep, replica occupancy, transfer latencies and Eqn.-1 cost —
``vmap``-ed over a scenario axis, so an entire Fig.-4 sweep is a single
batched device call (:func:`simulate_scenarios` for one application's grid,
:func:`sweep_scenarios` for a whole figure across applications).

Engine construction
-------------------
Influence in the platform model is strictly feed-forward: events at stage
``k`` are shaped by upstream completions and by stage ``k``'s own replica
occupancy, never by downstream stages (offloading forces *descendants*
public, replica pools are per-stage). The engine therefore simulates the
stages **in topological order**, each to completion, instead of
interleaving one global event heap. Per stage the event loop is a
``lax.while_loop`` whose carry is a handful of ``[J]`` vectors in *queue
coordinates* (the static ``(stage_key, job)`` priority permutation):

* queue membership is a boolean mask; *head-of-queue* is ``argmax``;
* the ACD kept-prefix is one masked ``cumsum``; the sequential
  first-violator semantics of Alg. 1 lines 14-20 are reproduced by
  evicting one first violator per iteration (everything ahead of the
  first violator is kept in both formulations);
* replica occupancy is an ``[I_max]`` vector of *per-replica completion
  clocks* — replica ``i`` is free iff ``clock[i] <= t``; a dispatch
  takes the **lowest-indexed free replica** (the deterministic tie-break
  the DES shares) and runs for the stage duration scaled by that
  replica's entry in a per-stage speed vector (1.0 = healthy, > 1 =
  straggler, ``inf`` = slot absent). Replica *identity* is therefore
  data, not an erased aggregate: ``replica_slowdown`` straggler
  injection runs batched, and the chosen replica index is reported per
  (job, stage);
* forced-public jobs (initialization offload and eviction cascades,
  constraint (12)) never enter a queue: their start/end times are closed
  forms of their arrival times, computed outside the loop, as are cost,
  completion times and the offload counters.

DAG structure as data
---------------------
Adjacency, descendant masks, sink/pinned flags and the per-stage
replica pools enter the engine as *arrays*, not trace-time constants:
one compiled executable serves every DAG with the same (padded) stage
count, job count and replica bound. Replica pools are a masked
``[M, I_max]`` *speed matrix* (finite entry = present replica with that
slowdown factor, ``inf`` = absent slot), so the replica counts ``I_k``
are scenario **data** too: ``sweep_scenarios`` takes a ``replicas=``
axis (a list of per-stage replica-count vectors) and a
``replica_speeds=`` axis (straggler grids), and a whole replica
autoscaling or robustness sweep batches into the same executable. The
provider portfolio is data as well — **segment-indexed** billed-cost /
selection matrices ``[P, S, J, M]`` plus per-segment latency / egress /
start-edge vectors ``[P, S]``, where S counts the price segments of the
portfolio's time-dependent pricing (:class:`.cost.PriceTrace`; 1 for a
static portfolio). The cheapest-feasible (provider, segment) pair is
resolved per stage at each job's *offload epoch* (decision-epoch
pricing), so spot-market and diurnal tariffs sweep as a
``price_traces=`` scenario axis and the shape family is
(M_pad, I_max, J, P, S, flags). Heterogeneous applications batch into a
single call — stages are topologically relabelled, short DAGs are padded
with inert stages (no jobs eligible, so their event loops run zero
iterations) — and the whole figure's scenario axis shards across the
local devices: the chips of a TPU host, or on a CPU run
(``JAX_PLATFORMS=cpu``) the virtual devices of
``XLA_FLAGS=--xla_force_host_platform_device_count=<cores>``. Lockstep
vmap iteration then amortizes the small applications inside the largest
one's event budget.

Exogenous arrivals are data too: the per-stage loop already consumes a
general per-job arrival vector (feed-forward stages arrive whenever their
predecessors finish), so an external release stream (:mod:`.arrivals`)
simply replaces the constant ``t0`` at source stages — release times enter
as one more ``[J]`` input, and per-job deadlines (``release + C_max``)
replace the scalar deadline in the ACD. No new executables: the shape
family stays (M_pad, I_max, J, P, S, flags), and a batch (all releases
at ``t0``) reproduces the pre-arrivals path bit-exactly.

All arithmetic runs in float64 (under ``jax.enable_x64(True)``) so
keep/offload decisions agree bit-for-bit with the numpy DES; equivalence
is exact for tie-free (continuous) latency draws, where the DES heap order
and the engine's index order coincide. On a TPU, XLA emulates float64
with float32 pairs: decisions still match the DES, while times and costs
round differently from numpy in their last bits.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from collections.abc import Mapping
import functools
import itertools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .arrivals import ArrivalsLike, resolve_release
from .coldstart import (ColdStartLike, ConcurrencyLike, PoolTraceLike,
                        as_coldstart, as_pool_trace, norm_concurrency,
                        validate_load_kwargs)
from .cost import (CostModel, EGRESS_GB_PER_S, LAMBDA_COST, PriceTrace,
                   ProviderPortfolio, as_portfolio)
from .dag import AppDAG
from .faults import RetryPolicy, max_outage_slots, normalize_fault_axis
from .greedy import init_offload, prefix_sum
from .priority import ORDERS
from ..kernels import ops as _kernel_ops

#: Inner-loop implementations of the vector engine. All three are
#: bit-exact twins (the equivalence suites pin them against each other
#: and the DES):
#:   "loop"   — the original one-event-per-iteration ``lax.while_loop``
#:              body (many small ops per event; the CPU equivalence twin)
#:   "scan"   — the fused segment-scan body: each iteration commits a
#:              whole same-instant event *batch* (every certain ACD
#:              eviction of the sweep cascade, every free-replica
#:              dispatch) through mask-selects instead of per-event
#:              scatters, cutting the trip count several-fold. The
#:              default off CPU: its wide fused ops are what
#:              accelerator backends vectorize, while the loop twin's
#:              per-event scalar scatters serialize.
#:   "pallas" — the scan structure with the two sequential hot spots
#:              (greedy ACD sweep, capped FIFO dispatch chain) replaced
#:              by Pallas kernels (:mod:`repro.kernels`); interpret mode
#:              on CPU, Mosaic on TPU, which refuses both kernels today
#:              (the call fails with Mosaic's reason; no fallback).
#:
#: The built-in default is backend-aware: on a CPU backend the scalar
#: loop twin measures faster at fig-4 scale (each scan trip touches
#: O(J)-wide operands whose cost scales with J on a serial backend,
#: while the loop body's per-event work is O(1) scalar updates), so CPU
#: defaults to "loop" and accelerator backends to "scan". Set
#: ``REPRO_ENGINE_IMPL`` or pass ``engine_impl=`` to override.
ENGINE_IMPLS = ("loop", "scan", "pallas")


def _default_engine_impl() -> str:
    return "loop" if jax.default_backend() == "cpu" else "scan"


def resolve_engine_impl(impl: Optional[str] = None) -> str:
    """Resolve an ``engine_impl=`` argument: ``None`` defers to the
    ``REPRO_ENGINE_IMPL`` environment variable, then the backend-aware
    default (see :data:`ENGINE_IMPLS`)."""
    eff = impl if impl is not None else os.environ.get(
        "REPRO_ENGINE_IMPL") or _default_engine_impl()
    if eff not in ENGINE_IMPLS:
        raise ValueError(
            f"unknown engine_impl {eff!r}: expected one of {ENGINE_IMPLS}")
    return eff


@dataclasses.dataclass
class VectorSimResult:
    """Batched twin of :class:`.simulator.SimResult`; axis 0 is scenarios.

    ``orders``/``c_max``/``batch_idx`` record the scenario grid: scenario
    ``s`` ran priority order ``orders[s]`` with deadline ``c_max[s]`` on
    latency-draw ``batch_idx[s]`` of the supplied pred/act batch.
    """

    makespan: np.ndarray            # [S]
    cost_usd: np.ndarray            # [S]
    public_mask: np.ndarray         # [S, J, M]
    start: np.ndarray               # [S, J, M]
    end: np.ndarray                 # [S, J, M]
    completion: np.ndarray          # [S, J]
    n_offloaded_stages: np.ndarray  # [S]
    n_init_offloaded_jobs: np.ndarray  # [S]
    per_stage_offloads: np.ndarray  # [S, M]
    provider: np.ndarray            # [S, J, M] int: -1 private, else index
    deadline: np.ndarray            # [S]
    orders: Tuple[str, ...]         # [S]
    c_max: np.ndarray               # [S]
    batch_idx: np.ndarray           # [S]
    release: Optional[np.ndarray] = None  # [S, J] job release times (None=batch)
    replica: Optional[np.ndarray] = None  # [S, J, M] int: private replica, -1 = public
    replicas: Optional[np.ndarray] = None  # [S, M] per-scenario replica counts
    segment: Optional[np.ndarray] = None  # [S, J, M] int: price segment, -1 = private
    trace_idx: Optional[np.ndarray] = None  # [S] index into the price_traces axis
    attempts: Optional[np.ndarray] = None  # [S, J, M] int: public attempts made
    failed: Optional[np.ndarray] = None    # [S, J, M] int: failed attempts
    abandoned: Optional[np.ndarray] = None  # [S, J] bool: recovery impossible
    fault_idx: Optional[np.ndarray] = None  # [S] index into the faults axis
    queue_wait: Optional[np.ndarray] = None  # [S, J, M] capped-slot FIFO wait
    cold: Optional[np.ndarray] = None       # [S, J, M] bool: paid a cold start

    @property
    def num_scenarios(self) -> int:
        return int(self.makespan.shape[0])

    @property
    def offload_fraction(self) -> np.ndarray:
        return self.public_mask.mean(axis=(1, 2))

    def scenario(self, s: int):
        """Slice scenario ``s`` into a plain :class:`SimResult`."""
        from .simulator import SimResult
        return SimResult(
            makespan=float(self.makespan[s]),
            cost_usd=float(self.cost_usd[s]),
            public_mask=self.public_mask[s],
            start=self.start[s], end=self.end[s],
            completion=self.completion[s],
            n_offloaded_stages=int(self.n_offloaded_stages[s]),
            n_init_offloaded_jobs=int(self.n_init_offloaded_jobs[s]),
            per_stage_offloads=self.per_stage_offloads[s],
            deadline=float(self.deadline[s]),
            provider=self.provider[s],
            release=None if self.release is None else self.release[s],
            replica=None if self.replica is None else self.replica[s],
            segment=None if self.segment is None else self.segment[s],
            attempts=None if self.attempts is None else self.attempts[s],
            failed=None if self.failed is None else self.failed[s],
            abandoned=None if self.abandoned is None else self.abandoned[s],
            queue_wait=None if self.queue_wait is None
            else self.queue_wait[s],
            cold=None if self.cold is None else self.cold[s])


def _inverse_perm(perm: jax.Array) -> jax.Array:
    """Inverse of a permutation vector: the same integers as a stable
    argsort of it, from one scatter instead of a sort."""
    return jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[-1], dtype=perm.dtype))


@functools.lru_cache(maxsize=None)
def _build_engine(M: int, I_max: int, J: int, P: int, S: int,
                  include_transfers: bool, init_mode: int, adaptive: bool,
                  A_att: int = 0, W: int = 0, faulty: bool = False,
                  lookahead: bool = False, capped: bool = False,
                  cold: bool = False, pooled: bool = False, C: int = 0,
                  impl: str = "scan", carry: bool = False):
    """Trace the stage-decomposed event loop for one (stage count, replica
    bound, job count, provider count, price-segment count, flags) shape
    family. DAG structure arrives as data: ``A``/``desc`` are [M, M]
    adjacency / strict-descendant masks over topologically-ordered stage
    indices (edges go low -> high), ``sink``/``pinned``/``inert`` are [M]
    stage flags, ``speed`` the [M, I_max] per-replica speed matrix
    (finite = present replica with that multiplicative slowdown, ``inf`` =
    absent slot) — replica counts and straggler factors are both scenario
    data. The provider portfolio arrives as data too, **segment-indexed**:
    billed-cost / selection-key matrices ``[P, S, J, M]`` and per-segment
    latency / egress / start-edge vectors ``[P, S]``. Placement is
    decision-epoch priced: after a stage's event loop resolves its offload
    epochs, the active segment of each provider at that epoch is a
    comparison-sum over the edge data, and the cheapest feasible
    (provider, segment) pair is gathered per job — so one executable
    serves any portfolio of the same (P, S), static portfolios being the
    S=1 (or constant-trace) case of the same arithmetic.

    With ``faulty``, the shape family grows a bounded **attempt axis**
    (``A_att`` retry slots, ``W`` outage-window slots per provider) and
    the per-stage placement unrolls into an attempt *chain*: failure
    draws / backoff delays / outage windows are scenario data
    (:mod:`.faults`), each attempt re-runs the masked placement argmin at
    its own epoch, terminal failures resolve to a private fallback slot
    or abandon the job, and dead stages propagate ``+inf`` ends so
    downstream stages of an abandoned job never become eligible. The
    degenerate chain (zero fault grid) reuses the fault-free expressions
    term-for-term, so it is bit-exact vs the ``faulty=False`` engine.

    ``capped``/``cold``/``pooled`` grow the graph with load-dependent
    latency (:mod:`.coldstart`): ``capped`` adds per-(provider, stage)
    FIFO slot pools of width ``C`` — public dispatches replay
    sequentially in the DES's chronological event order, each pricing
    its queueing delay (and warm-up, under ``cold``) into the placement
    argmin and the bill as occupancy $/s; ``cold`` threads per-replica
    idle timestamps through the private event loop (a dispatch after an
    idle gap longer than the keep-alive window pays the warm-up
    additively, *not* scaled by straggler slowdowns); ``pooled`` masks
    replica availability by per-slot [on, off) windows while keeping
    retired-slot completions as sweep time points (the DES's
    ``_private_done`` events still fire for draining slots). All three
    are build flags: a degenerate config compiles the pre-change graph,
    so uncapped / zero-penalty / constant-pool runs stay bit-exact.

    ``carry`` builds a page of a paged stream (:func:`_run_paged`): one
    more argument, ``fixed`` [J, M, 2], holds the queue exit and replica
    of every (job, stage) an earlier page already decided (NaN exit = not
    fixed). A fixed stage stays out of its queue and keeps its decision;
    everything derived from decisions (arrivals, placements, times,
    cost) is recomputed from it bit for bit. Each stage's event loop
    resumes at the page's cut rather than starting there: jobs that
    reached a queue before it are already queued, and no sweep runs at
    the cut unless an event falls on it. The ``t0`` argument holds
    ``[cut, next cut]``; the page emits its queue exits (``qexit``) and
    each replica's clock at the next cut (``clocks``).
    """
    loaded = capped or cold or pooled
    iota_J = jnp.arange(J)

    def run_stage(k, a, forced_k, elig, speed_k, clock0_k, acd_k, P_k,
                  rem_k, dur_k, keys_k, deadline, t0, t_cut=None,
                  off_k=None, csd=None):
        """Run stage k's event loop given per-job arrival times ``a`` [J].

        ``deadline`` is the per-job absolute deadline [J] (release + C_max;
        a constant vector for batch workloads). ``speed_k`` [I_max] holds
        the stage's replica pool, ``clock0_k`` [I_max] the busy-until
        clock each present replica starts from (``t0`` for a monolithic
        run; the clocks at the page's cut when paging the job axis).
        Returns (times, replica, clocks, cold, trips) in job coords:
        ``times`` holds the dispatch instant of private jobs and
        ``-(eviction instant) - 1`` of evicted ones (NaN = never exited);
        ``clocks`` the per-replica busy-until vector, final or (when
        paging) at the next cut ``t_cut``; ``trips`` the
        while-loop trips this lane needed (under vmap every lane runs as
        many as the slowest). Placement/pricing happen in the caller,
        where the offload epoch is known.
        """
        # queue coordinates: stable sort by stage key, ties by job id
        perm = jnp.argsort(keys_k, stable=True)
        inv = _inverse_perm(perm)
        P_q = P_k[perm]
        rem_q = rem_k[perm]
        dur_q = dur_k[perm]
        a_q = a[perm]
        elig_q = elig[perm]
        dl_q = deadline[perm]
        # arrival stream, time order; ineligible jobs never arrive.
        # arr_rank[p] = arrival index of queue position p, so the queue is
        # *derived* each iteration as (arr_rank < ap) & ~exited — arrivals
        # need no insert scatter, only the arrival cursor ``ap`` moves.
        a_elig = jnp.where(elig_q, a_q, jnp.inf)
        arr_order = jnp.argsort(a_elig, stable=True)
        arr_t = jnp.concatenate([a_elig[arr_order], jnp.full(1, jnp.inf)])
        arr_rank = _inverse_perm(arr_order)
        n_arr = elig_q.sum()
        if carry:
            # a page resumes at its cut: what arrived before it is queued
            ap0 = (elig_q & (a_q < t0)).sum()
        else:
            ap0 = (elig_q & (a_q <= t0)).sum()  # t0 batch (source stages)
        # I_k is derived from the pool: count of present (finite) slots
        I_k = jnp.isfinite(speed_k).sum().astype(jnp.float64)
        slack_c = I_k * dl_q  # hoisted per-job term of the ACD slack
        # the whole job-constant part of the ACD threshold hoists out of
        # the loop: thresh(t) = base_c - I_k * t (one subtract per sweep)
        base_c = slack_c - I_k * rem_q
        iota_I = jnp.arange(I_max, dtype=jnp.int32)
        # loop-invariant payload for the batched body's match matmul;
        # absent slots never match, so their inf speed sanitizes to 0
        pay_s = jnp.stack([(iota_I + 1).astype(jnp.float64),
                           jnp.where(jnp.isfinite(speed_k), speed_k, 0.0)],
                          axis=1)

        def cond(c):
            t, ap, exited, svr = c[0], c[1], c[2], c[3]
            it = c[7]
            return ((ap < n_arr) | ((arr_rank < ap) & ~exited).any()) \
                & (it < 4 * J + 16)

        def body(c):
            # One event per iteration, one ACD evaluation per iteration.
            # ``clean`` carries whether the sweep at (q, t) finished with no
            # violators: while False, time must not advance — remaining
            # violators of the current event evict first (the DES runs the
            # whole kept-prefix sweep before moving on), and dispatches wait
            # for a clean sweep (evict-before-dispatch at every event).
            #
            # A job leaves the queue by dispatch or eviction, never both,
            # and either way at the current event instant — so one `times`
            # array records both exits (dispatches as +t, evictions as
            # -t - 1; run_stage requires t0 >= 0) and a sentinel-index
            # scatter (J + mode="drop" = no-op) commits the conditional
            # write without a full-width select.
            if cold:
                t, ap, exited, svr, times, rep, clean, it, idle, coldq = c
            else:
                t, ap, exited, svr, times, rep, clean, it = c
            arrived = arr_rank < ap
            q = arrived & ~exited
            nq = q.any()
            done = (ap >= n_arr) & ~nq
            # next event: arrival vs dispatch opportunity (free replica now,
            # else the earliest completion)
            t_arr = arr_t[ap]
            mins = jnp.min(svr)
            next_comp = jnp.min(jnp.where(svr > t, svr, jnp.inf))
            if pooled:
                # a free-but-retired slot (window closed) offers no
                # dispatch *opportunity*, but retired-slot completions
                # stay in next_comp: the DES's drain events still sweep
                free_t = (svr <= t) & (t < off_k)
                td = jnp.where(nq, jnp.where(free_t.any(), t, next_comp),
                               jnp.inf)
            else:
                td = jnp.where(nq, jnp.where(mins <= t, t, next_comp),
                               jnp.inf)
            advance = clean & ~done
            is_arr = advance & (t_arr <= td)
            t_new = jnp.where(advance, jnp.minimum(t_arr, td), t)
            # admit every arrival tied at t_new in one step: an epoch's
            # jobs enqueue together *before* the ACD sweep, matching the
            # DES arrival-epoch semantics (rolling-horizon serving
            # quantizes releases onto a replan grid, so tied groups are
            # the norm there; for tie-free streams this is ap + 1). The
            # +inf sentinel and ineligible-job entries never compare <=.
            ap = jnp.where(is_arr, (arr_t <= t_new).sum().astype(ap.dtype),
                           ap)
            q1 = (arr_rank < ap) & ~exited
            # ACD sweep step at t_new; a single priority-encoded argmax
            # yields the first violator if any, else the queue head
            if adaptive:
                contrib = jnp.where(q1, P_q, 0.0)
                prefix_excl = prefix_sum(contrib) - contrib
                viol = (q1 & acd_k
                        & (prefix_excl > base_c - I_k * t_new))
                has_viol = viol.any()
                pos_x = jnp.argmax(q1 + 2 * viol.astype(jnp.int8))
            else:
                has_viol = jnp.asarray(False)
                pos_x = jnp.argmax(q1)
            # evict the first violator, else dispatch head-of-queue to the
            # lowest-indexed free replica (the deterministic tie-break the
            # DES shares; mutually exclusive with eviction: one queue exit).
            # A dispatched stage runs dur * speed of the chosen replica —
            # straggler factors bind at dispatch, exactly as in the DES.
            if pooled:
                free_new = (svr <= t_new) & (t_new < off_k)
                do_disp = ~has_viol & ~done & (nq | is_arr) & free_new.any()
                sidx = jnp.argmax(free_new)  # lowest live free slot
            else:
                do_disp = ~has_viol & ~done & (nq | is_arr) & (mins <= t_new)
                sidx = jnp.argmax(svr <= t_new)  # absent slots: never free
            exit_idx = jnp.where(has_viol | do_disp, pos_x, J)
            exited = exited.at[exit_idx].set(True, mode="drop")
            times = times.at[exit_idx].set(
                jnp.where(has_viol, -t_new - 1.0, t_new), mode="drop")
            rep = rep.at[jnp.where(do_disp, pos_x, J)].set(
                sidx.astype(rep.dtype), mode="drop")
            if cold:
                # cold start: the slot sat idle past the keep-alive window
                # (or was never used, under scale-to-zero). The warm-up is
                # additive — never scaled by the replica's slowdown — and
                # the slot frees at warm-up + scaled duration, exactly the
                # DES's `start + dur` completion event.
                wu_priv, ka, s2z = csd
                is_cold = do_disp & ((t_new - idle[sidx] > ka)
                                     | jnp.isneginf(idle[sidx]))
                wu_eff = jnp.where(is_cold, wu_priv, 0.0)
                svr_new = (t_new + wu_eff) + dur_q[pos_x] * speed_k[sidx]
                coldq = coldq.at[jnp.where(do_disp, pos_x, J)].set(
                    is_cold, mode="drop")
                idle = jnp.where(do_disp, idle.at[sidx].set(svr_new), idle)
                svr = jnp.where(do_disp, svr.at[sidx].set(svr_new), svr)
                return (t_new, ap, exited, svr, times, rep, ~has_viol,
                        it + 1, idle, coldq)
            svr = jnp.where(do_disp,
                            svr.at[sidx].set(
                                t_new + dur_q[pos_x] * speed_k[sidx]), svr)
            return (t_new, ap, exited, svr, times, rep, ~has_viol, it + 1)

        # the batched carry packs its four small integers/flags (arrival
        # pointer, clean flag, queue-nonempty flag, trip counter) into one
        # int64 word: each extra carry member costs a per-trip select and
        # inter-trip copy under vmap, while the pack/unpack shifts fuse
        # into the surrounding elementwise graph for free
        APB = int(J).bit_length() + 1
        AP_MASK = (1 << APB) - 1
        CLEAN_SHIFT, NQ_SHIFT, IT_SHIFT = APB, APB + 1, APB + 2

        def cond_batched(c):
            # the packed word holds the queue-nonempty flag, so the loop
            # guard is pure scalar arithmetic (the loop twin's guard
            # re-reduces the J-wide queue every trip)
            st = c[1]
            return ((((st & AP_MASK) < n_arr) | (((st >> NQ_SHIFT) & 1) > 0))
                    & ((st >> IT_SHIFT) < 4 * J + 16))

        def body_batched(c):
            # Fused segment-scan body ("scan"/"pallas" impls): one
            # iteration commits the whole event *batch* at the current
            # instant — the complete ACD eviction cascade *and* the
            # same-instant dispatch batch — instead of one event.
            # Exactness rests on three same-instant arguments, all shared
            # with the DES:
            #
            # * ACD cascade: the iterated first-violator removal only
            #   ever evicts jobs that violate under the *current* queue
            #   prefix (prefixes shrink monotonically as jobs leave), so
            #   any violator that still violates with every earlier
            #   violator's demand subtracted is *certainly* in the final
            #   evict set — evict all of them at once. The first
            #   violator always qualifies, so each round strictly
            #   shrinks the cascade, and every eviction of a cascade
            #   shares the instant (time is gated on a clean sweep), so
            #   the recorded times are identical to one-at-a-time. The
            #   pallas impl's kernel runs the whole greedy kept-prefix
            #   recurrence sequentially, so its round is always complete.
            # * cascade-complete test: re-checking the surviving
            #   violators against the post-eviction prefix (their old
            #   prefix minus the evicted demand ahead of them) decides
            #   *in the same trip* whether the cascade has converged —
            #   if it has, the dispatch batch commits immediately, which
            #   is exactly the sequential order (evict-all, then
            #   dispatch) without spending a trip per round boundary.
            # * dispatch batch: at a fixed instant the sequential loop
            #   hands queue rank r the r-th lowest free replica (each
            #   dispatch occupies its slot), and dispatches never create
            #   violators (prefixes only shrink) — so all same-instant
            #   dispatches commit together. The one exception is a
            #   dispatch whose busy increment is zero (its slot stays
            #   free and the sequential loop would *reuse* it): the
            #   batch truncates right after it and the next iteration
            #   re-derives the free set.
            #
            # Queue exits commit through full-width mask-selects (which
            # fuse into the surrounding elementwise graph) rather than
            # the loop twin's per-event scatters.
            if cold:
                t, st, svr, times, rep, idle, coldq = c
            else:
                t, st, svr, times, rep = c
            ap = st & AP_MASK
            clean = ((st >> CLEAN_SHIFT) & 1) > 0
            nq = ((st >> NQ_SHIFT) & 1) > 0
            it = st >> IT_SHIFT
            # a queue exit always stamps `times`, so the exited mask is
            # derivable — one fewer [J] carry member to select and copy
            exited = ~jnp.isnan(times)
            done = (ap >= n_arr) & ~nq
            t_arr = arr_t[ap]
            # one reduce for "t if any replica is free, else the next
            # completion": free slots clamp to t, busy slots keep their
            # clock, absent slots stay +inf (retired pool slots offer no
            # dispatch opportunity, but their completions still sweep)
            if pooled:
                td_core = jnp.min(jnp.where(
                    (svr <= t) & (t < off_k), t,
                    jnp.where(svr > t, svr, jnp.inf)))
            else:
                td_core = jnp.min(jnp.maximum(svr, t))
            # empty-queue fast-forward: with no free slot (busy clocks
            # are strictly > t, so a free slot shows as td_core <= t) and
            # the next arrival at or before the next completion, nothing
            # can dispatch until that completion — jump straight to it,
            # admitting every arrival on the way
            td = jnp.where(nq, td_core,
                           jnp.where((td_core <= t) | (t_arr > td_core),
                                     jnp.inf, td_core))
            advance = clean & ~done
            is_arr = advance & (t_arr <= td)
            # speculative arrival fast-forward: admit *every* arrival in
            # (t, td] in one trip and jump straight to the dispatch
            # opportunity at td. Safe whenever the ACD sweep at td over
            # the fully-admitted queue is clean: a job's kept prefix only
            # grows toward td (arrivals join, nothing exits in between)
            # and its threshold only shrinks (slack decays with t), so a
            # violation at any skipped intermediate instant would imply
            # one at td — clean at td means the skipped sweeps were
            # provably no-ops. A dirty speculation falls back to the
            # one-instant step at t_arr, which re-finds any intermediate
            # eviction at its exact event instant.
            # both admission counts (jump target td and fallback t_arr)
            # packed into a single reduce; J + 1 exceeds any count
            cnt_pack = ((arr_t <= td).astype(jnp.int32) * (J + 1)
                        + (arr_t <= t_arr)).sum()
            ap_td = (cnt_pack // (J + 1)).astype(ap.dtype)
            ap_arr = (cnt_pack % (J + 1)).astype(ap.dtype)
            spec = is_arr & jnp.isfinite(td)
            t_new = jnp.where(advance,
                              jnp.where(spec, td,
                                        jnp.minimum(t_arr, td)), t)
            ap = jnp.where(is_arr, jnp.where(spec, ap_td, ap_arr), ap)
            q1 = (arr_rank < ap) & ~exited
            if adaptive:
                thresh = base_c - I_k * t_new
                if impl == "pallas":
                    # kernel: the whole greedy evict set in one round, so
                    # the cascade is always complete this trip
                    evict_now = _kernel_ops.acd_evict(
                        P_q[None], thresh[None], (q1 & acd_k)[None],
                        use_pallas=True)[0]
                    leftover = None
                    has_viol = evict_now.any()
                else:
                    contrib = jnp.where(q1, P_q, 0.0)
                    prefix_excl = prefix_sum(contrib) - contrib
                    viol = q1 & acd_k & (prefix_excl > thresh)
                    vc = jnp.where(viol, P_q, 0.0)
                    vprev = prefix_sum(vc) - vc
                    evict_now = viol & (prefix_excl - vprev > thresh)
                    # conservative cascade-complete test: any violator
                    # surviving the certain-set round defers the dispatch
                    # batch one trip (the re-sweep at the same instant
                    # then sees the smaller prefix — same exits, same
                    # timestamps, occasionally one extra trip). The
                    # reduce is deferred: `leftover` folds into the
                    # first-stuck min below as a -1 sentinel.
                    leftover = viol & ~evict_now
                    has_viol = viol.any()
                # dirty speculation: the sweep at td over the fully
                # admitted queue found an eviction, so some skipped
                # intermediate instant may have needed one too. Rewind
                # to the one-instant step at t_arr (discarding this
                # trip's evictions and blocking its dispatch batch);
                # the next trip re-sweeps at t_arr exactly.
                dirty = spec & (t_arr < t_new) & has_viol
                evict_now = evict_now & ~dirty
                t_new = jnp.where(dirty, t_arr, t_new)
                ap = jnp.where(dirty, ap_arr, ap)
            else:
                leftover = None
                evict_now = jnp.zeros(J, dtype=bool)
            q2 = q1 & ~evict_now
            if pooled:
                free_new = (svr <= t_new) & (t_new < off_k)
            else:
                free_new = svr <= t_new
            # rank->slot matching without sorts, scatters or gathers (all
            # serial ops on CPU XLA): queue rank r pairs with the r-th
            # lowest free replica through a [J, I] one-hot match matrix
            # (I is small), which also carries the slot's speed/idle
            # state to the job row and the job's new busy-until clock
            # back to the slot row — everything fuses into elementwise
            # kernels plus one small reduction per quantity
            free_i = free_new.astype(jnp.int32)
            free_rank = jnp.cumsum(free_i) - free_i
            q2i = q2.astype(jnp.int32)
            qrank = jnp.cumsum(q2i) - q2i
            match = (free_new[None, :]
                     & (qrank[:, None] == free_rank[None, :]))  # [J, I]
            # one tiny matmul carries (slot index + 1, speed) across the
            # match — 0 = no free slot at this rank, else index + 1;
            # ranks match at most one slot, so each output is one value
            # plus exact zeros. The payload is loop-invariant.
            mf = match.astype(jnp.float64)                     # [J, I]
            mj = mf @ pay_s                                    # [J, 2]
            slot1_j = mj[:, 0]
            slot_j = (slot1_j - 1.0).astype(jnp.int32)
            speed_j = mj[:, 1]
            # no ``~done`` guard: a finished lane carries an empty queue,
            # so q2 is already all-False there
            disp0 = q2 & (slot1_j > 0)
            if cold:
                wu_priv, ka, _ = csd
                # per-slot coldness first (I-cheap), carried to the job
                # row through the match product — 1.0 or exact 0.0
                cold_i = ((t_new - idle > ka)
                          | jnp.isneginf(idle)).astype(jnp.float64)
                is_cold_j = disp0 & (mf @ cold_i > 0.5)
                wu_eff_j = jnp.where(is_cold_j, wu_priv, 0.0)
                svr_new_j = (t_new + wu_eff_j) + dur_q * speed_j
            else:
                svr_new_j = t_new + dur_q * speed_j
            stuck = disp0 & (svr_new_j <= t_new)
            fs = jnp.where(stuck, qrank, J)
            if leftover is not None:
                # -1 sentinel: an incomplete cascade defers the whole
                # batch (qrank <= -1 matches nothing) in the same reduce
                fs = jnp.where(leftover, -1, fs)
            first_stuck = jnp.min(fs)
            if adaptive:
                # a rewound trip likewise commits nothing; the follow-up
                # no-advance trip redoes the instant at t_arr
                first_stuck = jnp.where(dirty, -1, first_stuck)
                has2 = first_stuck < 0
            else:
                has2 = jnp.asarray(False)
            disp = disp0 & (qrank <= first_stuck)
            times = jnp.where(evict_now, -t_new - 1.0,
                              jnp.where(disp, t_new, times))
            rep = jnp.where(disp, slot_j.astype(rep.dtype), rep)
            # commit the batch to the slot rows through the transposed
            # match product — at most one dispatched job per slot makes
            # the sum exact (one value plus zeros), and the dispatched
            # ranks form a prefix, so the taken slots are exactly the
            # free ones ranked below the dispatch count
            slot_val = jnp.where(disp, svr_new_j, 0.0) @ mf    # [I]
            # the dispatched ranks form a prefix (rank < free count,
            # rank <= first_stuck, rank < member count), so the batch
            # size is scalar arithmetic on counts already in hand — no
            # J-wide re-reduce for the size, the taken set, or the
            # queue-nonempty flag
            n_free = free_rank[-1] + free_i[-1]
            n_q2 = qrank[-1] + q2i[-1]
            n_disp = jnp.minimum(jnp.minimum(first_stuck + 1, n_free),
                                 n_q2)
            taken = free_new & (free_rank < n_disp)
            svr = jnp.where(taken, slot_val, svr)
            nq = n_q2 > n_disp
            st_new = (ap.astype(jnp.int64)
                      | ((~has2).astype(jnp.int64) << CLEAN_SHIFT)
                      | (nq.astype(jnp.int64) << NQ_SHIFT)
                      | ((it + 1) << IT_SHIFT))
            if cold:
                coldq = jnp.where(disp, is_cold_j, coldq)
                idle = jnp.where(taken, slot_val, idle)
                return (t_new, st_new, svr, times, rep, idle, coldq)
            return (t_new, st_new, svr, times, rep)

        svr0 = jnp.where(jnp.isfinite(speed_k), clock0_k, jnp.inf)  # absent
        if cold:
            # idle-since per slot: the turn-on instant (clock0 covers late
            # pool slots), -inf = never used under scale-to-zero
            idle0 = jnp.where(csd[2] > 0.5,
                              jnp.full_like(clock0_k, -jnp.inf), clock0_k)
            cold0 = (idle0, jnp.zeros((J,), bool))
        else:
            cold0 = ()
        times0 = jnp.full((J,), jnp.nan)
        rep0 = jnp.full((J,), -1, jnp.int32)
        t0f = jnp.asarray(t0, jnp.float64)
        i_svr = 3 if impl == "loop" else 2  # position of svr in the carry

        def at_cut(step):
            """A page also tracks each replica's clock as it stands after
            the last step before ``t_cut``: the clocks the next page
            resumes from, as the loop itself computed them."""
            def f(c):
                c1 = step(c[:-1])
                return c1 + (jnp.where(c1[0] < t_cut, c1[i_svr], c[-1]),)
            return f

        cut0 = (svr0,) if carry else ()
        # a fresh loop sweeps its t0 batch before the first advance; a
        # resumed page's queue was swept at its last event, so it is clean
        clean0 = carry
        if impl == "loop":
            c = (t0f, ap0, jnp.zeros((J,), bool), svr0, times0, rep0,
                 jnp.asarray(clean0), jnp.zeros((), jnp.int32)) + cold0
            c = jax.lax.while_loop(cond, at_cut(body) if carry else body,
                                   c + cut0)
            trips = c[7]
        else:
            # initial word: the clean flag, it = 0, queue non-empty iff
            # the t0 batch admitted anything
            st0 = (ap0.astype(jnp.int64)
                   | (int(clean0) << CLEAN_SHIFT)
                   | ((ap0 > 0).astype(jnp.int64) << NQ_SHIFT))
            c = (t0f, st0, svr0, times0, rep0) + cold0
            step = at_cut(body_batched) if carry else body_batched
            # two body steps per while trip: the guard, carry select and
            # inter-trip copies amortize over both, and XLA fuses the
            # first step's tail into the second's head. Exact because a
            # finished lane's body is a fixed point (empty queue commits
            # nothing), so the odd extra step is a no-op.
            c = jax.lax.while_loop(cond_batched, lambda c: step(step(c)),
                                   c + cut0)
            # IT counts body steps, two per while trip
            trips = ((c[1] >> IT_SHIFT) >> 1).astype(jnp.int32)
        times, rep = c[i_svr + 1], c[i_svr + 2]
        svr = c[-1] if carry else c[i_svr]
        coldq = c[len(c) - 1 - carry][inv] if cold else jnp.zeros((J,), bool)
        # back to job coordinates
        return times[inv], rep[inv], svr, coldq, trips

    def run_one(P_pred, act_priv, pub_a, up_a, down_a, dgb_pred, cost_ps,
                sel_ps, lat_ps, eg_ps, edges_ps,
                stage_keys, deadline, t0, release, init_elig, live, A, desc, sink, pinned, inert, speed,
                clock0, *fault_args):
        if carry:
            # a page spans [t0, t_cut): it resumes at its own cut and
            # hands the next page the clocks at the next one
            *fault_args, fixed = fault_args
            t0, t_cut = t0[0], t0[1]
        else:
            t_cut = None
        if faulty:
            # scenario fault data: [J, M, A_att] failure draws + backoff
            # delays, [P, W, 2] outage windows, and scalar knobs
            fail_g, delay_g, outw, kill_frac, okill, fb_on = fault_args
        elif loaded:
            # load data (faults x load is rejected upstream, so *fault_args
            # carries exactly one of the two families): [P] concurrency
            # caps (inf = unbounded), [P, S, M] occupancy $/s, [P] public
            # warm-ups, (warm_up, keep_alive, scale_to_zero) scalars, and
            # [M, I_max] pool turn-off instants
            caps_v, occ_psm, wu_pub, cs3, off_pool = fault_args
            csd = (cs3[0], cs3[1], cs3[2])
        # per-stage critical-path remainder (reverse index order = reverse
        # topological order; edges go low -> high)
        rem_l: List[Optional[jax.Array]] = [None] * M
        for k in reversed(range(M)):
            best = jnp.zeros(P_pred.shape[0])
            for v in range(k + 1, M):
                best = jnp.maximum(best, jnp.where(A[k, v], rem_l[v], 0.0))
            rem_l[k] = P_pred[:, k] + best

        if init_mode == 2:
            # the offload plan (a policy mask, or the capacity-prefix
            # rule, which is *global* over the job axis) is resolved on
            # the host over the full job set and fed in, page by page
            # when paging
            off = init_elig & live
        else:
            off = jnp.zeros(J, dtype=bool)

        start_l: List[Optional[jax.Array]] = [None] * M
        end_l: List[Optional[jax.Array]] = [None] * M
        loc_l: List[Optional[jax.Array]] = [None] * M
        evict_l: List[Optional[jax.Array]] = [None] * M
        prov_l: List[Optional[jax.Array]] = [None] * M
        seg_l: List[Optional[jax.Array]] = [None] * M
        rep_l: List[Optional[jax.Array]] = [None] * M
        down_l: List[Optional[jax.Array]] = [None] * M
        cost_l: List[Optional[jax.Array]] = [None] * M
        att_l: List[Optional[jax.Array]] = [None] * M
        failc_l: List[Optional[jax.Array]] = [None] * M
        qexit_l: List[Optional[jax.Array]] = [None] * M
        clocks_l: List[Optional[jax.Array]] = [None] * M
        trips_l: List[Optional[jax.Array]] = [None] * M
        qwait_l: List[Optional[jax.Array]] = [None] * M
        coldm_l: List[Optional[jax.Array]] = [None] * M
        ab_j = jnp.zeros(J, dtype=bool)
        # per-job accumulators (host-side canonical-order reductions make
        # monolithic and paged runs bit-identical)
        lost_j = jnp.zeros(J)
        xeg_j = jnp.zeros(J)
        iota_P = jnp.arange(P)
        neg = jnp.full(J, -jnp.inf)
        for k in range(M):
            # source stages arrive at the job's release time (t0 for a
            # batch); downstream stages whenever their predecessors finish
            # (an abandoned predecessor's +inf end makes the job dead here)
            a = neg
            for u in range(k):
                a = jnp.maximum(a, jnp.where(A[u, k], end_l[u], -jnp.inf))
            a = jnp.where(A[:k, k].any() if k else False, a, release)
            # forced public at entry: init offload + upstream eviction
            # cascades (constraint (12)); privacy-pinned stages never leave
            forced_k = off
            for u in range(k):
                forced_k = forced_k | (desc[u, k] & evict_l[u])
            forced_k = forced_k & ~pinned[k]
            elig = ~forced_k & ~inert[k] & live
            if faulty:
                # dead jobs (abandoned upstream) never enter a queue
                elig = elig & jnp.isfinite(a)
            if carry:
                # a stage an earlier page decided stays out of the queue
                fixed_k = ~jnp.isnan(fixed[:, k, 0])
                elig = elig & ~fixed_k
            acd_k = ~pinned[k]
            times_j, rep_j, clocks_l[k], coldq, trips_l[k] = run_stage(
                k, a, forced_k, elig, speed[k], clock0[k], acd_k,
                P_pred[:, k], rem_l[k], act_priv[:, k], stage_keys[:, k],
                deadline, t0, t_cut,
                off_k=off_pool[k] if pooled else None,
                csd=csd if cold else None)
            if carry:
                times_j = jnp.where(fixed_k, fixed[:, k, 0], times_j)
                rep_j = jnp.where(fixed_k, fixed[:, k, 1].astype(rep_j.dtype),
                                  rep_j)
            qexit_l[k] = times_j
            evicted = times_j < -0.5  # NaN (never exited) compares False
            locpub = forced_k | evicted
            # decision-epoch pricing: the offload epoch is the stage's
            # arrival time when forced public, the eviction instant when
            # ACD-evicted; each provider's active segment at that epoch is
            # a comparison-sum over the edge data, and the cheapest
            # feasible (provider, segment) is locked for the whole stage
            tau = jnp.where(forced_k, a, -times_j - 1.0)

            def placement_at(tq, k=k):
                """[P, J] selection costs + active segments at epochs tq.

                Provider-affinity penalty: placing stage k on a provider
                other than a public predecessor's pays that predecessor's
                (predicted) egress to move the edge. Accumulated onto
                selc one predecessor at a time, in ascending topological
                order — the DES sums in the same order, so the floats
                associate identically and near-tie argmins cannot flip
                between engines.
                """
                seg_pj = jnp.maximum(
                    (edges_ps[:, :, None] <= tq[None, None, :]).sum(axis=1)
                    - 1, 0)                                    # [P, J]
                s = jnp.take_along_axis(sel_ps[:, :, :, k],
                                        seg_pj[:, None, :], axis=1)[:, 0, :]
                if include_transfers:
                    for u in range(k):
                        pen_u = jnp.where(
                            A[u, k] & loc_l[u],
                            eg_ps[prov_l[u], seg_l[u]] * dgb_pred[:, u],
                            0.0)
                        s = s + jnp.where(
                            iota_P[:, None] != prov_l[u][None, :],
                            pen_u[None, :], 0.0)
                if include_transfers and lookahead:
                    # one-edge downstream recourse: placing stage k on a
                    # candidate provider commits its successor edges to
                    # pay that provider's egress if they ever move, so
                    # the argmin sees (predicted edge volume) x (the
                    # candidate's egress rate at the epoch's segment).
                    # Successor terms add after the predecessor penalty,
                    # in ascending topological order — the DES sums in
                    # the same order (identical float association).
                    eg_cand = jnp.take_along_axis(eg_ps, seg_pj, axis=1)
                    for v in range(k + 1, M):
                        s = s + jnp.where(
                            A[k, v] & ~pinned[v],
                            eg_cand * dgb_pred[:, k][None, :], 0.0)
                return s, seg_pj

            if not faulty and capped:
                # ---- concurrency caps: sequential slot scan ------------
                # Public dispatches of stage k replay in the DES's
                # chronological event order — offload epoch first, forced
                # jobs (arrival-event order = ascending job id) before
                # evicted jobs (queue rank) on ties — each taking every
                # provider's earliest-free FIFO slot, pricing its wait
                # (+ warm-up, under ``cold``) into the argmin as
                # occupancy $/s, then advancing the chosen provider's
                # slot clock: ``_start_public_capped`` expression for
                # expression. Slot pools are per (provider, stage), so
                # the scan state never crosses stages.
                selc, seg_pj = placement_at(tau)
                lm_pj = jnp.take_along_axis(lat_ps, seg_pj, axis=1)
                occ_pj = jnp.take_along_axis(occ_psm[:, :, k], seg_pj,
                                             axis=1)          # [P, J]
                if include_transfers:
                    needs_up = jnp.zeros(J, dtype=bool)
                    for u in range(k):
                        needs_up = needs_up | (A[u, k] & ~loc_l[u])
                    has_pred = A[:k, k].any() if k else jnp.asarray(False)
                    needs_up = jnp.where(has_pred, needs_up, True)
                    up_raw = jnp.where(needs_up, up_a[:, k], 0.0)
                else:
                    up_raw = jnp.zeros(J)
                ready_pj = tau[None, :] + up_raw[None, :] * lm_pj
                dur_pj = pub_a[:, k][None, :] * lm_pj
                capped_p = jnp.isfinite(caps_v)
                wu_p = wu_pub if cold else jnp.zeros(P)
                qrank = _inverse_perm(jnp.argsort(stage_keys[:, k],
                                                  stable=True))
                if impl == "loop":
                    order_j = jnp.lexsort((
                        jnp.where(forced_k, iota_J, qrank),
                        jnp.where(forced_k, 0, 1),
                        jnp.where(locpub, tau, jnp.inf)))
                else:
                    # same comparator among public jobs, but with a
                    # public-first major key so the chain can stop at
                    # n_pub (the loop twin walks all J slots; the
                    # skipped private iterations write nothing)
                    order_j = jnp.lexsort((
                        jnp.where(forced_k, iota_J, qrank),
                        jnp.where(forced_k, 0, 1),
                        jnp.where(locpub, tau, jnp.inf), ~locpub))
                n_pub = locpub.sum()
                present = capped_p[:, None] & (jnp.arange(C)
                                               < caps_v[:, None])
                sclk0 = jnp.where(present, t0, jnp.inf)
                if cold:
                    sidle0 = jnp.where(
                        present,
                        jnp.where(csd[2] > 0.5, -jnp.inf, t0), jnp.inf)
                else:
                    sidle0 = sclk0

                def slot_step(i, c):
                    (sclk, sidle, prov_o, seg_o, wait_o, cold_o,
                     start_o, end_o, extra_o) = c
                    j = order_j[i]
                    pub = locpub[j]
                    ready_p = ready_pj[:, j]
                    si = jnp.argmin(sclk, axis=1)             # [P]
                    sc_sel = sclk[iota_P, si]
                    wait_p = jnp.where(
                        capped_p, jnp.maximum(0.0, sc_sel - ready_p), 0.0)
                    if cold:
                        idle_sel = sidle[iota_P, si]
                        cold_p = capped_p & (
                            (ready_p + wait_p - idle_sel > csd[1])
                            | jnp.isneginf(idle_sel))
                    else:
                        cold_p = jnp.zeros(P, dtype=bool)
                    pen = occ_pj[:, j] * (wait_p + cold_p * wu_p)
                    prov = jnp.argmin(selc[:, j] + pen)
                    start = (ready_p[prov] + wait_p[prov]
                             + cold_p[prov] * wu_p[prov])
                    end = start + dur_pj[prov, j]
                    tgt = jnp.where(pub, j, J)
                    prov_o = prov_o.at[tgt].set(
                        prov.astype(prov_o.dtype), mode="drop")
                    seg_o = seg_o.at[tgt].set(
                        seg_pj[prov, j].astype(seg_o.dtype), mode="drop")
                    wait_o = wait_o.at[tgt].set(wait_p[prov], mode="drop")
                    cold_o = cold_o.at[tgt].set(cold_p[prov], mode="drop")
                    start_o = start_o.at[tgt].set(start, mode="drop")
                    end_o = end_o.at[tgt].set(end, mode="drop")
                    extra_o = extra_o.at[tgt].set(pen[prov], mode="drop")
                    upd = pub & capped_p[prov]
                    sclk = jnp.where(
                        upd, sclk.at[prov, si[prov]].set(end), sclk)
                    sidle = jnp.where(
                        upd, sidle.at[prov, si[prov]].set(end), sidle)
                    return (sclk, sidle, prov_o, seg_o, wait_o, cold_o,
                            start_o, end_o, extra_o)

                if impl == "pallas":
                    # kernel: the whole chain in one launch
                    (pidx_k, seg_k, wait_f, coldpub_f, start_pub,
                     end_pub, extra_f) = _kernel_ops.fifo_dispatch(
                        order_j, locpub, n_pub, ready_pj, dur_pj, selc,
                        occ_pj, seg_pj, capped_p, wu_p, sclk0, sidle0,
                        csd[1] if cold else 0.0, cold=cold,
                        use_pallas=True)
                    pidx_k = pidx_k.astype(jnp.int64)
                    seg_k = seg_k.astype(jnp.int64)
                else:
                    (_, _, pidx_k, seg_k, wait_f, coldpub_f, start_pub,
                     end_pub, extra_f) = jax.lax.fori_loop(
                        0, J if impl == "loop" else n_pub, slot_step,
                        (sclk0, sidle0,
                         jnp.zeros(J, jnp.int64), jnp.zeros(J, jnp.int64),
                         jnp.zeros(J), jnp.zeros(J, bool),
                         jnp.zeros(J), jnp.zeros(J), jnp.zeros(J)))
                lm = lat_ps[pidx_k, seg_k]                    # [J]
                # billed + occupancy extra add as one value per (job,
                # stage) — the single float the DES adds to its total
                cost_l[k] = cost_ps[pidx_k, seg_k, iota_J, k] + extra_f
                down_l[k] = down_a[:, k] * lm
                prov_l[k] = pidx_k
                seg_l[k] = seg_k
                if include_transfers:
                    for u in range(k):
                        moved = (A[u, k] & loc_l[u] & locpub
                                 & (prov_l[u] != pidx_k))
                        rate_u = eg_ps[prov_l[u], seg_l[u]]
                        xeg_j = xeg_j + jnp.where(
                            moved,
                            rate_u * (down_a[:, u] * EGRESS_GB_PER_S),
                            0.0)
                if cold:
                    start_priv = times_j + coldq * csd[0]
                else:
                    start_priv = times_j
                start = jnp.where(locpub, start_pub, start_priv)
                priv_dur = act_priv[:, k] * speed[k][jnp.maximum(rep_j, 0)]
                end = jnp.where(locpub, end_pub, start_priv + priv_dur)
                start_l[k], end_l[k] = start, end
                loc_l[k], evict_l[k] = locpub, evicted
                rep_l[k] = jnp.where(locpub, -1, rep_j)
                qwait_l[k] = wait_f
                coldm_l[k] = coldpub_f | coldq
                continue

            if not faulty:
                selc, seg_pj = placement_at(tau)
                pidx_k = jnp.argmin(selc, axis=0)             # [J]
                seg_k = seg_pj[pidx_k, iota_J]                # [J]
                lm = lat_ps[pidx_k, seg_k]                    # [J]
                cost_l[k] = cost_ps[pidx_k, seg_k, iota_J, k]
                down_l[k] = down_a[:, k] * lm
                prov_l[k] = pidx_k
                seg_l[k] = seg_k
                # upload needed iff some input of stage k lives in private
                # storage (or the stage reads the original private input);
                # an edge whose endpoints run public on *different*
                # providers pays the upstream provider's egress (at the
                # upstream stage's recorded segment) on the un-multiplied
                # edge volume
                if include_transfers:
                    needs_up = jnp.zeros(J, dtype=bool)
                    for u in range(k):
                        needs_up = needs_up | (A[u, k] & ~loc_l[u])
                        moved = (A[u, k] & loc_l[u] & locpub
                                 & (prov_l[u] != pidx_k))
                        rate_u = eg_ps[prov_l[u], seg_l[u]]
                        xeg_j = xeg_j + jnp.where(
                            moved,
                            rate_u * (down_a[:, u] * EGRESS_GB_PER_S),
                            0.0)
                    has_pred = A[:k, k].any() if k else jnp.asarray(False)
                    needs_up = jnp.where(has_pred, needs_up, True)
                    upk = jnp.where(needs_up, up_a[:, k] * lm, 0.0)
                else:
                    upk = jnp.zeros(J)
                if cold:
                    # uncapped public = unbounded warm fleet (never cold);
                    # private dispatches pay the warm-up recorded by the
                    # event loop (additive: t + 0.0 == t keeps the
                    # zero-penalty graph bit-exact)
                    start_priv = times_j + coldq * csd[0]
                else:
                    start_priv = times_j
                start = jnp.where(locpub, tau + upk, start_priv)
                # private durations run on the *assigned* replica's speed
                # (the loop body already advanced the clock by the scaled
                # duration)
                priv_dur = act_priv[:, k] * speed[k][jnp.maximum(rep_j, 0)]
                end = start + jnp.where(locpub, pub_a[:, k] * lm, priv_dur)
                start_l[k], end_l[k] = start, end
                loc_l[k], evict_l[k] = locpub, evicted
                rep_l[k] = jnp.where(locpub, -1, rep_j)
                qwait_l[k] = jnp.zeros(J)
                coldm_l[k] = coldq
                continue

            # ---- fault layer: unrolled attempt chain -------------------
            # Same recovery semantics as the DES heap events: attempt a
            # re-runs the placement argmin at its own epoch over providers
            # that are feasible, not in outage and not yet failed for this
            # (job, stage); a grid draw fails at kill_frac of the
            # duration, an outage window starting inside the execution
            # interval reclaims at the window start; lost work bills
            # pro-rata; terminal failures fall back to a dedicated private
            # slot by the deadline (fb_on) or abandon the job.
            alive = jnp.isfinite(a)
            fail_k = fail_g[:, k, :]                          # [J, A_att]
            delay_k = delay_g[:, k, :]                        # [J, A_att]

            def out_at(tq):
                """[P, J] bool: provider inside an outage window at tq."""
                return ((outw[:, :, 0, None] <= tq[None, None, :])
                        & (tq[None, None, :] < outw[:, :, 1, None])
                        ).any(axis=1)

            def masked_placement(tq, maskPJ):
                s, seg_pj = placement_at(tq)
                s = (s + jnp.where(out_at(tq), jnp.inf, 0.0)
                     + jnp.where(maskPJ, jnp.inf, 0.0))
                return s, seg_pj

            maskPJ = jnp.zeros((P, J), dtype=bool)
            selc_cur, seg_cur = masked_placement(tau, maskPJ)
            feas0 = jnp.isfinite(selc_cur).any(axis=0)
            chain = alive & locpub
            nf0 = chain & ~feas0   # nothing dispatchable at the epoch
            pending = chain & feas0
            # inputs are staged once, before the first attempt; the upload
            # carries the first attempt's provider multiplier (identical
            # to the fault-free expression when the chain is trivial)
            p0 = jnp.argmin(selc_cur, axis=0)
            lm0 = lat_ps[p0, seg_cur[p0, iota_J]]
            if include_transfers:
                needs_up = jnp.zeros(J, dtype=bool)
                for u in range(k):
                    needs_up = needs_up | (A[u, k] & ~loc_l[u])
                has_pred = A[:k, k].any() if k else jnp.asarray(False)
                needs_up = jnp.where(has_pred, needs_up, True)
                upk = jnp.where(needs_up, up_a[:, k] * lm0, 0.0)
            else:
                upk = jnp.zeros(J)

            t_att = tau
            up_cur = upk
            succ = jnp.zeros(J, dtype=bool)
            term = jnp.zeros(J, dtype=bool)
            p_fin = jnp.zeros(J, dtype=p0.dtype)
            seg_fin = jnp.zeros(J, dtype=p0.dtype)
            e_fin = jnp.zeros(J)
            lm_fin = jnp.ones(J)
            t_res = jnp.zeros(J)
            cost_k = jnp.zeros(J)
            att_cnt = jnp.zeros(J, dtype=jnp.int64)
            fail_cnt = jnp.zeros(J, dtype=jnp.int64)
            for ai in range(A_att):
                p_a = jnp.argmin(selc_cur, axis=0)            # [J]
                sg_a = seg_cur[p_a, iota_J]
                lm_a = lat_ps[p_a, sg_a]
                dur_a = pub_a[:, k] * lm_a
                s_a = t_att + up_cur
                e_a = s_a + dur_a
                billed = cost_ps[p_a, sg_a, iota_J, k]
                t_gf = jnp.where(fail_k[:, ai], s_a + kill_frac * dur_a,
                                 jnp.inf)
                if W > 0:
                    w_st = outw[p_a, :, 0]                    # [J, W]
                    cand = jnp.where((w_st > s_a[:, None])
                                     & (w_st < e_a[:, None]), w_st, jnp.inf)
                    t_kl = jnp.where(okill, cand.min(axis=1), jnp.inf)
                else:
                    t_kl = jnp.full(J, jnp.inf)
                t_f = jnp.minimum(t_gf, t_kl)
                failed_now = pending & jnp.isfinite(t_f)
                ok = pending & ~jnp.isfinite(t_f)
                att_cnt = att_cnt + pending.astype(att_cnt.dtype)
                fail_cnt = fail_cnt + failed_now.astype(fail_cnt.dtype)
                succ = succ | ok
                p_fin = jnp.where(ok, p_a, p_fin)
                seg_fin = jnp.where(ok, sg_a, seg_fin)
                e_fin = jnp.where(ok, e_a, e_fin)
                lm_fin = jnp.where(ok, lm_a, lm_fin)
                cost_k = cost_k + jnp.where(ok, billed, 0.0)
                frac = jnp.where(dur_a > 0.0, (t_f - s_a) / dur_a, 0.0)
                lost_j = lost_j + jnp.where(failed_now, billed * frac, 0.0)
                maskPJ = maskPJ | (failed_now[None, :]
                                   & (iota_P[:, None] == p_a[None, :]))
                if ai + 1 < A_att:
                    t_next = t_f + delay_k[:, ai + 1]
                    selc_n, seg_n = masked_placement(t_next, maskPJ)
                    feas_n = jnp.isfinite(selc_n).any(axis=0)
                    retry = failed_now & (t_next <= deadline) & feas_n
                    term_now = failed_now & ~retry
                    pending = retry
                    t_att = jnp.where(retry, t_next, t_att)
                    up_cur = jnp.where(retry, 0.0, up_cur)
                    selc_cur = jnp.where(retry[None, :], selc_n, selc_cur)
                    seg_cur = jnp.where(retry[None, :], seg_n, seg_cur)
                else:
                    term_now = failed_now
                    pending = jnp.zeros(J, dtype=bool)
                term = term | term_now
                t_res = jnp.where(term_now, t_f, t_res)

            term_all = term | nf0
            t_res = jnp.where(nf0, tau, t_res)
            fb = term_all & fb_on & (t_res <= deadline)
            ab = term_all & ~fb
            ab_j = ab_j | ab

            # fallback = dedicated nominal-speed private slot at t_res;
            # abandoned stages never end (+inf, converted to NaN on
            # output) and their descendants inherit the +inf arrival
            end_pub = jnp.where(succ, e_fin,
                                jnp.where(fb, t_res + act_priv[:, k],
                                          jnp.inf))
            start_pub = jnp.where(fb, t_res,
                                  jnp.where(nf0, tau, tau + upk))
            priv_dur = act_priv[:, k] * speed[k][jnp.maximum(rep_j, 0)]
            start = jnp.where(~alive, jnp.nan,
                              jnp.where(locpub, start_pub, times_j))
            end = jnp.where(~alive, jnp.inf,
                            jnp.where(locpub, end_pub, times_j + priv_dur))
            # cascade billing reads *successful* placements only
            if include_transfers:
                for u in range(k):
                    moved = (A[u, k] & loc_l[u] & succ
                             & (prov_l[u] != p_fin))
                    rate_u = eg_ps[prov_l[u], seg_l[u]]
                    xeg_j = xeg_j + jnp.where(
                        moved,
                        rate_u * (down_a[:, u] * EGRESS_GB_PER_S),
                        0.0)
            cost_l[k] = cost_k
            down_l[k] = down_a[:, k] * lm_fin
            prov_l[k] = p_fin
            seg_l[k] = seg_fin
            start_l[k], end_l[k] = start, end
            loc_l[k], evict_l[k] = succ, evicted
            rep_l[k] = jnp.where(locpub, -1, rep_j)
            att_l[k] = att_cnt
            failc_l[k] = fail_cnt
            qwait_l[k] = jnp.zeros(J)
            coldm_l[k] = jnp.zeros(J, dtype=bool)

        start = jnp.stack(start_l, axis=1)
        end = jnp.stack(end_l, axis=1)
        locpub = jnp.stack(loc_l, axis=1)
        cost_m = jnp.stack(cost_l, axis=1)
        prov_m = jnp.stack(prov_l, axis=1)
        seg_m = jnp.stack(seg_l, axis=1)
        rep_m = jnp.stack(rep_l, axis=1)
        # job completion: results back in private storage (sink download)
        fin = end
        if include_transfers:
            fin = fin + jnp.where(locpub, jnp.stack(down_l, axis=1), 0.0)
        completion = jnp.max(
            jnp.where(sink[None, :], fin, -jnp.inf), axis=1)
        # per-job cost (stage billing in fixed [J, M] reduction order +
        # cross-provider egress and lost-work accumulated per job in the
        # stage loop above); the scalar totals — makespan, cost_usd, the
        # offload counters — reduce on the *host* over canonical job
        # order, so a paged run sums the exact same array as a monolithic
        # one. trips [M] (each stage's while-loop trips in this lane)
        # feeds the host's lockstep counters; a page adds qexit (raw
        # sign-encoded queue-exit times), from which the pager decides
        # which jobs its cut commits, and the replica clocks at the cut
        # [M, I_max], from which the next page resumes. The output set
        # leaves the engine through `_emitted`: outputs the static flags
        # fix (failed, abandoned and attempts unless faulty, queue_wait
        # unless capped, cold unless cold) stay on the device and the host
        # rebuilds them (`_restored`), and the index outputs leave as
        # int32.
        out = dict(
            init_off=off, trips=jnp.stack(trips_l), public_mask=locpub,
            start=start, provider=jnp.where(locpub, prov_m, -1),
            replica=rep_m, segment=jnp.where(locpub, seg_m, -1),
            queue_wait=jnp.stack(qwait_l, axis=1),
            cold=jnp.stack(coldm_l, axis=1))
        if carry:
            out.update(qexit=jnp.stack(qexit_l, axis=1),
                       clocks=jnp.stack(clocks_l, axis=0))
        cost_j = jnp.sum(jnp.where(locpub, cost_m, 0.0), axis=1) + xeg_j
        if not faulty:
            out.update(cost_j=cost_j, end=end, completion=completion,
                       attempts=locpub.astype(jnp.int64),
                       failed=jnp.zeros((J, M), dtype=jnp.int64),
                       abandoned=jnp.zeros(J, dtype=bool))
        else:
            # abandoned jobs never complete: NaN completion, NaN stage ends
            out.update(cost_j=cost_j + lost_j,
                       end=jnp.where(jnp.isinf(end), jnp.nan, end),
                       completion=jnp.where(~ab_j, completion, jnp.nan),
                       attempts=jnp.stack(att_l, axis=1),
                       failed=jnp.stack(failc_l, axis=1), abandoned=ab_j)
        return _emitted(out, faulty=faulty, capped=capped, cold=cold)

    return run_one


#: the index outputs, which cross as int32 (they lie in [-1, bound) for
#: bounds of a few providers, replica slots or price segments; int8
#: copied back no faster on a v5e), and their dtypes on the host
_INDEX_OUTPUTS = dict(provider=np.int64, replica=np.int32, segment=np.int64)


def _emitted(out: Dict[str, jax.Array], *, faulty: bool, capped: bool,
             cold: bool) -> Dict[str, jax.Array]:
    """The outputs one engine lane hands the host: ``out`` less those its
    static flags make constant (the fault counters unless ``faulty``,
    ``queue_wait`` unless ``capped``, ``cold`` unless ``cold``; the
    capped and cold branches run only without faults), with the index
    outputs narrowed to int32. :func:`_restored` inverts it."""
    out = dict(out)
    if not faulty:
        del out["attempts"], out["failed"], out["abandoned"]
    if faulty or not capped:
        del out["queue_wait"]
    if faulty or not cold:
        del out["cold"]
    for name in _INDEX_OUTPUTS:
        out[name] = out[name].astype(jnp.int32)
    return out


def _restored(out: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The host's side of :func:`_emitted`: the index outputs widened to
    their result dtypes and the constant outputs rebuilt, bit for bit."""
    mask = out["public_mask"]
    for name, dtype in _INDEX_OUTPUTS.items():
        out[name] = out[name].astype(dtype, copy=False)
    if "attempts" not in out:
        out["attempts"] = mask.astype(np.int64)
        out["failed"] = np.zeros(mask.shape, dtype=np.int64)
        out["abandoned"] = np.zeros(mask.shape[:2], dtype=bool)
    if "queue_wait" not in out:
        out["queue_wait"] = np.zeros(mask.shape)
    if "cold" not in out:
        out["cold"] = np.zeros(mask.shape, dtype=bool)
    return out


@functools.lru_cache(maxsize=None)
def _engine_fn(M: int, I_max: int, J: int, P: int, S: int,
               include_transfers: bool, init_mode: int, adaptive: bool,
               A_att: int, W: int, faulty: bool, lookahead: bool,
               capped: bool, cold: bool, pooled: bool, C: int,
               n_dev: int, impl: str = "scan", *, carry: bool = False):
    """The engine's program over a call's packed arguments
    (:func:`_pack`): ``fn(layout, *buffers)``. It cuts the buffers back
    into the arguments (:func:`_unpacked`) and runs vmap(run_one) over
    the scenario axis: jit on one device; pmap sharding the scenario axis
    across host devices when more are available. ``layout`` is static,
    one per shape family."""
    engine = _build_engine(M, I_max, J, P, S, include_transfers, init_mode,
                           adaptive, A_att, W, faulty, lookahead,
                           capped, cold, pooled, C, impl, carry)

    def run_one(layout, *bufs):
        return jax.vmap(engine)(*_unpacked(layout, bufs))

    if n_dev > 1:
        return jax.pmap(run_one, static_broadcasted_argnums=0)
    return jax.jit(run_one, static_argnums=0)


def _pack(args: Sequence[np.ndarray]) -> Tuple[tuple, List[np.ndarray]]:
    """One engine call's arguments, each ``[S, ...]``, as one contiguous
    ``[S, L]`` host buffer per dtype (in order of first use), scenario
    axis leading, and the static layout that cuts them back: per
    argument, (buffer, offset, shape less the scenario axis)."""
    S = args[0].shape[0]
    cols: Dict[np.dtype, List[np.ndarray]] = {}
    layout = []
    for a in args:
        rows = cols.setdefault(a.dtype, [])
        layout.append((list(cols).index(a.dtype),
                       sum(r.shape[1] for r in rows), a.shape[1:]))
        rows.append(a.reshape(S, -1))
    return tuple(layout), [np.concatenate(c, axis=1) for c in cols.values()]


def _unpacked(layout: tuple, bufs: Sequence[jax.Array]) -> List[jax.Array]:
    """The arguments :func:`_pack` packed, cut out of the ``[S, L]``
    buffers with static slices and reshapes: bit for bit."""
    return [bufs[b][:, off:off + int(np.prod(shape))].reshape(
                bufs[b].shape[:1] + shape)
            for b, off, shape in layout]


def _norm_batch(d: Dict[str, np.ndarray], B: int) -> Dict[str, np.ndarray]:
    """Broadcast [J,M] matrices to [B,J,M] (no copy via broadcast_to)."""
    out = {}
    for key, v in d.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 2:
            v = np.broadcast_to(v, (B,) + v.shape)
        elif v.ndim != 3 or v.shape[0] != B:
            raise ValueError(f"{key}: expected [J,M] or [{B},J,M], got {v.shape}")
        out[key] = v
    return out


def _validate_workload_axes(pred: Dict[str, np.ndarray],
                            act: Dict[str, np.ndarray],
                            where: str = "") -> None:
    """Check every pred/act matrix against pred['P_private'] up front.

    Mismatched job/stage/batch axes raise a :class:`ValueError` that names
    the offending entry (e.g. ``act['P_public']``) and the axis that
    disagrees, instead of a shape error surfacing from deep inside the
    batched engine.
    """
    pre = f"{where}: " if where else ""
    if "P_private" not in pred:
        raise ValueError(f"{pre}pred is missing 'P_private'")
    ref = np.asarray(pred["P_private"])
    if ref.ndim not in (2, 3):
        raise ValueError(
            f"{pre}pred['P_private']: expected [J, M] or [B, J, M], "
            f"got shape {ref.shape}")
    jm = ref.shape[-2:]
    batch_owner, batch = ("pred['P_private']", ref.shape[0]) \
        if ref.ndim == 3 else (None, None)
    for dname, d in (("pred", pred), ("act", act)):
        for key, v in d.items():
            v = np.asarray(v)
            name = f"{dname}['{key}']"
            if v.ndim not in (2, 3):
                raise ValueError(f"{pre}{name}: expected [J, M] or "
                                 f"[B, J, M], got shape {v.shape}")
            if v.shape[-2:] != jm:
                raise ValueError(
                    f"{pre}{name}: job/stage axes {v.shape[-2:]} do not "
                    f"match pred['P_private'] {jm}")
            if v.ndim == 3:
                if batch is None:
                    batch_owner, batch = name, v.shape[0]
                elif v.shape[0] != batch:
                    raise ValueError(
                        f"{pre}{name}: latency-draw batch axis "
                        f"{v.shape[0]} does not match {batch_owner} "
                        f"batch axis {batch}")


def _norm_replica_axis(replicas, dag: AppDAG,
                       where: str = "") -> List[np.ndarray]:
    """``replicas=`` axis -> list of per-stage count vectors [M] (ints).

    ``None`` is the one-point axis at the DAG's own replica counts — the
    degenerate sweep, bit-exact vs the pre-axis path.
    """
    pre = f"{where}: " if where else ""
    if replicas is None:
        return [np.asarray(dag.replicas, dtype=np.int64)]
    replicas = list(replicas)  # materialize one-shot iterators
    if not replicas:
        raise ValueError(f"{pre}replicas axis is empty")
    out = []
    for i, cfg in enumerate(replicas):
        v = np.asarray(cfg)
        if v.ndim != 1 or v.shape[0] != dag.num_stages:
            raise ValueError(
                f"{pre}replicas[{i}]: expected {dag.num_stages} per-stage "
                f"counts (M={dag.num_stages}), got shape {v.shape}")
        vf = v.astype(np.float64)
        if (vf % 1 != 0).any() or (vf < 1).any():
            raise ValueError(
                f"{pre}replicas[{i}]: counts must be integers >= 1, "
                f"got {v.tolist()}")
        out.append(vf.astype(np.int64))
    return out


def _norm_speed_axis(replica_speeds, M: int, I_max: int,
                     where: str = "") -> List[np.ndarray]:
    """``replica_speeds=`` axis -> list of [M, I_max] slowdown matrices.

    Each config is either a ``{(stage, replica): factor}`` dict (the DES's
    ``replica_slowdown`` format) or an array ``[M, I]``; entries are
    multiplicative slowdowns (1.0 = healthy), missing entries default to
    healthy, and entries for absent replica slots are ignored exactly as
    the DES ignores them. ``None`` is the one-point healthy axis.
    """
    pre = f"{where}: " if where else ""
    if replica_speeds is None:
        return [np.ones((M, I_max))]
    cfgs = list(replica_speeds)
    if not cfgs:
        raise ValueError(f"{pre}replica_speeds axis is empty")
    out = []
    for g, cfg in enumerate(cfgs):
        sp = np.ones((M, I_max))
        if cfg is None:
            pass
        elif isinstance(cfg, dict):
            # every entry is validated — including ones for slots absent
            # at this I_max, so acceptance never depends on the sweep's
            # replica bound (the engines must reject inputs identically)
            for key, f in cfg.items():
                try:
                    k, r = (int(key[0]), int(key[1]))
                except (TypeError, ValueError, IndexError):
                    raise ValueError(
                        f"{pre}replica_speeds[{g}]: keys must be "
                        f"(stage, replica) pairs, got {key!r}") from None
                if not 0 <= k < M:
                    raise ValueError(
                        f"{pre}replica_speeds[{g}]: stage {k} out of "
                        f"range for M={M}")
                try:
                    fv = float(f)
                except (TypeError, ValueError):
                    raise ValueError(
                        f"{pre}replica_speeds[{g}]: factor for "
                        f"({k}, {r}) must be a number, got {f!r}") from None
                if not (np.isfinite(fv) and fv > 0):
                    raise ValueError(
                        f"{pre}replica_speeds[{g}]: factors must be "
                        f"finite and > 0")
                if r < 0:
                    raise ValueError(
                        f"{pre}replica_speeds[{g}]: replica index {r} "
                        f"is negative")
                if r >= I_max:
                    continue  # slot absent in every config: a no-op
                sp[k, r] = fv
        else:
            arr = np.asarray(cfg, dtype=np.float64)
            if arr.ndim != 2 or arr.shape[0] != M:
                raise ValueError(
                    f"{pre}replica_speeds[{g}]: expected [M={M}, I] "
                    f"factors, got shape {arr.shape}")
            if not (np.isfinite(arr) & (arr > 0)).all():
                raise ValueError(
                    f"{pre}replica_speeds[{g}]: factors must be "
                    f"finite and > 0")
            w = min(arr.shape[1], I_max)
            sp[:, :w] = arr[:, :w]
        out.append(sp)
    return out


def _norm_trace_axis(price_traces, base: ProviderPortfolio,
                     where: str = "") -> List[ProviderPortfolio]:
    """``price_traces=`` axis -> list of portfolio variants.

    Each entry is a pricing of the *same* providers: a full
    :class:`ProviderPortfolio` (same provider count as ``base``), a
    sequence of per-provider :class:`PriceTrace` (applied to ``base``'s
    providers in order), a single :class:`PriceTrace` (applied to every
    provider), or ``None`` (``base`` unchanged — the degenerate entry).
    ``None`` as the whole axis is the one-point axis at ``base``.
    """
    pre = f"{where}: " if where else ""
    if price_traces is None:
        return [base]
    cfgs = list(price_traces)
    if not cfgs:
        raise ValueError(f"{pre}price_traces axis is empty")
    out = []
    for i, cfg in enumerate(cfgs):
        if cfg is None:
            out.append(base)
            continue
        if isinstance(cfg, ProviderPortfolio):
            if cfg.num_providers != base.num_providers:
                raise ValueError(
                    f"{pre}price_traces[{i}]: portfolio has "
                    f"{cfg.num_providers} providers, the sweep's base "
                    f"portfolio has {base.num_providers} (one shape "
                    f"family needs a fixed provider count)")
            out.append(cfg)
            continue
        if isinstance(cfg, PriceTrace):
            cfg = [cfg] * base.num_providers
        try:
            traces = list(cfg)
        except TypeError:
            raise ValueError(
                f"{pre}price_traces[{i}]: expected a ProviderPortfolio, "
                f"a PriceTrace, a sequence of PriceTrace, or None — got "
                f"{type(cfg).__name__}") from None
        if len(traces) != base.num_providers or not all(
                isinstance(t, PriceTrace) for t in traces):
            raise ValueError(
                f"{pre}price_traces[{i}]: expected {base.num_providers} "
                f"PriceTrace entries (one per provider), got "
                f"{[type(t).__name__ for t in traces]}")
        out.append(ProviderPortfolio(tuple(
            p.with_trace(t) for p, t in zip(base.providers, traces))))
    return out


def _max_segment_bound(trace_cfgs: List[ProviderPortfolio]) -> int:
    """S: the segment bound of one task's normalized price-trace axis."""
    return max(pf.num_segments for pf in trace_cfgs)


def _max_replica_bound(dag: AppDAG, repl_cfgs) -> int:
    """I_max contribution of one task: its largest replica count.

    ``repl_cfgs`` is a *normalized* axis (:func:`_norm_replica_axis`
    output) or ``None`` for the one-point axis at the DAG's own counts —
    callers normalize first, so one-shot iterators are consumed once.
    """
    if repl_cfgs is None:
        return max([1] + [int(r) for r in dag.replicas])
    return max([1] + [int(v.max()) for v in repl_cfgs if v.size])


class _Task:
    """One application's scenario grid, topologically relabelled and padded
    to the sweep's common (M_pad, I_max) shape family."""

    def __init__(self, dag: AppDAG, pred, act, c_max_grid, orders,
                 cost_model, t0, M_pad: int, I_max: int,
                 portfolio: Optional[ProviderPortfolio] = None,
                 include_transfers: bool = True,
                 arrivals: ArrivalsLike = None,
                 replicas=None, replica_speeds=None,
                 price_traces=None, S_seg: Optional[int] = None,
                 faults=None, retry=None, init_window=None,
                 A_att: int = 0, W: int = 0,
                 caps=None, coldstart=None, pool=None,
                 offload_mask=None, init_override=None,
                 adaptive_override=None, where: str = ""):
        from .simulator import _with_transfer_defaults

        act = act if act is not None else pred
        _validate_workload_axes(pred, act, where)
        pred = _with_transfer_defaults(pred)
        act = _with_transfer_defaults(act)
        B = max([v.shape[0] if np.asarray(v).ndim == 3 else 1
                 for v in list(pred.values()) + list(act.values())] or [1])
        pred = _norm_batch(pred, B)
        act = _norm_batch(act, B)
        self.dag = dag
        J, M = pred["P_private"].shape[1:]
        if M != dag.num_stages:
            raise ValueError(f"pred has {M} stages, dag has {dag.num_stages}")
        self.J, self.M = int(J), int(M)
        self.M_pad = M_pad
        self.I_max = int(I_max)
        orders = tuple(orders)
        # replica pools as scenario data: an axis of per-stage count
        # vectors x an axis of straggler-speed grids; both default to
        # one-point axes (the DAG's own counts, all replicas healthy),
        # keeping the degenerate sweep bit-exact vs the pre-axis path
        repl_cfgs = _norm_replica_axis(replicas, dag, where)
        speed_cfgs = _norm_speed_axis(replica_speeds, self.M, self.I_max,
                                      where)
        # price-trace axis: portfolio variants of the same provider count,
        # padded to the sweep's common segment bound (one-point axis at the
        # base portfolio when omitted — the degenerate, bit-exact sweep).
        # sweep_scenarios pre-normalizes every task's axis (with the
        # task's name in errors), so a list here is already portfolios.
        pf = as_portfolio(portfolio, cost_model)
        trace_cfgs = [pf] if price_traces is None else list(price_traces)
        self.n_segments = (_max_segment_bound(trace_cfgs) if S_seg is None
                           else int(S_seg))
        # fault axis: pre-normalized list of FaultModel (sweep_scenarios
        # handles the raw forms) or None — the one-point fault-free axis
        fault_cfgs = [None] if faults is None else list(faults)
        self.faulty = faults is not None
        self.n_attempts = int(A_att)
        self.n_windows = int(W)
        self.grid = [(b, o, float(c), r, g, tr, f)
                     for b in range(B) for o in orders for c in c_max_grid
                     for r in range(len(repl_cfgs))
                     for g in range(len(speed_cfgs))
                     for tr in range(len(trace_cfgs))
                     for f in range(len(fault_cfgs))]
        self.S = len(self.grid)
        self.orders_out = tuple(o for (_, o, _, _, _, _, _) in self.grid)
        self.c_max_out = np.array([c for (_, _, c, _, _, _, _) in self.grid])
        self.batch_out = np.array([b for (b, _, _, _, _, _, _) in self.grid])
        self.repl_out = np.stack([repl_cfgs[r]
                                  for (_, _, _, r, _, _, _) in self.grid])
        self.trace_out = np.array(
            [tr for (_, _, _, _, _, tr, _) in self.grid])
        self.fault_out = np.array(
            [f for (_, _, _, _, _, _, f) in self.grid])
        self.t0 = float(t0)
        # exogenous release stream (None = batch at t0); per-job absolute
        # deadlines are release + C_max, the batch deadline when no stream
        self.release = resolve_release(arrivals, self.J, self.t0)
        rel = (np.full(self.J, self.t0) if self.release is None
               else self.release)

        # topological stage relabelling: edges go low -> high afterwards
        topo = list(dag.topo_order())
        self.topo = topo
        self.inv_topo = np.argsort(np.array(topo))
        mem = dag.mem_mb

        def pad_cols(v):  # [., M] -> [., M_pad], stages in topo order
            out = np.zeros(v.shape[:-1] + (M_pad,), dtype=np.float64)
            out[..., :M] = v[..., topo]
            return out

        # priority keys + provider selection/billing: identical numpy math
        # to the DES preamble. Keys depend on (draw, order, trace) — they
        # see the trace prices at plan time t0 — while the segment-indexed
        # selection/billing matrices [P, S_seg, J, M] depend on
        # (draw, trace); per-segment latency/egress/edge vectors [P, S_seg]
        # only on the trace. The engine gathers the (provider, segment)
        # active at each offload epoch from these at run time.
        self.n_providers = pf.num_providers
        S_seg = self.n_segments
        sinkm = dag.is_sink if include_transfers else None
        uniq: Dict[Tuple[int, str, int],
                   Tuple[np.ndarray, np.ndarray]] = {}
        sel_bt: Dict[Tuple[int, int], np.ndarray] = {}
        cost_bt: Dict[Tuple[int, int], np.ndarray] = {}
        iota_P = np.arange(self.n_providers)
        for b in sorted({b for (b, _, _, _, _, _, _) in self.grid}):
            down_pred = pred["download"][b] if include_transfers else None
            down_act = act["download"][b] if include_transfers else None
            for tr, tpf in enumerate(trace_cfgs):
                sel_bt[(b, tr)] = tpf.np_selection_costs_seg(
                    pred["P_public"][b], mem, down_pred, sinkm,
                    require=~dag.must_private_mask,
                    num_segments=S_seg)                 # [P, S_seg, J, M]
                cost_bt[(b, tr)] = tpf.np_stage_costs_seg(
                    act["P_public"][b], mem, down_act, sinkm,
                    num_segments=S_seg)                 # [P, S_seg, J, M]
                seg0 = tpf.segments_at(self.t0)
                H = np.min(sel_bt[(b, tr)][iota_P, seg0], axis=0)
                for o in dict.fromkeys(orders):
                    key_fn = ORDERS[o]
                    uniq[(b, o, tr)] = (
                        np.stack([key_fn(pred["P_private"][b], H, k)
                                  for k in range(M)], axis=1),
                        key_fn(pred["P_private"][b], H, None))
        stage_keys = np.stack([uniq[(b, o, tr)][0]
                               for (b, o, _, _, _, tr, _) in self.grid])
        # the engine only sorts by the stage keys, so it gets their exact
        # stable ranks (ties by job id, as the DES queues order them):
        # float64 keys a few ulps apart, which HCF's summed costs produce,
        # cannot stay apart in a TPU's float32-pair float64
        stage_keys = np.argsort(np.argsort(stage_keys, axis=1, kind="stable"),
                                axis=1, kind="stable")
        # job keys and capacity feed the host-side init plan only
        self.job_keys = np.stack([uniq[(b, o, tr)][1]
                                  for (b, o, _, _, _, tr, _) in self.grid])
        bsel = self.batch_out
        sel_p = np.stack([sel_bt[(b, tr)]
                          for (b, _, _, _, _, tr, _) in self.grid])
        cost_p = np.stack([cost_bt[(b, tr)]
                           for (b, _, _, _, _, tr, _) in self.grid])
        lat_by_tr = [tpf.latency_mults_seg(S_seg) for tpf in trace_cfgs]
        eg_by_tr = [tpf.egress_seg(S_seg) for tpf in trace_cfgs]
        edges_by_tr = [tpf.segment_edges(S_seg) for tpf in trace_cfgs]
        lat_ps = np.stack([lat_by_tr[tr]
                           for (_, _, _, _, _, tr, _) in self.grid])
        eg_ps = np.stack([eg_by_tr[tr]
                          for (_, _, _, _, _, tr, _) in self.grid])
        edges_ps = np.stack([edges_by_tr[tr]
                             for (_, _, _, _, _, tr, _) in self.grid])
        # raw actual draws: the engine applies the locked (provider,
        # segment)'s latency multiplier after the placement resolves;
        # predicted download volumes (GB) feed the affinity penalty
        pub_a = act["P_public"][bsel]
        up_a = act["upload"][bsel]
        down_a = act["download"][bsel]
        dgb_pred = pred["download"][bsel] * EGRESS_GB_PER_S

        # structure as data, in relabelled indices, padded with inert stages
        A = np.zeros((M_pad, M_pad), dtype=bool)
        desc = np.zeros((M_pad, M_pad), dtype=bool)
        pos = {s: i for i, s in enumerate(topo)}
        for (u, v) in dag.edges:
            A[pos[u], pos[v]] = True
        dm = dag.descendant_masks
        for u in range(M):
            for v in range(M):
                if dm[u, v]:
                    desc[pos[u], pos[v]] = True
        sink = np.zeros(M_pad, dtype=bool)
        sink[[pos[s] for s in dag.sink_ids]] = True
        pinned = np.ones(M_pad, dtype=bool)  # inert pad stages: pinned
        pinned[:M] = dag.must_private_mask[topo]
        inert = np.ones(M_pad, dtype=bool)
        inert[:M] = False
        # each stage's predecessors, in relabelled indices (the pager's cut)
        self.preds = [np.flatnonzero(A[:, k]) for k in range(M_pad)]

        # per-(config, grid) replica pools as [M_pad, I_max] speed
        # matrices: finite entry = present replica with that slowdown,
        # inf = absent slot; inert pad stages keep one healthy slot
        def speed_matrix(rv: np.ndarray, sg: np.ndarray) -> np.ndarray:
            sp = np.full((M_pad, self.I_max), np.inf)
            sp[M:, 0] = 1.0
            cnt = np.maximum(rv, 1)
            for i, s in enumerate(topo):
                sp[i, :cnt[s]] = sg[s, :cnt[s]]
            return sp

        sp_by_rg = {(r, g): speed_matrix(repl_cfgs[r], speed_cfgs[g])
                    for r in range(len(repl_cfgs))
                    for g in range(len(speed_cfgs))}
        speed = np.stack([sp_by_rg[(r, g)]
                          for (_, _, _, r, g, _, _) in self.grid])
        # capacity T_max = sum_k I_k * C_max follows the scenario's own
        # replica config (raw counts, as in the DES's t_max)
        self.capacity = np.array([float(repl_cfgs[r].sum()) * c
                                  for (_, _, c, r, _, _, _) in self.grid])

        # per-task scheduling-flag overrides (None = inherit the sweep's
        # init_phase/adaptive) — the policy harness mixes e.g. an
        # ACD-adaptive task and a fixed-placement baseline in one sweep
        self.init_override = (None if init_override is None
                              else bool(init_override))
        self.adaptive_override = (None if adaptive_override is None
                                  else bool(adaptive_override))
        # externally-decided offload plan ([J] bool): replaces the
        # capacity-prefix rule; rides the init_mode=2 engine path (the
        # precomputed-plan branch the host-resolved rule also uses)
        if offload_mask is not None:
            if init_window is not None:
                raise ValueError(
                    f"{where + ': ' if where else ''}offload_mask and "
                    "init_window are mutually exclusive")
            offload_mask = np.asarray(offload_mask, dtype=bool)
            if offload_mask.shape != (self.J,):
                raise ValueError(
                    f"{where + ': ' if where else ''}offload_mask must "
                    f"have shape ({self.J},), got {offload_mask.shape}")
        self.mask = offload_mask

        # windowed init offload: only jobs released within the window
        # compete for the budget (all-True when no window — bit-exact).
        # A policy mask takes the same arg slot: init_mode=2 consumes it
        # as the resolved plan.
        if offload_mask is not None:
            init_elig = offload_mask
        else:
            init_elig = (np.ones(self.J, dtype=bool) if init_window is None
                         else rel <= self.t0 + float(init_window))

        S = self.S

        def pad_stage_mid(v: np.ndarray, fill) -> np.ndarray:
            # [S, J, M, A] -> [S, J, M_pad, A], stages in topo order
            out = np.full(v.shape[:2] + (M_pad,) + v.shape[3:], fill,
                          dtype=v.dtype)
            out[:, :, :M] = v[:, :, topo]
            return out

        # load-dependent latency (concurrency caps / cold starts / pool
        # traces) as engine data: per-call configs, not grid axes —
        # shared by every scenario, with occupancy rates per price trace.
        # Mutually exclusive with the fault axis (validated upstream), so
        # the engine's trailing *args carry exactly one family.
        self.capped = caps is not None
        self.cold = coldstart is not None
        self.pooled = pool is not None
        self.loaded = self.capped or self.cold or self.pooled
        caps_eff = (np.asarray(caps, dtype=np.float64) if self.capped
                    else np.full(self.n_providers, np.inf))
        self.C = (int(caps_eff[np.isfinite(caps_eff)].max())
                  if self.capped else 0)
        clock0 = np.full((S, M_pad, self.I_max), self.t0)
        load_args: Tuple[np.ndarray, ...] = ()
        if self.loaded:
            occ_by_tr = [tpf.np_occupancy_rates_seg(mem, num_segments=S_seg)
                         for tpf in trace_cfgs]       # [P, S_seg, M] each

            def pad_occ(o):
                out = np.zeros(o.shape[:2] + (M_pad,))
                out[:, :, :M] = o[:, :, topo]
                return out

            occ_s = np.stack([pad_occ(occ_by_tr[tr])
                              for (_, _, _, _, _, tr, _) in self.grid])
            cs = coldstart
            wu_p = (cs.provider_warm_ups(self.n_providers)
                    if self.cold else np.zeros(self.n_providers))
            cs3 = np.array([cs.warm_up_s if self.cold else 0.0,
                            cs.keep_alive_s if self.cold else np.inf,
                            1.0 if (self.cold and cs.scale_to_zero)
                            else 0.0])
            off_pad = np.full((M_pad, self.I_max), np.inf)
            if self.pooled:
                on_w, off_w = pool
                w = off_w.shape[1]
                off_pad[:M, :w] = off_w[topo, :]
                # late pool slots enter busy until their turn-on instant
                # (the DES's _pool_on_event twin); never-on slots are
                # absent from the speed matrix anyway
                clk = np.full((M_pad, self.I_max), self.t0)
                with np.errstate(invalid="ignore"):
                    clk[:M, :w] = np.where(
                        np.isfinite(on_w[topo, :]),
                        np.maximum(self.t0, on_w[topo, :]), self.t0)
                clock0 = np.broadcast_to(
                    clk, (S, M_pad, self.I_max)).copy()
            load_args = (
                np.broadcast_to(caps_eff, (S, self.n_providers)),
                occ_s,
                np.broadcast_to(wu_p, (S, self.n_providers)),
                np.broadcast_to(cs3, (S, 3)),
                np.broadcast_to(off_pad, (S, M_pad, self.I_max)))

        fault_args: Tuple[np.ndarray, ...] = ()
        if self.faulty:
            rt = retry if retry is not None else RetryPolicy()
            fail_s = pad_stage_mid(np.stack(
                [cfg.fail for cfg in fault_cfgs])[self.fault_out], False)
            delay_s = pad_stage_mid(np.stack(
                [rt.delays(cfg.jitter)
                 for cfg in fault_cfgs])[self.fault_out], 0.0)
            outw_s = np.stack(
                [cfg.outage_windows(self.n_providers,
                                    num_slots=self.n_windows)
                 for cfg in fault_cfgs])[self.fault_out]
            kill_s = np.array([cfg.kill_frac
                               for cfg in fault_cfgs])[self.fault_out]
            okill_s = np.array([cfg.outage_kills for cfg in fault_cfgs],
                               dtype=bool)[self.fault_out]
            fb_s = np.full(S, bool(rt.private_fallback))
            fault_args = (fail_s, delay_s, outw_s, kill_s, okill_s, fb_s)

        self.args = tuple(
            np.ascontiguousarray(x, dtype=x.dtype if x.dtype == bool
                                 else np.float64)
            for x in (
                pad_cols(pred["P_private"][bsel]),
                pad_cols(act["P_private"][bsel]),
                pad_cols(pub_a),
                pad_cols(up_a),
                pad_cols(down_a),
                pad_cols(dgb_pred),
                pad_cols(cost_p),
                pad_cols(sel_p),
                lat_ps,
                eg_ps,
                edges_ps,
                pad_cols(stage_keys).astype(np.int32),
                rel[None, :] + self.c_max_out[:, None],
                np.full(S, self.t0),
                np.broadcast_to(rel, (S, self.J)),
                np.broadcast_to(init_elig, (S, self.J)),
                np.ones((S, self.J), dtype=bool),           # live
                np.broadcast_to(A, (S,) + A.shape),
                np.broadcast_to(desc, (S,) + desc.shape),
                np.broadcast_to(sink, (S,) + sink.shape),
                np.broadcast_to(pinned, (S,) + pinned.shape),
                np.broadcast_to(inert, (S,) + inert.shape),
                speed,
                clock0,
            ) + load_args + fault_args)

    # engine-arg positions carrying a job axis (position -> axis), for the
    # job pager; fault args (fail/delay grids) follow at _N_BASE_ARGS
    _PAGE_J_AXES = {0: 1, 1: 1, 2: 1, 3: 1, 4: 1, 5: 1, 6: 3, 7: 3,
                    11: 1, 12: 1, 14: 1, 15: 1, 16: 1}
    _N_BASE_ARGS = 24
    _IDX_DEADLINE, _IDX_T0, _IDX_RELEASE = 12, 13, 14
    _IDX_INIT_ELIG, _IDX_LIVE, _IDX_CLOCK0 = 15, 16, 23

    def eff_modes(self, init_phase: bool, adaptive: bool) -> Tuple[int, bool]:
        """(engine init_mode, adaptive) for this task under the sweep's
        defaults: per-task overrides win. Any offload plan — a
        policy-supplied mask or the capacity-prefix rule, resolved on the
        host by :meth:`init_plan` — compiles the precomputed-plan engine
        (``init_mode=2``)."""
        ip = init_phase if self.init_override is None else self.init_override
        ad = adaptive if self.adaptive_override is None \
            else self.adaptive_override
        mode = 2 if self.mask is not None or ip else 0
        return mode, bool(ad)

    def init_plan(self, init_phase: bool) -> np.ndarray:
        """The [S, J] init-offload plan the ``init_mode=2`` engine reads
        from the ``init_elig`` slot: the policy mask, else the global
        capacity-prefix rule in the DES's own numpy arithmetic (so its
        decisions match the DES on every backend, and a paged run
        reproduces a monolithic one), else all-False."""
        if self.mask is not None:
            return np.broadcast_to(self.mask, (self.S, self.J)).copy()
        if self.eff_modes(init_phase, True)[0] == 2:
            return _host_init_offload(self)
        return np.zeros((self.S, self.J), dtype=bool)

    def engine_args(self, init_phase: bool) -> tuple:
        """The monolithic engine's arg tuple, init plan resolved."""
        args = list(self.args)
        args[self._IDX_INIT_ELIG] = self.init_plan(init_phase)
        return tuple(args)

    def page_args(self, idx: np.ndarray, J_fam: int, init_mask: np.ndarray,
                  clocks: np.ndarray) -> tuple:
        """Slice one page of jobs out of the full arg tuple.

        ``idx`` are ascending job ids; the page pads to the family size
        ``J_fam`` with inert pad jobs (``live=False``, infinite deadline —
        never eligible anywhere, so the executable's arithmetic on them is
        dead). ``init_mask`` [S, n] is the page's slice of the globally
        resolved init-offload mask (consumed as ``init_elig`` by the
        ``init_mode=2`` engine); ``clocks`` [S, M_pad, I_max] the carried
        per-replica busy-until vectors from the previous pages.
        """
        n = len(idx)
        pad = J_fam - n
        j_axes = dict(self._PAGE_J_AXES)
        for i in range(self._N_BASE_ARGS, len(self.args)):
            if i - self._N_BASE_ARGS in (0, 1):  # fail / delay grids
                j_axes[i] = 1
        out = []
        for i, a in enumerate(self.args):
            ax = j_axes.get(i)
            if ax is None:
                out.append(a)
                continue
            v = np.take(a, idx, axis=ax)
            if pad:
                fill = (np.inf if i == self._IDX_DEADLINE
                        else self.t0 if i == self._IDX_RELEASE else 0)
                shape = v.shape[:ax] + (pad,) + v.shape[ax + 1:]
                v = np.concatenate(
                    [v, np.full(shape, fill, dtype=v.dtype)], axis=ax)
            out.append(v)
        ini = np.zeros((self.S, J_fam), dtype=bool)
        ini[:, :n] = init_mask
        live = np.zeros((self.S, J_fam), dtype=bool)
        live[:, :n] = True
        out[self._IDX_INIT_ELIG] = ini
        out[self._IDX_LIVE] = live
        out[self._IDX_CLOCK0] = clocks
        return tuple(out)

    def pack(self, out: Dict[str, np.ndarray]) -> VectorSimResult:
        """Slice this task's scenarios out of a (possibly concatenated)
        engine output and undo the topological stage relabelling."""
        inv = self.inv_topo
        return VectorSimResult(
            makespan=out["makespan"], cost_usd=out["cost_usd"],
            public_mask=out["public_mask"][:, :, inv],
            start=out["start"][:, :, inv], end=out["end"][:, :, inv],
            completion=out["completion"],
            n_offloaded_stages=out["n_offloaded_stages"],
            n_init_offloaded_jobs=out["n_init_offloaded_jobs"],
            per_stage_offloads=out["per_stage_offloads"][:, inv],
            provider=out["provider"][:, :, inv],
            deadline=self.c_max_out.copy(), orders=self.orders_out,
            c_max=self.c_max_out, batch_idx=self.batch_out,
            release=None if self.release is None
            else np.broadcast_to(self.release, (self.S, self.J)).copy(),
            replica=out["replica"][:, :, inv],
            replicas=self.repl_out.copy(),
            segment=out["segment"][:, :, inv],
            trace_idx=self.trace_out.copy(),
            attempts=out["attempts"][:, :, inv],
            failed=out["failed"][:, :, inv],
            abandoned=out["abandoned"],
            fault_idx=self.fault_out.copy(),
            queue_wait=out["queue_wait"][:, :, inv],
            cold=out["cold"][:, :, inv])


def _dispatch(fn, args, S: int, n_dev: int) -> Dict[str, np.ndarray]:
    """Run a compiled engine over scenario-axis args, sharding across
    host devices, and return its outputs as numpy arrays, as the engine
    emits them (:func:`_emitted`; :func:`_finalize` restores the rest).
    Every copy starts before the first is waited on.

    The arguments cross in one transfer per dtype (:func:`_pack`): a
    transfer costs about as much per array as per megabyte. The host
    side is timed in four spans (``h2d``, ``launch``, ``wait``,
    ``d2h``); the call's bytes each way, the arrays handed over and
    copied back and its lanes' while-loop trips are counted (see
    :data:`_LAST_RUN_STATS`)."""
    with jax.enable_x64(True):
        with _span("h2d"):
            layout, bufs = _pack(args)
            if n_dev > 1:
                # strided scenario->device interleave balances
                # heterogeneous grids across the lockstep shards
                pad = (-S) % n_dev
                sel = np.arange(S + pad) % S
                perm = sel.reshape(-1, n_dev).T.reshape(-1)
                bufs = [x[perm].reshape(n_dev, -1, x.shape[1]) for x in bufs]
            dev_args = [jnp.asarray(x) for x in bufs]
        with _span("launch"):
            out = fn(layout, *dev_args)
        with _span("wait"):
            jax.block_until_ready(out)
        with _span("d2h"):
            for v in out.values():
                v.copy_to_host_async()
            raw = {k: np.asarray(v) for k, v in out.items()}
            if n_dev > 1:
                # position of each original scenario in the device-major
                # output (padding duplicates a few scenarios; any
                # occurrence works)
                pos = np.empty(S, dtype=np.int64)
                pos[perm] = np.arange(perm.shape[0])
                out = {k: x.reshape((-1,) + x.shape[2:])[pos]
                       for k, x in raw.items()}
            else:
                out = raw
    _count("engine_calls", 1)
    _count("h2d_arrays", len(dev_args))
    _count("h2d_bytes", sum(int(x.nbytes) for x in dev_args))
    _count("d2h_arrays", len(raw))
    _count("d2h_bytes", sum(int(x.nbytes) for x in raw.values()))
    # lanes of one device run in lockstep: each stage's while loop runs
    # as many trips as its slowest lane (padding lanes included)
    trips = raw["trips"].reshape((-1,) + raw["trips"].shape[-2:])
    loop = int(trips.max(axis=1).sum())
    _count("loop_trips", loop)
    _count("lane_trips", int(trips.sum()))
    _count("lane_slots", trips.shape[1] * loop)
    return out


def _finalize(task: _Task, out: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Host-side canonical reductions of the engine's per-job outputs,
    once the outputs the engine left on the device are restored
    (:func:`_restored`).

    Scalar fields (makespan, cost_usd, the offload counters) reduce over
    the canonical job order here rather than on-device, so a paged run —
    which assembles the very same per-job arrays page by page — sums
    bit-identical floats in bit-identical order to a monolithic run.
    """
    t0 = task.t0
    out = _restored(out)
    comp = out["completion"]
    if task.faulty:
        ok = ~out["abandoned"]
        safe = np.where(ok, np.where(np.isnan(comp), -np.inf, comp),
                        -np.inf)
        out["makespan"] = np.where(ok.any(axis=1),
                                   safe.max(axis=1) - t0, 0.0)
    else:
        out["makespan"] = comp.max(axis=1) - t0
    locpub = out["public_mask"]
    out["cost_usd"] = out.pop("cost_j").sum(axis=1)
    out["n_offloaded_stages"] = locpub.sum(axis=(1, 2))
    out["n_init_offloaded_jobs"] = out.pop("init_off").sum(axis=1)
    out["per_stage_offloads"] = locpub.sum(axis=1)
    out.pop("trips", None)
    return out


def _host_init_offload(task: _Task) -> np.ndarray:
    """Resolve the global capacity-prefix init-offload mask [S, J] on the
    host with the DES's numpy rule. ``init_elig`` gates the
    non-clairvoyant variant (``init_window``): ineligible jobs contribute
    zero demand to the prefix scan and are never marked."""
    P_pred, init_elig = task.args[0], task.args[task._IDX_INIT_ELIG]
    return np.stack([
        init_offload(np.where(elig, Pp.sum(axis=1), 0.0), keys, cap) & elig
        for Pp, keys, cap, elig in zip(P_pred, task.job_keys, task.capacity,
                                       init_elig)])


# most recent sweep's host spans in seconds (``prep_s``, ``plan_s``,
# ``engine_s`` and, inside it, ``h2d_s``/``launch_s``/``wait_s``/``d2h_s``,
# ``carry_s``, ``finalize_s``), its counters (``engine_calls``,
# ``h2d_arrays``, ``h2d_bytes``, ``d2h_arrays``, ``d2h_bytes``,
# ``loop_trips``, ``lane_trips``, ``lane_slots``; ``pages``, ``carried_jobs``,
# ``page_retries`` when paged) and the engine impl that ran it; not part of
# the result API (docs/architecture.md, "Reading a sweep's spans and
# counters")
_LAST_RUN_STATS: Dict[str, object] = {}


class _PageStats(Mapping):
    """The most recent sweep's paging counters under the names the
    benchmark's record reads (``bench/system.py``), a view of
    :data:`_LAST_RUN_STATS`: ``pages``, and ``retries`` for
    ``page_retries``. Empty after a sweep that did not page."""

    _NAMES = {"pages": "pages", "retries": "page_retries"}

    def __getitem__(self, key):
        return _LAST_RUN_STATS[self._NAMES[key]]

    def __iter__(self):
        return (k for k, v in self._NAMES.items() if v in _LAST_RUN_STATS)

    def __len__(self):
        return sum(1 for _ in self)


_LAST_PAGE_STATS = _PageStats()

# id of each sweep, the one argument of its root ``vs:sweep`` annotation
_SWEEP_IDS = itertools.count(1)


class _span:
    """A host phase of the current sweep: a ``vs:<name>`` annotation on
    the profiler's host plane (on the device trace's clock) whose
    seconds add to ``_LAST_RUN_STATS["<name>_s"]``. Always on: with no
    trace running an annotation costs about a microsecond."""

    __slots__ = ("key", "ann", "t")

    def __init__(self, name: str):
        self.key = name + "_s"
        self.ann = jax.profiler.TraceAnnotation("vs:" + name)

    def __enter__(self):
        self.ann.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        _count(self.key, time.perf_counter() - self.t)
        self.ann.__exit__(*exc)


def _count(name: str, n) -> None:
    """Add ``n`` to the current sweep's counter ``name``."""
    _LAST_RUN_STATS[name] = _LAST_RUN_STATS.get(name, 0) + n


def _decided(task: _Task, qexit: np.ndarray, t_cut: float) -> np.ndarray:
    """[S, n, M] bool: the (job, stage) decisions a cut at ``t_cut`` leaves
    final, from a page's sign-encoded queue exits ``qexit`` [S, n, M]: a
    stage that left its queue before the cut (or never queued), once
    every predecessor is final (stages are in topological order)."""
    with np.errstate(invalid="ignore"):
        done = ~(np.where(qexit < -0.5, -qexit - 1.0, qexit) >= t_cut)
    for k in range(task.M_pad):
        for u in task.preds[k]:
            done[:, :, k] &= done[:, :, u]
    return done


def _run_paged(task: _Task, I_max: int, include_transfers: bool,
               init_phase: bool, init_mode: int, adaptive: bool,
               lookahead: bool, chunk: int, n_dev: int,
               impl: str = "scan") -> Dict[str, np.ndarray]:
    """Page the job axis through fixed-J compiled executables.

    Jobs enter pages in release order, whole tied-release groups at a
    time. A page's engine call resumes every stage's event loop at the
    page's cut (its first release) and runs its jobs to completion; the
    next page's first release, ``T_next``, is the next cut. Events before
    a cut are the monolithic run's: every later release, and so every
    later job's arrival at any stage, comes at or after it. So at
    ``T_next`` the page commits each job whose queue exits, at every
    stage, all lie before ``T_next``. Every other job is *carried* into
    the next page:

    * a stage that left its queue before the cut (or never queued) with
      every predecessor decided keeps its decision: the next call gets
      its queue exit and replica (``fixed``) and recomputes the rest;
    * its other stages rerun there, those it reached before the cut as
      members of their stage queues at the cut, with their ready times;
    * each replica's busy-until clock at the cut goes along, as the
      engine's loop left it after its last step before ``T_next``.

    The next call then sees exactly the queues and clocks the monolithic
    run has at ``T_next``, so its decisions are the monolithic run's, bit
    for bit, and no page is ever run twice. A job carried in any scenario
    is carried in all (the job axis is shared); where it was committed,
    every stage is fixed. Carried jobs count against the page: its
    family is the smallest ``chunk * 2**k`` that they fill at most half
    of, the rest new jobs. Init offload — a global capacity-prefix rule
    — resolves host-side over the full job set before any paging.
    """
    S, J, M = task.S, task.J, task.M_pad
    order = np.argsort(task.release, kind="stable")
    rel_sorted = task.release[order]
    with _span("plan"):
        off_full = task.init_plan(init_phase)
    bufs: Optional[Dict[str, np.ndarray]] = None
    clocks = task.args[task._IDX_CLOCK0]
    t_cut = task.t0
    carried = np.zeros(0, dtype=np.int64)
    fixed_c = np.zeros((S, 0, M, 2))  # the carried jobs' decided stages
    pos = 0
    while pos < J:
        J_fam = int(chunk)
        while 2 * len(carried) > J_fam:
            J_fam *= 2
        end = min(pos + J_fam - len(carried), J)
        # never split a tied-release group across pages: an epoch's jobs
        # admit together before the sweep in both engines
        while end < J and rel_sorted[end] == rel_sorted[end - 1]:
            end += 1
        idx = np.concatenate([carried, order[pos:end]])
        n = len(idx)
        while J_fam < n:
            J_fam *= 2
        T_next = rel_sorted[end] if end < J else np.inf
        args = list(task.page_args(idx, J_fam, off_full[:, idx], clocks))
        args[task._IDX_T0] = np.broadcast_to([t_cut, T_next], (S, 2))
        fixed = np.full((S, J_fam, M, 2), np.nan)
        fixed[:, :len(carried)] = fixed_c
        fn = _engine_fn(task.M_pad, I_max, J_fam, task.n_providers,
                        task.n_segments, include_transfers,
                        init_mode, adaptive,
                        task.n_attempts, task.n_windows, task.faulty,
                        lookahead, task.capped, task.cold, task.pooled,
                        task.C, n_dev, impl, carry=True)
        out = _dispatch(fn, tuple(args) + (fixed,), S, n_dev)
        with _span("carry"):
            qx = out.pop("qexit")[:, :n]                    # [S, n, M]
            done = _decided(task, qx, T_next)
            keep = ~done.all(axis=(0, 2))
            carried = idx[keep]
            fixed_c = np.where(
                (done & ~np.isnan(qx))[:, keep, :, None],
                np.stack([qx, out["replica"][:, :n]], axis=-1)[:, keep],
                np.nan)
        clocks = out.pop("clocks")
        out.pop("trips")  # [S, M]: per page, already counted
        if bufs is None:
            bufs = {name: np.empty((S, J) + v.shape[2:], dtype=v.dtype)
                    for name, v in out.items()}
        # a carried job's row is written again by the page that commits it
        for name, v in out.items():
            bufs[name][:, idx] = v[:, :n]
        pos, t_cut = end, T_next
        _count("pages", 1)
        if pos < J:
            _count("carried_jobs", len(carried))
    assert bufs is not None and not len(carried)
    # every flag family the pager accepts carries its state: none retries
    _count("page_retries", 0)
    return bufs


def _run_task(task: _Task, I_max: int, include_transfers: bool,
              init_phase: bool, adaptive: bool, lookahead: bool = False,
              chunk_jobs: Optional[int] = None,
              impl: str = "scan") -> VectorSimResult:
    """Run one task's scenario grid through the engine, sharding the
    scenario axis over host devices when available. ``chunk_jobs`` pages
    the job axis (``None`` / a batch workload / small J = monolithic)."""
    S = task.S
    n_dev = jax.local_device_count() if S > 1 else 1
    chunked = (chunk_jobs is not None and task.release is not None
               and int(chunk_jobs) < task.J)
    init_mode, eff_adaptive = task.eff_modes(init_phase, adaptive)
    with _span("engine"):
        if chunked:
            out = _run_paged(task, I_max, include_transfers, init_phase,
                             init_mode, eff_adaptive, lookahead,
                             int(chunk_jobs), n_dev, impl)
        else:
            fn = _engine_fn(task.M_pad, I_max, task.J, task.n_providers,
                            task.n_segments, include_transfers,
                            init_mode, eff_adaptive,
                            task.n_attempts, task.n_windows, task.faulty,
                            lookahead, task.capped, task.cold, task.pooled,
                            task.C, n_dev, impl)
            out = _dispatch(fn, task.engine_args(init_phase), S, n_dev)
    with _span("finalize"):
        res = task.pack(_finalize(task, out))
    _LAST_RUN_STATS["impl"] = impl
    return res


def simulate_scenarios(
    dag: AppDAG,
    pred: Dict[str, np.ndarray],
    act: Optional[Dict[str, np.ndarray]] = None,
    c_max_grid: Sequence[float] = (60.0,),
    orders: Sequence[str] = ("spt",),
    cost_model: CostModel = LAMBDA_COST,
    include_transfers: bool = True,
    init_phase: bool = True,
    adaptive: bool = True,
    t0: float = 0.0,
    engine: str = "vector",
    portfolio: Optional[ProviderPortfolio] = None,
    arrivals: ArrivalsLike = None,
    replicas=None,
    replica_speeds=None,
    price_traces=None,
    faults=None,
    retry=None,
    init_window: Optional[float] = None,
    chunk_jobs: Optional[int] = None,
    egress_lookahead: bool = False,
    workload=None,
    concurrency: ConcurrencyLike = None,
    coldstart: ColdStartLike = None,
    pool_trace: PoolTraceLike = None,
    engine_impl: Optional[str] = None,
    offload_mask: Optional[np.ndarray] = None,
) -> VectorSimResult:
    """Run Alg. 1 over a whole scenario grid in one batched device call.

    ``pred``/``act`` values are [J, M] (shared) or [B, J, M] (a batch of
    latency draws, e.g. one per seed); the scenario axis enumerates
    ``batch x orders x c_max_grid x replicas x replica_speeds x
    price_traces`` in C order. ``engine="des"`` replays the same grid
    serially through the reference simulator — same result layout, used
    by the equivalence suite and benchmarks. ``portfolio`` generalizes
    the public cloud to N providers (cheapest-feasible placement per
    offloaded stage); default is the scalar ``cost_model``. ``arrivals``
    injects an exogenous release stream (:mod:`.arrivals`), shared by
    every scenario of the grid; ``None`` is the batch at ``t0``.

    ``replicas`` is an autoscaling axis: a list of per-stage replica
    count vectors [M], each a private-pool sizing of the same
    application (``None`` = the one-point axis at the DAG's own counts).
    ``replica_speeds`` is a straggler axis: a list of slowdown configs —
    ``{(stage, replica): factor}`` dicts or [M, I] factor arrays
    (``None`` entries/axis = all replicas healthy). Both are scenario
    *data* in the vector engine (a masked [M, I_max] speed matrix per
    scenario, same compiled executable); the DES replays them via
    :meth:`.dag.AppDAG.with_replicas` and ``replica_slowdown``.

    ``price_traces`` is a pricing axis: a list of portfolio variants of
    the same providers — :class:`ProviderPortfolio` objects, per-provider
    :class:`.cost.PriceTrace` sequences, single traces, or ``None``
    entries (= the base ``portfolio``). Spot markets, diurnal tariffs
    and flat pricing then sweep as scenario *data* (segment-indexed
    [P, S, J, M] billing matrices, one executable per
    (M, I_max, J, P, S, flags) shape family); the DES replays each
    variant as its ``portfolio=``.

    ``faults`` is a reliability axis: a list of failure configs — each a
    :class:`.faults.FaultModel`, a scalar per-attempt failure rate (drawn
    deterministically at seed = its axis index), or ``None`` (fault-free
    entry); a bare model/scalar is a one-point axis, the default ``None``
    axis is the pre-fault bit-exact path. ``retry`` (a
    :class:`.faults.RetryPolicy`) sets attempt budgets and backoff for
    every faulty scenario; the vector engine unrolls a bounded attempt
    chain per offloaded stage (shape family grows an attempt axis) while
    the DES replays failures via retry heap events. ``init_window``
    restricts init-phase offloading to jobs released within that many
    seconds of ``t0`` (``None`` = all jobs, the pre-window behavior).

    ``chunk_jobs`` turns the job axis into a *paged* dimension: the
    vector engine runs arrival windows of about that many jobs per
    fixed-J compiled executable (carrying the jobs still queued at each
    cut and the per-replica clocks into the next page, each page run
    once), and the DES admits arrival epochs into its heap one window
    at a time — results are identical to the monolithic path on
    tie-free streams. ``egress_lookahead`` adds a one-edge downstream
    egress term to the placement argmin (predicted successor-edge
    volume x the candidate provider's egress rate), identically in both
    engines. ``workload`` is a :mod:`.workloads` spec (e.g.
    ``"azure:day=tue,scale=1e5"``) deriving ``pred``/``act`` and the
    release stream from the committed Azure-calibrated trace sample —
    pass ``pred=None`` with it.

    ``concurrency``/``coldstart``/``pool_trace`` add load-dependent
    latency (:mod:`.coldstart`) — per-provider concurrency caps with
    FIFO queueing, a keep-alive/cold-start model, and time-varying
    private pool sizes. They are per-call configs shared by every
    scenario of the grid (not grid axes), identical in both engines;
    degenerate values compile the pre-change graph bit-exactly. They
    cannot combine with ``faults``, ``chunk_jobs``, or (for
    ``pool_trace``) a ``replicas`` axis.

    ``offload_mask`` ([J] bool) injects an externally-decided offload
    plan shared by every scenario of the grid (see
    :func:`.simulator.simulate`): the capacity-prefix rule is skipped
    and marked jobs are forced public at every non-pinned stage. The
    vector engine consumes it through the ``init_mode=2``
    precomputed-plan path; not combinable with ``init_window``.

    ``engine_impl`` picks the vector engine's inner-loop implementation:
    ``"loop"`` (the original one-event-per-iteration ``while_loop``),
    ``"scan"`` (fused batched sweep — the default, ~same graph depth per
    *epoch* instead of per event) or ``"pallas"`` (the scan structure
    with the ACD sweep and capped dispatch chain as Pallas kernels).
    ``None`` defers to the ``REPRO_ENGINE_IMPL`` env var (default
    ``"scan"``). All impls are bit-exact; ``engine="des"`` ignores it.
    """
    from .simulator import _with_transfer_defaults, simulate
    from .workloads import resolve_workload

    resolve_engine_impl(engine_impl)  # fail fast on bad impl, any engine
    if workload is not None:
        if pred is not None:
            raise ValueError("pass either pred or workload=, not both")
        pred, act, wl_release = resolve_workload(workload, dag, t0)
        if arrivals is None:
            arrivals = wl_release
    if engine == "des":
        # same load-config validation as the vector path (simulate() also
        # validates, but the replicas-axis x pool_trace exclusion is only
        # visible at the grid level)
        validate_load_kwargs(
            np.isfinite(norm_concurrency(
                concurrency, as_portfolio(portfolio, cost_model))).any(),
            as_coldstart(coldstart), as_pool_trace(pool_trace),
            faulty=faults is not None, chunk_jobs=chunk_jobs,
            replicas_axis=replicas is not None)
        act_d = act if act is not None else pred
        _validate_workload_axes(pred, act_d)
        pred_d = _with_transfer_defaults(pred)
        act_d = _with_transfer_defaults(act_d)
        B = max([v.shape[0] if np.asarray(v).ndim == 3 else 1
                 for v in list(pred_d.values()) + list(act_d.values())]
                or [1])
        pred_d = _norm_batch(pred_d, B)
        act_d = _norm_batch(act_d, B)
        J = pred_d["P_private"].shape[1]
        release = resolve_release(arrivals, J, t0)
        repl_cfgs = _norm_replica_axis(replicas, dag)
        I_max = _max_replica_bound(dag,
                                   None if replicas is None else repl_cfgs)
        speed_cfgs = _norm_speed_axis(replica_speeds, dag.num_stages, I_max)
        trace_cfgs = _norm_trace_axis(price_traces,
                                      as_portfolio(portfolio, cost_model))
        # the one-point axis reuses `dag` itself (cached structure, and
        # bit-exact replay of the pre-axis path)
        dags = [dag if replicas is None else dag.with_replicas(cfg)
                for cfg in repl_cfgs]
        slow = [{(k, i): float(sp[k, i])
                 for k in range(dag.num_stages) for i in range(I_max)
                 if sp[k, i] != 1.0} or None
                for sp in speed_cfgs]
        retry_eff = retry if faults is None else (retry or RetryPolicy())
        fault_cfgs = normalize_fault_axis(faults, J, dag.num_stages,
                                          retry_eff) or [None]
        grid = [(b, o, float(c), r, g, tr, f)
                for b in range(B) for o in orders for c in c_max_grid
                for r in range(len(repl_cfgs))
                for g in range(len(speed_cfgs))
                for tr in range(len(trace_cfgs))
                for f in range(len(fault_cfgs))]
        sims = [simulate(dags[r], {k: v[b] for k, v in pred_d.items()},
                         {k: v[b] for k, v in act_d.items()},
                         c_max=c, order=o, cost_model=cost_model,
                         include_transfers=include_transfers,
                         init_phase=init_phase, adaptive=adaptive, t0=t0,
                         portfolio=trace_cfgs[tr], arrivals=release,
                         replica_slowdown=slow[g],
                         faults=fault_cfgs[f], retry=retry_eff,
                         init_window=init_window, chunk_jobs=chunk_jobs,
                         egress_lookahead=egress_lookahead,
                         concurrency=concurrency, coldstart=coldstart,
                         pool_trace=pool_trace, offload_mask=offload_mask)
                for (b, o, c, r, g, tr, f) in grid]
        return VectorSimResult(
            makespan=np.array([r.makespan for r in sims]),
            cost_usd=np.array([r.cost_usd for r in sims]),
            public_mask=np.stack([r.public_mask for r in sims]),
            start=np.stack([r.start for r in sims]),
            end=np.stack([r.end for r in sims]),
            completion=np.stack([r.completion for r in sims]),
            n_offloaded_stages=np.array([r.n_offloaded_stages for r in sims]),
            n_init_offloaded_jobs=np.array(
                [r.n_init_offloaded_jobs for r in sims]),
            per_stage_offloads=np.stack([r.per_stage_offloads for r in sims]),
            provider=np.stack([r.provider for r in sims]),
            deadline=np.array([r.deadline for r in sims]),
            orders=tuple(o for (_, o, _, _, _, _, _) in grid),
            c_max=np.array([c for (_, _, c, _, _, _, _) in grid]),
            batch_idx=np.array([b for (b, _, _, _, _, _, _) in grid]),
            release=None if release is None
            else np.broadcast_to(release, (len(grid), J)).copy(),
            replica=np.stack([r.replica for r in sims]),
            replicas=np.stack(
                [repl_cfgs[r] for (_, _, _, r, _, _, _) in grid]),
            segment=np.stack([r.segment for r in sims]),
            trace_idx=np.array([tr for (_, _, _, _, _, tr, _) in grid]),
            attempts=np.stack([r.attempts for r in sims]),
            failed=np.stack([r.failed for r in sims]),
            abandoned=np.stack([r.abandoned for r in sims]),
            fault_idx=np.array([f for (_, _, _, _, _, _, f) in grid]),
            queue_wait=np.stack([r.queue_wait for r in sims]),
            cold=np.stack([r.cold for r in sims]))
    if engine != "vector":
        raise ValueError(f"unknown engine {engine!r}")
    return sweep_scenarios(
        [dict(dag=dag, pred=pred, act=act, c_max_grid=c_max_grid,
              orders=orders, arrivals=arrivals, replicas=replicas,
              replica_speeds=replica_speeds, price_traces=price_traces,
              faults=faults, offload_mask=offload_mask)],
        cost_model=cost_model, include_transfers=include_transfers,
        init_phase=init_phase, adaptive=adaptive, t0=t0,
        portfolio=portfolio, retry=retry, init_window=init_window,
        chunk_jobs=chunk_jobs, egress_lookahead=egress_lookahead,
        concurrency=concurrency, coldstart=coldstart,
        pool_trace=pool_trace, engine_impl=engine_impl)[0]


def _prep_fp(obj, refs: List[object]):
    """Structural fingerprint of one sweep input for the prep cache.

    Scalars, strings, sequences, dicts and ndarrays key by *value*
    (arrays by shape/dtype/content digest, so even an in-place edit
    misses cleanly); opaque config objects (portfolios, cost models,
    fault / cold-start configs) key by identity and are appended to
    ``refs`` so the cache entry can pin them alive — a live entry can
    therefore never collide with a recycled ``id``.
    """
    if obj is None or isinstance(obj, (bool, int, float, complex, str,
                                       bytes)):
        return obj
    if isinstance(obj, np.generic):
        return ("np", obj.dtype.str, obj.item())
    if isinstance(obj, np.ndarray):
        return ("nd", obj.shape, obj.dtype.str,
                hash(np.ascontiguousarray(obj).tobytes()))
    if isinstance(obj, (list, tuple)):
        return ("seq", tuple(_prep_fp(o, refs) for o in obj))
    if isinstance(obj, dict):
        return ("map", tuple(
            (k, _prep_fp(v, refs))
            for k, v in sorted(obj.items(), key=lambda kv: repr(kv[0]))))
    refs.append(obj)
    return ("id", id(obj))


# repeated sweeps over an unchanged grid (benchmark warm/timed call
# pairs, parameter studies re-running a figure) skip the whole numpy
# normalization pass below — several ms per call at fig-4 scale
_PREP_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_PREP_CACHE_MAX = 8


def _prep_sweep(tasks, cost_model, include_transfers, t0, portfolio,
                retry, init_window, chunk_jobs, concurrency, coldstart,
                pool_trace) -> Tuple[List[_Task], int]:
    """Validate and normalize a sweep's tasks into engine-ready
    :class:`_Task` bundles (the cacheable part of :func:`sweep_scenarios`)."""
    M_pad = max(t["dag"].num_stages for t in tasks)
    # normalize each task's replica and price-trace axes once (validates
    # with the task's name, materializes one-shot iterators); the replica
    # and segment bounds cover every task's axes, so one shape family
    # serves the whole sweep
    tasks = [dict(t) for t in tasks]
    base_pf = as_portfolio(portfolio, cost_model)
    any_faulty = any(t.get("faults") is not None for t in tasks)
    retry_eff = (retry or RetryPolicy()) if any_faulty else retry
    # load-dependent latency configs: per-call, shared by every task of
    # the sweep (caps bind per provider, which every price trace shares)
    cs = as_coldstart(coldstart)
    ptr = as_pool_trace(pool_trace)
    caps_vec = norm_concurrency(concurrency, base_pf)
    caps_eff = caps_vec if np.isfinite(caps_vec).any() else None
    validate_load_kwargs(
        caps_eff is not None, cs, ptr, faulty=any_faulty,
        chunk_jobs=chunk_jobs,
        replicas_axis=any(t.get("replicas") is not None for t in tasks))
    for i, t in enumerate(tasks):
        if ptr is not None:
            # provision each task's pool at the trace's per-stage max and
            # mask availability with the slot windows (the DES path of
            # simulate() applies the identical transform)
            on_t, off_t, _ = ptr.slot_windows(t["dag"].num_stages)
            t["dag"] = t["dag"].with_replicas(
                ptr.materialize(t["dag"].num_stages).max(axis=0))
            t["_pool"] = (on_t, off_t)
        if t.get("workload") is not None:
            from .workloads import resolve_workload
            if t.get("pred") is not None:
                raise ValueError(
                    f"tasks[{i}]: pass either pred or workload=, not both")
            t["pred"], t["act"], wl_release = resolve_workload(
                t["workload"], t["dag"], t0)
            if t.get("arrivals") is None:
                t["arrivals"] = wl_release
        if t.get("replicas") is not None:
            t["replicas"] = _norm_replica_axis(t["replicas"], t["dag"],
                                               where=f"tasks[{i}]")
        t["price_traces"] = _norm_trace_axis(t.get("price_traces"), base_pf,
                                             where=f"tasks[{i}]")
        if t.get("faults") is not None:
            J_t = int(np.asarray(t["pred"]["P_private"]).shape[-2])
            t["faults"] = normalize_fault_axis(
                t["faults"], J_t, t["dag"].num_stages, retry_eff,
                where=f"tasks[{i}]")
    I_max = max(_max_replica_bound(t["dag"], t.get("replicas"))
                for t in tasks)
    S_seg = max(_max_segment_bound(t["price_traces"]) for t in tasks)
    # attempt-axis and outage-window bounds of the sweep's shape family:
    # zero when no task is faulty (the engine compiles the pre-fault graph)
    A_att = retry_eff.max_attempts if any_faulty else 0
    W = max([max_outage_slots(t["faults"]) for t in tasks
             if t.get("faults") is not None] or [0])
    # the _Task constructors below ARE the replan/policy decisions:
    # priority keys, placement argmin matrices, offload-plan resolution.
    # Timed into the plan_s bucket so --profile can attribute policy
    # overhead separately from generic host prep (0 on a prep-cache hit
    # — the decisions were genuinely reused).
    with _span("plan"):
        prepped = [_Task(t["dag"], t["pred"], t.get("act"),
                         t.get("c_max_grid", (60.0,)),
                         t.get("orders", ("spt",)), cost_model, t0, M_pad,
                         I_max=I_max, portfolio=portfolio,
                         include_transfers=bool(include_transfers),
                         arrivals=t.get("arrivals"),
                         replicas=t.get("replicas"),
                         replica_speeds=t.get("replica_speeds"),
                         price_traces=t["price_traces"], S_seg=S_seg,
                         faults=t.get("faults"), retry=retry_eff,
                         init_window=t.get("init_window", init_window),
                         A_att=A_att, W=W,
                         caps=caps_eff, coldstart=cs, pool=t.get("_pool"),
                         offload_mask=t.get("offload_mask"),
                         init_override=t.get("init_phase"),
                         adaptive_override=t.get("adaptive"),
                         where=f"tasks[{i}]")
                   for i, t in enumerate(tasks)]
    return prepped, I_max


def sweep_scenarios(
    tasks: Sequence[Dict],
    cost_model: CostModel = LAMBDA_COST,
    include_transfers: bool = True,
    init_phase: bool = True,
    adaptive: bool = True,
    t0: float = 0.0,
    engine: str = "vector",
    portfolio: Optional[ProviderPortfolio] = None,
    retry=None,
    init_window: Optional[float] = None,
    chunk_jobs: Optional[int] = None,
    egress_lookahead: bool = False,
    concurrency: ConcurrencyLike = None,
    coldstart: ColdStartLike = None,
    pool_trace: PoolTraceLike = None,
    engine_impl: Optional[str] = None,
) -> List[VectorSimResult]:
    """Run several scenario grids — e.g. a whole Fig.-4 figure, one task per
    application — as one batched, device-parallel sweep.

    Each task is a dict with keys ``dag``, ``pred``, optional ``act``,
    ``c_max_grid``, ``orders``, ``arrivals`` (an exogenous release
    stream for that task's jobs; omitted = batch at ``t0``),
    ``replicas`` (an autoscaling axis: a list of per-stage replica count
    vectors [M]; omitted = the DAG's own counts), ``replica_speeds``
    (a straggler axis: a list of ``{(stage, replica): factor}`` dicts or
    [M, I] slowdown arrays; omitted = all healthy) and ``price_traces``
    (a pricing axis: portfolio variants / per-provider
    :class:`.cost.PriceTrace` lists; omitted = the sweep's
    ``portfolio``) and ``faults`` (a reliability axis: a list of
    :class:`.faults.FaultModel` / scalar failure rates / ``None``
    entries, or a bare model/rate as a one-point axis; omitted =
    fault-free, the pre-fault bit-exact path — the sweep-level ``retry``
    policy governs every faulty scenario and the attempt-axis bound of
    the shared shape family); results come back in task order. Every task's
    replica configs pad to the sweep's common ``I_max`` (absent slots
    are masked out) and every price trace to the common segment bound
    ``S`` (padded segments never activate), so the whole
    replica / straggler / pricing grid shares one compiled executable
    per ``(M_pad, I_max, J, P, S, flags)`` shape family. Tasks with a
    common job count batch into a single engine call (stages padded to
    the largest DAG; the scenario axis shards across host devices);
    differing job counts fall back to one call per group.

    Tasks may also override the sweep-level scheduling flags per task:
    ``init_phase``, ``adaptive``, ``init_window`` (each defaulting to
    the sweep-level keyword) and ``offload_mask`` (a [J] bool plan that
    replaces the capacity-prefix rule — see
    :func:`.simulator.simulate`). The policy-comparison harness
    (:mod:`repro.serving.policies`) relies on this to evaluate an
    ACD-adaptive policy and fixed-placement baselines in ONE batched
    sweep; tasks with differing effective flags simply land in
    different fusion groups (separate executables, same call).

    Malformed inputs fail fast with a :class:`ValueError` naming the
    task and the offending axis (e.g. ``tasks[1]: act['P_public']: ...``
    or ``tasks[0]: replicas[2]: ...``) instead of a shape error from
    inside the batched engine.
    """
    if engine == "des":
        return [simulate_scenarios(
            t["dag"], t.get("pred"), t.get("act"),
            t.get("c_max_grid", (60.0,)), t.get("orders", ("spt",)),
            cost_model=cost_model, include_transfers=include_transfers,
            init_phase=t.get("init_phase", init_phase),
            adaptive=t.get("adaptive", adaptive), t0=t0, engine="des",
            portfolio=portfolio, arrivals=t.get("arrivals"),
            replicas=t.get("replicas"),
            replica_speeds=t.get("replica_speeds"),
            price_traces=t.get("price_traces"),
            faults=t.get("faults"), retry=retry,
            init_window=t.get("init_window", init_window),
            chunk_jobs=chunk_jobs, egress_lookahead=egress_lookahead,
            workload=t.get("workload"), concurrency=concurrency,
            coldstart=coldstart, pool_trace=pool_trace,
            offload_mask=t.get("offload_mask"))
            for t in tasks]
    if engine != "vector":
        raise ValueError(f"unknown engine {engine!r}")
    if t0 < 0:
        # the engine sign-encodes eviction times as -t - 1, so the clock
        # must stay non-negative (the DES has no such restriction)
        raise ValueError("engine='vector' requires t0 >= 0")
    if chunk_jobs is not None and int(chunk_jobs) < 1:
        raise ValueError(f"chunk_jobs must be >= 1, got {chunk_jobs}")
    impl = resolve_engine_impl(engine_impl)
    _LAST_RUN_STATS.clear()
    # the root span: every vs: span of this sweep nests under it
    with jax.profiler.TraceAnnotation("vs:sweep", sweep=next(_SWEEP_IDS)):
        with _span("prep"):
            refs: List[object] = []
            fp = ("v1", _prep_fp(list(tasks), refs),
                  _prep_fp(cost_model, refs), bool(include_transfers),
                  float(t0), _prep_fp(portfolio, refs),
                  _prep_fp(retry, refs),
                  None if init_window is None else float(init_window),
                  None if chunk_jobs is None else int(chunk_jobs),
                  _prep_fp(concurrency, refs), _prep_fp(coldstart, refs),
                  _prep_fp(pool_trace, refs))
            hit = _PREP_CACHE.get(fp)
            if hit is not None:
                _PREP_CACHE.move_to_end(fp)
                prepped, I_max = hit[0], hit[1]
            else:
                prepped, I_max = _prep_sweep(
                    tasks, cost_model, include_transfers, t0, portfolio,
                    retry, init_window, chunk_jobs, concurrency, coldstart,
                    pool_trace)
                # refs pins every id-keyed object in fp for the entry's
                # lifetime, so a reclaimed id can never alias a live key
                _PREP_CACHE[fp] = (prepped, I_max, tuple(refs))
                while len(_PREP_CACHE) > _PREP_CACHE_MAX:
                    _PREP_CACHE.popitem(last=False)

        # Call batching policy: on a multi-device host, one engine call
        # per task, each sharding its own scenario axis — per-device state
        # stays small (cache-resident), which measures faster than one
        # wide fused batch. On a single device the bottleneck flips to
        # per-call dispatch overhead, so same-shape-family tasks *fuse*:
        # their scenario axes concatenate into one engine call (the
        # vmapped engine is per-scenario independent, so fusion is
        # result-invariant) and the output splits back per task. Either
        # way tasks share compiled executables through the (M_pad, I_max,
        # J) shape family.
        results: List[Optional[VectorSimResult]] = [None] * len(prepped)
        run_idx: List[int] = []
        for i, p in enumerate(prepped):
            if p.J == 0:
                z2, z3 = np.zeros((p.S, 0)), np.zeros((p.S, 0, p.M))
                results[i] = (VectorSimResult(
                    makespan=np.zeros(p.S), cost_usd=np.zeros(p.S),
                    public_mask=np.zeros((p.S, 0, p.M), dtype=bool),
                    start=z3, end=z3, completion=z2,
                    n_offloaded_stages=np.zeros(p.S, dtype=np.int64),
                    n_init_offloaded_jobs=np.zeros(p.S, dtype=np.int64),
                    per_stage_offloads=np.zeros((p.S, p.M),
                                                dtype=np.int64),
                    provider=np.full((p.S, 0, p.M), -1, dtype=np.int64),
                    deadline=p.c_max_out.copy(), orders=p.orders_out,
                    c_max=p.c_max_out, batch_idx=p.batch_out,
                    release=None if p.release is None
                    else np.zeros((p.S, 0)),
                    replica=np.full((p.S, 0, p.M), -1, dtype=np.int64),
                    replicas=p.repl_out.copy(),
                    segment=np.full((p.S, 0, p.M), -1, dtype=np.int64),
                    trace_idx=p.trace_out.copy(),
                    attempts=np.zeros((p.S, 0, p.M), dtype=np.int64),
                    failed=np.zeros((p.S, 0, p.M), dtype=np.int64),
                    abandoned=np.zeros((p.S, 0), dtype=bool),
                    fault_idx=p.fault_out.copy(),
                    queue_wait=np.zeros((p.S, 0, p.M)),
                    cold=np.zeros((p.S, 0, p.M), dtype=bool)))
            else:
                run_idx.append(i)

        n_dev = jax.local_device_count()
        groups: List[List[int]] = []
        by_key: Dict[tuple, List[int]] = {}
        for i in run_idx:
            p = prepped[i]
            paged = (chunk_jobs is not None and p.release is not None
                     and int(chunk_jobs) < p.J)
            if n_dev > 1 or paged:
                groups.append([i])
                continue
            key = (p.J, p.faulty, p.n_providers, p.n_segments,
                   p.n_attempts, p.n_windows, p.capped, p.cold, p.pooled,
                   p.C, p.eff_modes(bool(init_phase), bool(adaptive)))
            grp = by_key.get(key)
            if grp is None:
                by_key[key] = grp = []
                groups.append(grp)
            grp.append(i)
        for grp in groups:
            if len(grp) == 1:
                p = prepped[grp[0]]
                results[grp[0]] = _run_task(
                    p, I_max, bool(include_transfers), bool(init_phase),
                    bool(adaptive), lookahead=bool(egress_lookahead),
                    chunk_jobs=(None if chunk_jobs is None
                                else int(chunk_jobs)),
                    impl=impl)
                continue
            ps = [prepped[i] for i in grp]
            p0 = ps[0]
            with _span("engine"):
                task_args = [p.engine_args(bool(init_phase)) for p in ps]
                fused = tuple(np.concatenate([a[k] for a in task_args])
                              for k in range(len(p0.args)))
                grp_mode, grp_adapt = p0.eff_modes(bool(init_phase),
                                                   bool(adaptive))
                fn = _engine_fn(p0.M_pad, I_max, p0.J, p0.n_providers,
                                p0.n_segments, bool(include_transfers),
                                grp_mode, grp_adapt,
                                p0.n_attempts, p0.n_windows, p0.faulty,
                                bool(egress_lookahead), p0.capped, p0.cold,
                                p0.pooled, p0.C, 1, impl)
                out = _dispatch(fn, fused, sum(p.S for p in ps), 1)
            with _span("finalize"):
                lo = 0
                for i, p in zip(grp, ps):
                    sub = {k: v[lo:lo + p.S] for k, v in out.items()}
                    results[i] = p.pack(_finalize(p, sub))
                    lo += p.S
            _LAST_RUN_STATS["impl"] = impl
        return results
