"""Plain reference for the benchmark's `correct`: Skedulix's Algorithm 1
run as a discrete-event simulation in numpy, one scenario at a time.

It follows Das et al., "Skedulix" (arXiv:2006.03720), Sec. III: a
capacity-prefix initialization offload over T_max = sum_k I_k * C_max
(of the jobs released within an init window, where one is given), then per-stage priority queues whose ACD kept-prefix scan evicts jobs
(and all their descendant stages) to the public cloud, the head of a
queue taking the lowest-index free private replica. Public execution
is billed by Eqn. 1 (rounded quantum x memory x rate, at least one
quantum) and pays an upload when an input lives in private storage and
a download at the sink.

It covers what the benchmark's configurations use: one public provider
with static prices and no egress fee, transfers modelled, batch or
arrival-stream release, any replica counts. It imports nothing of the
system under test and reads only the configuration and the generated
inputs. The arithmetic follows the system's own event-heap simulator
expression for expression, so that a correct engine agrees with it in
every decision and in every float up to the rounding of float64.
"""
from __future__ import annotations

import bisect
import heapq
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WAITING, QUEUED, RUNNING, DONE = 0, 1, 2, 3
PRIVATE = -1

#: fields a scenario's result holds (decisions, then floats)
EXACT_FIELDS = ("public_mask", "n_offloaded_stages", "n_init_offloaded_jobs",
                "per_stage_offloads", "provider", "replica", "segment",
                "attempts", "failed", "abandoned", "cold")
FLOAT_FIELDS = ("makespan", "cost_usd", "completion", "start", "end",
                "queue_wait")


class Dag:
    """Stages, edges and replica counts of one application."""

    def __init__(self, app: dict, replicas: Optional[Sequence[int]] = None):
        self.stages = app["stages"]
        self.M = len(self.stages)
        self.edges = [tuple(e) for e in app["edges"]]
        counts = (replicas if replicas is not None
                  else [s["replicas"] for s in self.stages])
        self.replicas = np.array([int(c) for c in counts], dtype=np.int64)
        self.mem_mb = np.array([float(s["mem_mb"]) for s in self.stages])
        self.succ = [[v for (u, v) in self.edges if u == k]
                     for k in range(self.M)]
        self.pred = [[u for (u, v) in self.edges if v == k]
                     for k in range(self.M)]
        self.sources = [k for k in range(self.M) if not self.pred[k]]
        self.sinks = [k for k in range(self.M) if not self.succ[k]]
        # Kahn's order, popping the last of the frontier
        indeg = [len(p) for p in self.pred]
        frontier = [k for k in range(self.M) if indeg[k] == 0]
        self.topo: List[int] = []
        while frontier:
            k = frontier.pop()
            self.topo.append(k)
            for v in self.succ[k]:
                indeg[v] -= 1
                if indeg[v] == 0:
                    frontier.append(v)
        self.desc = []
        for k in range(self.M):
            seen, stack = set(), list(self.succ[k])
            while stack:
                v = stack.pop()
                if v not in seen:
                    seen.add(v)
                    stack.extend(self.succ[v])
            self.desc.append(sorted(seen))

    def path_remaining(self, lat: np.ndarray) -> np.ndarray:
        """[J, M]: latency of the longest path from each stage (included)
        to a sink."""
        out = np.zeros_like(lat)
        for k in reversed(self.topo):
            best = 0.0
            if self.succ[k]:
                best = np.max(np.stack([out[:, v] for v in self.succ[k]],
                                       axis=-1), axis=-1)
            out[:, k] = lat[:, k] + best
        return out


def lambda_cost(P_public_s: np.ndarray, mem_mb: np.ndarray,
                cost: dict) -> np.ndarray:
    """Eqn. 1: [J, M] billed USD of each (job, stage) run in public."""
    t_ms = 1.0 * np.asarray(P_public_s, dtype=np.float64) * 1e3
    quantums = np.maximum(np.ceil(t_ms / cost["quantum_ms"]),
                          cost["min_quantums"])
    return (cost["quantum_ms"] * quantums * (mem_mb[None, :] / 1024.0)
            * cost["usd_per_gb_ms"])


def init_offload(C_total: np.ndarray, keys: np.ndarray,
                 capacity: float) -> np.ndarray:
    """Capacity prefix rule: keep jobs head-first while their summed
    private demand fits, offload the tail."""
    order = np.argsort(keys, kind="stable")
    keep = np.cumsum(C_total[order]) <= capacity + 1e-12
    off = np.ones(C_total.shape[0], dtype=bool)
    off[order[keep]] = False
    return off


class Simulation:
    """One scenario: an application, its inputs, a deadline and an order."""

    def __init__(self, dag: Dag, pred: Dict[str, np.ndarray],
                 act: Dict[str, np.ndarray], c_max: float, order: str,
                 cost: dict, release: Optional[np.ndarray] = None,
                 t0: float = 0.0, init_window: Optional[float] = None):
        self.dag = dag
        self.init_window = init_window
        self.J, self.M = pred["P_private"].shape
        self.pred, self.c_max, self.t0 = pred, float(c_max), float(t0)
        self.release = release
        self.rel = (np.full(self.J, self.t0) if release is None
                    else np.asarray(release, dtype=np.float64))
        self.deadline_j = self.rel + self.c_max
        sink = np.zeros(self.M, dtype=bool)
        sink[dag.sinks] = True
        egress = float(cost.get("egress_usd_per_gb", 0.0))
        if egress:
            raise ValueError("the reference models a provider without "
                             "egress fees only")
        H_pred = lambda_cost(pred["P_public"], dag.mem_mb, cost)
        if order == "spt":
            self.stage_keys = np.asarray(pred["P_private"], np.float64)
            self.job_keys = self.stage_keys.sum(axis=1)
        elif order == "hcf":
            self.stage_keys = -H_pred
            self.job_keys = -(H_pred.sum(axis=1))
        else:
            raise ValueError(f"unknown order {order!r}")
        self.path_rem = dag.path_remaining(
            np.asarray(pred["P_private"], np.float64))
        self.P_pred = np.ascontiguousarray(pred["P_private"], np.float64)
        self.act_priv = act["P_private"].tolist()
        self.act_pub = (act["P_public"] * 1.0).tolist()
        self.act_up = (act["upload"] * 1.0).tolist()
        self.act_down = (act["download"] * 1.0).tolist()
        self.cost_l = lambda_cost(act["P_public"], dag.mem_mb, cost).tolist()
        self.keys_l = self.stage_keys.tolist()
        self.repl = [max(int(r), 1) for r in dag.replicas]
        self.sink_set = set(dag.sinks)

        self.status = np.full((self.J, self.M), WAITING, dtype=np.int8)
        self.loc = np.full((self.J, self.M), PRIVATE, dtype=np.int16)
        self.replica = np.full((self.J, self.M), -1, dtype=np.int32)
        self.forced_public = np.zeros((self.J, self.M), dtype=bool)
        self.start = np.full((self.J, self.M), np.nan)
        self.end = np.full((self.J, self.M), np.nan)
        self.completion = np.zeros(self.J)
        self.queues: List[List[Tuple[float, int]]] = [[] for _ in
                                                      range(self.M)]
        self.free = [list(range(int(r))) for r in dag.replicas]
        self.cost = 0.0
        self.n_offloaded = 0
        self.per_stage_offloads = np.zeros(self.M, dtype=np.int64)
        self.n_init_off = 0
        self.heap: List[tuple] = []
        self.seq = itertools.count()

    def at(self, t: float, fn, *args):
        heapq.heappush(self.heap, (t, next(self.seq), fn, args))

    def run(self) -> Dict[str, np.ndarray]:
        self.initialize()
        while self.heap:
            t, _, fn, args = heapq.heappop(self.heap)
            fn(t, *args)
        public = self.loc != PRIVATE
        makespan = (float(np.max(self.completion) - self.t0)
                    if self.J else 0.0)
        return dict(
            makespan=makespan, cost_usd=self.cost, public_mask=public,
            start=self.start, end=self.end, completion=self.completion,
            n_offloaded_stages=self.n_offloaded,
            n_init_offloaded_jobs=self.n_init_off,
            per_stage_offloads=self.per_stage_offloads,
            provider=self.loc.astype(np.int64),
            replica=self.replica.astype(np.int64),
            segment=np.where(public, 0, -1).astype(np.int64),
            attempts=public.astype(np.int64),
            failed=np.zeros((self.J, self.M), dtype=np.int64),
            abandoned=np.zeros(self.J, dtype=bool),
            queue_wait=np.zeros((self.J, self.M)),
            cold=np.zeros((self.J, self.M), dtype=bool))

    def initialize(self):
        C_total = self.pred["P_private"].sum(axis=1)
        cap = float(np.sum(self.dag.replicas) * self.c_max)
        # with an init window only the jobs released within it are known
        # to the plan: the rest add no demand and are never offloaded
        elig = (np.ones(self.J, dtype=bool) if self.init_window is None
                else self.rel <= self.t0 + float(self.init_window))
        off = init_offload(np.where(elig, C_total, 0.0), self.job_keys,
                           cap) & elig
        self.n_init_off = int(off.sum())
        self.forced_public[off, :] = True
        at_t0 = self.rel <= self.t0
        for j in range(self.J):
            if at_t0[j]:
                for k in self.dag.sources:
                    self.stage_ready(self.t0, j, k)
        for k in range(self.M):
            self.sweep_and_dispatch(self.t0, k)
        later = np.flatnonzero(~at_t0)
        if later.size:
            times = self.rel[later]
            for t_r in np.unique(times):
                jobs = tuple(int(j) for j in later[times == t_r])
                self.at(float(t_r), self.arrival_epoch, jobs)

    def arrival_epoch(self, t: float, jobs: Tuple[int, ...]):
        for j in jobs:
            for k in self.dag.sources:
                self.stage_ready(t, j, k)
        for k in self.dag.sources:
            if any(not self.forced_public[j, k] for j in jobs):
                self.sweep_and_dispatch(t, k)

    def stage_ready(self, t: float, j: int, k: int):
        self.status[j, k] = QUEUED
        if self.forced_public[j, k]:
            self.start_public(t, j, k)
        else:
            bisect.insort(self.queues[k], (self.keys_l[j][k], j))

    def sweep_and_dispatch(self, t: float, k: int):
        """ACD kept-prefix scan, then the queue's head takes the lowest
        free replica."""
        q = self.queues[k]
        if q:
            I_k = self.repl[k]
            jobs = np.fromiter((jj for (_, jj) in q), dtype=np.int64,
                               count=len(q))
            P = self.P_pred[jobs, k]
            slack = I_k * (self.deadline_j[jobs] - t - self.path_rem[jobs, k])
            while jobs.size:
                prefix_excl = np.cumsum(P) - P
                viol = np.flatnonzero(prefix_excl > slack)
                if viol.size == 0:
                    break
                i = int(viol[0])
                self.offload_now(t, int(jobs[i]), k)
                del q[i]
                jobs = np.delete(jobs, i)
                P = np.delete(P, i)
                slack = np.delete(slack, i)
        free = self.free[k]
        while free and q:
            _, j = q.pop(0)
            r = free.pop(0)
            self.start_private(t, j, k, r)

    def start_private(self, t: float, j: int, k: int, r: int):
        self.status[j, k] = RUNNING
        self.loc[j, k] = PRIVATE
        self.replica[j, k] = r
        self.start[j, k] = t
        self.at(t + self.act_priv[j][k], self.private_done, j, k, r)

    def private_done(self, t: float, j: int, k: int, r: int):
        self.status[j, k] = DONE
        self.end[j, k] = t
        bisect.insort(self.free[k], r)
        self.propagate_done(t, j, k)
        self.sweep_and_dispatch(t, k)

    def offload_now(self, t: float, j: int, k: int):
        # an eviction instant is carried sign-encoded as -t - 1 by an
        # engine that keeps it in its queue state; the round trip is
        # idempotent and is applied here too
        t = -(-t - 1.0) - 1.0
        self.forced_public[j, k] = True
        for d in self.dag.desc[k]:
            self.forced_public[j, d] = True
        self.start_public(t, j, k)

    def start_public(self, t: float, j: int, k: int):
        self.status[j, k] = RUNNING
        self.loc[j, k] = 0
        self.n_offloaded += 1
        self.per_stage_offloads[k] += 1
        up = 0.0
        preds = self.dag.pred[k]
        if (not preds) or any(self.loc[j, p] == PRIVATE for p in preds):
            up = self.act_up[j][k]
        self.start[j, k] = t + up
        self.cost += self.cost_l[j][k]
        self.at(t + up + self.act_pub[j][k], self.public_done, j, k)

    def public_done(self, t: float, j: int, k: int):
        self.status[j, k] = DONE
        self.end[j, k] = t
        self.propagate_done(t, j, k)

    def propagate_done(self, t: float, j: int, k: int):
        status_j = self.status[j]
        for q in self.dag.succ[k]:
            if status_j[q] == WAITING and all(
                    status_j[p] == DONE for p in self.dag.pred[q]):
                self.stage_ready(t, j, q)
                if not self.forced_public[j, q]:
                    self.sweep_and_dispatch(t, q)
        if k in self.sink_set:
            down = self.act_down[j][k] if self.loc[j, k] != PRIVATE else 0.0
            if t + down > self.completion[j]:
                self.completion[j] = t + down


def simulate(app: dict, pred: Dict[str, np.ndarray],
             act: Dict[str, np.ndarray], c_max: float, order: str,
             cost: dict, replicas: Optional[Sequence[int]] = None,
             release: Optional[np.ndarray] = None,
             t0: float = 0.0,
             init_window: Optional[float] = None) -> Dict[str, np.ndarray]:
    """One scenario of Algorithm 1: the result's fields by name."""
    return Simulation(Dag(app, replicas), pred, act, c_max, order, cost,
                      release, t0, init_window).run()
