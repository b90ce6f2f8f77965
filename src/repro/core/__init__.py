"""Skedulix core: cost/deadline scheduling of DAG workloads on a hybrid cloud.

Reproduces the paper's primary contribution — greedy scheduling (Alg. 1)
of multi-stage serverless applications across a fixed-capacity private
cloud and pay-per-use public clouds, minimizing public-cloud cost subject
to a deadline — and grows it toward continuous serving.

Layout (one module per concern):

``dag``
    :class:`AppDAG`/:class:`Stage` — the application model (Sec. II-A):
    stages with private replica counts and memory configs, precedence
    edges, cached structure queries. ``APPS`` holds the paper's three
    canonical applications.
``cost``
    Public-cloud billing (Eqn. 1): scalar :class:`CostModel` and the
    multi-provider :class:`ProviderPortfolio` (per-provider quantum, rate,
    egress, latency multiplier, memory cap; cheapest-feasible placement).
    :class:`PriceTrace` makes rate/egress/latency piecewise-constant over
    simulated time (spot markets via :func:`spot_portfolio`, tariffs via
    :func:`diurnal_portfolio`); placement then locks its (provider, price
    segment) at the offload epoch.
``arrivals``
    Exogenous release streams (:class:`PoissonArrivals`,
    :class:`MMPPArrivals`, :class:`TraceArrivals`) generalizing the
    paper's batch-at-``t0`` to continuous serving.
``workloads``
    Trace-derived workload families: the ``azure:`` spec samples
    whole invocation days (heavy-tailed durations, diurnal releases)
    from the committed Azure-2019-calibrated extract at any scale.
``coldstart``
    Load-dependent latency configs: :class:`ColdStartModel` (warm-up /
    keep-alive / scale-to-zero), :class:`PoolTrace` (time-varying
    private pool sizes) and concurrency-cap normalization — the
    ``concurrency=`` / ``coldstart=`` / ``pool_trace=`` keywords both
    engines accept.
``greedy``
    The vectorized Alg.-1 math: capacity-prefix initialization offload,
    ACD sweeps, provider selection — numpy and jit twins.
``priority``
    SPT / HCF priority orders (Sec. III-C).
``perfmodel``
    Ridge latency/size models fitted on execution traces (Sec. IV).
``simulator``
    ``engine="des"``: the discrete-event reference of the hybrid
    platform + Alg. 1 event loop (:func:`simulate`).
``vectorsim``
    ``engine="vector"``: the batched jit twin — whole scenario grids per
    device call (:func:`simulate_scenarios`, :func:`sweep_scenarios`),
    exactly equivalent to the DES on tie-free workloads.
``milp``
    Provider-indexed MILP reference bound (:func:`solve_milp`) and
    combinatorial lower bounds.
``scheduler``
    :class:`SkedulixScheduler` — the user-facing service tying
    predictions, scheduling and execution together.

Names resolve lazily (PEP 562): importing one submodule, e.g.
``repro.core.faults`` from the training stack, runs none of the others,
so the jit engine is loaded only by code that asks for it.
"""
import importlib

_EXPORTS = {
    "arrivals": ("ArrivalProcess", "BatchArrivals", "MMPPArrivals",
                 "PoissonArrivals", "TraceArrivals", "parse_arrivals",
                 "resolve_release"),
    "coldstart": ("ColdStartModel", "PoolTrace", "as_coldstart",
                  "as_pool_trace", "queue_wait_ewma"),
    "cost": ("CostModel", "LAMBDA_COST", "PriceTrace", "Provider",
             "ProviderPortfolio", "as_portfolio", "demo_portfolio",
             "diurnal_portfolio", "lambda_cost", "scaled_portfolio",
             "spot_portfolio", "stage_costs"),
    "dag": ("APPS", "AppDAG", "Stage", "image_app", "matrix_app",
            "video_app"),
    "faults": ("FaultModel", "RetryPolicy", "as_fault_model"),
    "greedy": ("acd_sweep", "acd_sweep_jax", "init_offload",
               "init_offload_jax", "offload_negative_acd", "select_provider",
               "select_provider_jax", "t_max"),
    "milp": ("MilpResult", "johnson_makespan", "knapsack_lower_bound",
             "solve_milp"),
    "perfmodel": ("AppPerfModel", "RidgeModel", "StageModels",
                  "fit_app_perf_model", "fit_ridge", "grid_search_ridge",
                  "mape"),
    "priority": ("ORDERS", "hcf_key", "sort_queue", "spt_key"),
    "scheduler": ("BatchReport", "SkedulixScheduler"),
    "simulator": ("SimResult", "simulate", "simulate_all_private",
                  "simulate_all_public"),
    "vectorsim": ("ENGINE_IMPLS", "VectorSimResult", "resolve_engine_impl",
                  "simulate_scenarios", "sweep_scenarios"),
    "workloads": ("AzureWorkload", "load_azure_sample", "parse_workload",
                  "resolve_workload"),
}
_OWNER = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = sorted(_OWNER)


def __getattr__(name):
    mod = _OWNER.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f".{mod}", __name__), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_OWNER))
