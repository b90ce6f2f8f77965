"""Streaming/paged job axis: chunked == monolithic, DES == vector.

The paged path must be *indistinguishable* from the monolithic one: the
vector engine pages jobs through fixed-shape chunks (each page resumes
from the queues and per-replica clocks at its cut, jobs still queued
there carried into the next page) and the DES admits arrival epochs in
windows — neither may change a single field of the result. The suite
pins:

* chunked vs monolithic bit-exactness on the vector engine
  (``chunk_jobs`` in {J, J/2, 17, 7}), every SimResult field including
  provider/segment/replica/attempts, with multi-page execution actually
  exercised (the ``pages`` counter);
* DES windowed admission bit-exact vs the monolithic DES, and
  DES == vector at every chunk size tested;
* carry: on streams whose queues span the cuts (the chain image DAG,
  the video DAG's fan-out and join, a stream denser than the service
  rate) every page is one engine call and jobs are carried, a fan-out
  job with one branch decided included, and on every flag family the
  pager accepts (faults, lookahead, adaptive off, init offload modes,
  replica / straggler / price axes, transfers off, sharded devices);
* the paged path under the full scenario stack (portfolio, price
  traces, faults, init offload);
* the ``azure:`` workload family: spec parsing, determinism,
  day-of-week variation, end-to-end equivalence through both engines;
* the ``egress_lookahead`` placement term: engines agree, solo
  portfolios are invariant, and it flips the "myopic portfolio loses
  to solo" regime;
* a hypothesis property: total cost and makespan are invariant to the
  chunk size.
"""
import os

import numpy as np
import pytest

from repro.core import APPS, AppDAG, Stage, simulate
from repro.core import vectorsim
from repro.core.cost import Provider, ProviderPortfolio
from repro.core.vectorsim import simulate_scenarios
from repro.core.workloads import (AzureWorkload, day_counts, parse_workload,
                                  resolve_workload)
from tests.test_vectorsim import FIELDS, assert_equivalent, workload

J = 64


def burst_workload(dag, J, seed, burst=8, gap=1000.0):
    """Bursts of ``burst`` jobs separated by ``gap`` seconds — every
    burst drains long before the next releases, so pages at any chunk
    size >= burst are provably safe (multi-page execution guaranteed)."""
    pred, act = workload(dag, J, seed)
    rng = np.random.default_rng(seed + 77)
    release = (np.arange(J) // burst) * gap + rng.uniform(0.0, 5.0, J)
    return pred, act, release


def poisson_stream(dag, J, seed, gap=0.8):
    """Exponential gaps of mean ``gap`` seconds against stage times of
    about 2 s on two replicas: queues form and span the cuts."""
    pred, act = workload(dag, J, seed)
    rng = np.random.default_rng(seed + 91)
    return pred, act, np.cumsum(rng.exponential(gap, J))


def assert_bit_exact(a, b):
    for fld in FIELDS + ("public_mask",):
        x = np.nan_to_num(np.asarray(getattr(a, fld), float), nan=-1.0)
        y = np.nan_to_num(np.asarray(getattr(b, fld), float), nan=-1.0)
        np.testing.assert_array_equal(x, y, err_msg=f"field {fld}")


def run_vec(dag, pred, act, release, chunk, **kw):
    return simulate_scenarios(
        dag, pred, act, arrivals=release, chunk_jobs=chunk,
        engine="vector", **kw)


# -- chunked vs monolithic, vector engine -------------------------------

@pytest.mark.parametrize("chunk", [J, J // 2, 17, 7])
def test_chunked_bit_exact_vs_monolithic(chunk):
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, J, seed=3)
    kw = dict(c_max_grid=(8.0, 40.0), orders=("spt", "hcf"))
    mono = run_vec(dag, pred, act, release, None, **kw)
    paged = run_vec(dag, pred, act, release, chunk, **kw)
    assert_bit_exact(paged, mono)
    if chunk < J:
        assert vectorsim._LAST_RUN_STATS["pages"] > 1


@pytest.mark.parametrize("chunk", [J, J // 2, 17, 7])
def test_chunked_matches_des(chunk):
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, J, seed=5)
    kw = dict(c_max=20.0, order="spt", arrivals=release, chunk_jobs=chunk)
    d = simulate(dag, pred, act, engine="des", **kw)
    v = simulate(dag, pred, act, engine="vector", **kw)
    assert_equivalent(v, d)
    # DES windowed admission replays the exact monolithic event order
    d_mono = simulate(dag, pred, act, c_max=20.0, order="spt",
                      arrivals=release)
    assert_bit_exact(d, d_mono)


def test_dense_stream_pages_by_carry():
    """A dense stream (every page's work overlaps the next release) is
    exact with no page run twice: the queued jobs are carried."""
    dag = APPS["image"]
    pred, act = workload(dag, 32, seed=9)
    release = np.linspace(0.0, 1.0, 32)  # far denser than the service rate
    mono = run_vec(dag, pred, act, release, None, c_max_grid=(15.0,))
    paged = run_vec(dag, pred, act, release, 4, c_max_grid=(15.0,))
    assert_bit_exact(paged, mono)
    st = vectorsim._LAST_RUN_STATS
    assert st["pages"] > 1 and st["carried_jobs"] > 0
    assert st["engine_calls"] == st["pages"] and st["page_retries"] == 0
    d = simulate_scenarios(dag, pred, act, arrivals=release, chunk_jobs=4,
                           c_max_grid=(15.0,), engine="des")
    assert_equivalent(paged.scenario(0), d.scenario(0))


def _carry_check(dag, pred, act, release, chunk, **kw):
    """Paged == monolithic bit for bit, one engine call a page, jobs
    carried, and == the DES field by field in every scenario."""
    mono = run_vec(dag, pred, act, release, None, **kw)
    paged = run_vec(dag, pred, act, release, chunk, **kw)
    assert_bit_exact(paged, mono)
    st = dict(vectorsim._LAST_RUN_STATS)
    des = simulate_scenarios(dag, pred, act, arrivals=release,
                             chunk_jobs=chunk, engine="des", **kw)
    for s in range(paged.num_scenarios):
        assert_equivalent(paged.scenario(s), des.scenario(s))
    return st


@pytest.mark.parametrize("chunk", [48, 24, 17, 7])
@pytest.mark.parametrize("app", ["image", "video"])
def test_carried_queues_exact(app, chunk):
    """Queues span the cuts on the chain and on the fan-out/join DAG."""
    dag = APPS[app]
    pred, act, release = poisson_stream(dag, 48, seed=31)
    st = _carry_check(dag, pred, act, release, chunk,
                      c_max_grid=(10.0, 30.0), orders=("spt", "hcf"))
    if chunk < 48:
        assert st["carried_jobs"] > 0
        assert st["engine_calls"] == st["pages"] > 1
        assert st["page_retries"] == 0


def test_fanout_job_carried_with_one_branch_decided(monkeypatch):
    """A video job whose DO branch left its queue before the cut, while
    its RI branch was still queued, enters the next page with DO fixed
    and RI back in its queue."""
    dag = APPS["video"]
    pred, act, release = poisson_stream(dag, 48, seed=31)
    calls = []
    orig = vectorsim._dispatch

    def spy(fn, args, S, n_dev):
        out = orig(fn, args, S, n_dev)
        calls.append((args, dict(out)))
        return out

    monkeypatch.setattr(vectorsim, "_dispatch", spy)
    _carry_check(dag, pred, act, release, 7, c_max_grid=(10.0, 30.0),
                 orders=("spt", "hcf"))
    b1, b2 = 1, 2  # DO and RI in topological order: EF, DO, RI, ME
    found = False
    for args, out in calls:
        fixed, qx = args[-1], out.get("qexit")
        if qx is None:
            continue  # the monolithic call
        t_cut = args[vectorsim._Task._IDX_T0][:, :1]  # the page's own cut
        for a, b in ((b1, b2), (b2, b1)):
            requeued = (~np.isnan(fixed[:, :, a, 0])
                        & np.isnan(fixed[:, :, b, 0])
                        & (np.abs(qx[:, :, b]) >= t_cut))
            found |= bool(requeued.any())
    assert found


def _families():
    from repro.core.cost import PriceTrace, demo_portfolio
    pf = demo_portfolio(3)
    spot = [PriceTrace((1.7e-8, 0.8e-8, 2.4e-8), breakpoints=(20.0, 45.0))
            ] * 3
    return {
        "faulty": dict(portfolio=pf, faults=[None, 0.3], retry=None),
        "lookahead": dict(portfolio=pf, egress_lookahead=True),
        "adaptive-off": dict(adaptive=False),
        "init-off": dict(init_phase=False),
        "init-window": dict(init_window=8.0),
        "offload-mask": dict(offload_mask=np.arange(40) % 5 == 0),
        "no-transfers": dict(include_transfers=False),
        "replica-axes": dict(replicas=[[1, 2, 3], [2, 2, 2]],
                             replica_speeds=[None, {(1, 0): 2.5}]),
        "price-traces": dict(portfolio=pf, price_traces=[None, spot]),
    }


@pytest.mark.parametrize("family", sorted(_families()))
def test_every_flag_family_pages_by_carry(family):
    dag = APPS["image"]
    pred, act, release = poisson_stream(dag, 40, seed=41)
    kw = dict(c_max_grid=(12.0,), orders=("spt",), **_families()[family])
    st = _carry_check(dag, pred, act, release, 9, **kw)
    assert st["carried_jobs"] > 0 and st["page_retries"] == 0
    assert st["engine_calls"] == st["pages"] > 1


def test_sharded_pages_match_one_device(tmp_path):
    """The scenario axis sharded over four devices pages by carry to the
    one-device result."""
    from tests._subproc import run_py

    path = tmp_path / "sharded.npz"
    code = f"""
import jax, numpy as np
from repro.core import APPS, vectorsim
from tests.test_streaming import poisson_stream, run_vec
assert jax.local_device_count() == 4
dag = APPS["video"]
pred, act, release = poisson_stream(dag, 48, seed=31)
res = run_vec(dag, pred, act, release, 7, c_max_grid=(10.0, 30.0),
              orders=("spt", "hcf"))
st = vectorsim._LAST_RUN_STATS
np.savez({str(path)!r}, carried=st["carried_jobs"], start=res.start,
         end=res.end, replica=res.replica, public_mask=res.public_mask,
         cost_usd=res.cost_usd)
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_py(f"import sys; sys.path.insert(0, {root!r})\n" + code, devices=4)
    sharded = np.load(path)
    dag = APPS["video"]
    pred, act, release = poisson_stream(dag, 48, seed=31)
    res = run_vec(dag, pred, act, release, 7, c_max_grid=(10.0, 30.0),
                  orders=("spt", "hcf"))
    assert int(sharded["carried"]) == vectorsim._LAST_RUN_STATS[
        "carried_jobs"] > 0
    for f in ("start", "end", "replica", "public_mask", "cost_usd"):
        np.testing.assert_array_equal(sharded[f], getattr(res, f),
                                      err_msg=f)


def test_chunked_full_scenario_stack():
    """Pages carry every axis shipped so far: multi-provider portfolio,
    fault grids + retry, init offload (the external-mask path)."""
    from repro.core.cost import demo_portfolio
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, 48, seed=11)
    kw = dict(c_max_grid=(10.0,), orders=("spt",),
              portfolio=demo_portfolio(3), faults=[0.25], retry=None,
              init_phase=True, arrivals=release)
    mono = simulate_scenarios(dag, pred, act, **kw)
    paged = simulate_scenarios(dag, pred, act, chunk_jobs=16, **kw)
    assert_bit_exact(paged, mono)
    # and the DES agrees at the same chunk size
    d = simulate(dag, pred, act, c_max=10.0, order="spt", faults=0.25,
                 arrivals=release, chunk_jobs=16, engine="des")
    v = simulate(dag, pred, act, c_max=10.0, order="spt", faults=0.25,
                 arrivals=release, chunk_jobs=16, engine="vector")
    assert_equivalent(v, d)


def test_chunk_jobs_validation():
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, 16, seed=1)
    with pytest.raises(ValueError, match="chunk_jobs"):
        simulate(dag, pred, act, arrivals=release, chunk_jobs=0)
    with pytest.raises(ValueError, match="chunk_jobs"):
        simulate_scenarios(dag, pred, act, arrivals=release, chunk_jobs=0)


# -- azure workload family ----------------------------------------------

def test_parse_workload_specs():
    wl = parse_workload("azure:day=tue,scale=1e5,seed=3,noise=0.1")
    assert wl == AzureWorkload(day="tue", scale=100000, seed=3, noise=0.1)
    assert parse_workload("azure") == AzureWorkload()
    assert parse_workload(wl) is wl
    with pytest.raises(ValueError, match="workload family"):
        parse_workload("gcp:scale=10")
    with pytest.raises(ValueError, match="unknown key"):
        parse_workload("azure:jobs=10")
    with pytest.raises(ValueError, match="malformed"):
        parse_workload("azure:day")
    with pytest.raises(ValueError, match="unknown day"):
        parse_workload("azure:day=xyz")
    with pytest.raises(ValueError, match="scale"):
        parse_workload("azure:scale=0")
    with pytest.raises(TypeError):
        parse_workload(42)


def test_workload_sampling_properties():
    dag = APPS["image"]
    wl = "azure:day=wed,scale=500,horizon=3600"
    p1, a1, r1 = resolve_workload(wl, dag)
    p2, a2, r2 = resolve_workload(wl, dag)
    np.testing.assert_array_equal(r1, r2)          # deterministic
    np.testing.assert_array_equal(p1["P_private"], p2["P_private"])
    assert r1.shape == (500,) and p1["P_private"].shape == (500, 3)
    assert (r1 >= 0).all() and (r1 <= 3600).all()
    assert len(np.unique(r1)) == 500               # continuous: tie-free
    assert (a1["P_private"] != p1["P_private"]).any()  # default model error
    _, act0, _ = resolve_workload("azure:scale=50,noise=0", dag)
    # different seeds/days resample
    _, _, r3 = resolve_workload("azure:day=thu,scale=500,horizon=3600", dag)
    assert not np.array_equal(r1, r3)
    # weekend dip scales traffic down, same function set
    assert day_counts(AzureWorkload(day="sat")).sum() \
        < day_counts(AzureWorkload(day="mon")).sum()


def test_workload_excludes_pred():
    dag = APPS["image"]
    pred, act = workload(dag, 8, seed=0)
    with pytest.raises(ValueError, match="not both"):
        simulate_scenarios(dag, pred, act, workload="azure:scale=8")


def test_azure_end_to_end_chunked():
    dag = APPS["image"]
    kw = dict(c_max_grid=(30.0,), orders=("spt",),
              workload="azure:day=tue,scale=300,horizon=600,noise=0")
    mono = simulate_scenarios(dag, None, engine="vector", **kw)
    paged = simulate_scenarios(dag, None, engine="vector", chunk_jobs=64,
                               **kw)
    assert_bit_exact(paged, mono)
    des = simulate_scenarios(dag, None, engine="des", chunk_jobs=64, **kw)
    assert_equivalent(paged.scenario(0), des.scenario(0))


# -- egress lookahead ----------------------------------------------------

def lookahead_setup():
    """Two chains: a->b (public sink, fat edges) and d->e (pinned sink).

    Provider "leaky" has the cheaper compute but a punitive egress rate;
    "safe" is slightly pricier with free egress. Myopic placement puts
    stage a on "leaky" (its selection cost ignores where a's fat output
    must go next) and then pays leaky egress either way at b; lookahead
    charges the candidate's own egress against a's downstream edge and
    routes a to "safe" — while stage d (pinned successor: no egress
    consequence, no lookahead term) still harvests leaky's discount.
    """
    dag = AppDAG(
        "lookahead",
        (Stage("a", 1), Stage("b", 1), Stage("d", 1),
         Stage("e", 1, must_private=True)),
        ((0, 1), (2, 3)))
    rng = np.random.default_rng(21)
    Jn, M = 12, 4
    P_priv = rng.uniform(1.0, 2.0, (Jn, M))
    pred = dict(P_private=P_priv,
                P_public=P_priv * rng.uniform(0.9, 1.1, (Jn, M)),
                upload=np.full((Jn, M), 0.01),
                download=np.full((Jn, M), 0.5))
    safe = Provider("safe", usd_per_gb_ms=3e-8, egress_usd_per_gb=0.0)
    leaky = Provider("leaky", usd_per_gb_ms=2e-8, egress_usd_per_gb=50.0)
    duo = ProviderPortfolio((safe, leaky))
    solo = ProviderPortfolio((safe,))
    return dag, pred, duo, solo


@pytest.mark.parametrize("engine", ["des", "vector"])
def test_lookahead_flips_portfolio_vs_solo(engine):
    dag, pred, duo, solo = lookahead_setup()
    # c_max ~ 0: the init phase offloads every job, every unpinned stage
    def run(pf, look):
        return simulate(dag, pred, c_max=1e-6, engine=engine, portfolio=pf,
                        egress_lookahead=look)
    myopic, aware = run(duo, False), run(duo, True)
    base = run(solo, False)
    assert myopic.cost_usd > base.cost_usd      # the pinned losing regime
    assert aware.cost_usd < base.cost_usd       # lookahead flips it
    # solo portfolios are argmin-invariant under the lookahead term
    assert run(solo, True).cost_usd == base.cost_usd


def test_lookahead_engines_agree():
    dag, pred, duo, _ = lookahead_setup()
    for look in (False, True):
        d = simulate(dag, pred, c_max=1e-6, engine="des", portfolio=duo,
                     egress_lookahead=look)
        v = simulate(dag, pred, c_max=1e-6, engine="vector", portfolio=duo,
                     egress_lookahead=look)
        assert_equivalent(v, d)
    # and on a streamed, chunked run
    rel = (np.arange(12) // 4) * 500.0
    d = simulate(dag, pred, c_max=1e-6, engine="des", portfolio=duo,
                 arrivals=rel, chunk_jobs=4, egress_lookahead=True)
    v = simulate(dag, pred, c_max=1e-6, engine="vector", portfolio=duo,
                 arrivals=rel, chunk_jobs=4, egress_lookahead=True)
    assert_equivalent(v, d)


# -- hypothesis: chunk-size invariance ----------------------------------

def test_chunk_size_invariance_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dag = APPS["image"]
    Jp = 24
    pred, act, release = burst_workload(dag, Jp, seed=2, burst=4, gap=400.0)
    mono = run_vec(dag, pred, act, release, None, c_max_grid=(12.0,))

    @settings(max_examples=8, deadline=None)
    @given(chunk=st.sampled_from([1, 3, 5, 8, 13, 24]))
    def prop(chunk):
        paged = run_vec(dag, pred, act, release, chunk, c_max_grid=(12.0,))
        assert float(np.asarray(paged.cost_usd).sum()) \
            == float(np.asarray(mono.cost_usd).sum())
        assert float(np.asarray(paged.makespan).max()) \
            == float(np.asarray(mono.makespan).max())

    prop()
