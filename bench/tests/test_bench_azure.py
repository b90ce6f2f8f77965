"""The replayed Azure Functions night (`azure.day`) on the CPU: the
recorded night as the configuration states it, a paged run that carries
jobs and is `correct`, a warm-up that compiles every page size the
window reaches, a carried queue dropped at a cut caught by the check,
and the readers of the paging counters."""
import numpy as np
import pytest

from bench import generate, harness, spec
from bench.tests.test_bench_correct import BM, run, small

CELL = "azure.day"


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def _config():
    return spec.config(BM, "azure-functions-2019-sample")


def test_the_recorded_night_is_the_configured_stretch():
    config = _config()
    wl = config["workload"]
    kind = spec.kind("azure")
    act, release = kind.day(wl, 3)
    assert release.shape == (wl["day_invocations"],) == (269_157,)
    # the sample's own hourly counts, no dilution: the night's hours
    # hold about the 6.2-6.8k invocations the sample records
    hours = np.histogram(release, bins=24, range=(0, 86400))[0]
    assert 6_000 < hours[2] < 6_500
    act, release = kind.stretch(config, "image")
    assert release.shape == (wl["invocations"],) == (4_096,)
    assert (np.diff(release) > 0).all()
    assert release[0] >= wl["stretch_start_s"]
    assert release[-1] - release[0] == pytest.approx(wl["horizon_s"],
                                                     abs=1e-3)
    entry = spec.workload(BM, CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "azure-night"


def test_queries_replay_one_night_against_three_deadline_tiers():
    config = _config()
    mix = spec.traffic("azure-night")
    qs = generate.build(config, mix, 2 ** 31 + 99, 3)
    act0 = qs[0].tasks[0]["act"]
    for q in qs:
        (t,) = q.tasks
        assert q.chunk_jobs == 256
        assert q.work == dict(scenarios=6, stages=73_728,
                              invocations=6 * 4_096)
        assert t["act"] is act0  # what ran is the same every query
        for c, (lo, hi) in zip(t["c_max_grid"], mix["deadline_tiers_s"]):
            assert lo <= c <= hi
    assert qs[0].tasks[0]["pred"]["P_private"].tobytes() \
        != qs[1].tasks[0]["pred"]["P_private"].tobytes()


def test_small_configuration_is_correct():
    out = run(CELL)
    assert out["correct"], out["compared"]
    assert out["compared"]["decision_mismatches"]["value"] == 0


def _engine_sizes(monkeypatch, queries, config):
    """Job counts of the engine programs each query calls."""
    from bench.system import System
    from repro.core import vectorsim

    sizes = []
    orig = vectorsim._engine_fn

    def spy(*key, **kw):
        sizes[-1].add(key[2])
        return orig(*key, **kw)

    monkeypatch.setattr(vectorsim, "_engine_fn", spy)
    system = System(config)
    for q in queries:
        sizes.append(set())
        system.run(q)
    return sizes


def test_warm_up_covers_every_page_size_the_window_reaches(monkeypatch):
    config, mix = small(CELL)
    ladder = config["workload"]["page_families"]
    warm = generate.warmup(config, mix, 5)
    assert [q.chunk_jobs for q in warm] == ladder
    window = generate.build(config, mix, 5, 4)
    sizes = _engine_sizes(monkeypatch, warm + window, config)
    warmed = set().union(*sizes[:len(warm)])
    assert set(ladder) <= warmed
    assert set().union(*sizes[len(warm):]) <= warmed


def test_carried_queue_dropped_at_a_cut_is_not_correct(monkeypatch):
    """Every job counts as committed at every cut: the jobs still queued
    there keep their page's answers and never meet the next page's."""
    from repro.core import vectorsim

    from bench.system import System

    config, mix = small(CELL)
    q = generate.build(config, mix, 11, 1)[0]
    res = System(config).run(q)
    assert vectorsim._LAST_RUN_STATS["carried_jobs"] > 0
    picks = [(0, sc) for sc in q.scenarios]
    sound, _ = harness.check(config, [q], [res], picks)
    assert sound["decision_mismatches"] == 0

    monkeypatch.setattr(vectorsim, "_decided",
                        lambda task, qexit, t_cut: np.ones(qexit.shape,
                                                           dtype=bool))
    res = System(config).run(q)
    assert vectorsim._LAST_RUN_STATS["carried_jobs"] == 0
    numbers, wrong = harness.check(config, [q], [res], picks)
    assert numbers["decision_mismatches"] > 0 and wrong == 1


def _run(stats):
    return harness.Run(cell=CELL, setup_s=1.0, window_s=2.0,
                       records=[dict(latency_s=0.1, work=dict(stages=1),
                                     stats=dict(run=s, page={}))
                                for s in stats])


#: two paged queries, as `System.stats` gives them
PAGED = [dict(engine_calls=17, pages=17, carried_jobs=16, page_retries=0,
              carry_s=0.004),
         dict(engine_calls=17, pages=17, carried_jobs=48, page_retries=0,
              carry_s=0.006)]


@pytest.mark.parametrize("name,expected", [
    ("page_calls", 17.0),
    ("carried_jobs", 2.0),   # 64 jobs over 32 cuts
    ("host_carry_ms", 5.0),
])
def test_paging_reader_gives_its_number(name, expected):
    assert spec.reader(name)(_run(PAGED)) == pytest.approx(expected,
                                                           rel=1e-12)


def test_paging_readers_on_the_parent_record():
    """The parent pages by retry: it counts engine calls but neither
    carried jobs nor a carry span, and reads as no value there."""
    parent = [dict(engine_calls=26, h2d_s=0.01, wait_s=0.2)] * 2
    assert spec.reader("page_calls")(_run(parent)) == 26.0
    assert spec.reader("carried_jobs")(_run(parent)) is None
    assert spec.reader("host_carry_ms")(_run(parent)) is None


def test_paging_metrics_are_entries_of_the_cell():
    per_layer = {m["name"]: m for m in BM["per_layer"]}
    for name in ("page_calls", "carried_jobs", "host_carry_ms"):
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["layer"] == "paging"
        assert m["moves"] == "sweep_stages_per_s"
    for name in ("host_prep_ms", "device_idle_pct.sweep"):
        assert per_layer[name]["workloads"][-1] == CELL
