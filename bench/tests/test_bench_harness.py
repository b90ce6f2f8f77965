"""The harness's pieces on the CPU: traffic, comparison, lookup by name,
and the refusal to run without an accelerator."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import compare, generate, reference, spec

BM = spec.benchmark()


def _cells():
    return [(w["name"], generate.small(spec.config(BM, w["config"])),
             spec.traffic(w["traffic"])) for w in BM["workloads"]]


def _inputs(q):
    return [np.concatenate([np.ravel(t[d][k]) for d in ("pred", "act")
                            for k in generate.INPUT_KEYS]
                           + ([t["release"]] if t["release"] is not None
                              else []))
            for t in q.tasks]


@pytest.mark.parametrize("cell,config,mix", _cells(),
                         ids=[w["name"] for w in BM["workloads"]])
def test_traffic_is_deterministic_per_seed_and_every_query_is_new(
        cell, config, mix):
    seed = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a = generate.build(config, mix, seed, 4)
    b = generate.build(config, mix, seed, 4)
    for qa, qb in zip(a, b):
        assert qa.work == qb.work
        assert qa.scenarios == qb.scenarios
        for x, y in zip(_inputs(qa), _inputs(qb)):
            np.testing.assert_array_equal(x, y)
    queries = a + generate.warmup(config, mix, seed)
    seen = set()
    for q in queries:
        for x in _inputs(q):
            key = x.tobytes()
            assert key not in seen, "two queries share an input"
            seen.add(key)
    other = generate.build(config, mix, seed + 1, 1)[0]
    assert _inputs(other)[0].tobytes() not in seen
    assert generate.build(config, mix, -seed, 1)[0].work == a[0].work


def test_grid_query_has_the_paper_grid():
    config = spec.config(BM, "skedulix-paper")
    q = generate.build(config, spec.traffic("grid"), 7, 1)[0]
    assert q.work == dict(scenarios=240, stages=136_000,
                          invocations=240 * 550 // 3)
    # every query reaches the replica bound, so one shape family serves
    # all of them
    assert max(max(max(r) for r in t["replicas"]) for t in q.tasks) == 4


def test_whatif_queries_take_the_apps_in_turn():
    config = spec.config(BM, "skedulix-paper")
    qs = generate.build(config, spec.traffic("whatif"), 7, 6)
    assert [q.tasks[0]["app"] for q in qs] == ["image", "matrix", "video"] * 2
    assert all(q.work["scenarios"] == 10 for q in qs)


def test_comparison_flags_a_perturbed_field():
    config = generate.small(spec.config(BM, "skedulix-paper"))
    q = generate.build(config, spec.traffic("whatif"), 3, 1)[0]
    t = q.tasks[0]
    app = config["apps"][t["app"]]
    ref = reference.simulate(app, t["pred"], t["act"], t["c_max_grid"][0],
                             "spt", config["public_cloud"])
    assert compare.compare(dict(ref), ref) == dict(decision_mismatches=0,
                                                   float_gap=0.0)
    for field, change in (("replica", lambda x: x + 1),
                          ("public_mask", lambda x: ~x),
                          ("end", lambda x: x * (1 + 1e-6)),
                          ("n_offloaded_stages", lambda x: x + 1)):
        got = dict(ref)
        got[field] = change(np.asarray(ref[field]))
        out = compare.compare(got, ref)
        assert not compare.verdict(out, config["correct"]["limits"]), field
    got = dict(ref, start=np.where(np.eye(*ref["start"].shape, dtype=bool),
                                   np.nan, ref["start"]))
    assert compare.compare(got, ref)["decision_mismatches"] > 0
    got = dict(ref, completion=ref["completion"][:-1])
    assert compare.compare(got, ref)["decision_mismatches"] > 0


def test_every_piece_is_found_by_name():
    for c in BM["configs"]:
        cfg = spec.config(BM, c["name"])
        assert cfg["name"] == c["name"]
        for key in ("source", "guarantee", "assumed", "reduced", "correct"):
            assert key in cfg, (c["name"], key)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        kind = spec.kind(cfg["workload"]["kind"])
        for fn in ("tasks", "warm", "small"):
            assert callable(getattr(kind, fn)), (cfg["workload"]["kind"], fn)
    for w in BM["workloads"]:
        assert spec.workload(BM, w["name"]) is w
        assert "queries_per_s_max" in spec.traffic(w["traffic"])
        for traced in (False, True):
            names = [m["name"] for m in spec.metrics(BM, w["name"], traced)]
            assert names, (w["name"], traced)
            for n in names:
                assert callable(spec.reader(n))
    assert "setup_s" in [m["name"] for m in BM["end_to_end"]]
    with pytest.raises(KeyError):
        spec.workload(BM, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.kind("no_such_kind")


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    cells = [w["name"] for w in BM["workloads"]]
    for m in BM["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_run_refuses_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "paper.whatif", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
