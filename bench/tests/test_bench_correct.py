"""`correct` on the CPU at a small size: a sound run passes, and the
control (the timed path fed float32-rounded inputs) fails.

The harness runs as the benchmark runs it, with its look for a chip
skipped and the persistent compilation cache left off."""
import pytest

from bench import generate, harness, spec

BM = spec.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def small(cell):
    """(config, mix) of `cell` at a size a unit test can run."""
    entry = spec.workload(BM, cell)
    config = generate.small(spec.config(BM, entry["config"]))
    mix = dict(spec.traffic(entry["traffic"]))
    mix["queries_per_s_max"] = 400
    mix["check_scenarios"] = 60
    return config, mix


def run(cell, seed=2 ** 31 + 7, seconds=0.3, config=None, **kw):
    small_config, mix = small(cell)
    return harness.run_cell(cell, seed, seconds, False, check_device=False,
                            config=config or small_config, mix=mix, **kw)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    for name, c in out["compared"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = run(cell, control=True)
    assert not out["correct"], out["compared"]
    assert out["failed"] >= 1
