"""The readers of the engine's own spans and counters, on hand-built runs:
each gives the number its file says, and nothing where the program keeps
no such span or counter."""
import pytest

from bench import harness, spec

#: two queries' records, as `System.stats` gives them
STATS = [
    dict(h2d_s=0.002, launch_s=0.010, wait_s=0.040, d2h_s=0.006,
         h2d_bytes=469_550, d2h_bytes=456_480, engine_calls=1,
         loop_trips=300, lane_trips=2_400, lane_slots=3_000),
    dict(h2d_s=0.004, launch_s=0.012, wait_s=0.050, d2h_s=0.008,
         h2d_bytes=3 * 2 ** 20, d2h_bytes=2 ** 20, engine_calls=2,
         loop_trips=900, lane_trips=5_600, lane_slots=9_000),
]

EXPECTED = {
    "host_h2d_ms": 3.0,
    "host_launch_ms": 11.0,
    "engine_wait_ms": 45.0,
    "host_d2h_ms": 7.0,
    "transfer_mib": (469_550 + 456_480 + 4 * 2 ** 20) / 2 / 2 ** 20,
    "lane_occupancy_pct": 100.0 * 8_000 / 12_000,
    "engine_trips": 1_200 / 3,
}


def _run(stats):
    return harness.Run(cell="paper.whatif", setup_s=1.0, window_s=2.0,
                       records=[dict(latency_s=0.1, work=dict(stages=1),
                                     stats=dict(run=s, page={}))
                                for s in stats])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_its_number(name):
    assert spec.reader(name)(_run(STATS)) == pytest.approx(EXPECTED[name],
                                                           rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_without_the_programs_counters(name):
    """A program that keeps only the phase spans (the parent of these
    counters) reads as no value, not as an error."""
    old = [dict(prep_s=0.002, engine_s=0.05, finalize_s=0.001,
                impl="scan")] * 2
    assert spec.reader(name)(_run(old)) is None


def test_every_engine_reader_is_a_per_layer_metric_of_both_cells():
    per_layer = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in EXPECTED:
        assert sorted(per_layer[name]["workloads"]) == ["paper.grid",
                                                        "paper.whatif"]
        assert per_layer[name]["moves"] == "sweep_stages_per_s"
