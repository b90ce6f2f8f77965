"""The reader of the engine's `d2h_arrays` counter, on hand-built runs:
arrays copied back per engine call, and nothing where the program keeps
no such counter."""
import pytest

from bench import harness, spec


def _run(stats):
    return harness.Run(cell="paper.grid", setup_s=1.0, window_s=2.0,
                       records=[dict(latency_s=0.1, work=dict(stages=1),
                                     stats=dict(run=s, page={}))
                                for s in stats])


def test_reads_arrays_per_engine_call():
    """Two queries: one call of 10 arrays, then two calls of 10 each."""
    run = _run([dict(engine_calls=1, d2h_arrays=10),
                dict(engine_calls=2, d2h_arrays=20)])
    assert spec.reader("d2h_arrays")(run) == pytest.approx(10.0, rel=1e-12)


def test_a_paged_call_counts_its_carry():
    """A page copies the pager's two carry outputs besides the ten."""
    run = _run([dict(engine_calls=3, d2h_arrays=36)])
    assert spec.reader("d2h_arrays")(run) == pytest.approx(12.0, rel=1e-12)


@pytest.mark.parametrize("stats", [
    dict(prep_s=0.002, engine_s=0.05, finalize_s=0.001, impl="scan"),
    dict(engine_calls=1, d2h_bytes=456_480, loop_trips=300),
], ids=["phase-spans-only", "no-array-counter"])
def test_gives_nothing_without_the_counter(stats):
    """A program without the counter (the parent of this metric) reads
    as no value, not as an error."""
    assert spec.reader("d2h_arrays")(_run([stats] * 2)) is None


def test_is_a_transfer_metric_of_both_cells():
    (m,) = [m for m in spec.benchmark()["per_layer"]
            if m["name"] == "d2h_arrays"]
    assert m["layer"] == "transfer" and m["better"] == "lower"
    assert m["moves"] == "sweep_stages_per_s"
    assert sorted(m["workloads"]) == ["paper.grid", "paper.whatif"]
