"""The reader of the engine's `h2d_arrays` counter, on hand-built runs:
arrays handed to the device per engine call, and nothing where the
program keeps no such counter."""
import pytest

from bench import harness, spec


def _run(stats, cell="paper.grid"):
    return harness.Run(cell=cell, setup_s=1.0, window_s=2.0,
                       records=[dict(latency_s=0.1, work=dict(stages=1),
                                     stats=dict(run=s, page={}))
                                for s in stats])


def test_reads_arrays_per_engine_call():
    """Two queries: one call of 2 buffers, then two calls of 2 each."""
    run = _run([dict(engine_calls=1, h2d_arrays=2),
                dict(engine_calls=2, h2d_arrays=4)])
    assert spec.reader("h2d_arrays")(run) == pytest.approx(2.0, rel=1e-12)


def test_a_paged_query_counts_every_page():
    """A paged query of 17 pages hands over its buffers once a page."""
    run = _run([dict(engine_calls=17, h2d_arrays=34, pages=17)] * 3,
               cell="azure.day")
    assert spec.reader("h2d_arrays")(run) == pytest.approx(2.0, rel=1e-12)


@pytest.mark.parametrize("stats", [
    dict(prep_s=0.002, engine_s=0.05, finalize_s=0.001, impl="scan"),
    dict(engine_calls=1, h2d_bytes=366_080, d2h_arrays=10),
], ids=["phase-spans-only", "no-array-counter"])
def test_gives_nothing_without_the_counter(stats):
    """A program without the counter (the parent of this metric) reads
    as no value, not as an error."""
    assert spec.reader("h2d_arrays")(_run([stats] * 2)) is None


def test_is_a_transfer_metric_of_every_cell():
    (m,) = [m for m in spec.benchmark()["per_layer"]
            if m["name"] == "h2d_arrays"]
    assert m["layer"] == "transfer" and m["better"] == "lower"
    assert m["moves"] == "sweep_stages_per_s"
    assert sorted(m["workloads"]) == ["azure.day", "paper.grid",
                                      "paper.whatif"]
