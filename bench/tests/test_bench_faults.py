"""Faults planted under the timed path make `correct` come out false.

Each test breaks the engine's output where it is produced (wrapping
`vectorsim._dispatch`) and drives the rest of a run as the benchmark
does, with its look for a chip skipped; or breaks the state a paged run
carries from page to page, which the check of a paged stream catches.
"""
import numpy as np
import pytest

from bench import compare, generate, harness, spec
from bench.tests.test_bench_correct import BM, CELLS, run

#: engine outputs that carry results (the rest steer the paging)
RESULT_KEYS = ("public_mask", "start", "end", "completion", "replica",
               "provider", "cost_j", "init_off", "segment", "attempts")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def _wrap_dispatch(monkeypatch, change):
    from repro.core import vectorsim

    orig = vectorsim._dispatch

    def broken(fn, args, S, n_dev):
        out = orig(fn, args, S, n_dev)
        for k in RESULT_KEYS:
            if k in out:
                out[k] = change(np.array(out[k]), S)
        return out

    monkeypatch.setattr(vectorsim, "_dispatch", broken)


def _flip_first_job(x, S):
    if x.ndim >= 2:
        x[:, 0] = np.logical_not(x[:, 0]) if x.dtype == bool else x[:, 0] + 1
    return x


def _half_left_out(x, S):
    """The second half of the batch never computed: it repeats the
    first half's answers (scenarios, or jobs where there is one)."""
    axis = 0 if S > 1 else 1
    if x.ndim <= axis:
        return x
    n = x.shape[axis]
    h = n // 2
    idx = [slice(None)] * x.ndim
    src = list(idx)
    idx[axis], src[axis] = slice(h, 2 * h), slice(0, h)
    x[tuple(idx)] = x[tuple(src)]
    return x


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(cell, monkeypatch):
    _wrap_dispatch(monkeypatch, _flip_first_job)
    out = run(cell)
    assert not out["correct"]
    assert out["compared"]["decision_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(cell, monkeypatch):
    _wrap_dispatch(monkeypatch, _half_left_out)
    out = run(cell)
    assert not out["correct"]


def _stream(seed):
    """A paged stream of the image application: 200 jobs released about
    every 6 s and paged at 32 jobs, planned with an init window of 0
    (the plan sees only the jobs released at the start). A page ends
    only where every queue has drained, so the carried clocks change an
    answer only where a replica still runs when the next page's jobs
    reach it: the sink stage is made four times as long as the others,
    which keeps its replicas busy across page boundaries."""
    config = generate.small(spec.config(BM, "skedulix-paper"))
    config["apps"] = {"image": dict(config["apps"]["image"], jobs=200)}
    config["scheduler"]["init_window_s"] = 0.0
    rng = np.random.default_rng(seed)
    (task,), _ = spec.kind("fig4").tasks(config, dict(orders=["spt"]), rng,
                                         ["image"])
    for d in ("pred", "act"):
        P = task[d]["P_private"].copy()
        P[:, -1] *= 4.0
        task[d] = dict(task[d], P_private=P)
    task.update(release=np.cumsum(rng.exponential(6.0, 200)),
                c_max_grid=(60.0,))
    return config, generate.query(config, [task], 32)


def _check_stream(config, q):
    from bench.system import System
    from repro.core import vectorsim

    res = System(config).run(q)
    assert vectorsim._LAST_PAGE_STATS["pages"] > 1
    numbers, _ = harness.check(config, [q], [res],
                               [(0, sc) for sc in q.scenarios])
    return numbers


def test_paged_state_left_unchanged(monkeypatch):
    """Every page starts from the initial replica clocks instead of the
    clocks the previous page left: a paged stream's answers change."""
    from repro.core import vectorsim

    config, q = _stream(5)
    sound = _check_stream(config, q)
    assert compare.verdict(sound, config["correct"]["limits"]), sound

    orig = vectorsim._Task.page_args

    def stale(self, idx, J_fam, init_mask, clocks):
        return orig(self, idx, J_fam, init_mask,
                    self.args[self._IDX_CLOCK0])

    monkeypatch.setattr(vectorsim._Task, "page_args", stale)
    assert _check_stream(config, q)["decision_mismatches"] > 0
