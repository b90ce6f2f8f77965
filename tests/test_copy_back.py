"""The engine's copy-back leaves on the device what the host can derive.

A monolithic call emits none of the pager's outputs (``qexit``,
``clocks``: only a page's engine does) and copies back none of the
outputs its static flags make constant (the fault
counters unless faulty, ``queue_wait`` unless capped, ``cold`` unless
cold), and copies the index outputs as int32; the host rebuilds and
widens them (``vectorsim._emitted`` / ``_restored``). Each static-flag
family runs once as shipped and once through an engine that emits every
output at its full width, as before the pruning: every result field must
agree, value and dtype. The scenario axis sharded over four virtual
devices (the pmap branch) must give the one-device result.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import APPS
from repro.core import vectorsim
from repro.core.coldstart import ColdStartModel
from repro.core.cost import demo_portfolio
from repro.core.dag import matrix_app
from repro.core.faults import RetryPolicy
from repro.core.vectorsim import sweep_scenarios
from tests.strategies import chaos_model
from tests.test_coldstart import congested
from tests.test_streaming import burst_workload
from tests.test_vectorsim import grid_for, workload

J = 12


def _plain():
    dag = APPS["video"]
    pred, act = workload(dag, J, seed=21)
    return [dict(dag=dag, pred=pred, act=act, orders=("spt", "hcf"),
                 c_max_grid=grid_for(dag, pred, (0.3, 0.9)))], \
        dict(portfolio=demo_portfolio(3))


def _faulty():
    dag = APPS["video"]
    pred, act = workload(dag, J, seed=22)
    return [dict(dag=dag, pred=pred, act=act, orders=("spt", "hcf"),
                 c_max_grid=grid_for(dag, pred, (0.25, 0.6)),
                 faults=[None, 0.3, chaos_model(dag, J, 22)])], \
        dict(portfolio=demo_portfolio(3),
             retry=RetryPolicy(max_attempts=3, backoff_s=0.3))


def _loaded(**kw):
    dag = matrix_app(replicas=2)
    pred, arrivals = congested(dag, J=10, seed=23)
    return [dict(dag=dag, pred=pred, orders=("spt", "hcf"),
                 c_max_grid=(4.0, 8.0), arrivals=arrivals)], kw


def _paged():
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, 40, seed=24)
    return [dict(dag=dag, pred=pred, act=act, orders=("spt", "hcf"),
                 c_max_grid=(8.0, 40.0), arrivals=release)], \
        dict(chunk_jobs=9)


CS = ColdStartModel(warm_up_s=0.5, keep_alive_s=1.0, scale_to_zero=True)

#: family: (tasks and sweep keywords, outputs a call copies back, a check
#: that the family's own outputs carry information)
FAMILIES = {
    "plain": (_plain, 10, lambda r: (r.provider >= 0).any()),
    "faulty": (_faulty, 13, lambda r: r.failed.sum() > 0),
    "capped": (lambda: _loaded(concurrency=1), 11,
               lambda r: (r.queue_wait > 0).any()),
    "cold": (lambda: _loaded(coldstart=CS), 11, lambda r: r.cold.any()),
    "paged": (_paged, 12, lambda r: vectorsim._LAST_RUN_STATS["pages"] > 1),
}


def _fresh_engines():
    vectorsim._engine_fn.cache_clear()
    vectorsim._build_engine.cache_clear()


def _sweep(tasks, kw):
    (res,) = sweep_scenarios(tasks, **kw)
    st = vectorsim._LAST_RUN_STATS
    return res, st["d2h_arrays"] / st["engine_calls"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_pruned_copy_back_gives_the_full_outputs(family, monkeypatch):
    make, n_copied, informative = FAMILIES[family]
    tasks, kw = make()
    res, copied = _sweep(tasks, kw)
    assert informative(res)
    assert copied == n_copied

    # the same engine emitting every output at its full width
    monkeypatch.setattr(vectorsim, "_emitted", lambda out, **_: out)
    _fresh_engines()
    try:
        full, full_copied = _sweep(tasks, kw)
    finally:
        monkeypatch.undo()
        _fresh_engines()
    assert full_copied > copied

    for f in dataclasses.fields(res):
        a, b = getattr(res, f.name), getattr(full, f.name)
        if b is None or isinstance(b, (tuple, list)):
            assert a == b, f.name
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, f.name
        np.testing.assert_array_equal(a, b, err_msg=f.name)


def test_sharded_call_gives_the_one_device_outputs(tmp_path):
    """The scenario axis sharded over four devices (the pmap branch of
    `_dispatch`: packed hand-off, copy-back, reshape and gather) gives
    the one-device result, field for field, with as many arrays handed
    over and copied per call."""
    from tests._subproc import run_py

    path = tmp_path / "sharded.npz"
    code = f"""
import dataclasses, jax, numpy as np
from repro.core import vectorsim
from tests.test_copy_back import _plain, _sweep
assert jax.local_device_count() == 4
res, copied = _sweep(*_plain())
st = vectorsim._LAST_RUN_STATS
arrays = {{f.name: np.asarray(getattr(res, f.name))
          for f in dataclasses.fields(res)
          if isinstance(getattr(res, f.name), np.ndarray)}}
np.savez({str(path)!r}, copied=copied,
         handed=st["h2d_arrays"] / st["engine_calls"], **arrays)
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_py(f"import sys; sys.path.insert(0, {root!r})\n" + code, devices=4)
    sharded = np.load(path)
    res, copied = _sweep(*_plain())
    assert float(sharded["copied"]) == copied == 10
    st = vectorsim._LAST_RUN_STATS
    assert float(sharded["handed"]) == st["h2d_arrays"] == 2
    for f in dataclasses.fields(res):
        if f.name not in sharded:
            continue
        a = np.asarray(getattr(res, f.name))
        assert sharded[f.name].dtype == a.dtype, f.name
        np.testing.assert_array_equal(sharded[f.name], a, err_msg=f.name)
