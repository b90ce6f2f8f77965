"""An engine call's arguments cross to the device in one buffer per dtype.

``vectorsim._pack`` lays a call's arguments, each ``[S, ...]``, side by
side in one ``[S, L]`` host buffer per dtype, scenario axis leading; the
compiled program cuts them back (``_unpacked``) with static slices and
reshapes before ``vmap(run_one)``. The suite pins:

* the round trip, bit for bit: NaN and infinite floats, bools, an
  argument of no width and one scalar per scenario;
* each static-flag family's results through the packed hand-off against
  those of one buffer per argument (the hand-off before packing), for
  the ``loop`` and ``scan`` engines, monolithic and paged: every field,
  value and dtype; and the counters of both: two arrays handed over per
  call against one per argument, the same bytes;
* the scenario axis sharded over four virtual devices, padded to a
  multiple of them (the pmap branch of ``_dispatch``), against the
  one-device result.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.core import APPS
from repro.core import vectorsim
from repro.core.vectorsim import sweep_scenarios
from tests.test_copy_back import FAMILIES, _fresh_engines
from tests.test_vectorsim import grid_for, workload


def _args(S=3):
    rng = np.random.default_rng(5)
    x = rng.standard_normal((S, 4, 3))
    x[0, 1, 2], x[1, 0, 0], x[2, 3, 1] = np.nan, np.inf, -np.inf
    return (x, rng.random((S, 5)) < 0.5, np.full(S, 7.25),
            np.zeros((S, 0)), np.full((S, 2, 2), np.nan),
            np.ones((S, 3), dtype=bool), rng.standard_normal((S, 2, 1, 2)))


def test_pack_round_trip_is_bit_exact():
    args = _args()
    layout, bufs = vectorsim._pack(args)
    assert [(b.dtype, b.shape) for b in bufs] == [
        (np.float64, (3, 12 + 1 + 0 + 4 + 4)), (np.bool_, (3, 5 + 3))]
    assert all(b.flags.c_contiguous for b in bufs)
    assert layout == ((0, 0, (4, 3)), (1, 0, (5,)), (0, 12, ()),
                      (0, 13, (0,)), (0, 13, (2, 2)), (1, 5, (3,)),
                      (0, 17, (2, 1, 2)))
    with jax.enable_x64(True):
        got = jax.jit(vectorsim._unpacked, static_argnums=0)(
            layout, [jax.numpy.asarray(b) for b in bufs])
        for a, g in zip(args, got):
            g = np.asarray(g)
            assert g.dtype == a.dtype and g.shape == a.shape
            # bit for bit, NaN payloads included
            assert g.tobytes() == a.tobytes()


def _per_array(args):
    """The hand-off before packing: one buffer per argument."""
    S = args[0].shape[0]
    return (tuple((i, 0, a.shape[1:]) for i, a in enumerate(args)),
            [a.reshape(S, -1) for a in args])


def _sweep(tasks, kw, impl, monkeypatch):
    """(result, counters, arguments of every engine call)."""
    seen = []
    orig = vectorsim._dispatch

    def spy(fn, args, S, n_dev):
        seen.append(args)
        return orig(fn, args, S, n_dev)

    with monkeypatch.context() as mp:
        mp.setattr(vectorsim, "_dispatch", spy)
        (res,) = sweep_scenarios(tasks, engine_impl=impl, **kw)
    return res, dict(vectorsim._LAST_RUN_STATS), seen


@pytest.mark.parametrize("impl", ["loop", "scan"])
@pytest.mark.parametrize("family", ["plain", "faulty", "capped", "paged"])
def test_packed_hand_off_gives_the_per_array_outputs(family, impl,
                                                     monkeypatch):
    make, _, informative = FAMILIES[family]
    tasks, kw = make()
    res, st, calls = _sweep(tasks, kw, impl, monkeypatch)
    assert informative(res)
    assert st["engine_calls"] == len(calls) > 0
    assert st["h2d_arrays"] == 2 * len(calls)
    assert st["h2d_bytes"] == sum(int(a.nbytes) for args in calls
                                  for a in args)
    # a monolithic call hands over 24 arguments, a page one more
    # (``fixed``), a loaded family 5 (the load args), a faulty one 6
    n_args = dict(plain=24, paged=25, capped=29, faulty=30)[family]
    assert {len(args) for args in calls} == {n_args}

    monkeypatch.setattr(vectorsim, "_pack", _per_array)
    _fresh_engines()
    try:
        ref, ref_st, _ = _sweep(tasks, kw, impl, monkeypatch)
    finally:
        monkeypatch.undo()
        _fresh_engines()
    assert ref_st["h2d_arrays"] == n_args * ref_st["engine_calls"]
    assert ref_st["h2d_bytes"] == st["h2d_bytes"]

    for f in dataclasses.fields(res):
        a, b = getattr(res, f.name), getattr(ref, f.name)
        if b is None or isinstance(b, (tuple, list)):
            assert a == b, f.name
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, f.name
        np.testing.assert_array_equal(a, b, err_msg=f.name)


def _padded():
    """Video x {spt, hcf} x 3 deadlines: 6 scenarios, so four devices
    take 8 lanes, two of them repeats."""
    dag = APPS["video"]
    pred, act = workload(dag, 12, seed=25)
    return [dict(dag=dag, pred=pred, act=act, orders=("spt", "hcf"),
                 c_max_grid=grid_for(dag, pred, (0.3, 0.6, 0.9)))]


def test_sharded_padded_call_gives_the_one_device_outputs(tmp_path):
    path = tmp_path / "padded.npz"
    code = f"""
import dataclasses, jax, numpy as np
from repro.core import vectorsim
from tests.test_hand_off import _padded
assert jax.local_device_count() == 4
(res,) = vectorsim.sweep_scenarios(_padded())
st = vectorsim._LAST_RUN_STATS
arrays = {{f.name: np.asarray(getattr(res, f.name))
          for f in dataclasses.fields(res)
          if isinstance(getattr(res, f.name), np.ndarray)}}
np.savez({str(path)!r}, h2d_arrays=st["h2d_arrays"],
         h2d_bytes=st["h2d_bytes"], **arrays)
"""
    from tests._subproc import run_py

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run_py(f"import sys; sys.path.insert(0, {root!r})\n" + code, devices=4)
    sharded = np.load(path)
    (res,) = sweep_scenarios(_padded())
    st = vectorsim._LAST_RUN_STATS
    assert int(sharded["h2d_arrays"]) == st["h2d_arrays"] == 2
    # the two repeated lanes cross too
    assert int(sharded["h2d_bytes"]) == st["h2d_bytes"] * 8 // 6
    for f in dataclasses.fields(res):
        if f.name not in sharded:
            continue
        a = np.asarray(getattr(res, f.name))
        assert sharded[f.name].dtype == a.dtype, f.name
        np.testing.assert_array_equal(sharded[f.name], a, err_msg=f.name)
