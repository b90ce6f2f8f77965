"""Compile the vector engine and its Pallas kernels for a TPU v5e, chip-free.

The TPU compiler ships with jaxlib's TPU plug-in and compiles for a
described, unattached ``v5e:2x2`` topology, so what XLA:TPU or Mosaic
would refuse on the chip fails here, on a CPU-only machine. Nothing
runs: these tests say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so the file must
collect the same tests in every pytest-xdist worker, and only the worker
that runs it loads the library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import APPS, demo_portfolio, sweep_scenarios
from repro.core import vectorsim
from repro.kernels import acd_sweep, dispatch

from .test_vectorsim import grid_for, workload

J = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU plug-in, or its lock is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _shapes(args, sharding):
    return [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=sharding) for a in args]


def test_scan_engine_compiles_for_v5e(one_chip, cache_off, monkeypatch):
    """The program the chip runs for the ``scan`` engine at a Fig-4 shape
    family (matrix app, spt/hcf x 5 deadlines, 3 providers, J = 4096):
    the packed arguments, cut back and fed to vmap(run_one), compile
    under x64."""
    # the engine's backend-aware choices (inner-loop impl, prefix sums)
    # read jax.default_backend(); steer them to their TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    caught = {}

    class Caught(Exception):
        pass

    build = vectorsim._engine_fn.__wrapped__

    def engine_fn(*key, **kw):
        # a fresh build: a cached one may hold the CPU branches
        caught["key"] = key
        return build(*key, **kw)

    def capture(fn, args, S, n_dev):
        caught.update(fn=fn, args=args)
        raise Caught

    # capture the program and the args of the real call path
    monkeypatch.setattr(vectorsim, "_engine_fn", engine_fn)
    monkeypatch.setattr(vectorsim, "_dispatch", capture)
    dag = APPS["matrix"]
    pred, act = workload(dag, J, seed=0)
    with pytest.raises(Caught):
        sweep_scenarios([dict(dag=dag, pred=pred, act=act,
                              c_max_grid=grid_for(dag, pred, (0.45, 0.575,
                                                              0.7, 0.825,
                                                              0.95)),
                              orders=("spt", "hcf"))],
                        portfolio=demo_portfolio(3))
    key = caught["key"]
    assert key[2] == J and key[-2:] == (1, "scan")
    layout, bufs = vectorsim._pack(caught["args"])
    assert len(bufs) == 2
    with jax.enable_x64(True):
        compiled = caught["fn"].lower(
            layout, *_shapes(bufs, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # a v5e chip has 16 GB of HBM
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


def _kernel_shapes(one_chip, dtype):
    def sd(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return sd


def test_acd_sweep_kernel_refused_by_mosaic(one_chip, cache_off):
    """Mosaic refuses the ACD kernel at J = 4096: it stores one scalar per
    step into its VMEM output block. Pinned so a repaired kernel shows
    here; until then ``engine_impl="pallas"`` fails with this reason on a
    TPU (it never falls back to interpret mode or to the scan twin)."""
    sd = _kernel_shapes(one_chip, jnp.float64)
    with jax.enable_x64(True), pytest.raises(
            ValueError, match="Cannot store scalars to VMEM"):
        jax.jit(acd_sweep.acd_evict).lower(
            sd((1, J)), sd((1, J)), sd((1, J), jnp.bool_)).compile()


def test_fifo_dispatch_kernel_refused_by_mosaic(one_chip, cache_off):
    """Mosaic refuses the capped FIFO dispatch kernel at J = 4096: it
    reads and writes its inputs and outputs in ``ANY`` memory directly,
    where only async copies are allowed."""
    P, C = 3, 2
    sd = _kernel_shapes(one_chip, jnp.float64)
    args = [sd((J,), jnp.int32), sd((J,), jnp.bool_), sd((), jnp.int32),
            sd((P, J)), sd((P, J)), sd((P, J)), sd((P, J)),
            sd((P, J), jnp.int32), sd((P,), jnp.bool_), sd((P,)),
            sd((P, C)), sd((P, C)), sd(())]
    with jax.enable_x64(True), pytest.raises(
            ValueError, match="ANY memory space can only be accessed"):
        jax.jit(lambda *a: dispatch.fifo_dispatch(*a, cold=True)).lower(
            *args).compile()

