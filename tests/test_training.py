"""Training substrate: optimizer, checkpoints, fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import Model
from repro.training import (AdamWConfig, PreemptionGuard, StepTimer, Trainer,
                            adamw_init, adamw_update, latest_step, restore,
                            run_with_restarts, save)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg, remat=False)
    data = SyntheticLM(cfg, DataConfig(seq_len=32, global_batch=4, seed=0))
    return cfg, m, data


class TestOptimizer:
    def test_first_step_matches_reference(self):
        ocfg = AdamWConfig(lr=1e-2, warmup_steps=1, weight_decay=0.0,
                           grad_clip=1e9)
        params = {"w": jnp.asarray([[1.0, 2.0]])}
        grads = {"w": jnp.asarray([[0.1, -0.2]])}
        state = adamw_init(params, ocfg)
        new_p, state, mets = adamw_update(grads, state, params, ocfg)
        # step 1: mhat = g, vhat = g^2 -> update = sign-ish g/|g|
        expect = np.asarray([[1.0, 2.0]]) - 1e-2 * np.sign([[0.1, -0.2]]) \
            / (1 + ocfg.eps)
        np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-4)

    def test_grad_clip(self):
        ocfg = AdamWConfig(lr=1e-3, grad_clip=0.5)
        params = {"w": jnp.ones((4,))}
        grads = {"w": jnp.full((4,), 100.0)}
        state = adamw_init(params, ocfg)
        _, _, mets = adamw_update(grads, state, params, ocfg)
        assert float(mets["grad_norm"]) == pytest.approx(200.0)

    @pytest.mark.parametrize("sd", ["float32", "bfloat16", "int8"])
    def test_state_dtypes_converge(self, sd, setup):
        cfg, m, data = setup
        tr = Trainer(m, AdamWConfig(lr=3e-3, state_dtype=sd, warmup_steps=5,
                                    total_steps=60))
        p, o = tr.init_state(jax.random.PRNGKey(0))
        p, o, log = tr.fit(p, o, data.iterate(), steps=25, log_every=25)
        assert log[-1]["loss"] < 5.0 and np.isfinite(log[-1]["loss"])


class TestCheckpoint:
    def test_roundtrip_bf16(self, tmp_path):
        tree = {"a": jnp.ones((3, 4), jnp.bfloat16),
                "b": {"c": jnp.arange(5, dtype=jnp.int32)}}
        save(tree, str(tmp_path), step=7)
        out, step = restore(str(tmp_path), tree)
        assert step == 7
        for k1, k2 in zip(jax.tree_util.tree_leaves(tree),
                          jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(k1, np.float32),
                                          np.asarray(k2, np.float32))

    def test_gc_keeps_last(self, tmp_path):
        tree = {"a": jnp.zeros(2)}
        for s in (1, 2, 3, 4, 5):
            save(tree, str(tmp_path), step=s, keep=2)
        steps = sorted(os.listdir(tmp_path))
        assert steps == ["step_00000004", "step_00000005"]

    def test_latest_step_none(self, tmp_path):
        assert latest_step(str(tmp_path)) is None

    def test_shape_mismatch_raises(self, tmp_path):
        save({"a": jnp.zeros((2, 2))}, str(tmp_path), step=1)
        with pytest.raises(ValueError):
            restore(str(tmp_path), {"a": jnp.zeros((3, 3))})


class TestFaultTolerance:
    def test_restart_resumes_from_checkpoint(self, setup, tmp_path):
        cfg, m, data = setup
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=40)

        def attempt_run(attempt):
            tr = Trainer(m, ocfg, ckpt_dir=str(tmp_path), ckpt_every=5)
            p, o = tr.init_state(jax.random.PRNGKey(0))
            p, o, start = tr.maybe_restore(p, o)
            # fail once at step 12 on the first attempt
            fail_at = 12 if attempt == 0 else None
            p, o, log = tr.fit(p, o, data.iterate(start), steps=20,
                               start_step=start, fail_at=fail_at)
            return start, log

        start, log = run_with_restarts(attempt_run, max_restarts=2)
        assert start >= 10          # resumed from a checkpoint, not scratch
        assert log[-1]["step"] == 20

    def test_step_timer_flags_stragglers(self):
        t = StepTimer(threshold=2.0)
        for _ in range(5):
            assert not t.observe(1.0)
        assert t.observe(5.0)        # straggler
        assert t.straggles == 1
        assert t.ewma == pytest.approx(1.0)   # baseline not poisoned

    def test_preemption_guard_triggers_final_ckpt(self, setup, tmp_path):
        cfg, m, data = setup
        tr = Trainer(m, AdamWConfig(lr=1e-3), ckpt_dir=str(tmp_path),
                     ckpt_every=1000)
        p, o = tr.init_state(jax.random.PRNGKey(0))
        guard = PreemptionGuard(signals=())
        guard._stop = True           # simulate SIGTERM delivery
        p, o, log = tr.fit(p, o, data.iterate(), steps=50, guard=guard)
        assert latest_step(str(tmp_path)) == 1   # stopped after 1 step, saved


def test_fault_plumbing_does_not_load_the_engine():
    """``training.fault`` takes ``RetryPolicy`` from ``core.faults``
    without running the scheduler or the jit engine, so an engine import
    error cannot fail the training stack."""
    from ._subproc import run_py

    out = run_py("import sys, repro.training.fault; "
                 "print(sorted(m for m in sys.modules "
                 "if m.startswith('repro.core.')))", devices=1)
    assert out.strip() == "['repro.core.faults']"
