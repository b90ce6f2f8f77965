"""Train a ~25M-parameter llama-family model for a few hundred steps on
this host, with sharded-ready code paths, checkpointing and a simulated
preemption + resume.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import Model, ModelConfig
from repro.training import AdamWConfig, Trainer


def small_lm() -> ModelConfig:
    return ModelConfig(
        name="llama-25m", family="dense", num_layers=6, d_model=384,
        num_heads=6, num_kv_heads=2, d_ff=1024, vocab_size=8192,
        norm="rmsnorm", act="silu", glu=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = small_lm()
    model = Model(cfg, remat=True)
    data = SyntheticLM(cfg, DataConfig(seq_len=args.seq,
                                       global_batch=args.batch, seed=0))
    ckpt = args.ckpt or tempfile.mkdtemp(prefix="skedulix_lm_")
    trainer = Trainer(model,
                      AdamWConfig(lr=3e-3, warmup_steps=20,
                                  total_steps=args.steps),
                      ckpt_dir=ckpt, ckpt_every=50)
    params, opt = trainer.init_state(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"model: {n / 1e6:.1f}M params; ckpts -> {ckpt}")

    half = args.steps // 2
    params, opt, log = trainer.fit(params, opt, data.iterate(), steps=half,
                                   log_every=20)
    for e in log:
        print(f"  step {e['step']:4d} loss={e['loss']:.4f} lr={e['lr']:.2e}")

    print(f"-- simulating preemption at step {half}: restart + resume --")
    params2, opt2 = trainer.init_state(jax.random.PRNGKey(1))
    params2, opt2, start = trainer.maybe_restore(params2, opt2)
    print(f"   resumed from step {start}")
    params2, opt2, log2 = trainer.fit(params2, opt2, data.iterate(start),
                                      steps=args.steps, start_step=start,
                                      log_every=20)
    for e in log2:
        print(f"  step {e['step']:4d} loss={e['loss']:.4f}")
    assert log2[-1]["loss"] < log[0]["loss"], "training must make progress"
    print("done.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
