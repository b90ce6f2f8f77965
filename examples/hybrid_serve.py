"""End-to-end driver (the paper's kind is serving): a small LM served with
batched requests through the hybrid scheduler.

Real execution: a reduced llama3-family model runs prefill/decode on this
host via InferenceEngine (the "private replica"); measured latencies
calibrate the serving latency model; the Skedulix greedy scheduler then
places a 48-request batch across private replicas + costed elastic
overflow under a deadline.

    PYTHONPATH=src python examples/hybrid_serve.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import Model
from repro.serving import (HybridServingScheduler, InferenceEngine,
                           Request)


def main():
    print("== hybrid LLM serving with Skedulix ==")
    cfg = get_smoke_config("llama3-8b")
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, cache_len=160)

    print("1. serving a real batch on the private replica (this host)...")
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(16, 128))).astype(np.int32),
                    max_new_tokens=16) for i in range(8)]
    t0 = time.perf_counter()
    outs = engine.generate_batch(reqs)
    dt = time.perf_counter() - t0
    print(f"   {len(outs)} requests, prefill={outs[0].prefill_s * 1e3:.1f}ms, "
          f"decode={outs[0].decode_s * 1e3:.1f}ms, total={dt:.2f}s")

    print("2. scheduling a 48-request batch over the hybrid fleet "
          "(llama3-8b production config, roofline latency models)...")
    h = HybridServingScheduler(get_config("llama3-8b"))
    h.fit_perf_models(n_train=200)
    plen = rng.integers(128, 4096, 48)
    ntok = rng.integers(32, 512, 48)
    pub, priv = h.baselines(plen, ntok)
    print(f"   all-private: {priv.makespan:6.2f}s  $0")
    print(f"   all-public : {pub.makespan:6.2f}s  ${pub.cost_usd:.4f}")
    for frac in (0.4, 0.6):
        c_max = priv.makespan * frac
        rep = h.schedule(plen, ntok, c_max=c_max, order="spt")
        r = rep.result
        print(f"   SLA={c_max:6.2f}s: makespan={r.makespan:6.2f}s "
              f"met={r.makespan <= c_max * 1.05} cost=${r.cost_usd:.4f} "
              f"({100 * r.cost_usd / pub.cost_usd:.0f}% of all-public)")
    print("done.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
