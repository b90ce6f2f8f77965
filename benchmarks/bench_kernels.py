"""Kernel microbenchmarks: jnp reference-path wall time (the CPU proxy) +
derived GFLOP/s, plus interpret-mode correctness deltas for the Pallas
kernels (wall time in interpret mode is meaningless — correctness only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import print_rows, row, timed


def _bench(fn, *args, repeats=5):
    out = jax.block_until_ready(fn(*args))          # compile + warm
    _, t = timed(lambda: jax.block_until_ready(fn(*args)), repeats=repeats)
    return out, t


def run(full: bool = False):
    rng = np.random.default_rng(0)
    rows = []
    n = 1024 if full else 512

    # matmul
    x = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    out, t = _bench(jax.jit(ref.matmul_ref), x, y)
    gf = 2 * n ** 3 / t / 1e9
    pall = ops.matmul(x[:256, :256], y[:256, :256], use_pallas=True)
    err = float(jnp.max(jnp.abs(pall - ref.matmul_ref(x[:256, :256],
                                                      y[:256, :256]))))
    rows.append(row("kernel/matmul", t * 1e6,
                    f"ref_gflops={gf:.1f};pallas_interp_maxerr={err:.2e}"))

    # flash attention (prefill)
    B, H, Hkv, S, D = 1, 8, 2, (2048 if full else 512), 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    fa = jax.jit(lambda *a: ref.flash_attention_ref(*a, causal=True))
    out, t = _bench(fa, q, k, v)
    fl = 4 * B * H * S * S * D
    small = ops.flash_attention(q[:, :, :128], k[:, :, :128], v[:, :, :128],
                                use_pallas=True, bq=64, bk=64)
    err = float(jnp.max(jnp.abs(
        small - ref.flash_attention_ref(q[:, :, :128], k[:, :, :128],
                                        v[:, :, :128]))))
    rows.append(row("kernel/flash_attention", t * 1e6,
                    f"ref_gflops={fl / t / 1e9:.1f};pallas_interp_maxerr={err:.2e}"))

    # flash decode
    S2 = 32768 if full else 4096
    kc = jnp.asarray(rng.normal(size=(B, Hkv, S2, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Hkv, S2, D)), jnp.float32)
    qd = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    fd = jax.jit(ref.flash_decode_ref)
    out, t = _bench(fd, qd, kc, vc)
    bytes_ = kc.nbytes + vc.nbytes
    err = float(jnp.max(jnp.abs(
        ops.flash_decode(qd, kc[:, :, :256], vc[:, :, :256], use_pallas=True,
                         bk=64)
        - ref.flash_decode_ref(qd, kc[:, :, :256], vc[:, :, :256]))))
    rows.append(row("kernel/flash_decode", t * 1e6,
                    f"ref_gbps={bytes_ / t / 1e9:.1f};pallas_interp_maxerr={err:.2e}"))

    # rglru
    Bt, T, Dm = 4, (4096 if full else 1024), 256
    xr = jnp.asarray(rng.normal(size=(Bt, T, Dm)), jnp.float32)
    ar = jnp.asarray(rng.uniform(0.5, 0.99, size=(Bt, T, Dm)), jnp.float32)
    rg = jax.jit(lambda a, b: ref.rglru_ref(a, b)[0])
    out, t = _bench(rg, xr, ar)
    rows.append(row("kernel/rglru", t * 1e6,
                    f"ref_gbps={2 * xr.nbytes / t / 1e9:.1f}"))

    # scheduler kernels (f64, like the vector engine): time the jnp
    # oracle (the CPU hot path) and check the Pallas kernel bodies in
    # interpret mode — both chains are sequential, so the figure of
    # merit is rows/sec of queue swept, not FLOPs
    with jax.enable_x64(True):
        B, J = (30, 512) if full else (30, 64)
        Ps = jnp.asarray(rng.lognormal(0.0, 0.6, (B, J)))
        th = jnp.asarray(rng.uniform(0.0, 0.5 * J, (B, J)) * float(Ps.mean()))
        mk = jnp.asarray(rng.random((B, J)) < 0.8)
        acd = jax.jit(ref.acd_evict_ref)
        out, t = _bench(acd, Ps, th, mk)
        err = int((np.asarray(ops.acd_evict(Ps, th, mk, use_pallas=True))
                   != np.asarray(out)).sum())
        rows.append(row("kernel/acd_sweep", t * 1e6,
                        f"rows_per_s={B / t:.0f};J={J};"
                        f"pallas_interp_mismatches={err}"))

        P_, C_, npub = 4, 2, int(0.8 * J)
        order = jnp.asarray(np.concatenate([
            rng.permutation(npub), np.arange(npub, J)]).astype(np.int32))
        locp = jnp.asarray(np.arange(J) < npub)
        ready = jnp.asarray(rng.uniform(0, 5, (P_, J)))
        dur = jnp.asarray(rng.lognormal(0, 0.5, (P_, J)))
        selc = jnp.asarray(rng.uniform(0, 2, (P_, J)))
        occ = jnp.asarray(rng.uniform(0, 0.3, (P_, J)))
        seg = jnp.asarray(rng.integers(0, 4, (P_, J)))
        cap = jnp.asarray(np.ones(P_, bool))
        wu = jnp.asarray(rng.uniform(0.1, 1.0, P_))
        clk = jnp.asarray(rng.uniform(0, 3, (P_, C_)))
        fd = jax.jit(lambda *a: ref.fifo_dispatch_ref(*a, cold=True))
        args = (order, locp, jnp.asarray(npub, jnp.int32), ready, dur,
                selc, occ, seg, cap, wu, clk, clk, 0.75)
        out, t = _bench(fd, *args)
        pall = ops.fifo_dispatch(*args, cold=True, use_pallas=True)
        err = int(sum((np.asarray(a) != np.asarray(b)).sum()
                      for a, b in zip(pall, out)))
        rows.append(row("kernel/fifo_dispatch", t * 1e6,
                        f"jobs_per_s={npub / t:.0f};J={J};"
                        f"pallas_interp_mismatches={err}"))

    # rwkv6
    Hh, Tk, Dk = 4, (1024 if full else 256), 64
    r_ = jnp.asarray(rng.normal(size=(1, Hh, Tk, Dk)), jnp.float32)
    k_ = jnp.asarray(rng.normal(size=(1, Hh, Tk, Dk)), jnp.float32)
    v_ = jnp.asarray(rng.normal(size=(1, Hh, Tk, Dk)), jnp.float32)
    w_ = jnp.asarray(rng.uniform(0.5, 0.99, size=(1, Hh, Tk, Dk)), jnp.float32)
    u_ = jnp.asarray(rng.normal(size=(Hh, Dk)), jnp.float32)
    rw = jax.jit(lambda *a: ref.rwkv6_ref(*a)[0])
    out, t = _bench(rw, r_, k_, v_, w_, u_)
    fl = 4 * Hh * Tk * Dk * Dk
    rows.append(row("kernel/rwkv6", t * 1e6, f"ref_gflops={fl / t / 1e9:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json
    import sys
    rows = run(full="--full" in sys.argv)
    print_rows(rows)
    with open("BENCH_kernels.json", "w") as f:
        json.dump({"rows": rows}, f, indent=2)
        f.write("\n")
    print("wrote BENCH_kernels.json")
