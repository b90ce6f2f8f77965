"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _arr(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=2e-3, atol=2e-3),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [(64, 64, 64), (200, 300, 150),
                                       (8, 512, 8), (129, 257, 65)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, rng, m, k, n, dtype):
        x, y = _arr(rng, (m, k), dtype), _arr(rng, (k, n), dtype)
        out = ops.matmul(x, y, use_pallas=True)
        want = ref.matmul_ref(x, y)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_block_shapes(self, rng):
        x, y = _arr(rng, (256, 256), jnp.float32), _arr(rng, (256, 256), jnp.float32)
        for bm, bn, bk in [(64, 64, 64), (128, 128, 128), (128, 64, 256)]:
            out = ops.matmul(x, y, use_pallas=True, bm=bm, bn=bn, bk=bk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(x @ y),
                                       rtol=1e-3, atol=1e-3)


class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_causal(self, rng, hq, hkv, causal):
        q = _arr(rng, (2, hq, 48, 32), jnp.float32)
        k = _arr(rng, (2, hkv, 48, 32), jnp.float32)
        v = _arr(rng, (2, hkv, 48, 32), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=causal, use_pallas=True,
                                  bq=16, bk=16)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window", [8, 16, 64])
    def test_sliding_window(self, rng, window):
        q = _arr(rng, (1, 2, 64, 16), jnp.float32)
        k = _arr(rng, (1, 2, 64, 16), jnp.float32)
        v = _arr(rng, (1, 2, 64, 16), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  use_pallas=True, bq=16, bk=16)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_unpadded_vs_padded_lengths(self, rng):
        q = _arr(rng, (1, 2, 37, 16), jnp.float32)   # non-multiple of block
        k = _arr(rng, (1, 2, 53, 16), jnp.float32)
        v = _arr(rng, (1, 2, 53, 16), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, use_pallas=True,
                                  bq=16, bk=16)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self, rng):
        q = _arr(rng, (1, 4, 32, 32), jnp.bfloat16)
        k = _arr(rng, (1, 2, 32, 32), jnp.bfloat16)
        v = _arr(rng, (1, 2, 32, 32), jnp.bfloat16)
        out = ops.flash_attention(q, k, v, use_pallas=True, bq=16, bk=16)
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestFlashDecode:
    @pytest.mark.parametrize("hq,hkv,s", [(4, 4, 64), (8, 2, 100), (16, 1, 48)])
    def test_vs_ref(self, rng, hq, hkv, s):
        q = _arr(rng, (2, hq, 32), jnp.float32)
        k = _arr(rng, (2, hkv, s, 32), jnp.float32)
        v = _arr(rng, (2, hkv, s, 32), jnp.float32)
        lens = jnp.asarray(rng.integers(1, s + 1, 2), jnp.int32)
        out = ops.flash_decode(q, k, v, lens, use_pallas=True, bk=16)
        want = ref.flash_decode_ref(q, k, v, length=lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_full_length(self, rng):
        q = _arr(rng, (1, 4, 16), jnp.float32)
        k = _arr(rng, (1, 2, 40, 16), jnp.float32)
        v = _arr(rng, (1, 2, 40, 16), jnp.float32)
        out = ops.flash_decode(q, k, v, use_pallas=True, bk=16)
        want = ref.flash_decode_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestRGLRU:
    @pytest.mark.parametrize("b,t,d", [(1, 16, 8), (3, 50, 16), (4, 33, 32)])
    def test_vs_ref(self, rng, b, t, d):
        x = _arr(rng, (b, t, d), jnp.float32)
        a = jnp.asarray(rng.uniform(0.2, 0.99, (b, t, d)), jnp.float32)
        y1, h1 = ops.rglru(x, a, use_pallas=True, bb=2, bt=16)
        y2, h2 = ref.rglru_ref(x, a)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=2e-3, atol=2e-3)

    def test_initial_state_chaining(self, rng):
        """Running [0:t1] then [t1:T] with carried state == full scan."""
        x = _arr(rng, (2, 32, 8), jnp.float32)
        a = jnp.asarray(rng.uniform(0.3, 0.95, (2, 32, 8)), jnp.float32)
        y_full, h_full = ref.rglru_ref(x, a)
        y1, h1 = ops.rglru(x[:, :16], a[:, :16], use_pallas=True, bt=8)
        y2, h2 = ops.rglru(x[:, 16:], a[:, 16:], h1, use_pallas=True, bt=8)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 16:]),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                                   rtol=2e-3, atol=2e-3)


class TestRWKV6:
    @pytest.mark.parametrize("b,h,t,dk", [(1, 1, 16, 8), (2, 2, 40, 16)])
    def test_vs_ref(self, rng, b, h, t, dk):
        r = _arr(rng, (b, h, t, dk), jnp.float32)
        k = _arr(rng, (b, h, t, dk), jnp.float32)
        v = _arr(rng, (b, h, t, dk), jnp.float32)
        w = jnp.asarray(rng.uniform(0.3, 0.98, (b, h, t, dk)), jnp.float32)
        u = _arr(rng, (h, dk), jnp.float32)
        o1, s1 = ops.rwkv6(r, k, v, w, u, use_pallas=True, bt=8)
        o2, s2 = ref.rwkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=2e-3, atol=2e-3)

    def test_state_chaining(self, rng):
        b, h, t, dk = 1, 2, 24, 8
        r = _arr(rng, (b, h, t, dk), jnp.float32)
        k = _arr(rng, (b, h, t, dk), jnp.float32)
        v = _arr(rng, (b, h, t, dk), jnp.float32)
        w = jnp.asarray(rng.uniform(0.4, 0.95, (b, h, t, dk)), jnp.float32)
        u = _arr(rng, (h, dk), jnp.float32)
        o_full, s_full = ref.rwkv6_ref(r, k, v, w, u)
        o1, s1 = ops.rwkv6(r[:, :, :12], k[:, :, :12], v[:, :, :12],
                           w[:, :, :12], u, use_pallas=True, bt=4)
        o2, s2 = ops.rwkv6(r[:, :, 12:], k[:, :, 12:], v[:, :, 12:],
                           w[:, :, 12:], u, s1, use_pallas=True, bt=4)
        np.testing.assert_allclose(np.asarray(o2),
                                   np.asarray(o_full[:, :, 12:]),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   rtol=2e-3, atol=2e-3)


def _brute_acd(P, thresh, mask):
    """Iterated remove-first-violator-and-resweep fixpoint (the DES's
    literal cascade) — the claim the one-pass kernels telescope into."""
    J = len(P)
    ev = np.zeros(J, bool)
    while True:
        s, viol = 0.0, None
        for i in range(J):
            if mask[i] and not ev[i]:
                if s > thresh[i]:
                    viol = i
                    break
                s += P[i]
        if viol is None:
            return ev
        ev[viol] = True


class TestACDEvict:
    """Scheduler hot spot #1: greedy ACD kept-prefix sweep."""

    @pytest.mark.parametrize("b,j", [(1, 8), (4, 64), (30, 64), (3, 512)])
    def test_pallas_vs_ref_f64(self, rng, b, j):
        with jax.enable_x64(True):
            P = jnp.asarray(rng.lognormal(0.0, 0.6, (b, j)))
            # thresholds in the contested range so sweeps actually evict
            thresh = jnp.asarray(
                rng.uniform(0.0, 0.5 * j, (b, j)) * float(P.mean()))
            mask = jnp.asarray(rng.random((b, j)) < 0.8)
            got = ops.acd_evict(P, thresh, mask, use_pallas=True)
            want = ref.acd_evict_ref(P, thresh, mask)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert not np.asarray(got)[~np.asarray(mask)].any()

    def test_matches_iterated_cascade(self, rng):
        with jax.enable_x64(True):
            for _ in range(10):
                j = int(rng.integers(4, 40))
                P = rng.lognormal(0.0, 0.8, j)
                thresh = rng.uniform(0.0, P.sum() * 0.6, j)
                mask = rng.random(j) < 0.7
                want = _brute_acd(P, thresh, mask)
                got = ops.acd_evict(jnp.asarray(P)[None],
                                    jnp.asarray(thresh)[None],
                                    jnp.asarray(mask)[None],
                                    use_pallas=True)[0]
                np.testing.assert_array_equal(np.asarray(got), want)

    def test_empty_mask_no_evictions(self, rng):
        P = jnp.asarray(rng.lognormal(0.0, 0.5, (2, 16)), jnp.float32)
        out = ops.acd_evict(P, jnp.zeros((2, 16), jnp.float32),
                            jnp.zeros((2, 16), bool), use_pallas=True)
        assert not np.asarray(out).any()


def _dispatch_inputs(rng, J, P, C, n_pub, cold):
    f = np.float64
    order = np.concatenate([rng.permutation(n_pub),
                            np.arange(n_pub, J)]).astype(np.int32)
    locpub = np.zeros(J, bool)
    locpub[order[:n_pub]] = True
    ready = rng.uniform(0.0, 5.0, (P, J)).astype(f)
    dur = rng.lognormal(0.0, 0.5, (P, J)).astype(f)
    selc = rng.uniform(0.0, 2.0, (P, J)).astype(f)
    occ = rng.uniform(0.0, 0.3, (P, J)).astype(f)
    seg = rng.integers(0, 4, (P, J))
    capped_p = rng.random(P) < 0.7
    wu_p = rng.uniform(0.1, 1.0, P).astype(f)
    sclk0 = rng.uniform(0.0, 3.0, (P, C)).astype(f)
    sidle0 = np.where(rng.random((P, C)) < (0.5 if cold else 0.0),
                      -np.inf, sclk0).astype(f)
    return (jnp.asarray(order), jnp.asarray(locpub),
            jnp.asarray(n_pub, jnp.int32), jnp.asarray(ready),
            jnp.asarray(dur), jnp.asarray(selc), jnp.asarray(occ),
            jnp.asarray(seg), jnp.asarray(capped_p), jnp.asarray(wu_p),
            jnp.asarray(sclk0), jnp.asarray(sidle0), 0.75)


class TestFIFODispatch:
    """Scheduler hot spot #2: capped FIFO pop/dispatch chain."""

    @pytest.mark.parametrize("cold", [False, True])
    @pytest.mark.parametrize("j,p,c,n_pub", [(8, 2, 2, 8), (24, 3, 4, 17),
                                             (64, 4, 2, 50)])
    def test_pallas_vs_ref_bitexact(self, rng, cold, j, p, c, n_pub):
        with jax.enable_x64(True):
            args = _dispatch_inputs(rng, j, p, c, n_pub, cold)
            got = ops.fifo_dispatch(*args, cold=cold, use_pallas=True)
            want = ref.fifo_dispatch_ref(*args, cold=cold)
            assert len(got) == len(want) == 7
            for g, w in zip(got, want):
                # bitwise: the kernel keeps gathers/argmins/float
                # association identical to the oracle
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_chain_advances_clocks_sequentially(self, rng):
        with jax.enable_x64(True):
            # all jobs to one capped provider with one slot: starts must
            # chain end-to-end in visit order (pure FIFO queueing)
            J = 6
            args = list(_dispatch_inputs(rng, J, 1, 1, J, False))
            args[8] = jnp.asarray(np.ones(1, bool))        # capped
            args[6] = jnp.asarray(np.zeros((1, J)))        # occ $0: no tiebreak
            got = ops.fifo_dispatch(*args, use_pallas=True)
            order = np.asarray(args[0])
            start, end = np.asarray(got[4]), np.asarray(got[5])
            for a, b in zip(order[:-1], order[1:]):
                assert start[b] >= end[a] or np.isclose(start[b], end[a])

    def test_n_pub_truncates(self, rng):
        with jax.enable_x64(True):
            args = list(_dispatch_inputs(rng, 12, 2, 2, 12, False))
            args[2] = jnp.asarray(5, jnp.int32)            # only 5 dispatch
            got = ops.fifo_dispatch(*args, use_pallas=True)
            tail = np.asarray(args[0])[5:]
            # untouched jobs keep the zero fill on every output
            assert (np.asarray(got[5])[tail] == 0.0).all()
