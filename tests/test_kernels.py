"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles,
executed in interpret mode on the CPU backend (the wrappers interpret only
when asked).  tests/test_tpu_compile.py compiles the same kernels for a
described TPU at model widths."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestChunkedMatmul:
    @pytest.mark.parametrize("m,n,k", [(32, 32, 32), (96, 64, 160),
                                       (17, 23, 40), (128, 128, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, m, n, k, dtype):
        x = jnp.asarray(RNG.standard_normal((m, k)), dtype)
        w = jnp.asarray(RNG.standard_normal((n, k)), dtype)
        got = ops.chunked_matmul(x, w, bm=32, bn=32, bk=32, interpret=True)
        want = ref.chunked_matmul(x, w)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))

    @pytest.mark.parametrize("bk", [16, 32, 64])
    def test_chunk_size_block_sweep(self, bk):
        """The relational chunk size (= bk) never changes the result."""
        x = jnp.asarray(RNG.standard_normal((64, 128)), jnp.float32)
        w = jnp.asarray(RNG.standard_normal((48, 128)), jnp.float32)
        got = ops.chunked_matmul(x, w, bm=32, bn=16, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.chunked_matmul(x, w)),
                                   rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("T,S,d,causal", [
        (32, 32, 16, True), (64, 64, 32, True), (32, 64, 16, False),
        (128, 128, 64, True)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, T, S, d, causal, dtype):
        q = jnp.asarray(RNG.standard_normal((2, 2, T, d)), dtype)
        k = jnp.asarray(RNG.standard_normal((2, 2, S, d)), dtype)
        v = jnp.asarray(RNG.standard_normal((2, 2, S, d)), dtype)
        got = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16,
                                  interpret=True)
        want = ref.flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))

    def test_block_shape_invariance(self):
        q = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.float32)
        a = ops.flash_attention(q, k, v, bq=16, bk=16, interpret=True)
        b = ops.flash_attention(q, k, v, bq=64, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


class TestPagedAttention:
    @pytest.mark.parametrize("lens", [[5, 17, 32], [1, 1, 1], [32, 8, 24]])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, lens, dtype):
        B, H, Hkv, d, page, P, MP = 3, 8, 2, 32, 8, 16, 4
        q = jnp.asarray(RNG.standard_normal((B, H, d)), dtype)
        kp = jnp.asarray(RNG.standard_normal((P, page, Hkv, d)), dtype)
        vp = jnp.asarray(RNG.standard_normal((P, page, Hkv, d)), dtype)
        pt = np.full((B, MP), -1, np.int32)
        used = iter(RNG.permutation(P))
        for b in range(B):
            for i in range(-(-lens[b] // page)):
                pt[b, i] = next(used)
        lens_a = jnp.asarray(lens, jnp.int32)
        got = ops.paged_attention(q, kp, vp, jnp.asarray(pt), lens_a,
                                  interpret=True)
        want = ref.paged_attention(q, kp, vp, jnp.asarray(pt), lens_a)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))

    def test_matches_dense_attention(self):
        """Paged attention over scattered pages == contiguous attention."""
        B, H, Hkv, d, page = 2, 4, 4, 16, 4
        T = 12
        q1 = jnp.asarray(RNG.standard_normal((B, 1, T, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((B, T, Hkv, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((B, T, Hkv, d)), jnp.float32)
        # build pools from contiguous K/V (one sequence per batch)
        P = B * 4
        kp = np.zeros((P, page, Hkv, d), np.float32)
        vp = np.zeros((P, page, Hkv, d), np.float32)
        pt = np.full((B, 4), -1, np.int32)
        pid = 0
        for b in range(B):
            for i in range(-(-T // page)):
                sl = np.asarray(k[b, i * page:(i + 1) * page])
                kp[pid, : sl.shape[0]] = sl
                vp[pid, : sl.shape[0]] = np.asarray(
                    v[b, i * page:(i + 1) * page])
                pt[b, i] = pid
                pid += 1
        qlast = jnp.asarray(RNG.standard_normal((B, H, d)), jnp.float32)
        got = ops.paged_attention(qlast, jnp.asarray(kp), jnp.asarray(vp),
                                  jnp.asarray(pt),
                                  jnp.asarray([T, T], jnp.int32),
                                  interpret=True)
        # dense reference: full attention of the single query over T tokens
        kk = jnp.repeat(k, H // Hkv, axis=2).transpose(0, 2, 1, 3)
        vv = jnp.repeat(v, H // Hkv, axis=2).transpose(0, 2, 1, 3)
        want = ref.flash_attention(qlast[:, :, None, :], kk, vv,
                                   causal=False)[:, :, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
