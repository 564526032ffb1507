"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    flash_attention,
    flash_attention_ref,
    fused_adamw,
    fused_adamw_ref,
    fused_dots,
    fused_dots_ref,
    fused_vma_dots,
    fused_vma_dots_ref,
    spmv_bell_pallas,
    spmv_bell_ref,
    spmv_dia_pallas,
    spmv_dia_ref,
)
from repro.sparse import bell_from_csr, csr_from_dia, poisson27, poisson125, synthetic_spd_dia

SIZES = [100, 1023, 4096, 20000]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)


def _rand(n, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype=dtype)


class TestFusedVMA:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        vecs = [_rand(n, dtype, seed=i) for i in range(10)]
        inv = jnp.abs(_rand(n, dtype, seed=99)) + 0.5
        alpha, beta = 0.37, 0.81
        out_k = fused_vma_dots(*vecs, inv, alpha, beta)
        out_r = fused_vma_dots_ref(*vecs, inv, alpha, beta)
        for i, (a, b) in enumerate(zip(out_k[:9], out_r[:9])):
            np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64), **_tol(dtype)
            )
        # dots: f32 accumulation, compare relative to magnitude ~ n
        np.testing.assert_allclose(
            np.asarray(out_k[9]), np.asarray(out_r[9]), rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4
        )

    def test_beta_zero_first_iteration(self):
        n = 512
        vecs = [_rand(n, jnp.float32, seed=i) for i in range(10)]
        inv = jnp.ones((n,))
        out_k = fused_vma_dots(*vecs, inv, 0.5, 0.0)
        out_r = fused_vma_dots_ref(*vecs, inv, 0.5, 0.0)
        np.testing.assert_allclose(np.asarray(out_k[0]), np.asarray(out_r[0]), rtol=1e-6)


class TestFusedDot:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        r, u, w = (_rand(n, dtype, seed=i) for i in range(3))
        k = np.asarray(fused_dots(r, u, w))
        ref = np.asarray(fused_dots_ref(r, u, w))
        np.testing.assert_allclose(k, ref, rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_uu_nonnegative(self):
        u = _rand(1000, jnp.float32, seed=5)
        k = np.asarray(fused_dots(u, u, u))
        assert k[2] >= 0


class TestSpmvDia:
    @pytest.mark.parametrize("gen,n", [(poisson27, 6), (poisson27, 9), (poisson125, 6)])
    def test_stencils(self, gen, n):
        A = gen(n)
        x = _rand(A.n, jnp.float32, seed=1)
        y_k = np.asarray(spmv_dia_pallas(A, x, tile=512))
        y_r = np.asarray(spmv_dia_ref(A.data, A.offsets, x))
        np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n", [100, 700])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_random_banded(self, n, dtype):
        A = synthetic_spd_dia(n, 9.0, seed=3).with_dtype(dtype)
        x = _rand(n, dtype, seed=2)
        y_k = np.asarray(spmv_dia_pallas(A, x, tile=128), np.float64)
        y_r = np.asarray(spmv_dia_ref(A.data, A.offsets, x), np.float64)
        np.testing.assert_allclose(y_k, y_r, **_tol(dtype))

    def test_tile_auto_raise_for_wide_band(self):
        A = poisson125(8)  # bandwidth 2*64+16+2 = 146... with n=8: 2*64+2*8+2
        x = _rand(A.n, jnp.float32, seed=4)
        # tile smaller than bandwidth must be raised internally, not crash
        y_k = np.asarray(spmv_dia_pallas(A, x, tile=128))
        y_r = np.asarray(spmv_dia_ref(A.data, A.offsets, x))
        np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)


class TestRowTile:
    """1-D row tiles are whole 1024-element TPU layout tiles, >= bandwidth + 1."""

    def test_hpcg_104_tile(self):
        from repro.core.pipecg import _padded_tile
        from repro.kernels import fused_iter_tile

        # 27-point stencil on a 104^3 grid: bandwidth 104^2 + 104 + 1
        assert _padded_tile("pallas", 10_921, None) == 11_264
        assert fused_iter_tile(10_921) == 11_264

    @pytest.mark.parametrize("bandwidth,tile", [(1, None), (3_962, None), (4_095, None),
                                                (4_096, None), (27_391, None), (10, 256)])
    def test_rule(self, bandwidth, tile):
        from repro.core.pipecg import _padded_length, _padded_tile
        from repro.kernels import fused_iter_tile

        for t in (_padded_tile("pallas", bandwidth, tile), fused_iter_tile(bandwidth, tile)):
            assert t % 1024 == 0 and t >= bandwidth + 1 and t >= (tile or 4096)
        t = _padded_tile("pallas", bandwidth, tile)
        n_pad = _padded_length(100_003, t)
        assert n_pad >= 100_003 and n_pad % t == 0 and n_pad % 4096 == 0


class TestVmap:
    """Under vmap the kernels run once per lane: same values as unbatched."""

    def test_spmv_dia(self):
        A = poisson27(12)
        X = jnp.stack([_rand(A.n, jnp.float32, seed=s) for s in range(3)])
        y = np.asarray(jax.vmap(lambda x: spmv_dia_pallas(A, x))(X))
        for i in range(3):
            np.testing.assert_array_equal(y[i], np.asarray(spmv_dia_pallas(A, X[i])))

    def test_fused_vma_batched_scalars(self):
        n = 3000
        vecs = [jnp.stack([_rand(n, jnp.float32, seed=10 * k + i) for k in range(2)])
                for i in range(10)]
        inv = jnp.ones((n,))
        alpha, beta = jnp.array([0.3, 0.6]), jnp.array([0.2, 0.9])
        outs = jax.vmap(fused_vma_dots, in_axes=(0,) * 10 + (None, 0, 0))(*vecs, inv, alpha, beta)
        for k in range(2):
            ref = fused_vma_dots(*[v[k] for v in vecs], inv, alpha[k], beta[k])
            for got, want in zip(outs, ref):
                np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want), rtol=1e-6)


class TestSpmvBell:
    @pytest.mark.parametrize("n", [64, 300, 2048])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        A = synthetic_spd_dia(n, 7.0, seed=5).with_dtype(dtype)
        B = bell_from_csr(csr_from_dia(A))
        x = _rand(n, dtype, seed=6)
        y_k = np.asarray(spmv_bell_pallas(B, x), np.float64)
        y_r = np.asarray(spmv_bell_ref(B.cols, B.vals, x), np.float64)
        np.testing.assert_allclose(y_k, y_r, **_tol(dtype))

    def test_vmem_guard(self):
        from repro.sparse.formats import BellMatrix

        big = BellMatrix(jnp.zeros((3 * 1024 * 1024, 1), jnp.int32), jnp.zeros((3 * 1024 * 1024, 1)), 3 * 1024 * 1024)
        with pytest.raises(ValueError, match="VMEM"):
            spmv_bell_pallas(big, jnp.zeros((3 * 1024 * 1024,)))


class TestFlashAttention:
    @pytest.mark.parametrize(
        "B,T,H,KV,hd",
        [(2, 256, 4, 2, 64), (1, 128, 8, 8, 32), (2, 384, 6, 3, 64), (1, 256, 4, 1, 16)],
    )
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, B, T, H, KV, hd, dtype):
        q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, hd), dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KV, hd), dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KV, hd), dtype)
        o = flash_attention(q, k, v, q_tile=128, kv_tile=128)
        r = flash_attention_ref(q, k, v)
        tol = 4e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(r, np.float32), rtol=tol, atol=tol
        )

    def test_noncausal(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 32), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 32), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 32), jnp.float32)
        o = flash_attention(q, k, v, causal=False, q_tile=128, kv_tile=128)
        r = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5, atol=2e-5)

    def test_tile_divisibility_guard(self):
        q = jnp.zeros((1, 100, 2, 32))
        with pytest.raises(ValueError, match="%"):
            flash_attention(q, q[:, :, :2], q[:, :, :2], q_tile=64)


class TestFusedAdam:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        p = _rand(n, dtype, seed=1)
        g = _rand(n, dtype, seed=2)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)
        for step in (1.0, 10.0):
            pk, mk, vk = fused_adamw(p, g, m, v, lr=3e-4, wd=0.1, step=step)
            pr, mr, vr = fused_adamw_ref(p, g, m, v, 3e-4, 0.9, 0.999, 1e-8, 0.1, step)
            np.testing.assert_allclose(np.asarray(pk, np.float64), np.asarray(pr, np.float64), **_tol(dtype))
            np.testing.assert_allclose(np.asarray(mk), np.asarray(mr), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), rtol=1e-5, atol=1e-6)
            p, m, v = pk, mk, vk

    def test_wd_zero_equals_adam(self):
        n = 500
        p = _rand(n, jnp.float32, seed=3)
        g = _rand(n, jnp.float32, seed=4)
        m = v = jnp.zeros((n,), jnp.float32)
        p1, _, _ = fused_adamw(p, g, m, v, lr=1e-3, wd=0.0)
        # hand-rolled adam step 1
        mh = 0.1 * np.asarray(g) / (1 - 0.9)
        vh = 0.001 * np.asarray(g) ** 2 / (1 - 0.999)
        expect = np.asarray(p) - 1e-3 * (mh / (np.sqrt(vh) + 1e-8))
        np.testing.assert_allclose(np.asarray(p1), expect, rtol=1e-5, atol=1e-6)
