"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Sweeps shapes/dtypes per the deliverable: every kernel asserts allclose
against ref.py, and the quantize kernel is additionally anchored to the
bit-exact core.hif4 implementation of Algorithm 1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hif4
from repro.kernels import ref
from repro.kernels.bfp_matmul import bfp_matmul_quantized
from repro.kernels.hif4_quant import hif4_quantize


def _rand(key, m, k, dtype, scale=1.0):
    x = jax.random.normal(key, (m, k), jnp.float32) * scale
    return x.astype(dtype)


SHAPES = [(8, 64), (16, 128), (64, 256), (128, 512), (32, 192)]
DTYPES = [jnp.bfloat16, jnp.float32]


class TestHiF4QuantKernel:
    @pytest.mark.parametrize("m,k", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, m, k, dtype):
        x = _rand(jax.random.PRNGKey(m * k), m, k, dtype)
        ints, scales = hif4_quantize(x, block_groups=128, interpret=True)
        ints_ref, scales_ref = ref.hif4_quantize_ref(x.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(ints), np.asarray(ints_ref))
        np.testing.assert_array_equal(np.asarray(scales), np.asarray(scales_ref))

    @pytest.mark.parametrize("scale_exp", [-30, -8, 0, 9])
    def test_wide_dynamic_range(self, scale_exp):
        x = _rand(jax.random.PRNGKey(7), 16, 128, jnp.float32, 2.0 ** scale_exp)
        ints, scales = hif4_quantize(x, interpret=True)
        recon = ref.hif4_dequantize_ref(ints, scales)
        rel = float(jnp.mean((recon - x) ** 2) / jnp.mean(x ** 2))
        assert rel < 0.01, rel

    def test_dequant_matches_core_algorithm1(self):
        """Kernel output dequantizes to exactly Algorithm 1's values."""
        x = _rand(jax.random.PRNGKey(3), 8, 256, jnp.bfloat16)
        ints, scales = hif4_quantize(x, interpret=True)
        got = ref.hif4_dequantize_ref(ints, scales)
        want = hif4.dequantize_groups(
            hif4.quantize_groups(x.astype(jnp.float32).reshape(8, 4, 64))
        ).reshape(8, 256)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_budget(self):
        """Absorbed ints stay within the 5-bit shifted budget |q| <= 28."""
        x = _rand(jax.random.PRNGKey(5), 32, 256, jnp.float32, 3.0)
        ints, _ = hif4_quantize(x, interpret=True)
        assert int(jnp.max(jnp.abs(ints.astype(jnp.int32)))) <= 28


class TestBfpMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [(8, 64, 8), (16, 128, 32),
                                       (32, 256, 64), (64, 512, 16)])
    def test_matches_ref(self, m, k, n):
        kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
        x = _rand(kx, m, k, jnp.bfloat16)
        w = _rand(kw, k, n, jnp.bfloat16).T.reshape(k, n)  # arbitrary layout
        ai, ascale = ref.hif4_quantize_ref(x.astype(jnp.float32))
        bi, bscale = ref.hif4_quantize_ref(jnp.asarray(w).T.astype(jnp.float32))
        got = bfp_matmul_quantized(
            ai, ascale, bi.T, bscale.T,
            block_m=min(m, 16), block_n=min(n, 16), block_k=min(k, 128),
            interpret=True,
        )
        want = ref.bfp_matmul_from_quantized_ref(ai, ascale, bi.T, bscale.T)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_end_to_end_close_to_f32_matmul(self):
        """Quantized matmul approximates the f32 matmul (4-bit tolerance)."""
        kx, kw = jax.random.split(jax.random.PRNGKey(11))
        m, k, n = 32, 512, 32
        x = _rand(kx, m, k, jnp.float32, 0.5)
        w = _rand(kw, k, n, jnp.float32, 0.05)
        from repro.kernels.ops import matmul
        got = matmul(x, w, block_m=16, block_n=16, block_k=128, interpret=True)
        want = x @ w
        # For zero-mean operands the output is a random walk, so per-element
        # quantization noise (~9% for two 4-bit operands) does NOT average
        # out with K; ~12% relative output error is the expected regime.
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < 0.2, rel
        # and it must beat MXFP4 (coarser format) on the same data
        from repro.core import mxfp4
        mx = mxfp4.qdq(x, axis=-1) @ mxfp4.qdq(w, axis=0)
        rel_mx = float(jnp.linalg.norm(mx - want) / jnp.linalg.norm(want))
        assert rel < rel_mx, (rel, rel_mx)

    def test_fixed_point_flow_is_exact_vs_dequant(self):
        """Paper §III.B claim: the integer flow loses NOTHING vs computing
        in floats on dequantized values."""
        kx, kw = jax.random.split(jax.random.PRNGKey(13))
        m, k, n = 16, 128, 16
        x = _rand(kx, m, k, jnp.float32)
        w = _rand(kw, k, n, jnp.float32)
        ai, ascale = ref.hif4_quantize_ref(x)
        bi, bscale = ref.hif4_quantize_ref(w.T)
        got = bfp_matmul_quantized(ai, ascale, bi.T, bscale.T,
                                   block_m=16, block_n=16, block_k=128,
                                   interpret=True)
        a_deq = ref.hif4_dequantize_ref(ai, ascale)
        b_deq = ref.hif4_dequantize_ref(bi, bscale)
        want = a_deq @ b_deq.T
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
