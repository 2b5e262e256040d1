"""Unit + property tests for the HiF4 format (paper SS II, Table I/II, Alg. 1)."""
import pytest

hypothesis = pytest.importorskip("hypothesis")
hnp = pytest.importorskip("hypothesis.extra.numpy")
st = pytest.importorskip("hypothesis.strategies")
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hif4, qlinear
from repro.core import rounding as R

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=50, derandomize=True
)
hypothesis.settings.load_profile("ci")


def _rand_groups(seed, n=8, scale=1.0):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, hif4.GROUP_SIZE)).astype(np.float32) * scale
    # inputs are BF16 per Algorithm 1
    return jnp.asarray(v, jnp.bfloat16).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Table I / Table II constants
# ---------------------------------------------------------------------------


class TestFormatConstants:
    def test_e6m2_range(self):
        assert float(R.round_e6m2(jnp.float32(1e30))) == 2.0 ** 15 * 1.5
        assert float(R.round_e6m2(jnp.float32(1e-30))) == 2.0 ** -48

    def test_e6m2_never_nan_pattern(self):
        # 2^15 * 1.75 would encode as the NaN pattern; rounding must avoid it
        v = R.round_e6m2(jnp.float32(2.0 ** 15 * 1.75))
        assert float(v) == 2.0 ** 15 * 1.5
        assert int(R.encode_e6m2(v)) != R.E6M2_NAN_BITS

    def test_table2_max_min(self):
        assert hif4.MAX_POS == 2.0 ** 18 * 1.3125
        assert hif4.MIN_POS == 2.0 ** -50

    def test_global_dynamic_range_69_binades(self):
        # Table II: [-50, 18] exponent span
        assert np.isclose(np.log2(hif4.MAX_POS) - np.log2(hif4.MIN_POS), 68.39, atol=0.1)

    def test_s1p2_grid(self):
        xs = jnp.linspace(-2.5, 2.5, 101)
        q = R.quantize_s1p2(xs)
        assert float(jnp.max(jnp.abs(q))) == 1.75
        assert np.allclose(np.asarray(q) % 0.25, 0)

    def test_s1p2_rne_ties(self):
        # 0.125 is a tie between 0.0 (even) and 0.25 (odd) -> 0.0
        assert float(R.quantize_s1p2(jnp.float32(0.125))) == 0.0
        # 0.375 ties between 0.25 (odd) and 0.5 (even) -> 0.5
        assert float(R.quantize_s1p2(jnp.float32(0.375))) == 0.5

    def test_e6m2_codec_roundtrip(self):
        codes = jnp.arange(255, dtype=jnp.uint8)  # skip NaN code 255
        vals = R.decode_e6m2(codes)
        back = R.encode_e6m2(vals)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))

    def test_e6m2_reciprocal_matches_lut_semantics(self):
        """The reciprocal must factor as 2^-E * LUT[M]: only then can the
        paper's 4-entry-LUT + exponent-subtraction instruction realize it."""
        # bf16 (7 mantissa bits) RNE of 1/1.M:
        lut = {0: 1.0, 1: 0.80078125, 2: 0.66796875, 3: 0.5703125}
        for m, frac in lut.items():
            v = jnp.float32(1 + m * 0.25)
            assert float(R.e6m2_reciprocal_bf16(v)) == frac
        # separability over the full exponent range (all non-NaN codes)
        codes = jnp.arange(255, dtype=jnp.uint8)
        vals = R.decode_e6m2(codes)
        rec = np.asarray(R.e6m2_reciprocal_bf16(vals))
        eb = np.asarray(codes >> 2).astype(np.int32) - 48
        mm = np.asarray(codes & 0x3)
        expect = np.asarray([lut[int(m)] for m in mm]) * np.exp2(-eb.astype(np.float64))
        np.testing.assert_array_equal(rec.astype(np.float64), expect)


# ---------------------------------------------------------------------------
# Algorithm 1 semantics
# ---------------------------------------------------------------------------


class TestAlgorithm1:
    def test_intra_group_normalization(self):
        """Scale maps group peak near 7 = intra-structure max (Alg.1 line 8)."""
        v = _rand_groups(0, n=64)
        g = hif4.quantize_groups(v)
        vmax = jnp.max(jnp.abs(v), axis=-1)
        norm = vmax / g.e6m2
        # RNE on E6M2 has <=12.5% relative error; peak lands in [6.1, 8.0]
        assert float(jnp.min(norm)) > 6.0
        assert float(jnp.max(norm)) < 8.1

    def test_peak_element_saturates_hierarchy(self):
        """The group's peak element must use both micro-exponent levels."""
        v = _rand_groups(1, n=32)
        g = hif4.quantize_groups(v)
        i = jnp.argmax(jnp.abs(v), axis=-1)
        lvl2 = jnp.take_along_axis(g.e1_8, i[:, None] // 8, axis=-1)[:, 0]
        lvl3 = jnp.take_along_axis(g.e1_16, i[:, None] // 4, axis=-1)[:, 0]
        # peak normalized to ~7 > 4 => E1_8 = 1; /2 >= 2 => E1_16 = 1
        assert np.all(np.asarray(lvl2) == 1)
        assert np.all(np.asarray(lvl3) == 1)

    def test_all_zero_group(self):
        g = hif4.quantize_groups(jnp.zeros((1, 64), jnp.float32))
        out = hif4.dequantize_groups(g)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert float(g.e6m2[0]) == R.E6M2_MIN  # no zero in E6M2

    def test_constant_group_exact(self):
        """Powers of two in a flat group should reconstruct near-exactly."""
        v = jnp.full((1, 64), 2.0 ** -3, jnp.float32)
        out = hif4.dequantize_groups(hif4.quantize_groups(v))
        np.testing.assert_allclose(np.asarray(out), 2.0 ** -3, rtol=0.08)

    def test_quantization_error_bound(self):
        """|err| <= half step at the element's effective scale (+bf16 eps)."""
        v = _rand_groups(2, n=128)
        g = hif4.quantize_groups(v)
        out = hif4.dequantize_groups(g)
        shift = jnp.repeat(g.e1_8, 8, -1) + jnp.repeat(g.e1_16, 4, -1)
        step = g.e6m2[:, None] * jnp.exp2(shift.astype(jnp.float32)) * 0.25
        err = jnp.abs(out - v)
        # elements can clamp at 1.75 when the scale rounded down; exclude
        # clamps. The bf16 multiply in Alg.1 line 16 adds up to ~2^-8
        # relative error on top of the half-step rounding bound.
        clamped = jnp.abs(g.s1p2) == 1.75
        bound = 0.5 * step + jnp.abs(v) * 2.0 ** -7 + 1e-6
        ok = jnp.where(clamped, True, err <= bound)
        assert bool(jnp.all(ok))

    def test_wide_dynamic_range_no_crash(self):
        """69-binade global range: extreme tensors stay finite (vs NVFP4)."""
        for exp in (-45, -20, 0, 14):
            v = _rand_groups(3, n=4, scale=2.0 ** exp)
            out = hif4.dequantize_groups(hif4.quantize_groups(v))
            assert bool(jnp.all(jnp.isfinite(out)))
            rel = float(
                jnp.mean(jnp.square(out - v)) / jnp.maximum(jnp.mean(jnp.square(v)), 1e-38)
            )
            assert rel < 0.02, f"exp={exp} rel={rel}"


# ---------------------------------------------------------------------------
# Packing / int-flow properties (hypothesis)
# ---------------------------------------------------------------------------


@st.composite
def group_arrays(draw):
    n = draw(st.integers(1, 4))
    scale = draw(st.sampled_from([2.0 ** e for e in range(-40, 15, 5)]))
    arr = draw(
        hnp.arrays(
            np.float32,
            (n, hif4.GROUP_SIZE),
            elements=st.floats(-4.0, 4.0, width=32),
        )
    )
    return jnp.asarray(arr * scale, jnp.bfloat16).astype(jnp.float32)


class TestNativeBf16Path:
    @hypothesis.given(group_arrays())
    def test_bf16_native_bitwise_equals_f32_simulated(self, v):
        """Algorithm 1 on bf16 inputs, under ``jit``, must agree BITWISE
        with the eager f32-emulated path: XLA may fuse bf16 arithmetic in
        excess precision, so every bf16 rounding has to stay explicit."""
        g32 = hif4.quantize_groups(v)                      # eager, f32
        g16 = jax.jit(hif4.quantize_groups)(v.astype(jnp.bfloat16))
        np.testing.assert_array_equal(np.asarray(g32.e6m2), np.asarray(g16.e6m2))
        np.testing.assert_array_equal(np.asarray(g32.e1_8), np.asarray(g16.e1_8))
        np.testing.assert_array_equal(np.asarray(g32.e1_16), np.asarray(g16.e1_16))
        np.testing.assert_array_equal(
            np.asarray(g32.s1p2), np.asarray(g16.s1p2).astype(np.float32)
        )
        d32 = hif4.dequantize_groups(g32)
        d16 = hif4.dequantize_groups(g16)
        np.testing.assert_array_equal(
            np.asarray(d32), np.asarray(d16).astype(np.float32)
        )


class TestPackingAndIntFlow:
    @hypothesis.given(group_arrays())
    def test_pack_unpack_roundtrip(self, v):
        g = hif4.quantize_groups(v)
        g2 = hif4.unpack_groups(hif4.pack_groups(g))
        np.testing.assert_array_equal(np.asarray(g.e6m2), np.asarray(g2.e6m2))
        np.testing.assert_array_equal(np.asarray(g.e1_8), np.asarray(g2.e1_8))
        np.testing.assert_array_equal(np.asarray(g.e1_16), np.asarray(g2.e1_16))
        np.testing.assert_array_equal(np.asarray(g.s1p2), np.asarray(g2.s1p2))

    @hypothesis.given(group_arrays())
    def test_absorbed_int_exact(self, v):
        """Int view must reproduce dequantized values exactly (SS III.B)."""
        g = hif4.quantize_groups(v)
        ints, scale = hif4.to_absorbed_int(g)
        recon = scale[:, None] * ints.astype(jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(recon), np.asarray(hif4.dequantize_groups(g))
        )

    @hypothesis.given(group_arrays())
    def test_absorbed_int_range(self, v):
        """Absorbed ints fit the 5-bit-shifted-int8 budget |q| <= 28."""
        ints, _ = hif4.to_absorbed_int(hif4.quantize_groups(v))
        assert int(jnp.max(jnp.abs(ints.astype(jnp.int32)))) <= 28

    def test_fixed_point_dot_equals_dequant_dot(self):
        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.standard_normal(64), jnp.bfloat16).astype(jnp.float32)
        b = jnp.asarray(rng.standard_normal(64), jnp.bfloat16).astype(jnp.float32)
        fp = float(qlinear.hif4_dot_fixed_point(a, b))
        da = hif4.dequantize_groups(hif4.quantize_groups(a.reshape(1, 64)))
        db = hif4.dequantize_groups(hif4.quantize_groups(b.reshape(1, 64)))
        ref = float(jnp.sum(da * db))
        assert fp == pytest.approx(ref, rel=1e-6)


class TestTensorQDQ:
    def test_axis_handling(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((3, 128, 5)), jnp.float32)
        y0 = hif4.qdq(x, axis=1)
        # grouping along axis=1 must equal transposing and grouping last axis
        y1 = jnp.moveaxis(hif4.qdq(jnp.moveaxis(x, 1, -1), axis=-1), -1, 1)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1))

    def test_padding_path(self):
        x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 100)), jnp.float32)
        y = hif4.qdq(x, axis=-1)  # 100 -> padded to 128 internally
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_requantization_contracts(self):
        """HiF4 is not bit-idempotent (clamped peaks re-scale the group on a
        second pass — same as NVFP4), but requantization error must be much
        smaller than first-pass error and must not drift."""
        from repro.core.metrics import mse

        x = jnp.asarray(np.random.default_rng(2).standard_normal((16, 256)), jnp.float32)
        y = hif4.qdq(x)
        z = hif4.qdq(y)
        assert float(mse(y, z)) < 0.3 * float(mse(x, y))
        assert float(mse(x, z)) < 1.5 * float(mse(x, y))
