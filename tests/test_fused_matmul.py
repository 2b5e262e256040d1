"""Fused dequantize-in-kernel packed matmul: bit-exactness and dispatch.

The serving claim (docs/EXECUTION.md): consuming the 4.5-bit PackedW
payload directly inside the kernel changes WHERE the bits expand, never
what is computed. So across the serving shape matrix — decode M=1..4,
prefill M >= 256, non-square N, stacked-layer weights — the fused kernel
(interpret mode, runs in tier-1 CI on CPU) must be bitwise identical to

  * its straight-line XLA twin (what the engine serves off-TPU),
  * materializing the absorbed-int operand first and running the plain
    quantized kernel (``packed_to_absorbed`` + ``bfp_matmul_quantized``),

and float-close (f32 rounding only) to ``PackedW.dequantize()`` + dense
f32 dot — the dequantize reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, hif4
from repro.core.qlinear import PackedW, QuantConfig
from repro.kernels import ref
from repro.kernels.bfp_matmul import (
    _TILE_ELEMS_MAX,
    GROUP,
    K_GRID_AXIS,
    bfp_matmul_quantized,
    select_block_sizes,
)
from repro.kernels.fused_matmul import (
    absorbed_activation,
    fused_packed_matmul,
    fused_packed_matmul_xla,
)

# decode (M=1..4), prefill (M>=256), non-square N, odd group counts
SHAPES = [
    (1, 128, 96),      # decode, single request
    (2, 192, 64),      # decode, K = 3 groups
    (4, 256, 160),     # decode, the benchmark batch
    (3, 128, 256),     # decode, N > K
    (256, 256, 128),   # prefill
    (320, 128, 96),    # prefill, M not a power of two
]


def _packed(k, n, seed=0):
    w = (jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * 0.05).astype(
        jnp.bfloat16)
    return w, PackedW.from_dense(w, (0,))


def _activation(m, k, seed=1):
    x = (jax.random.normal(jax.random.PRNGKey(seed), (m, k)) * 0.1).astype(
        jnp.bfloat16)
    return x, absorbed_activation(x)


# ---------------------------------------------------------------------------
# Layout round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", SHAPES[:3])
def test_kernel_layout_same_bits_same_values(m, k, n):
    """K-major re-layout preserves payload size, value grid, and bytes."""
    _, pw = _packed(k, n)
    kw = pw.to_kernel_layout()
    assert kw.kernel_layout and kw.codes.shape == (k // 2, n)
    assert kw.meta.shape == (k // GROUP, n)
    assert kw.nbytes_packed == pw.nbytes_packed
    assert kw.n_values == pw.n_values == k * n
    np.testing.assert_array_equal(np.asarray(kw.dequantize()),
                                  np.asarray(pw.dequantize()))
    # idempotent
    assert kw.to_kernel_layout() is kw


def test_expand_meta_km_scale_parity_all_codes():
    """Every E6M2 code — the full [-48, 15] exponent range and the NaN
    pattern 0xFF — must decode on the kernel-tile path exactly like the
    artifact path (rounding.decode_e6m2). Catches both a dropped NaN and
    any approximate power-of-two construction (jnp.exp2 is NOT exact
    across this range)."""
    from repro.core import rounding as R

    codes = jnp.arange(256, dtype=jnp.uint32)
    _, scale = hif4.absorbed_int_km(jnp.zeros((32, 256), jnp.uint8),
                                    (codes << 24).reshape(1, -1))
    ref_scale = R.decode_e6m2(codes.astype(jnp.uint8)) * 0.25
    np.testing.assert_array_equal(np.asarray(scale)[0],
                                  np.asarray(ref_scale))


def test_absorbed_int_km_matches_unpack_path():
    """The in-kernel bit helpers == unpack_groups + to_absorbed_int."""
    _, pw = _packed(256, 96)
    codes_km, meta_km = pw.kernel_operands()
    ints, scale = hif4.absorbed_int_km(codes_km, meta_km)
    g = hif4.unpack_groups(hif4.HiF4Packed(pw.codes, pw.meta))
    ints_ref, scale_ref = hif4.to_absorbed_int(g)           # (n, k/64, 64)
    np.testing.assert_array_equal(np.asarray(ints),
                                  np.asarray(ints_ref.reshape(96, 256).T))
    np.testing.assert_array_equal(
        np.asarray(scale), np.asarray(scale_ref.astype(jnp.float32).T))


def _artifact_reference(codes_km, meta_km):
    """``to_absorbed_int(unpack_groups(...))`` of K-major buffers, laid out
    K-major again: (ints (K, N) int8, scale (K/64, N) f32)."""
    half, n = codes_km.shape
    g = meta_km.shape[0]
    codes = jnp.transpose(codes_km).reshape(n, g, GROUP // 2)
    ints, scale = hif4.to_absorbed_int(hif4.unpack_groups(
        hif4.HiF4Packed(codes, jnp.transpose(meta_km))))
    return (jnp.transpose(ints.reshape(n, 2 * half)),
            jnp.transpose(scale.astype(jnp.float32)))


def _assert_word_decode_matches(codes_km, meta_km):
    """The word-level decode == the artifact path, bit for bit (NaN
    scales included)."""
    ints_ref, scale_ref = _artifact_reference(codes_km, meta_km)
    ints, scale = hif4.absorbed_int_km(codes_km, meta_km)
    np.testing.assert_array_equal(np.asarray(ints), np.asarray(ints_ref))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale_ref))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_word_decode_random_bytes(seed):
    """Arbitrary code bytes — -0 (code 8) and every sign/magnitude pair
    included — and arbitrary 32-bit records, NaN scales among them."""
    rng = np.random.default_rng(seed)
    k, n = 512, 384
    codes_km = jnp.asarray(rng.integers(0, 256, (k // 2, n), np.uint8))
    meta_km = jnp.asarray(rng.integers(0, 2 ** 32, (k // GROUP, n),
                                       np.uint32))
    _assert_word_decode_matches(codes_km, meta_km)


def test_word_decode_every_micro_exponent_word():
    """Every E1_16 word (65536 columns) and, across them, every E1_8 word
    and every E6M2 code — 0xFF, the NaN record, too — against the
    artifact path. The codes hold every byte in every code row."""
    n = 1 << 16
    col = np.arange(n, dtype=np.uint32)
    w16 = col
    w8 = (col ^ (col >> 8)) & 0xFF
    e6m2 = (col * 37 + (col >> 8)) & 0xFF
    meta_km = jnp.asarray(((e6m2 << 24) | (w8 << 16) | w16)[None, :])
    rows = np.arange(GROUP // 2, dtype=np.uint32)[:, None]
    codes_km = jnp.asarray(((col[None, :] + 97 * rows) & 0xFF).astype(
        np.uint8))
    assert len(set(np.asarray(meta_km >> 24)[0].tolist())) == 256
    assert len(set(np.asarray(meta_km >> 16 & 0xFF)[0].tolist())) == 256
    _assert_word_decode_matches(codes_km, meta_km)


# ---------------------------------------------------------------------------
# Bit-exactness across the shape matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_fused_kernel_bit_exact(m, k, n):
    _, pw = _packed(k, n, seed=m)
    _, (ai, asc) = _activation(m, k, seed=m + 1)
    codes_km, meta_km = pw.kernel_operands()

    # single K-step so kernel/twin/materialized share one group reduction
    got = fused_packed_matmul(ai, asc, codes_km, meta_km, block_k=k,
                              interpret=True)
    twin = fused_packed_matmul_xla(ai, asc, codes_km, meta_km)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))

    wi, wsc = engine.packed_to_absorbed(pw)
    materialized = bfp_matmul_quantized(ai, asc, wi, wsc, block_k=k,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(materialized))

    # dequantize reference: float-close at f32 rounding (the flat f32 dot
    # associates the K reduction differently; values are identical)
    a_deq = ref.hif4_dequantize_ref(ai, asc)
    want = np.asarray(a_deq) @ np.asarray(pw.dequantize().astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def _twin_by_k_steps(ai, asc, codes_km, meta_km, bk):
    """The XLA twin over each K tile of ``bk``, summed in the kernel's
    order (zeros, then += one tile's groups per K step)."""
    m, k = ai.shape
    acc = jnp.zeros((m, codes_km.shape[1]), jnp.float32)
    for k0 in range(0, k, bk):
        g0, g1 = k0 // GROUP, (k0 + bk) // GROUP
        acc = acc + fused_packed_matmul_xla(
            ai[:, k0:k0 + bk], asc[:, g0:g1],
            codes_km[k0 // 2:(k0 + bk) // 2], meta_km[g0:g1])
    return acc


# decode M = 1, 8, 16 and a prefill M; K = 2560 and 9728 (Qwen3-4B's d_model
# and d_ff, which 1024 does not divide) at their real depth, N cut for CPU time
DEFAULT_TILE_SHAPES = [
    (1, 1024, 256),
    (8, 1024, 384),
    (16, 2560, 256),
    (16, 9728, 128),
    (96, 1024, 256),
]


@pytest.mark.parametrize("m,k,n", DEFAULT_TILE_SHAPES)
def test_fused_kernel_default_tiles_bit_exact(m, k, n):
    """At the tiles the chip would run (``select_block_sizes``), the
    kernel equals the twin taken K tile by K tile, bit for bit."""
    _, pw = _packed(k, n, seed=k + m)
    _, (ai, asc) = _activation(m, k, seed=m)
    codes_km, meta_km = pw.kernel_operands()
    _, _, bk = select_block_sizes(m, n, k)
    got = fused_packed_matmul(ai, asc, codes_km, meta_km, interpret=True)
    want = _twin_by_k_steps(ai, asc, codes_km, meta_km, bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n,bk,bn", [
    (8, 512, 96, 128, 96),        # four K steps
    (16, 1536, 256, 512, 128),    # three K steps, two N tiles
])
def test_fused_kernel_multi_k_step_bit_exact(m, k, n, bk, bn):
    _, pw = _packed(k, n, seed=3)
    _, (ai, asc) = _activation(m, k, seed=4)
    codes_km, meta_km = pw.kernel_operands()
    got = fused_packed_matmul(ai, asc, codes_km, meta_km, block_k=bk,
                              block_n=bn, interpret=True)
    want = _twin_by_k_steps(ai, asc, codes_km, meta_km, bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_kernel_multi_k_step():
    """Tiled K accumulation (the revisit pattern) stays float-identical in
    value to the single-step contraction."""
    m, k, n = 8, 512, 96
    _, pw = _packed(k, n)
    _, (ai, asc) = _activation(m, k)
    codes_km, meta_km = pw.kernel_operands()
    one = fused_packed_matmul(ai, asc, codes_km, meta_km, block_k=k,
                              interpret=True)
    tiled = fused_packed_matmul(ai, asc, codes_km, meta_km, block_k=128,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(one),
                               rtol=1e-6, atol=1e-7)


def test_stacked_layer_weights_slice_like_scan():
    """A stacked kernel-layout PackedW sliced per layer (what lax.scan does
    to the pytree leaves) contracts exactly like packing that layer alone."""
    L, k, n, m = 3, 128, 96, 4
    ws = [(jax.random.normal(jax.random.PRNGKey(10 + i), (k, n)) * 0.05)
          .astype(jnp.bfloat16) for i in range(L)]
    per_layer = [PackedW.from_dense(w, (0,)).to_kernel_layout() for w in ws]
    stacked = PackedW(
        jnp.stack([p.codes for p in per_layer]),
        jnp.stack([p.meta for p in per_layer]),
        (k, n), jnp.bfloat16, (None, None), kernel_layout=True)
    x, (ai, asc) = _activation(m, k, seed=7)
    for i in range(L):
        layer = jax.tree_util.tree_map(lambda b, i=i: b[i], stacked)
        got = fused_packed_matmul_xla(ai, asc, *layer.kernel_operands())
        want = fused_packed_matmul_xla(ai, asc, *per_layer[i].kernel_operands())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Engine dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["packed", "pallas"])
@pytest.mark.parametrize("layout", ["artifact", "kernel"])
def test_engine_routes_packedw_to_fused(impl, layout):
    """impl=packed and impl=pallas on a PackedW both serve the fused
    contraction (off-TPU: the XLA twin), in either payload layout."""
    m, k, n = 4, 128, 96
    x, (ai, asc) = _activation(m, k)
    _, pw = _packed(k, n)
    if layout == "kernel":
        pw = pw.to_kernel_layout()
    got = engine.matmul(x, pw, engine.EngineCtx(
        quant=QuantConfig(fmt="hif4", impl=impl)))
    want = fused_packed_matmul_xla(ai, asc, *pw.kernel_operands())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.astype(jnp.bfloat16)))


def test_engine_fused_fallbacks_dequantize():
    """weights_only / non-HiF4 fmt / qdq impl cannot run the fused kernel
    (it inherently quantizes activations): they must take the
    dequantize-then-dot path unchanged."""
    m, k, n = 4, 128, 96
    x, _ = _activation(m, k)
    _, pw = _packed(k, n)
    for cfg in (QuantConfig(fmt="hif4", impl="packed", weights_only=True),
                QuantConfig(fmt="nvfp4", impl="packed"),
                QuantConfig(fmt="hif4", impl="qdq")):
        got = engine.matmul(x, pw, engine.EngineCtx(quant=cfg))
        wd = pw.dequantize()
        from repro.core.qlinear import quantize_activation
        xq = quantize_activation(x, cfg, axis=-1)
        want = jax.lax.dot_general(
            xq, wd, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=x.dtype).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_xla_twin_caps_intermediate(monkeypatch):
    """Off-TPU, a contraction whose (K/64, M, N) batched-dot intermediate
    exceeds the cap must route to dequantize-then-dot (memory safety at
    large-M prefill), and stay numerically close to the fused result."""
    m, k, n = 16, 256, 96
    x, _ = _activation(m, k)
    _, pw = _packed(k, n)
    ectx = engine.EngineCtx(quant=QuantConfig(fmt="hif4", impl="packed"))
    fused = engine.matmul(x, pw, ectx)
    monkeypatch.setattr(engine, "_XLA_FUSED_PART_BYTES_MAX", 0)
    fallback = engine.matmul(x, pw, ectx)
    # fallback is the bf16-accumulated dequantize dot: same quantized
    # values, different accumulation — bf16-rounding close, and bitwise
    # equal to the explicit _packed_matmul path
    np.testing.assert_allclose(
        np.asarray(fallback, jnp.float32), np.asarray(fused, jnp.float32),
        rtol=0.02, atol=0.01)
    want = engine._packed_matmul(x, pw, ectx, contract_x=-1, accum_dtype=None)
    np.testing.assert_array_equal(np.asarray(fallback), np.asarray(want))


def test_packed_dispatch_info():
    _, pw = _packed(128, 96)
    q = QuantConfig(fmt="hif4", impl="packed")
    info = engine.packed_dispatch_info(q, pw, decode_m=4, prefill_m=128)
    assert info["fused"]
    # off-TPU: the XLA twin, no tiling to report
    assert "XLA" in info["execution"] and info["decode_blocks"] is None
    kernel = engine.packed_dispatch_info(q, pw, decode_m=4, prefill_m=128,
                                         interpret=False)
    assert kernel["fused"] and kernel["decode_blocks"] == (4, 96, 128)
    # qwen1.5-0.5b's MLP up projection: the decode tile spans 1408 of the
    # 2816 columns at the full 1024-deep K (two grid steps, not eleven)
    _, up = _packed(1024, 2816)
    kernel = engine.packed_dispatch_info(q, up, decode_m=1, prefill_m=512,
                                         interpret=False)
    assert kernel["decode_blocks"] == (1, 1408, 1024)
    assert kernel["prefill_blocks"] == (256, 256, 512)
    off = engine.packed_dispatch_info(
        QuantConfig(fmt="hif4", impl="qdq"), pw, decode_m=4, prefill_m=128)
    assert not off["fused"]


# ---------------------------------------------------------------------------
# Block-size selection
# ---------------------------------------------------------------------------


def test_select_block_sizes_regimes():
    # decode: whole M, deep K, as wide in N as the VMEM cap allows
    bm, bn, bk = select_block_sizes(4, 1024, 2048)
    assert bm == 4 and bn == 1024 and bk == 1024
    # Qwen3-4B's widths: K tiles of 512 (1024 divides neither 2560 nor
    # 9728), N up to the 2 M-value cap: 20 and 19 grid steps, not 95
    assert select_block_sizes(16, 9728, 2560) == (16, 2432, 512)
    assert select_block_sizes(16, 2560, 9728) == (16, 2560, 512)
    # a full-K tile (no 512-multiple divides 2816) narrows N to the cap
    assert select_block_sizes(1, 1024, 2816) == (1, 512, 2816)
    for m, n, k in [(16, 9728, 2560), (1, 1024, 2816), (8, 4096, 2560)]:
        _, bn, bk = select_block_sizes(m, n, k)
        assert bn * bk <= _TILE_ELEMS_MAX
    # prefill: square-ish MXU tiles
    bm, bn, bk = select_block_sizes(512, 1024, 2048)
    assert bm == 256 and bn == 256 and bk == 512
    # everything divides and holds whole groups
    for m, n, k in [(1, 96, 128), (7, 160, 192), (300, 96, 448)]:
        bm, bn, bk = select_block_sizes(m, n, k)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0
        assert bk % GROUP == 0


def test_k_axis_is_innermost_grid_axis():
    """The accumulator-revisit invariant the kernels assert: K must be the
    last grid axis so consecutive steps revisit one output tile."""
    assert K_GRID_AXIS == 2  # grid is (M/bm, N/bn, K/bk)
    # and a multi-K-step quantized matmul is numerically right (the revisit
    # pattern actually accumulates rather than overwrites)
    m, k, n = 8, 512, 32
    x = (jax.random.normal(jax.random.PRNGKey(2), (m, k)) * 0.1).astype(
        jnp.float32)
    w = (jax.random.normal(jax.random.PRNGKey(3), (k, n)) * 0.1).astype(
        jnp.float32)
    ai, asc = ref.hif4_quantize_ref(x)
    bi, bsc = ref.hif4_quantize_ref(jnp.asarray(w).T)
    got = bfp_matmul_quantized(ai, asc, bi.T, bsc.T, block_m=8, block_n=16,
                               block_k=128, interpret=True)
    want = ref.bfp_matmul_from_quantized_ref(ai, asc, bi.T, bsc.T)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
