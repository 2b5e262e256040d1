"""Hypothesis property tests for repro.core.hif4 round-trip invariants.

Randomized shapes / magnitudes / group boundaries pin the properties the
scenario matrix and the packed serving stack rest on: exact power-of-two
group scales (scale equivariance), 0xFF-metadata NaN propagation through
EVERY decode path, bit-level pack/unpack idempotence, and bulk-pack ==
token-at-a-time append for the KV cache. Deterministic ci profile, same
importorskip guards as the tier-1 hypothesis tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
hnp = pytest.importorskip("hypothesis.extra.numpy")
st = pytest.importorskip("hypothesis.strategies")

from repro.core import hif4, kvcache
from repro.core import rounding as R

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=50, derandomize=True)
hypothesis.settings.load_profile("ci")


@st.composite
def group_batches(draw, min_scale=-20, max_scale=8):
    """(n, 64) f32 arrays on the bf16 grid, group magnitudes randomized
    across power-of-two decades (well inside the E6M2 scale range)."""
    n = draw(st.integers(min_value=1, max_value=4))
    scale = 2.0 ** draw(st.integers(min_value=min_scale, max_value=max_scale))
    arr = draw(hnp.arrays(
        np.float32, (n, hif4.GROUP_SIZE),
        elements=st.floats(min_value=-4.0, max_value=4.0, width=32)))
    x = jnp.asarray(arr * scale, jnp.bfloat16).astype(jnp.float32)
    return np.asarray(x)


@hypothesis.given(group_batches())
def test_group_scale_is_exactly_on_e6m2_grid(x):
    """The group scale Algorithm 1 emits lives EXACTLY on the E6M2 grid
    (power-of-two times {1, 1.25, 1.5, 1.75}): encoding and decoding it
    is bitwise lossless, so the packed artifact loses nothing."""
    g = hif4.quantize_groups(jnp.asarray(x))
    rt = R.decode_e6m2(R.encode_e6m2(g.e6m2))
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(g.e6m2))


@hypothesis.given(group_batches(min_scale=-10, max_scale=4),
                  st.integers(min_value=-4, max_value=4))
def test_power_of_two_scaling_equivariance(x, k):
    """Scaling a group by 2^k shifts only the (exact power-of-two) scale:
    the reconstruction scales by exactly 2^k, bitwise — the property that
    makes HiF4 payload bytes an exact roofline numerator regardless of
    tensor magnitude."""
    vm = np.abs(x).max(axis=-1)
    hypothesis.assume(bool(np.all((vm == 0) | (vm >= 2.0 ** -16))))
    base = hif4.dequantize_groups(hif4.quantize_groups(jnp.asarray(x)))
    scaled = hif4.dequantize_groups(
        hif4.quantize_groups(jnp.asarray(x * 2.0 ** k)))
    np.testing.assert_array_equal(
        np.asarray(scaled), np.asarray(base) * 2.0 ** k)


@hypothesis.given(group_batches())
def test_pack_unpack_is_bitwise_idempotent(x):
    """unpack(pack(g)) == g on every component, and re-packing reproduces
    the identical 4.5-bit artifact — the packed bytes are a lossless
    encoding of the quantized value."""
    g = hif4.quantize_groups(jnp.asarray(x))
    p = hif4.pack_groups(g)
    g2 = hif4.unpack_groups(p)
    for a, b in zip(g, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p2 = hif4.pack_groups(g2)
    np.testing.assert_array_equal(np.asarray(p.codes), np.asarray(p2.codes))
    np.testing.assert_array_equal(np.asarray(p.meta), np.asarray(p2.meta))


@hypothesis.given(group_batches())
def test_corrupt_meta_nan_propagates_every_path(x):
    """E6M2 code 0xFF decodes to NaN on EVERY path — artifact-layout
    unpack, packed dequantize, and both K-major kernel-tile helpers.
    Corrupted metadata must poison the whole group loudly, never decode
    to silently-wrong values."""
    n = x.shape[0]
    p = hif4.quantize_packed(jnp.asarray(x))
    bad_meta = (p.meta & jnp.uint32(0x00FFFFFF)) | jnp.uint32(0xFF << 24)
    bad = hif4.HiF4Packed(codes=p.codes, meta=bad_meta)

    assert np.all(np.isnan(np.asarray(hif4.unpack_groups(bad).e6m2)))
    assert np.all(np.isnan(
        np.asarray(hif4.dequantize_packed(bad), np.float32)))

    # K-major kernel-tile layout: one column per group row
    codes_km = jnp.asarray(np.asarray(p.codes).reshape(n * 32, 1))
    meta_km = jnp.asarray(np.asarray(bad_meta).reshape(n, 1))
    _, scale_abs = hif4.absorbed_int_km(codes_km, meta_km)
    assert np.all(np.isnan(np.asarray(scale_abs)))
    deq = hif4.dequantize_km(codes_km, meta_km, dtype=jnp.float32)
    assert np.all(np.isnan(np.asarray(deq)))


@hypothesis.given(group_batches(), st.booleans())
def test_dequantize_km_split_is_dequantize_km_deinterleaved(x, nan_meta):
    """The paged attention kernel's dequantize keeps a code byte's two
    rows apart (``dequantize_km_split``); interleaved, its values are
    ``dequantize_km``'s bit for bit, the NaN sentinel included."""
    n = x.shape[0]
    p = hif4.quantize_packed(jnp.asarray(x))
    meta = p.meta
    if nan_meta:
        meta = (meta & jnp.uint32(0x00FFFFFF)) | jnp.uint32(0xFF << 24)
    codes_km = jnp.asarray(np.asarray(p.codes).reshape(n * 32, 1))
    meta_km = jnp.asarray(np.asarray(meta).reshape(n, 1))
    full = np.asarray(hif4.dequantize_km(codes_km, meta_km,
                                         dtype=jnp.float32))
    even, odd = hif4.dequantize_km_split(codes_km, meta_km)
    np.testing.assert_array_equal(np.asarray(even), full[0::2])
    np.testing.assert_array_equal(np.asarray(odd), full[1::2])


@hypothesis.given(group_batches(),
                  st.integers(min_value=0, max_value=31),
                  st.data())
def test_single_meta_bit_flip_is_nan_or_group_local(x, bit, data):
    """The corruption-semantics contract (docs/FORMATS.md): flip ANY
    single bit of ANY packed meta word and the decode either goes NaN
    (the E6M2 byte became the 0xFF sentinel) or perturbs ONLY that
    64-element group — every other group decodes bitwise identically, on
    the artifact path (dequantize_packed) and the K-major kernel path
    (dequantize_km) alike. This locality is what makes quarantining the
    owning request a complete containment."""
    n = x.shape[0]
    g = data.draw(st.integers(min_value=0, max_value=n - 1), label="group")
    p = hif4.quantize_packed(jnp.asarray(x))
    meta = np.asarray(p.meta).copy()
    meta[g] ^= np.uint32(1 << bit)
    bad = hif4.HiF4Packed(codes=p.codes, meta=jnp.asarray(meta))

    clean_pk = np.asarray(hif4.dequantize_packed(p), np.float32)
    flip_pk = np.asarray(hif4.dequantize_packed(bad), np.float32)
    codes_km = jnp.asarray(np.asarray(p.codes).reshape(n * 32, 1))
    clean_km = np.asarray(hif4.dequantize_km(
        codes_km, jnp.asarray(np.asarray(p.meta).reshape(n, 1)),
        dtype=jnp.float32)).reshape(n, hif4.GROUP_SIZE)
    flip_km = np.asarray(hif4.dequantize_km(
        codes_km, jnp.asarray(meta.reshape(n, 1)),
        dtype=jnp.float32)).reshape(n, hif4.GROUP_SIZE)
    np.testing.assert_array_equal(clean_km, clean_pk)   # paths agree clean

    for flip, clean in ((flip_pk, clean_pk), (flip_km, clean_km)):
        others = np.ones(n, bool)
        others[g] = False
        # blast radius: every OTHER group is bitwise untouched
        np.testing.assert_array_equal(flip[others], clean[others])
        if (meta[g] >> 24) == hif4.META_NAN:
            # NaN sentinel: the whole group poisons loudly
            assert np.all(np.isnan(flip[g]))
        else:
            assert np.all(np.isfinite(flip[g]))


@st.composite
def kv_shapes(draw):
    """Randomized KV geometry crossing group boundaries: F = Hkv*Dh sweeps
    whole-group (F % 64 == 0) and staging-tail (F % 64 != 0) layouts."""
    b = draw(st.integers(min_value=1, max_value=2))
    s = draw(st.integers(min_value=1, max_value=6))
    hkv = draw(st.integers(min_value=1, max_value=4))
    dh = draw(st.sampled_from((8, 16, 24, 32, 48, 64)))
    seed = draw(st.integers(min_value=0, max_value=2 ** 16))
    return b, s, hkv, dh, seed


@hypothesis.given(kv_shapes())
def test_bulk_pack_equals_token_at_a_time_append(shape):
    """Per-token grouping: bulk-quantizing a whole sequence produces the
    very bytes of appending its tokens one at a time — in BOTH layouts.
    This is the invariant continuous batching and prefix packing rest on,
    here pinned across randomized batch/seq/head/tail geometry."""
    b, s, hkv, dh, seed = shape
    kv = (jax.random.normal(jax.random.PRNGKey(seed), (b, s, hkv, dh))
          * 0.3).astype(jnp.bfloat16)
    for to_layout in (lambda t: t, kvcache.to_kernel_layout):
        bulk = to_layout(kvcache.quantize_kv(kv))
        cache = jax.tree_util.tree_map(lambda t: jnp.zeros(t.shape, t.dtype),
                                       bulk)
        for i in range(s):
            cache = kvcache.append_token(cache, kv[:, i: i + 1],
                                         jnp.asarray(i))
        for key in bulk:
            np.testing.assert_array_equal(np.asarray(cache[key]),
                                          np.asarray(bulk[key]))
