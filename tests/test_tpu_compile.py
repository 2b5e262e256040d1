"""Ahead-of-time compiles of the serve path's Pallas kernels for TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies as plain
jnp, so it cannot see what the chip's compiler refuses: blocks that are
not (8, 128)-tiled, casts it has no lowering for, VMEM overruns. Here each
kernel is lowered and compiled for one chip of a DESCRIBED v5e:2x2
topology — the compiler is installed, no chip is attached, nothing runs —
at the published widths of qwen1.5-0.5b (d_model 1024, d_ff 2816, 16 KV
heads x 64) and qwen3-4b (d_model 2560, d_ff 9728, 8 KV heads x 128), in
the decode (M <= 32) and prefill regimes.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process at a time may load the
TPU library, and pytest-xdist workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kvcache
from repro.kernels.fused_attention import (
    fused_decode_attention,
    fused_paged_decode_attention,
)
from repro.kernels.fused_matmul import fused_packed_matmul
from repro.kernels.hif4_quant import hif4_quantize

# (d_model, d_ff, n_kv_heads, n_heads, d_head) at published width
WIDTHS = {
    "qwen1.5-0.5b": (1024, 2816, 16, 16, 64),
    "qwen3-4b": (2560, 9728, 8, 32, 128),
}
DECODE_M = 8             # a batch of single-token rows
PREFILL_M = 8 * 128      # 8 prompts of 128 tokens
PAGE = 64                # KV page tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """Shape-and-dtype on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compiles_a_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def _packed_kv(spec, lead, hkv, dh, tokens):
    g, t = kvcache.split_features(hkv, dh)
    assert t == 0
    return {"codes": spec(lead + (g * 32, tokens), jnp.uint8),
            "meta": spec(lead + (g, tokens), jnp.uint32),
            "tail": spec(lead + (0, tokens), jnp.bfloat16)}


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_hif4_quantize_compiles(spec, arch, m):
    d_model, d_ff, *_ = WIDTHS[arch]
    for k in (d_model, d_ff):
        _compiles_a_kernel(hif4_quantize.lower(spec((m, k), jnp.bfloat16)))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_fused_packed_matmul_compiles(spec, arch, m):
    """Both contraction widths of the MLP: up (K=d_model, N=d_ff) and
    down (K=d_ff, N=d_model) — d_ff has no 512-multiple divisor at
    qwen1.5-0.5b, so the down projection takes a full-K tile."""
    d_model, d_ff, *_ = WIDTHS[arch]
    for k, n in ((d_model, d_ff), (d_ff, d_model)):
        _compiles_a_kernel(fused_packed_matmul.lower(
            spec((m, k), jnp.int8), spec((m, k // 64), jnp.float32),
            spec((k // 2, n), jnp.uint8), spec((k // 64, n), jnp.uint32)))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("capacity,block_kv", [
    (160, None),         # prompt 128 + 32 new tokens: one whole-cache tile
    (192, PAGE),         # page-aligned capacity at the page's tile
    (2048, None),        # streamed 256-token tiles
])
def test_fused_decode_attention_compiles(spec, arch, capacity, block_kv):
    *_, hkv, h, dh = WIDTHS[arch]
    cache = _packed_kv(spec, (DECODE_M,), hkv, dh, capacity)
    _compiles_a_kernel(fused_decode_attention.lower(
        spec((DECODE_M, h, dh), jnp.bfloat16), cache, cache,
        spec((DECODE_M,), jnp.int32), n_kv_heads=hkv, d_head=dh,
        block_kv=block_kv))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_fused_paged_decode_attention_compiles(spec, arch):
    *_, hkv, h, dh = WIDTHS[arch]
    n_pages, max_pages = 64, 3
    pool = _packed_kv(spec, (n_pages,), hkv, dh, PAGE)
    _compiles_a_kernel(fused_paged_decode_attention.lower(
        spec((DECODE_M, h, dh), jnp.bfloat16), pool, pool,
        spec((DECODE_M, max_pages), jnp.int32), spec((DECODE_M,), jnp.int32),
        n_kv_heads=hkv, d_head=dh))
