"""PackedW serving path: 4.5-bit packed weights must produce EXACTLY the
same logits as offline-QDQ'd dense weights (pack/unpack is lossless on
quantized values), at 3.56x less weight residency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.qlinear import PackedW, QuantConfig, quantize_params_offline
from repro.models import lm
from repro.models.common import ModelCtx

CFG = get_arch("qwen1.5-0.5b").reduced()
CTX = ModelCtx(quant=QuantConfig(fmt="hif4", offline_weights=True),
               remat=False, attn_q_chunk=32, attn_k_chunk=32)


def test_packedw_roundtrip_2d():
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 96), jnp.bfloat16) * 0.05
    p = PackedW.from_dense(w, (0,))
    deq = p.dequantize()
    assert deq.shape == (128, 96) and deq.dtype == jnp.bfloat16
    # equals direct QDQ along axis 0
    from repro.core import hif4
    want = hif4.qdq(w.astype(jnp.float32), axis=0)
    np.testing.assert_array_equal(
        np.asarray(deq.astype(jnp.float32)), np.asarray(want))
    # 3.56x storage
    packed_bytes = p.codes.size + 4 * p.meta.size
    assert packed_bytes / (w.size * 2) < 0.30


def test_packedw_roundtrip_4d_wo():
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 128), jnp.bfloat16) * 0.1
    p = PackedW.from_dense(w, (0, 1))          # contract (H, Dh)
    deq = p.dequantize()
    assert deq.shape == (128, 128)


@pytest.mark.slow
def test_packed_serving_matches_offline_qdq():
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, CFG.vocab)

    # reference: offline QDQ'd dense weights
    ref_params = dict(params)
    ref_params["blocks"] = quantize_params_offline(
        params["blocks"], QuantConfig(fmt="hif4"), contract_axis=0)
    ref_logits, _ = lm.prefill(ref_params, {"tokens": tokens}, CFG, CTX)

    # packed: same quantized values, 4.5-bit buffers, dequantized in-graph
    packed_params = lm.pack_params_for_serving(params, CFG)
    logits, cache = lm.prefill(packed_params, {"tokens": tokens}, CFG, CTX)

    # packed weights only cover the default-packable matmuls; biases/norms are
    # identical, so logits should agree to bf16 tolerance
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=0.02, atol=0.02)

    # and a decode step runs
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    cache = lm.pad_cache(cache, CFG, 24)
    logits2, _ = lm.decode_step(packed_params, tok, cache, CFG, CTX)
    assert bool(jnp.all(jnp.isfinite(logits2)))


@pytest.mark.slow
def test_fully_packed_serving_residency():
    """Packed weights AND a packed KV cache together: the whole serving
    working set (weights 0.5625 B/value, cache 4.5 bits/value + tail)
    measured off the real pytrees, while decode still runs."""
    from repro.runtime.serve_loop import (
        ServeConfig, kv_cache_bytes, packed_weight_bytes,
        prepare_params_for_serving, serve)

    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    qp = QuantConfig(fmt="hif4", impl="packed")
    serving_params = prepare_params_for_serving(params, CFG, qp)
    nbytes, nvals = packed_weight_bytes(serving_params)
    assert nvals and nbytes / nvals == 0.5625

    cap = 24
    packed_cache = lm.init_cache(CFG, 2, cap, kv_format="hif4")
    bf16_cache = lm.init_cache(CFG, 2, cap, kv_format="bf16")
    pk_bytes, slots = kv_cache_bytes(packed_cache)
    bf_bytes, slots_bf = kv_cache_bytes(bf16_cache)
    assert slots == slots_bf == 2 * cap
    assert bf_bytes / pk_bytes >= 3.0          # >= 3x cache reduction

    ctx = ModelCtx(quant=qp, remat=False, attn_q_chunk=32, attn_k_chunk=32)
    prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 8),
                                            0, CFG.vocab)}
    toks = serve(CFG, serving_params, prompts, ctx,
                 ServeConfig(max_new_tokens=4, kv_format="hif4"))
    assert toks.shape == (2, 4) and bool(jnp.all(toks >= 0))


@pytest.mark.parametrize("impl", ["qdq", "packed"])
def test_prefill_jitted_equals_eager_bitwise(impl):
    """HiF4 activation quantization is discontinuous, so the bf16 glue
    feeding it (residual stream, norm inputs and outputs) must round as
    written however XLA fuses: a jitted and an eager prefill of the same
    model give bitwise the same logits. Tests run with XLA's default
    (excess precision allowed), so the model's own rounding is what holds."""
    import dataclasses

    from repro.core import kvcache
    from repro.core.policy import get_policy
    from repro.runtime.serve_loop import (prepare_params_for_serving,
                                          serving_ctx)

    cfg = dataclasses.replace(CFG, n_layers=2)
    plan = lm.quant_plan(cfg, get_policy("paper-iv", impl=impl,
                                         kv=kvcache.KVCacheConfig("hif4")))
    ctx = serving_ctx(ModelCtx(quant=plan.base, plan=plan, remat=False,
                               attn_q_chunk=32, attn_k_chunk=32))
    params = prepare_params_for_serving(
        lm.init_params(cfg, jax.random.PRNGKey(0)), cfg, plan)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                          cfg.vocab)}
    jitted = jax.jit(lambda p, b: lm.prefill(p, b, cfg, ctx)[0])(params, batch)
    with jax.disable_jit():
        eager = lm.prefill(params, batch, cfg, ctx)[0]
    np.testing.assert_array_equal(np.asarray(jitted, np.float32),
                                  np.asarray(eager, np.float32))


@pytest.mark.parametrize("width", [1024, 896, 1])
def test_row_mean_sums_in_one_fixed_order(width):
    """Norm statistics add in one pairwise order, not in the order of the
    layout XLA picks for a reduce: jitted or eager, the mean is bitwise a
    host-side halving tree over the zero-padded row."""
    from repro.models.common import row_mean

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (4, width)),
                   np.float32) ** 2
    ref = np.pad(x, ((0, 0), (0, (1 << (width - 1).bit_length()) - width)))
    while ref.shape[-1] > 1:
        half = ref.shape[-1] // 2
        ref = ref[:, :half] + ref[:, half:]
    ref = ref * np.float32(1.0 / width)
    np.testing.assert_array_equal(np.asarray(jax.jit(row_mean)(x)), ref)
    np.testing.assert_array_equal(np.asarray(row_mean(jnp.asarray(x))), ref)
