"""The kernels' work counts (``bench/work``) against the program's own byte
count of a decode step (``runtime/scenario.decode_step_bytes``), at a
reduced size on the CPU."""
from __future__ import annotations

import jax
import pytest

from bench.harness import program_config
from bench.tests.helpers import finder

B, PROMPT, NEW = 2, 64, 8


@pytest.fixture(scope="module", params=["tiny-qwen3", "tiny-qwen15"])
def served(request):
    from repro.core import kvcache
    from repro.core.policy import get_policy
    from repro.models import lm
    from repro.models.common import ModelCtx
    from repro.runtime import serve_loop
    from repro.runtime.scenario import prefill_batch

    f = finder()
    conf = f.json("configs", request.param)
    cfg = program_config(conf)
    sizes = f.module("reference", conf["reference"]).Sizes(
        conf["published"], conf["architecture"])
    plan = lm.quant_plan(cfg, get_policy(
        "paper-iv", impl="packed", kv=kvcache.KVCacheConfig("hif4")))
    ctx = ModelCtx(quant=plan.base, plan=plan, remat=False)
    params = serve_loop.prepare_params_for_serving(
        lm.init_params(cfg, jax.random.PRNGKey(0)), cfg, plan)
    sc = serve_loop.ServeConfig(max_new_tokens=NEW, kv_format="hif4")
    _, cache = serve_loop.build_decode_cache(
        cfg, params, prefill_batch(cfg, B, PROMPT), serve_loop.serving_ctx(ctx),
        sc, quant=ctx.quant)
    return f, cfg, sizes, params, cache


def test_fused_matmul_weight_bytes(served):
    from repro.runtime.serve_loop import packed_weight_bytes

    f, cfg, sizes, params, _ = served
    work = f.module("work", "fused_matmul")
    per_layer = sum(work.WEIGHT_BYTES_PER_VALUE * k * n
                    for k, n in work.layer_shapes(sizes))
    assert per_layer * sizes.layers == packed_weight_bytes(params)[0]
    # the weight term of one call's bytes is its packed payload
    k, n = work.layer_shapes(sizes)[-1]
    _, one = work.cost(0, k, n)
    assert one == work.WEIGHT_BYTES_PER_VALUE * k * n


def test_paged_attention_kv_bytes(served):
    from repro.runtime.scenario import decode_step_bytes

    f, cfg, sizes, params, cache = served
    work = f.module("work", "paged_attention")
    cap = PROMPT + NEW
    got = decode_step_bytes(cfg, params, cache, valid_len=cap)["kv_bytes"]
    assert B * sizes.layers * work.kv_bytes(sizes, cap) == got
    # a partly filled cache counts its valid tokens only
    got = decode_step_bytes(cfg, params, cache, valid_len=PROMPT)["kv_bytes"]
    assert B * sizes.layers * work.kv_bytes(sizes, PROMPT) == pytest.approx(got, abs=4 * B * sizes.layers)


def test_model_ops_count_every_token():
    f = finder()
    conf = f.json("configs", "tiny-qwen3")
    s = f.module("reference", conf["reference"]).Sizes(
        conf["published"], conf["architecture"])
    model = f.module("work", "model")
    attn = f.module("work", "paged_attention")
    body = 2 * (s.matmul_params() - s.d * s.vocab)
    head = 2 * s.d * s.vocab
    prompt, new = 16, 5
    by_hand = 0.0
    for p in range(prompt + new - 1):          # positions processed
        by_hand += body + 4 * s.layers * s.heads * s.d_head * (p + 1)
    by_hand += new * head
    assert model.request_ops(s, prompt, new) == pytest.approx(by_hand)
    # decode attention's operations are the decode positions' share of that
    ops, _ = attn.request_cost(s, prompt, new)
    dec = sum(4 * s.layers * s.heads * s.d_head * (p + 1)
              for p in range(prompt, prompt + new - 1))
    assert ops == pytest.approx(dec)


def test_mlp_shapes_follow_the_config():
    f = finder()
    conf = f.json("configs", "tiny-qwen15")
    s = f.module("reference", conf["reference"]).Sizes(
        conf["published"], conf["architecture"])
    shapes = f.module("work", "fused_matmul").layer_shapes(s)
    cfg = program_config(conf)
    assert (cfg.d_model, cfg.d_ff) in shapes and (cfg.d_ff, cfg.d_model) in shapes
    assert cfg.attn.n_heads * cfg.attn.d_head == shapes[0][1]
