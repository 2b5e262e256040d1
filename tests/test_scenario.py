"""The analytic dispatch probe agrees with what a cell actually serves.

Builds two tiny cells (dense hif4 + audio hif4) and checks that
``scenario.probe_dispatch`` agrees with ``engine.attention_dispatch_info``
evaluated on the cache leaves the cell ACTUALLY served (built via
``build_decode_cache``). The probe is trusted because the serve jit cache
prevents runtime spying, so this equivalence is the load-bearing test
behind the dispatch table in ``tests/test_kv_format_matrix.py``.
"""
import jax
import pytest

from repro.configs import get_arch
from repro.core import engine as qengine
from repro.core import kvcache
from repro.core.policy import get_policy
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime import scenario, serve_loop

CELLS = {"dense-hif4": "qwen1.5-0.5b", "audio-hif4": "whisper-tiny"}


@pytest.mark.slow
@pytest.mark.parametrize("arch", CELLS.values(), ids=CELLS.keys())
def test_probe_agrees_with_served_cache(arch):
    """probe_dispatch == attention_dispatch_info on the served leaves."""
    cfg = get_arch(arch).reduced()
    plan = lm.quant_plan(cfg, get_policy(
        "uniform:hif4", impl="packed", kv=kvcache.KVCacheConfig("hif4")))
    ctx = ModelCtx(quant=plan.base, plan=plan, remat=False,
                   attn_q_chunk=8, attn_k_chunk=8)
    sp = serve_loop.prepare_params_for_serving(
        lm.init_params(cfg, jax.random.PRNGKey(0)), cfg, plan)
    sc = serve_loop.ServeConfig(max_new_tokens=4, kv_format="hif4")
    probe = scenario.probe_dispatch(cfg, ctx.quant, sc, sp, batch=1,
                                    prompt_len=8)
    _, cache = serve_loop.build_decode_cache(
        cfg, sp, scenario.prefill_batch(cfg, 1, 8),
        serve_loop.serving_ctx(ctx), sc, quant=ctx.quant)
    entry = cache["self"] if cfg.family == "audio" else cache["kv"]
    a = cfg.attn
    actual = qengine.attention_dispatch_info(
        ctx.quant, entry["k"], n_kv_heads=a.n_kv_heads, d_head=a.d_head)
    assert actual["kernel_eligible"] == probe["attn"]["kernel_eligible"]
    assert actual["route"] == probe["attn"]["route"]
    assert actual["execution"] == probe["attn"]["execution"]
