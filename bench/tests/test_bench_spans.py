"""The paged scheduler's spans and counters, recorded by JAX's profiler on
a toy serve inside a ``bench.call`` annotation and reduced as a
``--trace 1`` run reduces them: every span is there, nested as the
scheduler opens them, counted once per admission or chunk, and the
window the benchmark takes from its own marks does not move."""
from __future__ import annotations

import glob
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import pytest

from bench.trace import Trace
from repro.configs import get_arch
from repro.core import kvcache
from repro.core.qlinear import QuantConfig
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime.serve_loop import ServeConfig, serve_requests

CFG = get_arch("qwen1.5-0.5b").reduced()
CHUNK = 2


def _served_trace():
    """Three prompts through two slots (the third waits for a slot), traced;
    returns (the reduced trace, the call's stats)."""
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    ctx = ModelCtx(quant=QuantConfig(fmt="hif4", impl="packed",
                                     kv=kvcache.KVCacheConfig("hif4")),
                   remat=False, attn_q_chunk=2, attn_k_chunk=2)
    reqs = [jax.random.randint(jax.random.PRNGKey(40 + i), (6 + 4 * i,), 0,
                               CFG.vocab) for i in range(3)]
    sc = ServeConfig(max_new_tokens=5, decode_chunk=CHUNK, cache_capacity=24,
                     kv_format="hif4", kv_pages=12, kv_page_tokens=8)
    serve_requests(CFG, params, reqs, ctx, sc, slots=2)     # compile first
    d = tempfile.mkdtemp(prefix="bench-spans-")
    stats: dict = {}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.call"):
                jax.device_get(serve_requests(CFG, params, reqs, ctx, sc,
                                              slots=2, stats=stats))
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        trace = Trace.from_profile(jax.profiler.ProfileData.from_file(path))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return trace, stats


@pytest.fixture(scope="module")
def served():
    return _served_trace()


def _spans(trace, name):
    return [(s, e) for s, e, n in trace.host if n == name]


def _inside(inner, outer):
    return all(any(os <= s and e <= oe for os, oe in outer) for s, e in inner)


def test_spans_nest_inside_the_call(served):
    trace, _ = served
    call = _spans(trace, "bench.call")
    assert len(call) == 1
    names = ("serve.setup", "serve.admit", "serve.prefill", "serve.pages",
             "serve.decode", "serve.account", "serve.finish")
    for name in names:
        assert _spans(trace, name), name
        assert _inside(_spans(trace, name), call), name
    assert _inside(_spans(trace, "serve.prefill"), _spans(trace, "serve.admit"))
    # the scheduler's phases follow one another, never overlap, from its
    # set-up to its finish
    phases = sorted(sp for name in names if name != "serve.prefill"
                    for sp in _spans(trace, name))
    assert all(e <= s2 for (_, e), (s2, _) in zip(phases, phases[1:]))
    assert phases[0] == _spans(trace, "serve.setup")[0]
    assert phases[-1] == _spans(trace, "serve.finish")[0]
    # the window is still the benchmark's own marks
    assert (trace.lo, trace.hi) == call[0]


def test_span_counts_match_the_counters(served):
    trace, stats = served
    times = stats["request_times"]
    assert sorted(times) == [0, 1, 2]
    assert len(_spans(trace, "serve.admit")) == len(times)
    assert len(_spans(trace, "serve.prefill")) == stats["prefills"] == 3
    assert stats["prefill_tokens"] == 6 + 10 + 14
    assert len(_spans(trace, "serve.decode")) == stats["decode_chunks"]
    assert len(_spans(trace, "serve.pages")) == stats["decode_chunks"]
    assert len(_spans(trace, "serve.account")) == stats["decode_chunks"]
    assert stats["decode_steps"] == stats["decode_chunks"] * CHUNK
    # 5 tokens: the first from the prefill, 4 from two chunks of 2, so each
    # request is decoded for two chunks; the third waits for a free slot
    assert stats["decode_chunks"] == 4


def test_request_times_are_ordered(served):
    _, stats = served
    times = stats["request_times"]
    for t in times.values():
        assert 0 <= t["admitted"] <= t["first_token"] <= t["finished"]
        assert t["tokens"] == 5
    # the third request is admitted only after one of the first two finished
    assert times[2]["admitted"] >= min(times[0]["finished"],
                                       times[1]["finished"])
    assert "request_times" not in stats["reports"]
