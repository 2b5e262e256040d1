"""The page-table counters of paged decode attention and their reader
(``metrics/attn.pages_walked_share.py``): the counters a toy paged serve
adds to ``stats``, worked out by hand; the reader on hand-built call stats;
on a program that records neither (it reads nothing, and does not raise);
and in a traced run of the harness on the CPU."""
from __future__ import annotations

import json
from types import SimpleNamespace

import jax
import pytest

from bench.discover import ROOT
from bench.tests.helpers import DATA, cpu_run_process, finder
from bench.trace import Trace
from repro.configs import get_arch
from repro.core import kvcache
from repro.core.qlinear import QuantConfig
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime.serve_loop import ServeConfig, serve_requests

NAME = "attn.pages_walked_share"


def read(run):
    return finder().module("metrics", NAME).read(run)


def fake_run(trace, calls, traced):
    return SimpleNamespace(trace=trace, calls=calls,
                           traced_calls=lambda: calls[:traced])


def call(**stats):
    return SimpleNamespace(seconds=1.0, stats=stats)


def counted_run(trace=None):
    """Two traced calls and one after the trace stopped."""
    calls = [call(attn_pages_walked=30, attn_pages_table=100),
             call(attn_pages_walked=60, attn_pages_table=100),
             call(attn_pages_walked=1, attn_pages_table=1)]
    return fake_run(trace, calls, traced=2)


@pytest.mark.parametrize("trace", [None, Trace({}, [(0.0, 1.0, "bench.call")])],
                         ids=["untraced", "traced"])
def test_share_of_the_traced_calls(trace):
    # (30 + 60) walked of (100 + 100) table entries; the third call is
    # after the trace stopped. Read from the counters, with or without a
    # trace.
    assert read(counted_run(trace)) == pytest.approx(45.0)


def test_nothing_recorded_reads_none():
    """A program without the counters gives no reading and no error."""
    host = [(0.0, 1.0, "bench.call"), (0.2, 0.3, "PjitFunction(serve)")]
    bare = fake_run(Trace({}, host), [call(max_concurrent=1)], traced=1)
    assert read(bare) is None
    assert read(fake_run(None, [], traced=0)) is None


def test_serve_counts_the_pages_each_step_walks():
    """Two slots, prompts of 6 and 14 tokens, 8-token pages, a table of
    3 entries (capacity 24): each decode step attends over one more token
    per slot, and walks the pages those tokens fill."""
    cfg = get_arch("qwen1.5-0.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ModelCtx(quant=QuantConfig(fmt="hif4", impl="packed",
                                     kv=kvcache.KVCacheConfig("hif4")),
                   remat=False, attn_q_chunk=2, attn_k_chunk=2)
    reqs = [jax.random.randint(jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab)
            for i, n in enumerate((6, 14))]
    sc = ServeConfig(max_new_tokens=5, decode_chunk=2, cache_capacity=24,
                     kv_format="hif4", kv_pages=12, kv_page_tokens=8)
    stats: dict = {}
    serve_requests(cfg, params, reqs, ctx, sc, slots=2, stats=stats)
    steps = stats["decode_steps"]
    assert steps == 4                  # the first token comes from prefill
    # slot 0 attends over 7, 8, 9, 10 tokens: 1 + 1 + 2 + 2 pages;
    # slot 1 over 15, 16, 17, 18: 2 + 2 + 3 + 3
    assert stats["attn_pages_walked"] == 6 + 10
    assert stats["attn_pages_table"] == steps * 2 * 3


def test_traced_cpu_run_reads_pages_walked_share(tmp_path):
    """A traced run of the tiny cell through the harness and the committed
    reader prints a share of the table in range."""
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    (metric,) = [m for m in real["per_layer"] if m["name"] == NAME]
    bench["per_layer"].append(dict(metric, workloads=["tiny-qwen15.tiny"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (out,) = cpu_run_process("run", "tiny-qwen15.tiny", "5", "trace",
                             str(tmp_path), str(tmp_path / "BENCHMARK.json"))
    assert 0 < out["metrics"][NAME]["value"] <= 100
