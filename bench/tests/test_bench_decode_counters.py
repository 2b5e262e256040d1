"""The decode counters of the paged scheduler (``decode_slot_steps_live``,
``decode_kv_tokens``) and their readers (``metrics/sched.decode_fill.py``,
``metrics/model.decode_hbm_roofline.py``): the counters a toy paged serve
adds to ``stats``, worked out by hand; the readers on hand-built call
stats and trace spans; on a program that records neither (they read
nothing, and do not raise); and in a traced run of the harness on the
CPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from bench.discover import ROOT
from bench.tests.helpers import DATA, finder
from bench.trace import Trace
from repro.configs import get_arch
from repro.core import kvcache
from repro.core.qlinear import QuantConfig
from repro.models import lm
from repro.models.common import ModelCtx
from repro.runtime.serve_loop import ServeConfig, serve_requests

FILL, ROOFLINE = "sched.decode_fill", "model.decode_hbm_roofline"

# 2 layers of d 128, ff 256, 4 query heads and 2 KV heads of 32, vocab 512
SIZES = SimpleNamespace(d=128, ff=256, heads=4, kv_heads=2, d_head=32,
                        vocab=512, layers=2)


def read(name, run):
    return finder().module("metrics", name).read(run)


def fake_run(trace, calls, traced, peaks=None):
    return SimpleNamespace(trace=trace, calls=calls, batch=4, sizes=SIZES,
                           peaks=peaks, finder=finder(),
                           traced_calls=lambda: calls[:traced])


def call(**stats):
    return SimpleNamespace(seconds=1.0, stats=stats)


def counted_run(trace=None, peaks=None):
    """Two traced calls of 16 decode steps on 4 slots and one after the
    trace stopped. Decode spans of 0.016 s in each traced call, and one
    after the window."""
    calls = [call(decode_steps=16, decode_slot_steps_live=50,
                  decode_kv_tokens=300),
             call(decode_steps=16, decode_slot_steps_live=30,
                  decode_kv_tokens=500),
             call(decode_steps=1, decode_slot_steps_live=4,
                  decode_kv_tokens=99)]
    return fake_run(trace, calls, traced=2, peaks=peaks)


def spans():
    return Trace({}, [(0.0, 0.4, "bench.call"), (0.1, 0.116, "serve.decode"),
                      (0.4, 1.0, "bench.call"), (0.5, 0.516, "serve.decode"),
                      (5.0, 6.0, "serve.decode")])


@pytest.mark.parametrize("trace", [None, spans()], ids=["untraced", "traced"])
def test_decode_fill_of_the_traced_calls(trace):
    # (50 + 30) live slot-steps of 4 slots x (16 + 16) steps; the third
    # call is after the trace stopped. Read from the counters alone.
    assert read(FILL, counted_run(trace)) == pytest.approx(62.5)


def test_decode_hbm_roofline_worked_by_hand():
    # Body, per layer, (K, N) of q, k, v, o, gate, up, down:
    # 128*128 + 2 * 128*64 + 128*128 + 2 * 128*256 + 256*128 = 147456
    # values; 2 layers at 0.5625 B/value = 165888 B. Head: 2 B x 128 x
    # 512 = 131072 B. KV: (300 + 500) tokens over 32 steps = 25 a step,
    # K and V of 2 heads x 32 at 0.5625 B/value in 2 layers = 3600 B.
    # 300560 B at 1e9 B/s is 300.56 us against (0.016 + 0.016) s / 32
    # steps = 1000 us a step.
    peaks = SimpleNamespace(hbm_bw=1e9)
    assert read(ROOFLINE, counted_run(spans(), peaks)) == pytest.approx(30.056)
    assert read(ROOFLINE, counted_run(None, peaks)) is None     # no trace
    assert read(ROOFLINE, counted_run(spans())) is None         # no chip


@pytest.mark.parametrize("name", [FILL, ROOFLINE])
def test_nothing_recorded_reads_none(name):
    """A program without the counters gives no reading and no error."""
    host = [(0.0, 1.0, "bench.call"), (0.2, 0.3, "serve.decode")]
    bare = fake_run(Trace({}, host), [call(max_concurrent=1, decode_steps=4)],
                    traced=1, peaks=SimpleNamespace(hbm_bw=1e9))
    assert read(name, bare) is None
    assert read(name, fake_run(None, [], traced=0)) is None


# Two requests, prompts of 6 and 14 tokens, decode chunks of 2 steps; the
# first token comes from prefill, so a budget of 5 takes 4 steps (2
# chunks, all kept) and a budget of 4 takes 3 (2 chunks, the last step
# past the budget). A third slot holds no request and counts nothing.
@pytest.mark.parametrize("budget,slots,live,kv_tokens", [
    (5, 2, 2 * 4, (7 + 8 + 9 + 10) + (15 + 16 + 17 + 18)),
    (4, 2, 2 * 3, (7 + 8 + 9) + (15 + 16 + 17)),
    (4, 3, 2 * 3, (7 + 8 + 9) + (15 + 16 + 17)),
], ids=["within-budget", "past-budget", "empty-slot"])
def test_serve_counts_the_live_slot_steps(budget, slots, live, kv_tokens):
    """Each kept step of a slot attends over one more token: the prompt
    and the tokens before it, its own included."""
    cfg = get_arch("qwen1.5-0.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ModelCtx(quant=QuantConfig(fmt="hif4", impl="packed",
                                     kv=kvcache.KVCacheConfig("hif4")),
                   remat=False, attn_q_chunk=2, attn_k_chunk=2)
    reqs = [jax.random.randint(jax.random.PRNGKey(50 + i), (n,), 0, cfg.vocab)
            for i, n in enumerate((6, 14))]
    sc = ServeConfig(max_new_tokens=budget, decode_chunk=2, cache_capacity=24,
                     kv_format="hif4", kv_pages=12, kv_page_tokens=8)
    stats: dict = {}
    serve_requests(cfg, params, reqs, ctx, sc, slots=slots, stats=stats)
    assert stats["decode_steps"] == 4
    assert stats["decode_slot_steps_live"] == live
    assert stats["decode_kv_tokens"] == kv_tokens


# The harness with the chip's peak table lent to a CPU run, so that the
# roofline reader has a bandwidth to divide by: a test of the reader's
# path, not a device number.
LEND_PEAKS = """
import sys
from bench import harness
from bench.peaks import peaks_for
from bench.tests import helpers

setup = harness.Run.setup


def setup_with_peaks(self, *args, **kw):
    setup(self, *args, **kw)
    self.peaks = peaks_for("TPU v5 lite")


harness.Run.setup = setup_with_peaks
helpers._main(sys.argv[1:])
"""


def test_traced_cpu_run_reads_both(tmp_path):
    """A traced run of the tiny cell through the harness and the committed
    readers prints both readings, each a share in range."""
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in real["per_layer"]:
        if m["name"] in (FILL, ROOFLINE):
            bench["per_layer"].append(dict(m, workloads=["tiny-qwen3.tiny"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run(
        [sys.executable, "-c", LEND_PEAKS, "run", "tiny-qwen3.tiny", "5",
         "trace", str(tmp_path), str(tmp_path / "BENCHMARK.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    (out,) = [json.loads(line) for line in p.stdout.splitlines()
              if line.startswith("{")]
    for name in (FILL, ROOFLINE):
        assert 0 < out["metrics"][name]["value"] <= 100, out["metrics"]
