"""The readers of the scheduler's spans and counters
(``metrics/sched.queue_wait_p95_ms.py``, ``model.prefill_ms_per_ktok.py``,
``model.decode_step_ms.py``, ``sched.host_share.py``) on a hand-built
trace and hand-built call stats whose answers are worked out by hand, on
a program that records neither (they read nothing, and do not raise), and
in a traced run of the harness on the CPU."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.discover import ROOT
from bench.tests.helpers import DATA, cpu_run_process, finder
from bench.trace import Trace

NEW = ("sched.queue_wait_p95_ms", "model.prefill_ms_per_ktok",
       "model.decode_step_ms", "sched.host_share")


def read(name, run):
    return finder().module("metrics", name).read(run)


def fake_run(trace, calls, traced):
    return SimpleNamespace(trace=trace, calls=calls,
                           traced_calls=lambda: calls[:traced])


def call(seconds, **stats):
    return SimpleNamespace(seconds=seconds, stats=stats)


def small_run():
    """Two traced calls and one after the trace stopped.

    Call 1 (0.1 .. 2.1 s): two prefills of 0.3 s and 0.2 s, two chunks of
    decode (0.4 s, 0.6 s), host work 0.05 + 0.05 and 0.02 + 0.08 s.
    Call 2 (2.2 .. 3.2 s): one prefill of 0.5 s, one chunk of 0.2 s, host
    work 0.1 + 0.1 s. A decode span after the window is left out.
    """
    host = [
        (0.0, 0.1, "bench.prepare"),
        (0.1, 2.1, "bench.call"),
        (0.2, 0.6, "serve.admit"), (0.25, 0.55, "serve.prefill"),
        (0.6, 0.65, "serve.pages"), (0.65, 1.05, "serve.decode"),
        (1.05, 1.1, "serve.account"),
        (1.1, 1.3, "serve.admit"), (1.1, 1.3, "serve.prefill"),
        (1.3, 1.32, "serve.pages"), (1.32, 1.92, "serve.decode"),
        (1.92, 2.0, "serve.account"),
        (2.1, 2.2, "bench.prepare"),
        (2.2, 3.2, "bench.call"),
        (2.25, 2.8, "serve.admit"), (2.3, 2.8, "serve.prefill"),
        (2.8, 2.9, "serve.pages"), (2.9, 3.1, "serve.decode"),
        (3.1, 3.2, "serve.account"),
        (5.0, 6.0, "serve.decode"),
    ]
    trace = Trace({}, host)
    # admitted: 0, 1, ..., 20 ms over 21 requests of the two traced calls
    times = [{"admitted": i * 1e-3, "first_token": 0.5, "finished": 1.0,
              "tokens": 8} for i in range(21)]
    calls = [
        call(2.0, prefill_tokens=300 + 200, decode_steps=2 * 16,
             request_times=dict(enumerate(times[:11]))),
        call(1.0, prefill_tokens=500, decode_steps=16,
             request_times=dict(enumerate(times[11:]))),
        call(9.0, prefill_tokens=1, decode_steps=1,
             request_times={0: dict(times[0], admitted=99.0)}),
    ]
    return fake_run(trace, calls, traced=2)


def test_queue_wait_p95():
    # inclusive quantile of 0..20 ms: position 0.95 * 20 = 19
    assert read("sched.queue_wait_p95_ms", small_run()) == pytest.approx(19.0)
    one = fake_run(None, [call(1.0, request_times={0: {"admitted": 0.25}})],
                   traced=1)
    assert read("sched.queue_wait_p95_ms", one) == pytest.approx(250.0)


def test_prefill_ms_per_ktok():
    # (0.3 + 0.2 + 0.5) s over 1000 prompt tokens: 1 ms a token
    assert read("model.prefill_ms_per_ktok", small_run()) == pytest.approx(1000.0)


def test_decode_step_ms():
    # (0.4 + 0.6 + 0.2) s over 48 steps; the span after the window is out
    assert read("model.decode_step_ms", small_run()) == pytest.approx(25.0)


def test_host_share():
    # (0.05 + 0.05 + 0.02 + 0.08 + 0.1 + 0.1) s of 3.0 s of calls
    assert read("sched.host_share", small_run()) == pytest.approx(40.0 / 3)


@pytest.mark.parametrize("name", NEW)
def test_nothing_recorded_reads_none(name):
    """A program without the spans and counters (or a run without a
    trace) gives no reading and no error."""
    host = [(0.0, 1.0, "bench.call"), (0.2, 0.3, "PjitFunction(serve)")]
    bare = fake_run(Trace({}, host), [call(1.0, max_concurrent=1)], traced=1)
    assert read(name, bare) is None
    untraced = small_run()
    untraced.trace = None
    if name == "sched.queue_wait_p95_ms":        # read from the counters
        assert read(name, untraced) == pytest.approx(19.0)
    else:
        assert read(name, untraced) is None


def test_traced_cpu_run_reads_every_new_metric(tmp_path):
    """A traced run of the tiny cell through the harness and the committed
    readers prints all four readings, each in its range."""
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in real["per_layer"]:
        if m["name"] in NEW:
            bench["per_layer"].append(dict(m, workloads=["tiny-qwen15.tiny"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (out,) = cpu_run_process("run", "tiny-qwen15.tiny", "5", "trace",
                             str(tmp_path), str(tmp_path / "BENCHMARK.json"))
    got = {name: out["metrics"][name]["value"] for name in NEW}
    assert got["sched.queue_wait_p95_ms"] >= 0
    assert got["model.prefill_ms_per_ktok"] > 0
    assert got["model.decode_step_ms"] > 0
    assert 0 < got["sched.host_share"] < 100
