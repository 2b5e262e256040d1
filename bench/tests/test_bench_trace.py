"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on small traces whose answers are worked out by hand."""
from __future__ import annotations

import json

import pytest

from bench.tests.helpers import DATA
from bench.trace import Op, Trace

MATMUL = "%fused_packed_matmul.3 = f32[8,2816]{1,0} custom-call(s8[16,8,64] %a, u8[512,2816] %c)"
ATTN = "%fused_paged_decode_attention.1 = f32[8,16,1,64]{3,2,1,0} custom-call(s32[8] %p)"


def small_trace() -> Trace:
    ops = [
        Op(0.5, 1.5, MATMUL),                           # clipped to [1, ...]
        Op(2.0, 3.0, MATMUL),
        Op(2.5, 4.0, ATTN),                             # overlaps the matmul
        Op(2.0, 9.0, "%while.7 = (s32[]) while(%t)"),    # a container: left out
        Op(6.0, 7.0, "%copy.2 = u8[4,4]{1,0} copy(%x)"),
        Op(11.0, 12.0, MATMUL),                         # after the window
    ]
    host = [
        (1.0, 1.2, "bench.prepare"),
        (1.2, 5.2, "bench.call"),
        (5.2, 5.5, "bench.prepare"),
        (5.5, 10.0, "bench.call"),
        (4.8, 5.1, "PJRT_LoadedExecutable_Execute"),
    ]
    return Trace({"/device:TPU:0": ops}, host)


def test_window_busy_and_idle():
    t = small_trace()
    assert (t.lo, t.hi, t.window_s) == (1.0, 10.0, 9.0)
    # busy: [1, 1.5] + [2, 4] + [6, 7] = 3.5 s
    assert t.busy_s == pytest.approx(3.5)
    gaps = t.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([3.0, 2.0, 0.5])
    assert gaps[0][0] == "bench.call"                    # 7 .. 10
    assert gaps[1][0] == "bench.call > PJRT_LoadedExecutable_Execute"  # 4 .. 6
    assert gaps[2][0] == "bench.call"                    # 1.5 .. 2


def test_kernel_time_and_shapes():
    t = small_trace()
    assert t.kernel_seconds(r"%fused_packed_matmul[.\d]* = ") == pytest.approx(1.5)
    assert t.kernel_seconds(r"%fused_paged_decode_attention[.\d]* = ") == pytest.approx(1.5)
    assert t.custom_calls() == {"%fused_packed_matmul": 2,
                                "%fused_paged_decode_attention": 1}
    top = dict(t.top_ops())
    assert top["%fused_packed_matmul.3 = f32[8,2816]"] == pytest.approx(1.5)
    assert not any(name.startswith("%while") for name in top)
    assert t.ops_outside == 1


def test_trace_needs_the_window_annotation():
    with pytest.raises(ValueError):
        Trace({"/device:TPU:0": [Op(0.0, 1.0, MATMUL)]}, [(0.0, 1.0, "other")])


def test_recorded_chip_trace():
    """An excerpt of a trace recorded on a TPU v5e (``data/tpu_trace.json``:
    device ops and host spans as the profiler gave them) reduces to the
    numbers stored beside it."""
    rec = json.loads((DATA / "tpu_trace.json").read_text())
    ops = {dev: [Op(*o) for o in evs] for dev, evs in rec["device_ops"].items()}
    t = Trace(ops, [tuple(h) for h in rec["host"]])
    want = rec["expect"]
    # the kernel's time, summed here straight from the excerpt
    lo, hi = t.lo, t.hi
    by_hand = sum(min(e, hi) - max(s, lo) for s, e, text in
                  rec["device_ops"]["/device:TPU:0"]
                  if text.startswith("%fused_paged_decode_attention"))
    assert t.kernel_seconds(want["kernel"]) == pytest.approx(by_hand)
    assert 0 < t.busy_s <= t.window_s
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s == pytest.approx(want["busy_s"])
    assert t.kernel_seconds(want["kernel"]) == pytest.approx(want["kernel_s"])
    assert t.custom_calls() == want["custom_calls"]
