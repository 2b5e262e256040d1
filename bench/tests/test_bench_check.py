"""The output check that decides ``correct``: sound runs pass it, and a
token altered where it is produced, or the float8-stored control, fail
it. At a test size on the CPU; the chip readings are in PERF.md."""
from __future__ import annotations

import numpy as np
import pytest

from bench.generator import Traffic
from bench.tests.helpers import cpu_run_process, finder

CELL = "tiny-qwen3.tiny"


def test_same_work_for_every_seed():
    mix = finder().json("traffic", "tiny")
    a, b = Traffic(mix, 5, 512), Traffic(mix, 2**31 + 12345, 512)
    la = sorted(a.length(r) for r in range(30))
    lb = sorted(b.length(r) for r in range(30))
    assert la == lb                                   # same lengths, other order
    assert [a.length(r) for r in range(30)] != [b.length(r) for r in range(30)]
    assert np.array_equal(a.prompt(7), Traffic(mix, 5, 512).prompt(7))
    assert not np.array_equal(a.prompt(7), b.prompt(7))
    assert a.pool_pages == 4 * (64 + 12 + 4) // 8 + 1


def test_sound_run_is_correct():
    (out,) = cpu_run_process("run", CELL, str(2**31 + 7))
    assert out["correct"] and out["failed"] == 0, out
    assert set(out["checks"]) == {"mean_logit_gap", "worst_request_gap"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_altered_token_is_caught():
    (out,) = cpu_run_process("altered", CELL, "11")
    assert not out["correct"], out
    assert any(c["value"] > c["limit"] for c in out["checks"].values()), out


def test_control_run_is_not_correct():
    (out,) = cpu_run_process("control", CELL, "13")
    assert not out["correct"], out
    assert any(c["value"] > c["limit"] for c in out["checks"].values()), out


@pytest.mark.parametrize("cell", ["tiny-qwen3.tiny", "tiny-qwen15.tiny"])
def test_control_fails_where_the_program_passes(cell):
    limits = finder().json("limits", cell)
    rows = cpu_run_process("readings", cell, "21,22")
    assert len(rows) == 2
    for row in rows:
        assert all(row["program"][k] <= limits[k] for k in row["program"]), row
        assert any(row["control"][k] > limits[k] for k in row["control"]), row
        assert any(row["altered"][k] > limits[k] for k in row["altered"]), row


def test_sample_is_whole_calls_longest_first():
    from types import SimpleNamespace

    from bench.check import sample

    mix = finder().json("traffic", "tiny")
    t = Traffic(mix, 3, 512)
    calls = [SimpleNamespace(rids=list(range(k * 6, k * 6 + 6)),
                             lengths=[t.length(r) for r in range(k * 6, k * 6 + 6)],
                             tokens=[np.full(12, r) for r in range(k * 6, k * 6 + 6)])
             for k in range(4)]
    run = SimpleNamespace(calls=calls, seed=3, traffic=t,
                          mix=dict(mix, check_requests=6))
    picks = sample(run)
    rids = [int(out[0]) for _, out in picks]
    assert len({r // 6 for r in rids}) == 1            # one whole call
    assert len(picks[0][0]) == max(mix["prompt_lengths"])
    assert sorted(rids) == list(range(rids[0] // 6 * 6, rids[0] // 6 * 6 + 6))
