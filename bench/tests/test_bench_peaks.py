"""The table of published peaks, keyed by device kind."""
from __future__ import annotations

import json

import pytest

from bench.peaks import UnknownDevice, peaks_for


def test_v5e_peaks_and_source():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bw) == (197e12, 393e12, 819e9)
    assert p.highest_ops == 393e12
    assert "TPU v5e" in p.source


def test_unknown_device_is_an_error(tmp_path):
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v5 lite", table=_table(tmp_path, {}))


def test_least_time_names_its_bound():
    p = peaks_for("TPU v5 lite")
    assert p.least_seconds(393e12, 1.0) == (1.0, "compute")
    assert p.least_seconds(1.0, 819e9) == (1.0, "memory")


def _table(tmp_path, entries):
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps(entries))
    return path
