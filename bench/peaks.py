"""Published peaks of each chip, keyed by JAX's ``device_kind``.

The table is ``peaks.json``; a device that is not in it is an error, never
a default. ``highest_ops`` is the largest operation rate the chip has for
any operand type: a share of it cannot pass 100% whatever type the
program's matrix units are fed.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


class Peaks:
    def __init__(self, kind: str, entry: dict):
        self.kind = kind
        self.source = entry["source"]
        self.bf16_flops = float(entry["bf16_flops_per_s"])
        self.int8_ops = float(entry["int8_ops_per_s"])
        self.hbm_bw = float(entry["hbm_bytes_per_s"])

    @property
    def highest_ops(self) -> float:
        return max(self.bf16_flops, self.int8_ops)

    def least_seconds(self, ops: float, nbytes: float) -> tuple[float, str]:
        """The least time a call can take, and which bound sets it."""
        t_ops, t_bytes = ops / self.highest_ops, nbytes / self.hbm_bw
        return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def peaks_for(kind: str, table: Path = TABLE) -> Peaks:
    with open(table) as f:
        entries = json.load(f)
    if kind not in entries:
        raise UnknownDevice(f"no published peaks for device_kind {kind!r} in "
                            f"{table}; known: {sorted(entries)}")
    return Peaks(kind, entries[kind])
