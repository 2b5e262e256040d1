"""Find the benchmark's parts by name, from files.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kernel's work count sits in a file of its own
under ``bench/``, named after it:

    configs/<config>.json      sizes, source, departures, program build
    traffic/<mix>.json         parameters of the one traffic generator
    limits/<workload>.json     the limit of the output check and its readings
    metrics/<metric>.py        a reader: ``read(run) -> float | None``
    work/<kernel>.py           operations and bytes of a kernel call
    reference/<family>.py      the plain reference a configuration names

Adding a cell, a configuration, a mix or a metric is adding files and an
entry in ``BENCHMARK.json``; nothing here changes. ``Finder`` searches its
directories in order, so a test can lay new files beside the real ones.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Finder:
    def __init__(self, dirs=(), benchmark: Path | None = None):
        self.dirs = [Path(d) for d in dirs] + [BENCH_DIR]
        self.benchmark_path = Path(benchmark or ROOT / "BENCHMARK.json")
        self._modules: dict = {}

    def path(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind} named {name!r}: looked for "
                                f"{kind}/{name}{suffix} under "
                                f"{', '.join(str(d) for d in self.dirs)}")

    def json(self, kind: str, name: str) -> dict:
        with open(self.path(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        p = self.path(kind, name, ".py")
        mod = self._modules.get(p)
        if mod is None:
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), p)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
            self._modules[p] = mod
        return mod

    def benchmark(self) -> dict:
        with open(self.benchmark_path) as f:
            return json.load(f)

    def cell(self, workload: str) -> tuple[dict, dict]:
        """(the workload entry, the whole BENCHMARK.json)."""
        bench = self.benchmark()
        for w in bench["workloads"]:
            if w["name"] == workload:
                return w, bench
        raise KeyError(f"no workload {workload!r} in {self.benchmark_path}")


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` (end_to_end | per_layer) a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]
