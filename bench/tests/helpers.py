"""Shared set-up of the benchmark's CPU tests: a Finder over the test data
(tiny configurations of both families, a tiny mix, their limits) laid
beside the real files, and runs of the harness with no chip.

A run that compares numbers goes through ``cpu_run_process``: a fresh
process set up as the benchmark sets itself up (``configure_jax``: bf16
rounded as written), which a test process whose JAX backend already runs
cannot be."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from bench.discover import ROOT, Finder

DATA = Path(__file__).resolve().parent / "data"


def finder(extra=(), benchmark=None) -> Finder:
    return Finder(dirs=[*extra, DATA], benchmark=benchmark or DATA / "BENCHMARK.json")


def cpu_run(workload: str, seed: int, *, seconds: float = 0.5,
            trace: bool = False, f: Finder | None = None,
            control: bool = False) -> dict:
    """One run in this process, JAX as the test process has it."""
    from bench import harness

    return harness.run(workload, seed, seconds, trace,
                       t_start=time.perf_counter(), finder=f or finder(),
                       require_tpu=False, configure=False, control=control)


def cpu_run_process(*args: str, timeout: float = 600) -> list:
    """``python -m bench.tests.helpers <args>`` in a fresh process; the JSON
    lines it prints."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.helpers", *args],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]


def _main(argv):
    """run <workload> <seed> [trace] [extra dir] [benchmark json]
    altered <workload> <seed>
    control <workload> <seed>
    readings <workload> <seed,...>"""
    from bench.harness import configure_process

    configure_process()
    mode, workload = argv[0], argv[1]
    if mode == "readings":
        from bench.control import readings

        seeds = [int(s) for s in argv[2].split(",")]
        for row in readings(workload, seeds, seeds, 0.3, finder=finder()):
            print(json.dumps(row), flush=True)
        return
    seed = int(argv[2])
    if mode == "altered":      # one token of every request changed where made
        from repro.runtime import serve_loop

        real = serve_loop.serve_requests

        def altered(cfg, *a, **kw):
            return [o.at[5].set((o[5] + 1) % cfg.vocab)
                    for o in real(cfg, *a, **kw)]

        serve_loop.serve_requests = altered
    trace = len(argv) > 3 and argv[3] == "trace"
    extra = argv[4:5]
    bench = argv[5] if len(argv) > 5 else None
    print(json.dumps(cpu_run(workload, seed, trace=trace,
                             f=finder(extra, bench),
                             control=mode == "control")), flush=True)


if __name__ == "__main__":
    _main(sys.argv[1:])
