"""The harness finds a cell's configuration, traffic mix, limit and metric
readers by name, from files; a new cell is new files and entries."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.discover import ROOT
from bench.tests.helpers import DATA, cpu_run_process, finder

READER = '''"""Whole serve_requests calls in the window."""


def read(run):
    return len(run.calls)
'''


def test_new_cell_config_mix_and_metric_from_files(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "limits").mkdir()
    conf = json.loads((DATA / "configs" / "tiny-qwen15.json").read_text())
    conf["name"] = "tiny-other"
    (tmp_path / "configs" / "tiny-other.json").write_text(json.dumps(conf))
    mix = {"why": "two lengths", "slots": 2, "requests_per_call": 3,
           "prompt_lengths": [24, 48], "new_tokens": 6, "page_tokens": 8,
           "pool_share": 1.0, "check_requests": 2}
    (tmp_path / "traffic" / "pair.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "window.calls.py").write_text(READER)
    (tmp_path / "limits" / "tiny-other.pair.json").write_text(
        json.dumps({"mean_logit_gap": 0.02, "worst_request_gap": 0.03}))
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-other.pair", "config": "tiny-other",
                               "traffic": "pair", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "window.calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tokens_per_s",
                               "workloads": ["tiny-other.pair"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    where = (str(tmp_path), str(tmp_path / "BENCHMARK.json"))
    (out,) = cpu_run_process("run", "tiny-other.pair", "3", "trace", *where)
    assert out["correct"], out
    assert out["metrics"]["window.calls"]["value"] >= 1
    assert out["metrics"]["sched.max_concurrent"]["value"] == 2
    assert list(out)[-1] == "checks"
    (out,) = cpu_run_process("run", "tiny-other.pair", "4", "no", *where)
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def test_unknown_names_are_errors():
    f = finder()
    with pytest.raises(KeyError):
        f.cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        f.json("traffic", "no-such-mix")


def test_no_tpu_no_result():
    from bench import harness

    with pytest.raises(SystemExit):
        harness.run("tiny-qwen3.tiny", 1, 1.0, False, t_start=0.0,
                    finder=finder(), configure=False)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen1.5-0.5b.interactive", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen1.5-0.5b.interactive", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert Path(tmp_path / "bench" / "run.py").is_file()
