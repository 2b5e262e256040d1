"""Entry-point JAX setup: where the compilation cache lands, and the XLA
flag that makes bf16 rounding independent of fusion.

Each case runs in a fresh interpreter: XLA reads its flags once, when
the first backend starts, so the setup must precede any computation."""
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os, jax
from repro.jax_setup import configure_jax
returned = configure_jax()
jax.numpy.zeros(1).block_until_ready()
print(json.dumps({"returned": returned,
                  "config": jax.config.jax_compilation_cache_dir,
                  "xla_flags": os.environ.get("XLA_FLAGS", "")}))
"""


def _run(script, **env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO_ROOT, "src"), **env_overrides)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("placed", [False, True])
def test_cache_dir_placed_from_outside_or_fixed_in_checkout(tmp_path, placed):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if placed else {}
    r = _run(_PROBE, **env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if placed:
        # JAX reads the variable itself; code configures no other path
        assert out["returned"] == str(tmp_path)
        assert out["config"] in (None, str(tmp_path))
    else:
        assert out["returned"] == out["config"] == os.path.join(
            REPO_ROOT, ".jax_cache")
    assert "--xla_allow_excess_precision=false" in out["xla_flags"]


def test_caller_xla_flag_is_kept():
    r = _run(_PROBE, XLA_FLAGS="--xla_allow_excess_precision=true")
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["xla_flags"] == "--xla_allow_excess_precision=true"


def test_refuses_after_a_backend_started():
    r = _run("import jax; jax.numpy.zeros(1)\n"
             "from repro.jax_setup import configure_jax\nconfigure_jax()")
    assert r.returncode != 0
    assert "before JAX starts a backend" in r.stderr
