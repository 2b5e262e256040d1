"""Process-wide JAX setup that entry points make before anything compiles.

Entry points (``chip_smoke.py``, ``python -m repro``, the serve and train
launchers, ``bench/harness.py``) call :func:`configure_jax` once, before
JAX starts a backend; library modules never do. It does two things.

* **Compilation cache.** With ``JAX_COMPILATION_CACHE_DIR`` set, nothing
  is configured in code — JAX reads the variable itself, so whoever runs
  the program decides where the cache lives. Otherwise the cache goes to
  ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path never
  depends on a temporary name, a process id or the time: it is part of
  what a later run must find again.
* **bf16 rounding as written.** ``--xla_allow_excess_precision=false`` is
  added to ``XLA_FLAGS`` unless the caller already chose a value. With
  excess precision allowed, XLA may keep fused bf16 intermediates (the
  residual stream, norm outputs) in f32, and how much it keeps depends on
  how each program fused. HiF4 activation quantization is discontinuous:
  one last-bit difference before a quantization site moves a group's
  scale or a quarter step. So the same model served through two programs
  (packed kernels vs the qdq reference, batch 1 vs 8, paged vs contiguous
  decode) would quantize differently, and results would depend on the
  compiler's fusion choices rather than on the model.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import xla_bridge

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
EXCESS_PRECISION_FLAG = "--xla_allow_excess_precision=false"


def configure_jax() -> str:
    """Set the XLA flag and the compilation cache; returns the cache
    directory. Raises if a JAX backend already exists (XLA reads its flags
    once, when the first backend starts)."""
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("configure_jax() must run before JAX starts a "
                           "backend (before the first computation)")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_allow_excess_precision" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {EXCESS_PRECISION_FLAG}".strip()
    placed = os.environ.get(CACHE_ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
