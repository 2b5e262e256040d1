"""Production meshes for the multi-pod dry-run and the launchers.

v5e target: one pod = 16x16 = 256 chips. Single-pod mesh is
("data", "model") = (16, 16); the multi-pod mesh adds a leading "pod" axis
(2 pods = 512 chips) used for inter-pod data parallelism.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_host_mesh():
    """Whatever devices exist (CPU smoke tests / examples): 1D data mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), **_axis_kw(2))


# Hardware constants for the roofline analysis (TPU v5e, per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
PEAK_OPS_INT8 = 394e12        # OP/s  (the 2x 4-bit-BFP claim maps here)
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link (~ per-device usable)
