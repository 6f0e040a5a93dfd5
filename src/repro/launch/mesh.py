"""Production meshes.

Target: TPU v5e pods, 256 chips/pod.  Single-pod (16, 16) ("data","model");
multi-pod (2, 16, 16) ("pod","data","model") — the "pod" axis hosts the
PyVertical data-owner dimension (2 owners = 2 pods).

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding rules place arrays with
    # ``with_sharding_constraint``, which Explicit axes (the default of
    # ``jax.make_mesh`` in the installed JAX) refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh for tests/examples on however many devices exist."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
