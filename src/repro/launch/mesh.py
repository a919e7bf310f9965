"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing never touches
jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; real deployments get the same shapes from actual TPU slices.

Mesh axes:
    pod    — outer data parallelism across pod boundaries (DCI links);
             hierarchical gradient reduction + optional compression
    data   — in-pod data parallelism (+ FSDP param sharding)
    model  — tensor/expert/sequence parallelism (ICI)
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the model code places
    data with sharding hints and lets GSPMD propagate the rest."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (same axis names, all size 1)."""
    return make_mesh((1, 1), ("data", "model"))
