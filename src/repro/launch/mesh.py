"""Production meshes.

Single pod: (16, 16) = ("data", "model") - 256 chips (one v5e pod).
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") - 512 chips.

In serving, the "pod" axis is the disaggregation axis (new-generation pool
vs old-generation pool - each pool runs its own pjit program and the
interconnect model prices the cross-pod traffic); in training it is an
extra data-parallel axis. The dry-run proves every (arch x shape) program
shards over all axes of both meshes.

Defined as functions (never module-level constants) so importing this
module cannot touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """A small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple[str, ...]:
    """The axes a global-batch dimension shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
