"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
XLA_FLAGS ordering and for tests that run on 1 CPU device. Every mesh has
Auto axis types: shardings are propagated by the compiler from the
in/out shardings the step builders give it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """AbstractMesh: spec logic needs only shape+names, not real devices."""
    return jax.sharding.AbstractMesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis is
    data-parallel over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: tuple[int, ...] = (1,), axes: tuple[str, ...] = ("data",)):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    return make_mesh(shape, axes)
