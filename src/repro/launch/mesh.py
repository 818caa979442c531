"""Production mesh construction (DESIGN.md §6, assignment §dry-run).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (8 fake devices)."""
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_trainer_mesh(n_devices: int | None = None):
    """1-D mesh for the batched SVM trainer's shard_map variant.

    The single axis is named ``"pairgrid"`` (`trainer.PAIRGRID_AXIS`): the
    flattened OvO-pair x gamma axis of the CV-grid program shards across
    it with no collectives (DESIGN.md §4.4).
    """
    n = int(n_devices) if n_devices is not None else len(jax.devices())
    return jax.make_mesh((n,), ("pairgrid",), **_axis_kwargs(1))


#: Mesh axis the streaming Monte-Carlo chunk shards over (DESIGN.md §10).
VARIANTS_AXIS = "variants"


def make_variant_mesh(n_devices: int | None = None):
    """1-D mesh for the streaming Monte-Carlo engine's shard_map leg.

    The single axis is named ``"variants"`` (:data:`VARIANTS_AXIS`): each
    device generates and scores its slice of a variant chunk, the
    psum-able accumulator sums merge with one collective per chunk, and
    the running :class:`~repro.core.mcstream.StreamStats` state stays
    replicated (DESIGN.md §10.4).
    """
    n = int(n_devices) if n_devices is not None else len(jax.devices())
    return jax.make_mesh((n,), (VARIANTS_AXIS,), **_axis_kwargs(1))


#: Mesh axis the serving engine's data-parallel forward shards over
#: (DESIGN.md §12).
SERVING_AXIS = "batch"


def make_serving_mesh(n_devices: int | None = None):
    """1-D mesh for the fleet serving engine's data-parallel forward.

    The single axis is named ``"batch"`` (:data:`SERVING_AXIS`): the
    engine's padded dispatch batch shards across it (banks replicated,
    no collectives — each device runs the exact single-device labels
    program on its row slice, DESIGN.md §12.1).  A 1-device mesh is
    valid and is how the analyzer verifies the sharded program's
    donation contract on single-device CI.
    """
    n = int(n_devices) if n_devices is not None else len(jax.devices())
    return jax.make_mesh((n,), (SERVING_AXIS,), **_axis_kwargs(1))


def dp_axes(multi_pod: bool) -> tuple:
    return ("pod", "data") if multi_pod else ("data",)
