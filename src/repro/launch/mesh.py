"""Mesh construction (assignment §MULTI-POD DRY-RUN + the sharded driver).

Functions — not module-level constants — so importing this module never
touches JAX device state. Every mesh in the repo (production dry-run,
tests, the sharded MWEM driver) goes through `make_mesh`, which asks for
Auto axis types: sharding follows the in/out specs the drivers give.
"""

from __future__ import annotations

import math

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the visible devices, with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_driver_mesh(n_devices: int | None = None, *, model_degree: int = 1):
    """A ("data", "model") mesh over the available devices for the sharded
    MWEM driver: all parallelism on "data" (query rows) by default, with an
    optional model degree for domain-sharded log-weights."""
    if n_devices is None:
        n_devices = jax.device_count()
    if n_devices % model_degree:
        raise ValueError(f"model_degree {model_degree} does not divide "
                         f"device count {n_devices}")
    return make_mesh((n_devices // model_degree, model_degree),
                     ("data", "model"))


def batch_axes(multi_pod: bool):
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if multi_pod else ("data",)
