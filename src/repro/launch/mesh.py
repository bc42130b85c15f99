"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Target: TPU v5e pods — 256 chips per pod as a
(data=16, model=16) mesh; multi-pod adds a leading "pod" axis.
"""
from __future__ import annotations


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto (GSPMD) axis types on every axis — the
    step functions rely on sharding propagation, which ``jax.make_mesh``'s
    default Explicit axes would replace with sharding-in-types."""
    import jax
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over the CPU's forced host devices (tests/examples)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
