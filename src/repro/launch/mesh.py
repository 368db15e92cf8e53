"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips).

    Axis order is DCN-outermost: the `pod` axis varies slowest so that
    cross-pod collectives (gradient all-reduce over `pod`+`data`) decompose
    into intra-pod ICI reductions plus one DCN exchange.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model) mesh with
    model=1 — used by smoke tests and the CPU examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
