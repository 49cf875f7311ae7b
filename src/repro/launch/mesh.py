"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The dry-run forces 512 host devices *before*
importing jax; tests and benches see the real single CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=jax.devices()[: _prod(shape)],
    )


def make_nonp2_mesh():
    """Non-power-of-two demo mesh (the paper's headline case): 6 x 16 = 96
    chips — e.g. a 128-chip pod after 2 DP-slice failures, kept running by
    the MRD shifts instead of regrouping to 64."""
    return jax.make_mesh(
        (6, 16), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
        devices=jax.devices()[:96],
    )


def make_mesh_by_name(name: str):
    if name in ("single", "single_pod"):
        return make_production_mesh(multi_pod=False)
    if name in ("multi", "multi_pod"):
        return make_production_mesh(multi_pod=True)
    if name == "nonp2":
        return make_nonp2_mesh()
    raise ValueError(f"unknown mesh {name!r} (single|multi|nonp2)")


def _prod(t):
    out = 1
    for x in t:
        out *= x
    return out
