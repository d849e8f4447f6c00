"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS before first jax init, while tests/benches must see 1.
"""

from __future__ import annotations

import jax

# Auto axes: the sharding rules constrain activations with
# with_sharding_constraint, which jax.make_mesh's default Explicit axes
# refuse
_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has (CPU tests: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=(_AUTO,) * 2)
