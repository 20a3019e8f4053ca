"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — smoke tests see 1 device; only
dryrun.py sets the 512-placeholder-device XLA flag before first jax use.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds the 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (smoke tests, examples, elastic-rescale tests)."""
    # Auto axes: shardings are propagated by the compiler, as the
    # training stack's NamedSharding annotations expect
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
