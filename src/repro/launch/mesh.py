"""Production meshes.  Functions, not module constants: importing this module
never touches jax device state (the dry-run sets XLA_FLAGS first)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  jax 0.9 defaults to Explicit
    axes, on which ``with_sharding_constraint`` (``repro.sharding``)
    refuses to place arrays."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16,16) over ("data","model").
    Multi-pod: 2 pods = 512 chips (2,16,16) over ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist locally (examples/tests)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))
