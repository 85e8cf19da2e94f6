"""Thin wrappers over the JAX 0.9 mesh and shard_map calls.

Each wrapper is now the plain JAX 0.9.0 call (``requirements.txt`` pins
that release); the names stay so that the many call sites need not change
in this step.  Lives in ``repro.core`` (not ``repro.distributed``) so the
core table modules can use them without a core -> distributed import
cycle; ``repro.distributed.sharding`` re-exports them for existing callers.
"""

from __future__ import annotations

import jax
import numpy as np


def supports_u64_sort() -> bool:
    """True when XLA sorts *genuine* uint64 operands on this config.

    jax's default (x64-disabled) config silently canonicalizes uint64
    arrays down to uint32, which would corrupt a packed two-plane sort
    word — so the check is on the **effective** dtype:
    ``canonicalize_dtype(uint64)`` only survives as uint64 when
    ``jax_enable_x64`` is on (globally or inside ``jax.enable_x64(True)``).
    Evaluated at trace time on every call (it is one dict lookup) because
    the x64 config can toggle mid-process; jit caches are keyed on that
    config, so a flip retraces into the matching lane.
    """
    return jax.dtypes.canonicalize_dtype(np.uint64) == np.dtype("uint64")


def axis_size_compat(axis) -> int:
    """Static mesh-axis size inside shard_map."""
    return jax.lax.axis_size(axis)


def shard_map_compat(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off (table pytrees carry
    per-shard state on purpose); ``axis_names`` restricts the manual axes."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False, **kwargs)


def make_mesh_compat(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def set_mesh_compat(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)
