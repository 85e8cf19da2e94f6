"""Sharding rules: parameter / optimizer / batch / cache PartitionSpecs.

Strategy (DESIGN.md §5): FSDP over the ``data`` axis + TP over ``model`` +
EP (experts over ``model``) + sequence sharding for long-context caches.
The ``pod`` axis, when present, extends data parallelism (batch and FSDP
both widen over pod x data).

Rules are path-keyword driven with a final divisibility guard: any dim not
divisible by its assigned axis size falls back to replication for that dim
— so one rule set serves all ten architectures (uneven head counts, odd
vocab sizes, 1500-frame cross caches, ...).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def _fit(spec: tuple, shape: tuple, mesh: Mesh) -> P:
    """Clip a logical spec to a concrete shape with divisibility fallback."""
    if len(spec) < len(shape):                       # leading stack dims
        spec = (None,) * (len(shape) - len(spec)) + tuple(spec)
    spec = tuple(spec[-len(shape):]) if shape else ()
    out = []
    for dim, axis in zip(shape, spec):
        out.append(axis if axis and dim % _axis_size(mesh, axis) == 0 else None)
    return P(*out)


# logical 2-D cores: (row_axis, col_axis).  DATA/MODEL are placeholders
# resolved against the mesh (DATA widens to ('pod','data') on multi-pod).
_PARAM_RULES: list[tuple[str, tuple]] = [
    # MoE experts: (E, D, F) / (E, F, D) with E on the model axis (EP)
    (r"experts.*(gate|up)", ("MODEL", "DATA", None)),
    (r"experts.*down", ("MODEL", None, "DATA")),
    # embeddings / heads
    (r"embed.*table", ("MODEL", "DATA")),
    (r"lm_head", ("DATA", "MODEL")),
    (r"frontend_proj", ("DATA", "MODEL")),
    # attention projections
    (r"(wq|wk|wv|wuq|wdq|wdkv|wkr|wuk|wuv)\b.*w$", ("DATA", "MODEL")),
    (r"wo\b.*w$", ("MODEL", "DATA")),
    # dense mlp
    (r"(gate|up|wk)\b.*w$", ("DATA", "MODEL")),
    (r"(down|wv)\b.*w$", ("MODEL", "DATA")),
    # mamba
    (r"in_proj", ("DATA", "MODEL")),
    (r"out_proj", ("MODEL", "DATA")),
    (r"x_proj", ("MODEL", None)),
    (r"dt_proj", (None, "MODEL")),
    (r"(conv_w|conv_b|a_log|d_skip)", ("MODEL",)),
    # rwkv
    (r"(wr|wg)\b.*w$", ("DATA", "MODEL")),
    (r"(mix_a|decay_a)", ("DATA", None)),
    (r"(mix_b|decay_b)", (None, None, "MODEL")),
    # router fp32, norms, biases: replicate
    (r"router", (None, None)),
]


def _resolve(axis, mesh: Mesh, mode: str = "train"):
    if axis == "DATA":
        if mode == "serve":
            return None          # no FSDP: weights must not gather per token
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return dp if len(dp) > 1 else (dp[0] if dp else None)
    if axis == "MODEL":
        if mode == "serve":
            # serving: the whole mesh is tensor-parallel for weights — a
            # decode step touches every weight once, so FSDP-style gathers
            # would move the full model over ICI per token (§Perf cell 3)
            axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.axis_names)
            return axes if len(axes) > 1 else (axes[0] if axes else None)
        return "model" if "model" in mesh.axis_names else None
    return axis


def param_spec(path: str, shape: tuple, mesh: Mesh, mode: str = "train") -> P:
    for pat, spec in _PARAM_RULES:
        if re.search(pat, path):
            resolved = tuple(_resolve(a, mesh, mode) for a in spec)
            return _fit(resolved, shape, mesh)
    if len(shape) >= 2:
        resolved = (_resolve("DATA", mesh, mode), _resolve("MODEL", mesh, mode))
        return _fit(resolved, shape, mesh)
    return _fit((None,) * len(shape), shape, mesh)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def tree_shardings(tree: Any, mesh: Mesh, mode: str = "train") -> Any:
    """NamedSharding pytree for params or optimizer state (same rules —
    opt-state leaves inherit the rule matched by their param path prefix,
    clipped to their own rank, so Adafactor's vr/vc factor shardings follow
    the param automatically).  mode="serve" turns FSDP off (see _resolve)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in leaves:
        spec = param_spec(_path_str(kp), tuple(leaf.shape), mesh, mode)
        out.append(NamedSharding(mesh, spec))
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_spec(shape: tuple, mesh: Mesh) -> P:
    """Shard dim0 (batch) over pod+data when divisible."""
    dp = _resolve("DATA", mesh)
    return _fit((dp,) + (None,) * (len(shape) - 1), shape, mesh)


def batch_shardings(batch_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_spec(tuple(leaf.shape), mesh)),
        batch_tree)


def cache_shardings(cache_tree: Any, mesh: Mesh, cfg: ModelConfig,
                    cell: ShapeCell) -> Any:
    """Decode-cache placement.

    Priority per leaf (shape (L, B, S, [H, hd]) or state tensors):
      1. batch over pod+data when divisible,
      2. kv-heads over model when divisible, else sequence over model,
      3. batch==1 long-context: sequence over every available axis.
    """
    dp = _resolve("DATA", mesh)
    model = _resolve("MODEL", mesh)
    dp_size = _axis_size(mesh, dp)

    def leaf_spec(leaf):
        shape = tuple(leaf.shape)
        if len(shape) < 3:
            return P(*([None] * len(shape)))
        b, rest = shape[1], shape[2:]
        spec = [None] * len(shape)
        if b % dp_size == 0:
            spec[1] = dp
            seq_axes = model
        else:
            seq_axes = (tuple(a for a in ((dp,) if isinstance(dp, str) else dp)
                              ) + (model,)) if model else dp
            if isinstance(seq_axes, tuple) and len(seq_axes) == 1:
                seq_axes = seq_axes[0]
        # heads over model (dim -2 for (L,B,S,H,hd))
        if (len(shape) == 5 and model
                and shape[3] % _axis_size(mesh, model) == 0):
            spec[3] = model
        elif len(shape) >= 4 and shape[2] % _axis_size(mesh, seq_axes or ()) == 0 \
                and seq_axes:
            spec[2] = seq_axes
        elif len(shape) == 4 and model and shape[3] % _axis_size(mesh, model) == 0:
            spec[3] = model                    # e.g. MLA (L,B,S,rank): rank/model
        return _fit(tuple(spec), shape, mesh)

    return jax.tree.map(lambda l: NamedSharding(mesh, leaf_spec(l)), cache_tree)


def scalar_sharding(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# mesh wrappers + ownership partitioning (re-exported from repro.core)
# ---------------------------------------------------------------------------
#
# The hash-table side of the system (repro.core.distributed) assigns every
# key exactly one owner shard via ``hash_owner``.  The relational operators
# (repro.relational.join) reuse that rule to co-partition *both* sides of a
# join: route build and probe batches to the key's owner, and each shard
# joins only the keys it owns — one writer per shard, no CAS, no result
# merging.  Composite multi-word keys ride the same exchange: ``owner_of``
# folds every key plane before ``hash_owner``, so (n, key_words) batches
# co-partition uniformly and the sharded join accepts tuple-of-column keys
# end-to-end.  The routing block itself (owner_of -> make_plan -> scatter ->
# all_to_all) lives in ``repro.core.exchange`` — one implementation for the
# distributed tables AND the relational shuffle — and the mesh wrappers in
# ``repro.core.compat``; both are re-exported here for existing callers
# (distributed code may import core, never the reverse).

from repro.core.compat import (  # noqa: E402,F401  (re-exports)
    axis_size_compat,
    make_mesh_compat,
    set_mesh_compat,
    shard_map_compat,
)
from repro.core.exchange import (  # noqa: E402,F401  (re-exports)
    ExchangePlan,
    ownership_exchange,
    ownership_return,
)
