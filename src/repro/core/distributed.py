"""Distributed hash tables over a device mesh (paper §IV-E).

Two modes, exactly as in the paper:

- **distributed** — every key is owned by exactly one shard (device).  An
  insert/query batch is partitioned by owner with a *multisplit*, exchanged
  with an all-to-all, and applied to the local shard.  Retrieval needs no
  result merging (single owner per key).  On the GPU the multisplit is
  Ashkiani et al.'s warp-level primitive [16]; on TPU the idiomatic
  equivalent is a stable sort by owner (no scatter hardware, sorts are
  fast), and the NVLink all-to-all becomes ``jax.lax.all_to_all`` over an
  ICI mesh axis.

- **independent** — one autonomous table per device; inserts apply locally,
  queries are answered by every shard (caller merges).

The owner-routing block itself (owner_of -> make_plan -> scatter ->
all_to_all) lives in ``repro.core.exchange`` — one implementation shared
with the relational operators via ``repro.distributed.sharding`` — and the
ops here are thin compositions of ``ownership_exchange`` /
``ownership_return`` with the local table ops.

All functions here run *inside* shard_map (they use axis names); the
``shard_*`` wrappers at the bottom build it via
``repro.core.compat.shard_map_compat``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import single_value as sv
from repro.core.compat import axis_size_compat, shard_map_compat
from repro.core.exchange import (
    ExchangePlan,
    exchange,
    gather_from_buffer,
    make_plan,
    multisplit,
    owner_of,
    ownership_exchange,
    ownership_return,
    scatter_to_buffer,
)

_U = jnp.uint32
_I = jnp.int32


# ---------------------------------------------------------------------------
# distributed-mode ops (call inside shard_map over ``axis``)
# ---------------------------------------------------------------------------

def insert_distributed(table: sv.SingleValueHashTable, keys, values, axis: str,
                       slack: float = 2.0, insert_fn: Callable | None = None):
    """Route (key, value) pairs to their owner shard and insert locally.

    Returns (table, status_of_received, overflow).  ``insert_fn`` lets the
    multi-value / counting variants reuse the same routing.
    """
    values = sv.normalize_words(values, table.value_words, "values")
    recv_keys, recv_values, recv_mask, plan = ownership_exchange(
        keys, values, axis, key_words=table.key_words, slack=slack)
    fn = insert_fn or sv.insert
    table, status = fn(table, recv_keys, recv_values, mask=recv_mask)
    return table, status, plan.overflow


def retrieve_distributed(table: sv.SingleValueHashTable, keys, axis: str,
                         slack: float = 2.0):
    """Route queries to owners, look up locally, route answers back.

    Returns (values, found, overflow) aligned with the local query batch.
    No merge step is needed — single-owner keys (paper §IV-E).
    """
    recv_keys, _, _, plan = ownership_exchange(
        keys, (), axis, key_words=table.key_words, slack=slack)
    vals, found = sv.retrieve(table, recv_keys)
    vals = sv.normalize_words(vals, table.value_words, "values")
    # answers travel the reverse path: all_to_all is its own inverse here
    out_vals = ownership_return(plan, vals, axis)
    out_found = ownership_return(plan, found, axis, fill=False)
    if table.value_words == 1:
        out_vals = out_vals[:, 0]
    return out_vals, out_found, plan.overflow


def retrieve_distributed_filtered(table: sv.SingleValueHashTable, filt,
                                  keys, axis: str, slack: float = 2.0):
    """Bloom-filtered distributed retrieve: absent keys die locally.

    ``filt`` is this shard's :class:`~repro.core.bloom.BloomFilter` over
    its table's live keys (folded key word — see ``bloom.rebuild_from_
    table``).  The filter planes are all-gathered once (they are tiny
    next to the table), each query is admission-tested against its
    *owner's* plane, and only admitted keys enter the all_to_all —
    masked-out keys answer ``found=False`` locally, which is exact
    because a bloom miss is proof of absence.  Returns ``(values, found,
    skips, overflow)`` aligned with the local query batch; ``skips``
    counts the queries this shard never sent (the saved traffic).
    """
    from repro.core import bloom
    num = axis_size_compat(axis)
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    owners = owner_of(keys, num, table.key_words)
    words = sv.key_hash_word(keys)
    bits_all = jax.lax.all_gather(filt.bits, axis)   # (P, blocks, block_bits)
    admit = bloom.contains_stack(filt, bits_all, owners, words)
    recv_keys, _, _, plan = ownership_exchange(
        keys, (), axis, key_words=table.key_words, slack=slack, mask=admit)
    vals, found = sv.retrieve(table, recv_keys)
    vals = sv.normalize_words(vals, table.value_words, "values")
    out_vals = ownership_return(plan, vals, axis)
    out_found = ownership_return(plan, found, axis, fill=False)
    if table.value_words == 1:
        out_vals = out_vals[:, 0]
    return out_vals, out_found, jnp.sum(~admit, dtype=_I), plan.overflow


def erase_distributed(table: sv.SingleValueHashTable, keys, axis: str,
                      slack: float = 2.0):
    recv_keys, _, recv_mask, plan = ownership_exchange(
        keys, (), axis, key_words=table.key_words, slack=slack)
    table, erased = sv.erase(table, recv_keys, mask=recv_mask)
    return table, ownership_return(plan, erased, axis, fill=False), \
        plan.overflow


# ---------------------------------------------------------------------------
# independent-mode ops (paper §IV-E second mode)
# ---------------------------------------------------------------------------

def insert_independent(table: sv.SingleValueHashTable, keys, values,
                       insert_fn: Callable | None = None):
    """Insert the local batch into the local table (data already scattered)."""
    fn = insert_fn or sv.insert
    table, status = fn(table, keys, values)
    return table, status


def retrieve_independent(table: sv.SingleValueHashTable, keys, axis: str):
    """Broadcast queries to every shard; each answers from its own table.

    Returns (values, found) where found is True iff ANY shard holds the key
    and values comes from the lowest-indexed shard that holds it (merge rule).
    """
    all_keys = jax.lax.all_gather(keys, axis, tiled=True)     # (P*n, kw?) queries
    vals, found = sv.retrieve(table, all_keys)
    vals = sv.normalize_words(vals, table.value_words, "values")
    # merge: each shard contributes only where it found the key; lowest shard wins
    idx = jax.lax.axis_index(axis)
    rank = jnp.where(found, idx, axis_size_compat(axis))
    best = jax.lax.pmin(rank, axis)
    mine = rank == best
    contrib = jnp.where(mine[:, None] & found[:, None], vals, 0)
    merged_vals = jax.lax.psum(contrib, axis)
    merged_found = jax.lax.psum(found.astype(_I), axis) > 0
    # return this shard's slice of the gathered batch
    n = keys.shape[0]
    lo = idx * n
    out_v = jax.lax.dynamic_slice_in_dim(merged_vals, lo, n, 0)
    out_f = jax.lax.dynamic_slice_in_dim(merged_found, lo, n, 0)
    if table.value_words == 1:
        out_v = out_v[:, 0]
    return out_v, out_f


# ---------------------------------------------------------------------------
# host-level wrappers: build the shard_map for a mesh axis
# ---------------------------------------------------------------------------

def create_sharded(mesh: Mesh, axis: str, capacity_per_shard: int, **kwargs
                   ) -> sv.SingleValueHashTable:
    """A table whose arrays have a leading shard dim sharded over ``axis``."""
    num = int(mesh.shape[axis])

    def mk():
        t = sv.create(capacity_per_shard, **kwargs)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (num,) + x.shape), t)

    template = jax.eval_shape(mk)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, P(axis, *([None] * (len(s.shape) - 1)))),
        template)
    return jax.jit(mk, out_shardings=shardings)()


def _local(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _relift(tree):
    return jax.tree.map(lambda x: x[None], tree)


def shard_insert(mesh: Mesh, axis: str, table, keys, values, slack: float = 2.0,
                 insert_fn: Callable | None = None):
    """Host-level distributed-mode insert: keys/values sharded over ``axis``."""
    spec = jax.tree.map(lambda _: P(axis), table)

    def body(t, k, v):
        t_loc, s, ov = insert_distributed(_local(t), k, v, axis, slack, insert_fn)
        return _relift(t_loc), s, ov[None]

    f = shard_map_compat(body, mesh, in_specs=(spec, P(axis), P(axis)),
                         out_specs=(spec, P(axis), P(axis)))
    return f(table, keys, values)


def shard_retrieve(mesh: Mesh, axis: str, table, keys, slack: float = 2.0):
    spec = jax.tree.map(lambda _: P(axis), table)

    def body(t, k):
        v, fnd, ov = retrieve_distributed(_local(t), k, axis, slack)
        return v, fnd, ov[None]

    f = shard_map_compat(body, mesh, in_specs=(spec, P(axis)),
                         out_specs=(P(axis), P(axis), P(axis)))
    return f(table, keys)
