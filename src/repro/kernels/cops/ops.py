"""Jitted wrappers dispatching table ops to the COPS Pallas kernel.

Handles batch padding/tiling, table-struct plumbing, and the
interpret-mode switch: kernels compile for the chip when JAX's default
backend is a TPU and run in the Pallas interpreter everywhere else.
Tables whose resident planes exceed ``kernel.VMEM_RESIDENT_LIMIT`` are
refused with ``TableTooLargeForKernel`` — the tiles hold the whole table
in VMEM.
Kernel path restrictions (the ``*_ok`` eligibility checks below): SOA
layout (``ops.planar``), 1-word values, and 1- or 2-plane keys — the
2-plane composite/u64 key variants ride the ``*64`` tiles for insert,
lookup and the fused retrieval walk.  Wider configurations (key_words >
2, multi-word values, group-by on composite keys) fall back to the
pure-JAX engines in repro.core, which handle any plane count.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import probing
from repro.core.common import EMPTY_KEY, STATUS_INSERTED, STATUS_MASKED
from repro.kernels.cops import kernel as K

_U = jnp.uint32
_I = jnp.int32

#: schemes the kernel tiles understand (bucketed = cops truncated to two
#: rows via the clamped budget; quotient stores change the compare target
#: per attempt and stay on the jax engines)
_KERNEL_SCHEMES = ("cops", "linear", "bucketed")


def should_interpret() -> bool:
    return jax.default_backend() != "tpu"


class TableTooLargeForKernel(ValueError):
    """The table's planes do not fit the VMEM a kernel tile may hold."""


def check_resident(what: str, *shapes) -> None:
    """Raise unless the (rows, lanes) planes a tile keeps resident fit."""
    need = K.resident_bytes(*shapes)
    if need > K.VMEM_RESIDENT_LIMIT:
        raise TableTooLargeForKernel(
            f"{what}: the Pallas tile would hold {need} bytes of table planes "
            f"{list(shapes)} in VMEM, above its limit of "
            f"{K.VMEM_RESIDENT_LIMIT}; use backend='jax' for this table")


def _probes(table) -> int:
    """Coverage-clamped probe budget for the kernel walks (the same
    ``probing.effective_probes`` clamp the jax/scan engines apply)."""
    return probing.effective_probes(table.scheme, table.max_probes,
                                    table.num_rows)


def _kernel_ok(table) -> bool:
    # the kernels take bare (p, W) planes: any plane-addressable protocol
    return (table.ops.planar and table.key_words in (1, 2)
            and table.value_words == 1
            and table.scheme in _KERNEL_SCHEMES
            and not table.ops.quotient)


def _tile_batch(x, tile, fill):
    """(n,) -> padded (G, 1, tile) per-key array, and n."""
    n = x.shape[0]
    g = max(1, -(-n // tile))
    pad = g * tile - n
    x = jnp.pad(x, ((0, pad),), constant_values=fill)
    return x.reshape(g, 1, tile), n


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme", "tile", "multi_value", "interpret"))
def _insert_jit(tk, tv, keys, vals, mask, *, seed, max_probes, scheme, tile,
                multi_value, interpret):
    k2, n = _tile_batch(keys, tile, EMPTY_KEY)
    v2, _ = _tile_batch(vals, tile, 0)
    m2, _ = _tile_batch(mask.astype(_I), tile, 0)
    tk, tv, st2 = K.insert_call(tk, tv, k2, v2, m2, seed=seed,
                                max_probes=max_probes, scheme=scheme,
                                multi_value=multi_value, interpret=interpret)
    return tk, tv, st2.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme", "tile", "multi_value", "interpret"))
def _insert64_jit(tk0, tk1, tv, k0, k1, vals, mask, *, seed, max_probes,
                  scheme, tile, multi_value, interpret):
    k0_2, n = _tile_batch(k0, tile, EMPTY_KEY)
    k1_2, _ = _tile_batch(k1, tile, 0)
    v2, _ = _tile_batch(vals, tile, 0)
    m2, _ = _tile_batch(mask.astype(_I), tile, 0)
    tk0, tk1, tv, st2 = K.insert64_call(
        tk0, tk1, tv, k0_2, k1_2, v2, m2, seed=seed, max_probes=max_probes,
        scheme=scheme, multi_value=multi_value, interpret=interpret)
    return tk0, tk1, tv, st2.reshape(-1)[:n]


def _insert_dispatch(table, keys, values, mask, multi_value):
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    values = sv.normalize_words(values, 1, "values")[:, 0]
    if mask is None:
        mask = jnp.ones(values.shape, bool)
    interp = should_interpret()
    tile = min(K.DEFAULT_TILE, values.shape[0])
    plane = (table.num_rows, table.window)
    check_resident("insert", *[plane] * (table.key_words + 1))
    if table.key_words == 2:
        tk0, tk1 = table.store["keys"][0], table.store["keys"][1]
        tv = table.store["values"][0]
        tk0, tk1, tv, status = _insert64_jit(
            tk0, tk1, tv, keys[:, 0], keys[:, 1], values, mask,
            seed=table.seed, max_probes=_probes(table), scheme=table.scheme,
            tile=tile, multi_value=multi_value, interpret=interp)
        store = {"keys": jnp.stack([tk0, tk1]), "values": tv[None]}
    else:
        tk = table.store["keys"][0]
        tv = table.store["values"][0]
        tk, tv, status = _insert_jit(
            tk, tv, keys[:, 0], values, mask, seed=table.seed,
            max_probes=_probes(table), scheme=table.scheme, tile=tile,
            multi_value=multi_value, interpret=interp)
        store = {"keys": tk[None], "values": tv[None]}
    count = table.count + jnp.sum(status == STATUS_INSERTED, dtype=_I)
    return dataclasses.replace(table, store=store, count=count), status


def insert(table, keys, values, mask=None):
    """SingleValueHashTable upsert via the Pallas kernel (u32 or 2-plane u64
    keys — the paper's beyond-32-bit claim on the kernel path)."""
    from repro.core import single_value as sv
    if not _kernel_ok(table):
        jx = dataclasses.replace(table, backend="jax")
        if table.scheme == "bucketed":
            # bucketed callers wrap THIS function with the cuckoo rescue
            # (sv._insert_bucketed); the fallback must stay rescue-free
            # or the jax fallback would rescue twice and break parity
            from repro.core import bulk
            return bulk.insert_single(jx, keys, values, mask)
        return sv.insert(jx, keys, values, mask)
    return _insert_dispatch(table, keys, values, mask, multi_value=False)


def insert_multi(table, keys, values, mask=None):
    """MultiValueHashTable append via the Pallas kernel."""
    from repro.core import multi_value as mv
    if not _kernel_ok(table):
        jx = dataclasses.replace(table, backend="jax")
        if table.scheme == "bucketed":
            # rescue-free fallback — see insert()
            from repro.core import bulk
            return bulk.insert_multi(jx, keys, values, mask)
        return mv.insert(jx, keys, values, mask)
    return _insert_dispatch(table, keys, values, mask, multi_value=True)


def _groupby_ok(table) -> bool:
    # composite (key_words >= 2) group-bys fall back to the vectorized jax
    # RMW path — no *64 update tile yet (ROADMAP follow-on)
    return (table.ops.planar and table.key_words == 1
            and table.value_words == 2
            and table.scheme in _KERNEL_SCHEMES
            and not table.ops.quotient)


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme",
                                             "tile", "agg", "interpret"))
def _update_jit(tk, tv0, tv1, keys, vals, mask, *, seed, max_probes, scheme,
                tile, agg, interpret):
    k2, n = _tile_batch(keys, tile, EMPTY_KEY)
    v2, _ = _tile_batch(vals, tile, 0)
    m2, _ = _tile_batch(mask.astype(_I), tile, 0)
    tk, tv0, tv1, st2 = K.update_call(tk, tv0, tv1, k2, v2, m2, seed=seed,
                                      max_probes=max_probes, scheme=scheme,
                                      agg=agg, interpret=interpret)
    return tk, tv0, tv1, st2.reshape(-1)[:n]


def update_groupby(table, agg, keys, payload, mask=None):
    """Fused group-by RMW via the Pallas tile (probe + fold + store while
    the table shard stays in VMEM) — the kernel path that replaces the
    update_values scan fallback for aggregates.  ``payload`` is the
    (n, 2) [operand, weight] plane pair built by relational.groupby.
    Wider configurations fall back to the vectorized jax path.
    """
    from repro.core import single_value as sv
    from repro.relational import groupby as gb
    if not _groupby_ok(table):
        jx = dataclasses.replace(table, backend="jax")
        t, status = sv.update_values(jx, keys, gb._fold_fn(agg), payload,
                                     mask=mask, combine=gb._combine_fn(agg))
        return dataclasses.replace(t, backend=table.backend), status
    keys = sv.normalize_key_batch(keys, 1, "keys")[:, 0]
    vals = payload[:, 0]
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    tile = min(K.DEFAULT_TILE, keys.shape[0])
    check_resident("update_groupby", *[(table.num_rows, table.window)] * 3)
    tk = table.store["keys"][0]
    tv0, tv1 = table.store["values"][0], table.store["values"][1]
    tk, tv0, tv1, status = _update_jit(
        tk, tv0, tv1, keys, vals, mask, seed=table.seed,
        max_probes=_probes(table), scheme=table.scheme, tile=tile, agg=agg,
        interpret=should_interpret())
    store = {"keys": tk[None], "values": jnp.stack([tv0, tv1])}
    count = table.count + jnp.sum(status == STATUS_INSERTED, dtype=_I)
    return dataclasses.replace(table, store=store, count=count), status


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme", "tile", "interpret"))
def _lookup_jit(tk, tv, keys, *, seed, max_probes, scheme, tile, interpret):
    k2, n = _tile_batch(keys, tile, EMPTY_KEY)
    v2, f2 = K.lookup_call(tk, tv, k2, seed=seed, max_probes=max_probes,
                           scheme=scheme, interpret=interpret)
    return v2.reshape(-1)[:n], f2.reshape(-1)[:n] != 0


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme", "tile", "interpret"))
def _lookup64_jit(tk0, tk1, tv, k0, k1, *, seed, max_probes, scheme, tile,
                  interpret):
    k0_2, n = _tile_batch(k0, tile, EMPTY_KEY)
    k1_2, _ = _tile_batch(k1, tile, 0)
    v2, f2 = K.lookup64_call(tk0, tk1, tv, k0_2, k1_2, seed=seed,
                             max_probes=max_probes, scheme=scheme,
                             interpret=interpret)
    return v2.reshape(-1)[:n], f2.reshape(-1)[:n] != 0


# ---------------------------------------------------------------------------
# fused multi-value retrieval — the walk tile + the engine's compaction
# ---------------------------------------------------------------------------

def _retrieve_ok(table) -> bool:
    # 1-word keys walk the u32 tile, 2-plane composite/u64 keys the *64
    # tile; wider composite keys (key_words > 2) fall back to the jax
    # engine, whose general lane handles any plane count
    return (table.ops.planar and table.key_words in (1, 2)
            and table.scheme in _KERNEL_SCHEMES
            and not table.ops.quotient)


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme",
                                             "tile", "sentinel", "collect",
                                             "interpret"))
def _retrieve_walk_jit(tk, keys, active, *, seed, max_probes, scheme, tile,
                       sentinel, collect, interpret):
    num_rows, window = tk.shape
    k2, n = _tile_batch(keys, tile, EMPTY_KEY)
    m2, _ = _tile_batch(active.astype(_I), tile, 0)
    ashape = (num_rows, window) if collect else (1, 1)
    qa0 = jnp.full(ashape, _I(sentinel), _I)
    ra0 = jnp.zeros(ashape, _I)
    qa, ra, cnt2 = K.retrieve_multi_call(tk, qa0, ra0, k2, m2, seed=seed,
                                         max_probes=max_probes, scheme=scheme,
                                         collect=collect, interpret=interpret)
    return cnt2.reshape(-1)[:n], qa.reshape(-1), ra.reshape(-1)


@functools.partial(jax.jit, static_argnames=("seed", "max_probes", "scheme",
                                             "tile", "sentinel", "collect",
                                             "interpret"))
def _retrieve_walk64_jit(tk0, tk1, k0, k1, active, *, seed, max_probes,
                         scheme, tile, sentinel, collect, interpret):
    num_rows, window = tk0.shape
    k0_2, n = _tile_batch(k0, tile, EMPTY_KEY)
    k1_2, _ = _tile_batch(k1, tile, 0)
    m2, _ = _tile_batch(active.astype(_I), tile, 0)
    ashape = (num_rows, window) if collect else (1, 1)
    qa0 = jnp.full(ashape, _I(sentinel), _I)
    ra0 = jnp.zeros(ashape, _I)
    qa, ra, cnt2 = K.retrieve_multi64_call(tk0, tk1, qa0, ra0, k0_2, k1_2,
                                           m2, seed=seed,
                                           max_probes=max_probes,
                                           scheme=scheme, collect=collect,
                                           interpret=interpret)
    return cnt2.reshape(-1)[:n], qa.reshape(-1), ra.reshape(-1)


def _fused_walk_pallas(table, keys_n, live, collect=True):
    """Dedup front-end + kernel walk; returns (is_rep, rep_of, rcnt, qa, ra).

    Dispatches on ``table.key_words``: 1 -> the u32 walk tile, 2 -> the
    two-plane composite/u64 tile (callers gate wider keys via
    ``_retrieve_ok``).
    """
    from repro.core import bulk_retrieve as br
    n = keys_n.shape[0]
    plane = (table.num_rows, table.window)
    check_resident("retrieve_all", *[plane] * (table.key_words
                                               + (2 if collect else 0)))
    is_rep, rep_of = br.group_queries(keys_n, live)
    tile = min(K.DEFAULT_TILE, n)
    if table.key_words == 2:
        rcnt, qa, ra = _retrieve_walk64_jit(
            table.store["keys"][0], table.store["keys"][1], keys_n[:, 0],
            keys_n[:, 1], is_rep, seed=table.seed,
            max_probes=_probes(table), scheme=table.scheme, tile=tile,
            sentinel=n, collect=collect, interpret=should_interpret())
    else:
        rcnt, qa, ra = _retrieve_walk_jit(
            table.store["keys"][0], keys_n[:, 0], is_rep, seed=table.seed,
            max_probes=_probes(table), scheme=table.scheme, tile=tile,
            sentinel=n, collect=collect, interpret=should_interpret())
    return is_rep, rep_of, rcnt, qa, ra


def count_multi(table, keys, mask=None):
    """MultiValueHashTable counting pass via the counts-only walk tile
    (no arena planes allocated or written)."""
    from repro.core import bulk_retrieve as br
    from repro.core import single_value as sv
    keys_n = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys_n.shape[0]
    if n == 0 or not _retrieve_ok(table):
        return br.count_multi(table, keys_n, mask)
    live = jnp.ones((n,), bool) if mask is None else mask
    _, rep_of, rcnt, _, _ = _fused_walk_pallas(table, keys_n, live,
                                               collect=False)
    return br._fan_out(rcnt, rep_of, live, n)


def retrieve_all_multi(table, keys, out_capacity, mask=None):
    """MultiValueHashTable retrieve_all: one kernel walk, then the
    bulk-retrieval engine's scatter/gather compaction."""
    from repro.core import bulk_retrieve as br
    from repro.core import single_value as sv
    keys_n = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys_n.shape[0]
    if n == 0 or not _retrieve_ok(table):
        return br.retrieve_all_multi(table, keys_n, out_capacity, mask)
    live = jnp.ones((n,), bool) if mask is None else mask
    is_rep, rep_of, rcnt, qa, ra = _fused_walk_pallas(table, keys_n, live)
    counts = br._fan_out(rcnt, rep_of, live, n)
    out, offsets, counts = br._emit_store(table, out_capacity, counts,
                                          is_rep, rep_of, rcnt, qa, ra)
    if table.value_words == 1:
        return out[:, 0], offsets, counts
    return out, offsets, counts


# ---------------------------------------------------------------------------
# bucket-list retrieval — kernel chain walk + the engine's compaction
# ---------------------------------------------------------------------------

def _pool_rows(pool_cap):
    return -(-pool_cap // K.POOL_LANES)


@functools.partial(jax.jit, static_argnames=("tile", "sentinel", "interpret"))
def _bucket_walk_jit(pool, ptr, cnt, bidx, act, sizes, cum, *, tile,
                     sentinel, interpret):
    p2, n = _tile_batch(ptr.astype(_I), tile, 0)
    c2, _ = _tile_batch(cnt.astype(_I), tile, 0)
    b2, _ = _tile_batch(bidx.astype(_I), tile, 0)
    a2, _ = _tile_batch(act.astype(_I), tile, 0)
    pool_cap = pool.shape[0]
    shape = (_pool_rows(pool_cap), K.POOL_LANES)
    pool2 = jnp.pad(pool, (0, shape[0] * shape[1] - pool_cap)).reshape(shape)
    qa0 = jnp.full(shape, _I(sentinel), _I)
    ra0 = jnp.zeros(shape, _I)
    qa, ra = K.bucket_walk_call(pool2, qa0, ra0, p2, c2, b2, a2,
                                sizes[None, :], cum[None, :],
                                interpret=interpret)
    return qa.reshape(-1)[:pool_cap], ra.reshape(-1)[:pool_cap]


def bucket_retrieve_all(table, keys, out_capacity):
    """BucketListHashTable retrieve_all via the bucket-walk tile.

    Handles are pre-probed host-side (counts are O(1) off the handle, so
    only the chain walk runs on-core); the tile stamps the pool slot arena
    in VMEM and the compaction is shared with the jax engine — mirroring
    how ``retrieve_all_multi`` wraps the fused retrieve tile.
    """
    from repro.core import bucket_list as bl
    from repro.core import bulk_retrieve as br
    from repro.core import single_value as sv
    ks = table.key_store
    keys_n = sv.normalize_key_batch(keys, ks.key_words, "keys")
    n = keys_n.shape[0]
    if n == 0 or not (ks.ops.planar and ks.key_words == 1):
        return bl._retrieve_fused(table, keys_n, out_capacity)
    check_resident("bucket_retrieve_all",
                   *[(_pool_rows(table.pool_capacity), K.POOL_LANES)] * 3)
    is_rep, rep_of, found, ptr, rcnt, bidx, counts = bl._handle_probe(
        table, keys_n)
    tile = min(K.DEFAULT_TILE, n)
    qa, ra = _bucket_walk_jit(
        table.pool, ptr, rcnt, bidx, found,
        jnp.asarray(table.sizes, _I), jnp.asarray(table.cum, _I),
        tile=tile, sentinel=n, interpret=should_interpret())
    out, offsets, counts = br._emit(
        lambda s: table.pool[s][:, None], table.pool_capacity, out_capacity,
        counts, is_rep, rep_of, rcnt, qa, ra)
    return out[:, 0], offsets, counts


def retrieve(table, keys):
    """Batch lookup via the Pallas kernel -> (values, found)."""
    from repro.core import single_value as sv
    if not _kernel_ok(table):
        return sv.retrieve(dataclasses.replace(table, backend="jax"), keys)
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    tile = min(K.DEFAULT_TILE, keys.shape[0])
    check_resident("retrieve", *[(table.num_rows, table.window)]
                   * (table.key_words + 1))
    if table.key_words == 2:
        return _lookup64_jit(
            table.store["keys"][0], table.store["keys"][1],
            table.store["values"][0], keys[:, 0], keys[:, 1],
            seed=table.seed, max_probes=_probes(table), scheme=table.scheme,
            tile=tile, interpret=should_interpret())
    return _lookup_jit(table.store["keys"][0], table.store["values"][0],
                       keys[:, 0], seed=table.seed,
                       max_probes=_probes(table), scheme=table.scheme,
                       tile=tile, interpret=should_interpret())
