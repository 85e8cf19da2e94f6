"""Pallas TPU kernel for COPS probing (paper §IV-B.2, Fig. 2).

TPU mapping of the warp-cooperative scheme:

- The table shard lives entirely in VMEM for the duration of the kernel:
  the full (p, W) key/value planes are single-buffered resident blocks
  with a constant index_map.  Planes the tile writes arrive in HBM
  (aliased to the output) and are copied into the resident output block
  once, at the first grid step.  This is the TPU analogue of "all probes
  of a group hit one cache line" — probes cost VMEM-latency row slices,
  never HBM round trips.  The whole table must fit in VMEM:
  ``resident_bytes`` is what a tile holds there, and the dispatch layer
  (``ops.py``) refuses tables above ``VMEM_RESIDENT_LIMIT``.
- One probe window = one (1, W) row slice; the warp vote becomes a vector
  compare + iota-min over the W lanes.
- The key batch streams through the grid as (G, 1, T) arrays whose (1, T)
  blocks sit in SMEM, so each key and each per-key result is a scalar
  read or write.  Keys are processed *sequentially* inside each tile
  (fori_loop) and tiles execute sequentially on the core (TPU grid
  semantics) — the single-writer serialization that replaces atomicCAS
  under ownership partitioning.
- Slot claims are read-modify-write of the whole row (vector-aligned store),
  not a scalar lane store.

The kernel supports the single-value upsert (claim-or-update), the
multi-value append (claim-only), lookup, the fused group-by RMW, the
fused multi-value retrieval walk, and the bucket-list chain walk.  u32
keys / u32 values, SOA layout; 2-plane composite/u64 keys have dedicated
``*64`` variants (insert64 / lookup64 / retrieve_multi64) whose window
match ANDs both planes — wider configurations take the pure-JAX path
(see the dispatchers' eligibility checks in ``ops.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.common import (
    EMPTY_KEY,
    STATUS_FULL,
    STATUS_INSERTED,
    STATUS_MASKED,
    STATUS_UPDATED,
    TOMBSTONE_KEY,
)
from repro.core import hashing

_U = jnp.uint32
_I = jnp.int32

DEFAULT_TILE = 256

#: VMEM the compiler may give one tile: a TPU v5e has 128 MiB, and the
#: default scoped limit (16 MiB) would cap a table at a few MiB
VMEM_LIMIT = 112 << 20
#: VMEM the resident planes of one tile may take, leaving room for the
#: per-key blocks and the compiler's own scratch.  Every tile compiles for a
#: v5e at this size (``tests/test_tpu_compile.py``).
VMEM_RESIDENT_LIMIT = 104 << 20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def resident_bytes(*shapes) -> int:
    """VMEM bytes of 32-bit planes held resident, in the (8, 128) tiling."""
    total = 0
    for rows, lanes in shapes:
        total += (-(-rows // 8) * 8) * (-(-lanes // 128) * 128) * 4
    return total


def per_key_spec(tile):
    """One (1, T) SMEM block of a (G, 1, T) per-key array per grid step."""
    return pl.BlockSpec((None, 1, tile), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def resident_spec(shape):
    """A whole 2-D plane held in VMEM across the grid, fetched once."""
    return pl.BlockSpec(shape, lambda i: (0, 0), pipeline_mode=pl.Buffered(1))


#: a plane the tile updates in place: passed in HBM, aliased to its output
IN_PLACE = pl.BlockSpec(memory_space=pl.ANY)


def load_resident(*pairs):
    """At the first grid step copy each in-place plane from HBM into its
    resident output block (an output block starts uninitialized)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for src, dst in pairs:
            pltpu.sync_copy(src, dst)


def _win_vote(mask_row):
    """Lowest set lane in a (W,) bool row, W if none — the group vote."""
    w = mask_row.shape[0]
    lanes = jax.lax.broadcasted_iota(_I, (1, w), 1)[0]
    return jnp.min(jnp.where(mask_row, lanes, _I(w)))


def _pick_lane(row, sel):
    """The u32 word of ``row`` in the one lane ``sel`` marks (0 if none).
    The chip reduces signed words only, so the sum runs on the bit pattern."""
    bits = jax.lax.bitcast_convert_type(row, _I)
    return jnp.sum(jnp.where(sel, bits, 0)).astype(_U)   # i32 -> u32 wraps


def _inclusive_count(mask_row):
    """Per lane, how many lanes at or before it are set — a prefix count of
    a (W,) bool row as one (1, W) x (W, W) product with a triangle of ones
    (exact: counts stay far below 2^24)."""
    w = mask_row.shape[0]
    src = jax.lax.broadcasted_iota(_I, (w, w), 0)
    dst = jax.lax.broadcasted_iota(_I, (w, w), 1)
    upper = (src <= dst).astype(jnp.float32)
    counts = jnp.dot(mask_row.astype(jnp.float32)[None, :], upper,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return counts[0].astype(_I)


def _probe_setup(k, num_rows, seed, scheme):
    row0 = hashing.hash_rows(k, num_rows, seed)
    if scheme in ("cops", "bucketed"):
        # bucketed IS the cops walk truncated to its two buckets — the
        # dispatch layer clamps max_probes to 2 (probing.effective_probes),
        # so the bucket tile reuses the double-hashing step unchanged
        step = hashing.hash_step(k, num_rows, seed)
    else:  # "linear" baseline
        step = _U(1)
    return row0, step


# ---------------------------------------------------------------------------
# insert (single-value upsert OR multi-value append)
# ---------------------------------------------------------------------------

def _insert_kernel(keys_ref, vals_ref, mask_ref, tk_in_ref, tv_in_ref,
                   tk_ref, tv_ref, status_ref,
                   *, num_rows, window, seed, max_probes, scheme, multi_value):
    # tk_ref/tv_ref are the resident OUTPUT blocks, aliased onto the HBM
    # planes tk_in_ref/tv_in_ref — all reads and writes go through them.
    load_resident((tk_in_ref, tk_ref), (tv_in_ref, tv_ref))
    tile = keys_ref.shape[1]

    def one_key(j, _):
        k = keys_ref[0, j]
        v = vals_ref[0, j]
        m = mask_ref[0, j] != 0

        row0, step = _probe_setup(k, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, *_ = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            (attempt, row, done, crow, clane, have_cand, mrow, mlane,
             matched) = st
            win = tk_ref[pl.ds(row.astype(_I), 1), :][0]           # (W,)
            empty = win == EMPTY_KEY
            tomb = win == TOMBSTONE_KEY
            cand = empty | tomb
            c_lane = _win_vote(cand)
            has_empty = jnp.any(empty)
            if multi_value:
                hit = jnp.zeros((), bool)
                m_lane = _I(window)
            else:
                match = win == k
                m_lane = _win_vote(match)
                hit = m_lane < window
            new_cand = jnp.logical_and(~have_cand, c_lane < window)
            crow = jnp.where(new_cand, row, crow)
            clane = jnp.where(new_cand, c_lane, clane)
            have_cand = have_cand | (c_lane < window)
            mrow = jnp.where(hit, row, mrow)
            mlane = jnp.where(hit, m_lane, mlane)
            matched = matched | hit
            if multi_value:
                done = have_cand                      # first candidate wins
            else:
                done = hit | has_empty                # match or absence proof
            nrow = (row + step) % _U(num_rows)
            return (attempt + 1, jnp.where(done, row, nrow), done, crow,
                    clane, have_cand, mrow, mlane, matched)

        zu = jnp.zeros((), _U)
        zi = jnp.zeros((), _I)
        st = (zi, row0, jnp.zeros((), bool), zu, zi, jnp.zeros((), bool),
              zu, zi, jnp.zeros((), bool))
        (_, _, _, crow, clane, have_cand, mrow, mlane, matched) = \
            jax.lax.while_loop(cond, body, st)

        do_update = m & matched & (not multi_value)
        do_claim = m & ~matched & have_cand
        row = jnp.where(matched, mrow, crow).astype(_I)
        lane = jnp.where(matched, mlane, clane)
        write = do_update | do_claim

        @pl.when(write)
        def _():
            lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
            sel = lanes == lane
            vrow = tv_ref[pl.ds(row, 1), :][0]
            tv_ref[pl.ds(row, 1), :] = jnp.where(sel, v, vrow)[None, :]

        @pl.when(do_claim)
        def _():
            lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
            sel = lanes == lane
            krow = tk_ref[pl.ds(row, 1), :][0]
            tk_ref[pl.ds(row, 1), :] = jnp.where(sel, k, krow)[None, :]

        status = jnp.where(~m, _I(STATUS_MASKED),
                           jnp.where(do_update, _I(STATUS_UPDATED),
                                     jnp.where(do_claim, _I(STATUS_INSERTED),
                                               _I(STATUS_FULL))))
        status_ref[0, j] = status
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def insert_call(table_keys, table_vals, keys3d, vals3d, mask3d, *, seed,
                max_probes, scheme="cops", multi_value=False, interpret=True):
    """keys3d/vals3d/mask3d: (G, 1, T). Returns (table_keys, table_vals,
    status3d)."""
    num_rows, window = table_keys.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(
        _insert_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme, multi_value=multi_value)
    full = resident_spec((num_rows, window))
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, row_tile, IN_PLACE, IN_PLACE],
        out_specs=[full, full, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, vals3d, mask3d, table_keys, table_vals)


# ---------------------------------------------------------------------------
# fused RMW tile — group-by aggregation without leaving VMEM
# ---------------------------------------------------------------------------
#
# The group-by table is a SingleValueHashTable with two value planes
# (plane 0 = aggregate accumulator, plane 1 = group cardinality).  The scan
# reference folds one element at a time through update_values; this kernel
# fuses probe + fold + store per key while the whole table shard stays
# resident in VMEM — the ROADMAP "group-by on the Pallas kernel path" item.
# ``agg`` is a static switch: sum/mean accumulate, min/max clamp, count
# ignores the operand; claims seed the accumulator exactly like the scan
# path's init write.

AGG_KINDS = ("sum", "mean", "min", "max", "count")


def _update_kernel(keys_ref, vals_ref, mask_ref, tk_in, tv0_in, tv1_in,
                   tk_ref, tv0_ref, tv1_ref, status_ref,
                   *, num_rows, window, seed, max_probes, scheme, agg):
    load_resident((tk_in, tk_ref), (tv0_in, tv0_ref), (tv1_in, tv1_ref))
    tile = keys_ref.shape[1]

    def one_key(j, _):
        k = keys_ref[0, j]
        v = vals_ref[0, j]
        m = mask_ref[0, j] != 0
        row0, step = _probe_setup(k, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, *_ = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            (attempt, row, done, crow, clane, have_cand, mrow, mlane,
             matched) = st
            win = tk_ref[pl.ds(row.astype(_I), 1), :][0]
            empty = win == EMPTY_KEY
            cand = empty | (win == TOMBSTONE_KEY)
            c_lane = _win_vote(cand)
            has_empty = jnp.any(empty)
            m_lane = _win_vote(win == k)
            hit = m_lane < window
            new_cand = jnp.logical_and(~have_cand, c_lane < window)
            crow = jnp.where(new_cand, row, crow)
            clane = jnp.where(new_cand, c_lane, clane)
            have_cand = have_cand | (c_lane < window)
            mrow = jnp.where(hit, row, mrow)
            mlane = jnp.where(hit, m_lane, mlane)
            matched = matched | hit
            done = hit | has_empty
            nrow = (row + step) % _U(num_rows)
            return (attempt + 1, jnp.where(done, row, nrow), done, crow,
                    clane, have_cand, mrow, mlane, matched)

        zu = jnp.zeros((), _U)
        zi = jnp.zeros((), _I)
        st = (zi, row0, jnp.zeros((), bool), zu, zi, jnp.zeros((), bool),
              zu, zi, jnp.zeros((), bool))
        (_, _, _, crow, clane, have_cand, mrow, mlane, matched) = \
            jax.lax.while_loop(cond, body, st)

        do_update = m & matched
        do_claim = m & ~matched & have_cand
        row = jnp.where(matched, mrow, crow).astype(_I)
        lane = jnp.where(matched, mlane, clane)
        lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
        sel = lanes == lane

        operand = _U(1) if agg == "count" else v

        @pl.when(do_update | do_claim)
        def _():
            acc_row = tv0_ref[pl.ds(row, 1), :][0]
            cnt_row = tv1_ref[pl.ds(row, 1), :][0]
            acc = _pick_lane(acc_row, sel)
            if agg in ("sum", "mean", "count"):
                folded = acc + operand
            elif agg == "min":
                folded = jnp.minimum(acc, operand)
            else:  # max
                folded = jnp.maximum(acc, operand)
            cnt = _pick_lane(cnt_row, sel)
            new_acc = jnp.where(do_update, folded, operand)
            new_cnt = jnp.where(do_update, cnt + _U(1), _U(1))
            tv0_ref[pl.ds(row, 1), :] = jnp.where(sel, new_acc, acc_row)[None, :]
            tv1_ref[pl.ds(row, 1), :] = jnp.where(sel, new_cnt, cnt_row)[None, :]

        @pl.when(do_claim)
        def _():
            krow = tk_ref[pl.ds(row, 1), :][0]
            tk_ref[pl.ds(row, 1), :] = jnp.where(sel, k, krow)[None, :]

        status_ref[0, j] = jnp.where(
            ~m, _I(STATUS_MASKED),
            jnp.where(do_update, _I(STATUS_UPDATED),
                      jnp.where(do_claim, _I(STATUS_INSERTED),
                                _I(STATUS_FULL))))
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def update_call(tk, tv0, tv1, keys3d, vals3d, mask3d, *, seed, max_probes,
                scheme="cops", agg="sum", interpret=True):
    """Fused group-by RMW: keys3d/vals3d/mask3d (G, 1, T).

    Returns (tk, tv0, tv1, status3d) with tv0/tv1 the aggregate/count
    planes updated in place (input/output aliased).
    """
    num_rows, window = tk.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(
        _update_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme, agg=agg)
    full = resident_spec((num_rows, window))
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, row_tile] + [IN_PLACE] * 3,
        out_specs=[full, full, full, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, vals3d, mask3d, tk, tv0, tv1)


# ---------------------------------------------------------------------------
# fused retrieve tile — multi-value counts + match arena in one walk
# ---------------------------------------------------------------------------
#
# The TPU rendering of the bulk-retrieval engine's fused walk
# (repro.core.bulk_retrieve): per query the tile walks the probe sequence
# ONCE, accumulating the match count and stamping (query index, walk-order
# rank) into two slot-shaped arena planes held in VMEM alongside the key
# plane.  The host-side compaction (`bulk_retrieve._emit`) then turns
# counts + arena into the paper's (values, offsets, counts) layout — so
# the kernel replaces both the counting pass and the gather re-probe with
# a single pass over the store, mirroring how `update_call` fuses the
# group-by RMW.  Queries are pre-deduped by the caller (mask selects the
# group representatives), so arena writes never collide.

def _retrieve_kernel(keys_ref, mask_ref, tk_ref, qa_in, ra_in,
                     qa_ref, ra_ref, cnt_ref,
                     *, num_rows, window, seed, max_probes, scheme, collect):
    load_resident((qa_in, qa_ref), (ra_in, ra_ref))
    tile = keys_ref.shape[1]
    i = pl.program_id(0)

    def one_key(j, _):
        k = keys_ref[0, j]
        m = mask_ref[0, j] != 0
        qidx = i * tile + j
        row0, step = _probe_setup(k, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, seen = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            attempt, row, done, seen = st
            ri = row.astype(_I)
            win = tk_ref[pl.ds(ri, 1), :][0]
            match = win == k
            nm = jnp.sum(match.astype(_I))
            has_empty = jnp.any(win == EMPTY_KEY)

            if collect:
                rank = _inclusive_count(match) - 1 + seen

                @pl.when(nm > 0)
                def _():
                    qrow = qa_ref[pl.ds(ri, 1), :][0]
                    qa_ref[pl.ds(ri, 1), :] = jnp.where(match, qidx,
                                                        qrow)[None, :]
                    rrow = ra_ref[pl.ds(ri, 1), :][0]
                    ra_ref[pl.ds(ri, 1), :] = jnp.where(match, rank,
                                                        rrow)[None, :]

            seen = seen + nm
            done = has_empty
            nrow = (row + step) % _U(num_rows)
            return attempt + 1, jnp.where(done, row, nrow), done, seen

        st = (jnp.zeros((), _I), row0, ~m, jnp.zeros((), _I))
        _, _, _, seen = jax.lax.while_loop(cond, body, st)
        cnt_ref[0, j] = seen
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def retrieve_multi_call(tk, qa0, ra0, keys3d, mask3d, *, seed, max_probes,
                        scheme="cops", collect=True, interpret=True):
    """Fused retrieval walk: keys3d/mask3d (G, 1, T); qa0/ra0 the sentinel-
    initialized (p, W) arena planes (aliased in/out) — pass (1, 1) dummies
    with ``collect=False`` for the counts-only walk (no arena writes).

    Returns (qarena, rank_arena, counts3d).
    """
    num_rows, window = tk.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(
        _retrieve_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme, collect=collect)
    arena = resident_spec(qa0.shape)
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, resident_spec((num_rows, window)),
                  IN_PLACE, IN_PLACE],
        out_specs=[arena, arena, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct(qa0.shape, _I),
            jax.ShapeDtypeStruct(ra0.shape, _I),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, mask3d, tk, qa0, ra0)


# ---------------------------------------------------------------------------
# bucket-walk tile — the bucket-list chain walk over the pool slot arena
# ---------------------------------------------------------------------------
#
# Mirrors the fused retrieve tile above, with the bucket store's chain in
# place of the probe sequence: per query the tile walks its bucket list
# tail -> head (handles are pre-probed by the caller — counts are O(1)
# from the handle, so only the arena walk runs on-core), stamping (query
# index, head-first value rank) into two pool-shaped arena planes held in
# VMEM next to the pool.  The host-side compaction (`bulk_retrieve._emit`)
# is shared with the jax engine, exactly like `retrieve_multi_call`.
# Distinct queries own disjoint chains, so arena writes never collide.
# The pool and both arenas are (R, 128) lane-dense reshapes of the flat
# slot range: a bucket's values cover a run of slots, written one aligned
# 128-lane row at a time with the lanes outside the run masked.

POOL_LANES = 128


def _bucket_walk_kernel(ptr_ref, cnt_ref, bidx_ref, act_ref, sizes_ref,
                        cum_ref, pool_ref, qa_in, ra_in, qa_ref, ra_ref):
    load_resident((qa_in, qa_ref), (ra_in, ra_ref))
    tile = ptr_ref.shape[1]
    i = pl.program_id(0)
    lanes = jax.lax.broadcasted_iota(_I, (1, POOL_LANES), 1)[0]

    def one_query(jq, _):
        act = act_ref[0, jq] != 0
        cnt = cnt_ref[0, jq]
        qidx = i * tile + jq

        def cond(st):
            j, p = st
            return j >= 0

        def body(st):
            j, p = st
            bsize = sizes_ref[0, j]
            base = cum_ref[0, j]
            has_link = j > 0
            start = p + jnp.where(has_link, 1, 0)
            valid = jnp.minimum(cnt - base, bsize)      # tail partially filled
            stop = start + valid

            def write_row(r, _):
                slot = r * POOL_LANES + lanes
                ok = (slot >= start) & (slot < stop)
                cur_q = qa_ref[pl.ds(r, 1), :][0]
                qa_ref[pl.ds(r, 1), :] = jnp.where(ok, qidx, cur_q)[None, :]
                cur_r = ra_ref[pl.ds(r, 1), :][0]
                ra_ref[pl.ds(r, 1), :] = jnp.where(
                    ok, base + slot - start, cur_r)[None, :]
                return 0

            jax.lax.fori_loop(start // POOL_LANES,
                              (stop + POOL_LANES - 1) // POOL_LANES,
                              write_row, 0)
            prow = pool_ref[pl.ds(p // POOL_LANES, 1), :][0]
            link = _pick_lane(prow, lanes == p % POOL_LANES)
            p = jnp.where(has_link, link.astype(_I), p)
            return j - 1, p

        j0 = jnp.where(act, bidx_ref[0, jq], _I(-1))
        jax.lax.while_loop(cond, body, (j0, ptr_ref[0, jq]))
        return 0

    jax.lax.fori_loop(0, tile, one_query, 0)


def bucket_walk_call(pool, qa0, ra0, ptr3d, cnt3d, bidx3d, act3d, sizes, cum,
                     *, interpret=True):
    """Bucket-list chain walk: ptr3d/cnt3d/bidx3d/act3d (G, 1, T) pre-probed
    i32 handle planes; pool and the sentinel-initialized qa0/ra0 arenas
    (aliased in/out) are (R, POOL_LANES) reshapes of the flat slot range;
    sizes/cum the (1, L) i32 growth schedule.

    Returns (qarena, rank_arena), both (R, POOL_LANES).
    """
    g, _, tile = ptr3d.shape
    row_tile = per_key_spec(tile)
    schedule = pl.BlockSpec(memory_space=pltpu.SMEM)
    arena = resident_spec(qa0.shape)
    return pl.pallas_call(
        _bucket_walk_kernel,
        grid=(g,),
        in_specs=[row_tile] * 4 + [schedule, schedule,
                                   resident_spec(pool.shape), IN_PLACE,
                                   IN_PLACE],
        out_specs=[arena, arena],
        out_shape=[
            jax.ShapeDtypeStruct(qa0.shape, _I),
            jax.ShapeDtypeStruct(ra0.shape, _I),
        ],
        input_output_aliases={7: 0, 8: 1},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(ptr3d, cnt3d, bidx3d, act3d, sizes, cum, pool, qa0, ra0)


# ---------------------------------------------------------------------------
# 64-bit keys: two u32 planes (hi, lo).  The window match is
# two vector compares ANDed; sentinels live on plane 0.  This is the kernel
# path for the paper's "beyond 32-bit" claim (WarpDrive was 32-bit-only).
# ---------------------------------------------------------------------------

def _insert64_kernel(k0_ref, k1_ref, vals_ref, mask_ref, tk0_in, tk1_in,
                     tv_in, tk0_ref, tk1_ref, tv_ref, status_ref,
                     *, num_rows, window, seed, max_probes, scheme,
                     multi_value):
    load_resident((tk0_in, tk0_ref), (tk1_in, tk1_ref), (tv_in, tv_ref))
    tile = k0_ref.shape[1]

    def one_key(j, _):
        k0 = k0_ref[0, j]                 # primary plane (sentinels)
        k1 = k1_ref[0, j]
        v = vals_ref[0, j]
        m = mask_ref[0, j] != 0
        word = hashing.combine_planes(k1, k0)
        row0, step = _probe_setup(word, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, *_ = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            (attempt, row, done, crow, clane, have_cand, mrow, mlane,
             matched) = st
            win0 = tk0_ref[pl.ds(row.astype(_I), 1), :][0]
            win1 = tk1_ref[pl.ds(row.astype(_I), 1), :][0]
            empty = win0 == EMPTY_KEY
            tomb = win0 == TOMBSTONE_KEY
            cand = empty | tomb
            c_lane = _win_vote(cand)
            has_empty = jnp.any(empty)
            if multi_value:
                hit = jnp.zeros((), bool)
                m_lane = _I(window)
            else:
                match = (win0 == k0) & (win1 == k1)
                m_lane = _win_vote(match)
                hit = m_lane < window
            new_cand = jnp.logical_and(~have_cand, c_lane < window)
            crow = jnp.where(new_cand, row, crow)
            clane = jnp.where(new_cand, c_lane, clane)
            have_cand = have_cand | (c_lane < window)
            mrow = jnp.where(hit, row, mrow)
            mlane = jnp.where(hit, m_lane, mlane)
            matched = matched | hit
            done = have_cand if multi_value else (hit | has_empty)
            nrow = (row + step) % _U(num_rows)
            return (attempt + 1, jnp.where(done, row, nrow), done, crow,
                    clane, have_cand, mrow, mlane, matched)

        zu = jnp.zeros((), _U)
        zi = jnp.zeros((), _I)
        st = (zi, row0, jnp.zeros((), bool), zu, zi, jnp.zeros((), bool),
              zu, zi, jnp.zeros((), bool))
        (_, _, _, crow, clane, have_cand, mrow, mlane, matched) = \
            jax.lax.while_loop(cond, body, st)

        do_update = m & matched & (not multi_value)
        do_claim = m & ~matched & have_cand
        row = jnp.where(matched, mrow, crow).astype(_I)
        lane = jnp.where(matched, mlane, clane)
        write = do_update | do_claim
        lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
        sel = lanes == lane

        @pl.when(write)
        def _():
            vrow = tv_ref[pl.ds(row, 1), :][0]
            tv_ref[pl.ds(row, 1), :] = jnp.where(sel, v, vrow)[None, :]

        @pl.when(do_claim)
        def _():
            krow0 = tk0_ref[pl.ds(row, 1), :][0]
            tk0_ref[pl.ds(row, 1), :] = jnp.where(sel, k0, krow0)[None, :]
            krow1 = tk1_ref[pl.ds(row, 1), :][0]
            tk1_ref[pl.ds(row, 1), :] = jnp.where(sel, k1, krow1)[None, :]

        status_ref[0, j] = jnp.where(
            ~m, _I(STATUS_MASKED),
            jnp.where(do_update, _I(STATUS_UPDATED),
                      jnp.where(do_claim, _I(STATUS_INSERTED),
                                _I(STATUS_FULL))))
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def insert64_call(tk0, tk1, tv, k0_3d, k1_3d, vals3d, mask3d, *, seed,
                  max_probes, scheme="cops", multi_value=False,
                  interpret=True):
    num_rows, window = tk0.shape
    g, _, tile = k0_3d.shape
    kern = functools.partial(
        _insert64_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme, multi_value=multi_value)
    full = resident_spec((num_rows, window))
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile] * 4 + [IN_PLACE] * 3,
        out_specs=[full, full, full, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((num_rows, window), _U),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(k0_3d, k1_3d, vals3d, mask3d, tk0, tk1, tv)


def _lookup64_kernel(k0_ref, k1_ref, tk0_ref, tk1_ref, tv_ref, vals_ref,
                     found_ref, *, num_rows, window, seed, max_probes, scheme):
    tile = k0_ref.shape[1]

    def one_key(j, _):
        k0 = k0_ref[0, j]
        k1 = k1_ref[0, j]
        word = hashing.combine_planes(k1, k0)
        row0, step = _probe_setup(word, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, *_ = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            attempt, row, done, frow, flane, found = st
            win0 = tk0_ref[pl.ds(row.astype(_I), 1), :][0]
            win1 = tk1_ref[pl.ds(row.astype(_I), 1), :][0]
            match = (win0 == k0) & (win1 == k1)
            m_lane = _win_vote(match)
            hit = m_lane < window
            has_empty = jnp.any(win0 == EMPTY_KEY)
            frow = jnp.where(hit, row, frow)
            flane = jnp.where(hit, m_lane, flane)
            found = found | hit
            done = hit | has_empty
            nrow = (row + step) % _U(num_rows)
            return attempt + 1, jnp.where(done, row, nrow), done, frow, flane, found

        zu = jnp.zeros((), _U)
        zi = jnp.zeros((), _I)
        st = (zi, row0, jnp.zeros((), bool), zu, zi, jnp.zeros((), bool))
        _, _, _, frow, flane, found = jax.lax.while_loop(cond, body, st)
        vrow = tv_ref[pl.ds(frow.astype(_I), 1), :][0]
        lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
        val = _pick_lane(vrow, lanes == flane)
        vals_ref[0, j] = jnp.where(found, val, _U(0))
        found_ref[0, j] = found.astype(_I)
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def lookup64_call(tk0, tk1, tv, k0_3d, k1_3d, *, seed, max_probes,
                  scheme="cops", interpret=True):
    num_rows, window = tk0.shape
    g, _, tile = k0_3d.shape
    kern = functools.partial(
        _lookup64_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme)
    full = resident_spec((num_rows, window))
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, full, full, full],
        out_specs=[row_tile, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct((g, 1, tile), _U),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(k0_3d, k1_3d, tk0, tk1, tv)


def _retrieve64_kernel(k0_ref, k1_ref, mask_ref, tk0_ref, tk1_ref,
                       qa_in, ra_in, qa_ref, ra_ref, cnt_ref,
                       *, num_rows, window, seed, max_probes, scheme,
                       collect):
    """Two-plane fused retrieval walk (composite / u64 keys).

    Mirrors ``_retrieve_kernel`` with the window match ANDed over both
    key planes: one walk emits per-query counts and stamps (query, rank)
    into the slot arena.  Probe row/step come from the same
    ``combine_planes`` fold the host engines use, so the walk visits
    exactly the rows the jax path visits.
    """
    load_resident((qa_in, qa_ref), (ra_in, ra_ref))
    tile = k0_ref.shape[1]
    i = pl.program_id(0)

    def one_key(j, _):
        k0 = k0_ref[0, j]                 # primary plane (sentinels)
        k1 = k1_ref[0, j]
        m = mask_ref[0, j] != 0
        qidx = i * tile + j
        word = hashing.combine_planes(k1, k0)
        row0, step = _probe_setup(word, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, seen = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            attempt, row, done, seen = st
            ri = row.astype(_I)
            win0 = tk0_ref[pl.ds(ri, 1), :][0]
            win1 = tk1_ref[pl.ds(ri, 1), :][0]
            match = (win0 == k0) & (win1 == k1)
            nm = jnp.sum(match.astype(_I))
            has_empty = jnp.any(win0 == EMPTY_KEY)

            if collect:
                rank = _inclusive_count(match) - 1 + seen

                @pl.when(nm > 0)
                def _():
                    qrow = qa_ref[pl.ds(ri, 1), :][0]
                    qa_ref[pl.ds(ri, 1), :] = jnp.where(match, qidx,
                                                        qrow)[None, :]
                    rrow = ra_ref[pl.ds(ri, 1), :][0]
                    ra_ref[pl.ds(ri, 1), :] = jnp.where(match, rank,
                                                        rrow)[None, :]

            seen = seen + nm
            done = has_empty
            nrow = (row + step) % _U(num_rows)
            return attempt + 1, jnp.where(done, row, nrow), done, seen

        st = (jnp.zeros((), _I), row0, ~m, jnp.zeros((), _I))
        _, _, _, seen = jax.lax.while_loop(cond, body, st)
        cnt_ref[0, j] = seen
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def retrieve_multi64_call(tk0, tk1, qa0, ra0, k0_3d, k1_3d, mask3d, *, seed,
                          max_probes, scheme="cops", collect=True,
                          interpret=True):
    """Two-plane ``retrieve_multi_call``: k0/k1/mask (G, 1, T), qa0/ra0 the
    sentinel-initialized (p, W) arena planes (aliased in/out).  Returns
    (qarena, rank_arena, counts3d)."""
    num_rows, window = tk0.shape
    g, _, tile = k0_3d.shape
    kern = functools.partial(
        _retrieve64_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme, collect=collect)
    full = resident_spec((num_rows, window))
    arena = resident_spec(qa0.shape)
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, row_tile, full, full, IN_PLACE,
                  IN_PLACE],
        out_specs=[arena, arena, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct(qa0.shape, _I),
            jax.ShapeDtypeStruct(ra0.shape, _I),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(k0_3d, k1_3d, mask3d, tk0, tk1, qa0, ra0)


# ---------------------------------------------------------------------------
# lookup
# ---------------------------------------------------------------------------

def _lookup_kernel(keys_ref, tk_ref, tv_ref, vals_ref, found_ref,
                   *, num_rows, window, seed, max_probes, scheme):
    tile = keys_ref.shape[1]

    def one_key(j, _):
        k = keys_ref[0, j]
        row0, step = _probe_setup(k, num_rows, seed, scheme)

        def cond(st):
            attempt, row, done, *_ = st
            return jnp.logical_and(attempt < max_probes, ~done)

        def body(st):
            attempt, row, done, frow, flane, found = st
            win = tk_ref[pl.ds(row.astype(_I), 1), :][0]
            match = win == k
            m_lane = _win_vote(match)
            hit = m_lane < window
            has_empty = jnp.any(win == EMPTY_KEY)
            frow = jnp.where(hit, row, frow)
            flane = jnp.where(hit, m_lane, flane)
            found = found | hit
            done = hit | has_empty
            nrow = (row + step) % _U(num_rows)
            return attempt + 1, jnp.where(done, row, nrow), done, frow, flane, found

        zu = jnp.zeros((), _U)
        zi = jnp.zeros((), _I)
        st = (zi, row0, jnp.zeros((), bool), zu, zi, jnp.zeros((), bool))
        _, _, _, frow, flane, found = jax.lax.while_loop(cond, body, st)

        vrow = tv_ref[pl.ds(frow.astype(_I), 1), :][0]
        lanes = jax.lax.broadcasted_iota(_I, (1, window), 1)[0]
        val = _pick_lane(vrow, lanes == flane)
        vals_ref[0, j] = jnp.where(found, val, _U(0))
        found_ref[0, j] = found.astype(_I)
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def lookup_call(table_keys, table_vals, keys3d, *, seed, max_probes,
                scheme="cops", interpret=True):
    """keys3d: (G, 1, T). Returns (vals3d, found3d)."""
    num_rows, window = table_keys.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(
        _lookup_kernel, num_rows=num_rows, window=window, seed=seed,
        max_probes=max_probes, scheme=scheme)
    full = resident_spec((num_rows, window))
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, full, full],
        out_specs=[row_tile, row_tile],
        out_shape=[
            jax.ShapeDtypeStruct((g, 1, tile), _U),
            jax.ShapeDtypeStruct((g, 1, tile), _I),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, table_keys, table_vals)
