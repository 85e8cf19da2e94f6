"""Vectorized bulk-retrieval engine — one fused probe walk per query batch.

The retrieval counterpart of the bulk-build engine (``repro.core.bulk``).
The paper's §IV-B.4 pattern sizes multi-value output with a *counting
pass* and then re-probes to gather — two full walks over the store — and
the scan-reference paths in ``multi_value`` keep exactly that shape.  GPU
hash-table throughput is dominated by passes over the store (cache-line
efficiency — Compact Parallel Hash Tables, 2406.09255), so this module
collapses count + gather into ONE walk:

1. **Dedup front-end** — duplicate probe keys are grouped (the bulk
   engine's sort + ``searchsorted`` fast lane for 1-word keys; for wide
   keys — u64 or composite ``key_words >= 2`` — the stable multi-plane
   lexicographic payload sort, whose group segments are bounded by the
   all-plane adjacent compare, so composite keys differing only in a
   high plane never share a representative) and only one
   *representative* per distinct live key walks the table; results fan
   back out to every duplicate by segment at the end.
2. **Fused walk** — representatives run a single vectorized COPS walk
   that simultaneously accumulates per-query match *counts* and records
   every matching slot in a slot-space *arena*: ``arena[slot] = (query,
   local_rank)`` where ``local_rank`` is the match's position in walk
   order (window by window, lane order within a window — exactly the
   order the reference gather pass emits).  A slot holds one key, and
   representatives are distinct keys, so arena writes never collide.
3. **Compact** — per-query counts produce the output offsets (the
   prefix-sum layout callers already rely on); one batched scatter packs
   the arena into a representative-dense slot list, and one batched
   gather reads ``values[offsets[i] + j]`` straight from the store planes
   through that list.  Duplicate queries replicate their representative's
   segment for free (a gather has no write hazards).

The walk also drives **bulk erase**: the arena's occupied-slot mask IS
the set of slots to tombstone, applied as one dense batched write after
the walk instead of a scatter per probe window (WarpSpeed, 2509.16407,
makes the case that bulk erase belongs in the same engine as bulk build).
Tombstoning after the walk is bit-equivalent to the reference's in-walk
scatters: a tombstone never matches another live query key and never
creates an EMPTY, so no other query's walk can observe the difference.

**The slot-arena contract** (``layouts.StoreOps``): the walk records
matches as FLAT SLOT IDS — ``arena_capacity`` ids, ``arena_values(store,
slots)`` gathers value vectors by id, ``arena_tombstone`` deletes by
occupied-mask.  Any store that renders those three rides this engine's
walk + compaction unchanged: open-addressing layouts expose
``row * window + lane``, the bucket-list table its value pool.

**The single-value walk.**  ``retrieve_single``, ``contains_single`` and
``erase_single`` locate their representatives with the build engine's
match walk, ``bulk.probe_matches``: a single-value key has one slot, so
they need no counts and no arena.  A large batch walks in stages there:
window 1 over the whole batch, then the keys still pending move into
buffers of n/16, n/256, ... (down to ``probing.COMPACT_LANES`` lanes), each
of which runs one window, hands its own pending keys on, and scatters its
results back by index; the last buffer runs the loop to the end, and a
buffer whose pending keys outnumber it takes them in rounds.  Results are
exact: every key reads the same immutable store, visits the same rows and
stops on the same rule whichever lanes share its window.  ``fused_walk``
below still runs every element to the slowest one's end (ROADMAP).

**The revisit-free guard** (``fused_ok``): the arena holds at most one
(query, rank) pair per slot, so the fused gather/erase path requires
walks that never visit a probe row twice — cops/linear with
``max_probes <= num_rows``.  Quadratic or wrapped walks can legitimately
re-emit a slot per visit, semantics only the two-walk reference
produces, so dispatchers fall back to it (counting has no arena and
stays fused regardless).

Everything here is bit-exact against the ``backend="scan"`` reference
paths (the pre-PR while-loop walks kept in ``single_value`` /
``multi_value``): identical values, offsets, counts, found/erased masks,
and post-erase store planes.  ``tests/test_retrieve.py`` asserts this on
adversarial batches (duplicates, masks, tombstone-riddled tables,
``out_capacity`` overflow, u64 keys, empty batches);
``tests/test_composite_keys.py`` extends the matrix to composite
multi-column keys against packed single-word references.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import probing
from repro.core.common import EMPTY_KEY, TOMBSTONE_KEY

_U = jnp.uint32
_I = jnp.int32

UNROLL_PROBES = probing.UNROLL_PROBES


def _tstatic(table):
    """(store protocol, scheme, seed, effective_probes) — the engines'
    static tuple; the budget is coverage-clamped like ``bulk._tstatic``."""
    return (table.ops, table.scheme, table.seed,
            probing.effective_probes(table.scheme, table.max_probes,
                                     table.num_rows))


def fused_ok(table) -> bool:
    """Static predicate: can the slot arena represent this table's walks?

    The arena maps each store slot to at most one (query, rank) pair, so
    the fused gather/erase requires *revisit-free* walks — no probe row
    visited twice.  With every engine's budget clamped to the scheme's
    distinct-row coverage (``probing.effective_probes`` — the
    coverage-clamp bugfix), EVERY scheme's walk is revisit-free by
    construction: cops/linear generate Z_p for the first ``num_rows``
    attempts, quadratic's first (p+1)/2 residues ``l^2 mod p`` are
    distinct, bucketed visits exactly its two buckets.  This predicate
    therefore now always holds; it is kept as the documented eligibility
    switch for future walks that may revisit (e.g. multi-pass or wrapped
    schemes with an un-clampable budget).
    """
    return (probing.effective_probes(table.scheme, table.max_probes,
                                     table.num_rows)
            <= probing.scheme_coverage(table.scheme, table.num_rows))


# ---------------------------------------------------------------------------
# dedup front-end — one representative walk per distinct live key
# ---------------------------------------------------------------------------

def group_queries(keys, live):
    """Group duplicate query keys; returns (is_rep, rep_of) in batch order.

    ``is_rep`` marks the first live occurrence of each distinct live key
    (the element that walks the table); ``rep_of[i]`` is the batch index
    of element i's representative — ``n`` when i's key has no live
    occurrence (only possible for masked elements, which never read it).
    Reuses the bulk-build engine's group machinery: the payload-free sort
    fast lane for 1-word keys, the stable (masked, key words, index) sort
    for wide keys.
    """
    from repro.core import bulk
    n, kw = keys.shape
    if kw == 1:
        is_rep, rep_of, _, _, _ = bulk._group_fast(keys[:, 0], live)
        return is_rep, rep_of.astype(_I)
    flag, skeys, sidx, _ = bulk._sort_batch(keys, live, [])
    live_s, is_rep_s, first_pos, _ = bulk._group_structure(flag, skeys)
    rep_s = jnp.where(live_s, sidx[first_pos].astype(_I), _I(n))
    rep_of = jnp.zeros((n,), _I).at[sidx].set(rep_s)
    is_rep = jnp.zeros((n,), bool).at[sidx].set(is_rep_s)
    return is_rep, rep_of


# ---------------------------------------------------------------------------
# the fused walk — counts + slot arena in a single pass over the store
# ---------------------------------------------------------------------------

def fused_walk(tstatic, store, keys, words, active, *, collect, count=None,
               stats=False):
    """One COPS walk for every active element, emitting counts AND matches.

    Returns ``(cnt, qarena, rank_arena)``: per-element match counts (0 for
    inactive elements), and — when ``collect`` — two flat (capacity,)
    arenas giving, per store slot, the matching element's batch index
    (sentinel ``n`` if none) and the match's walk-order rank within that
    element's result segment.  Stops per element at the first window
    containing EMPTY (the absence frontier; tombstones do not stop the
    walk) or after ``max_probes`` windows, exactly like the reference
    counting/gather walks.  ``count`` (the table's live count) short-cuts
    the walk on an empty store.

    Distinct active keys can never match the same slot, so arena writes
    are collision-free by construction — the retrieval-side analogue of
    the build engine's unique (row, rank) placement invariant.

    ``stats`` (static) additionally carries a per-element probe-length
    counter (windows examined) and returns it as a fourth output; the
    stats-off graph is byte-identical to the three-output walk.
    """
    ops, scheme, seed, max_probes = tstatic
    num_rows, w = ops.num_rows, ops.window
    n = keys.shape[0]
    cap = ops.arena_capacity
    ashape = (cap,) if collect else (1,)
    # pack (query, rank) into one i32 arena when it cannot overflow —
    # halves the per-window scatter traffic on the hot path
    packed = collect and n * cap < 2 ** 31
    row0 = probing.initial_row(words, num_rows, seed, ops.quotient)
    step = probing.row_step(scheme, words, num_rows, seed, ops.quotient)
    qa0 = jnp.full(ashape, _I(-1) if packed else _I(n), _I)
    ra0 = jnp.zeros(ashape if not packed else (1,), _I)
    idx = jnp.arange(n, dtype=_I)

    def empty(_):
        out = (jnp.zeros((n,), _I), qa0, ra0)
        return out + ((jnp.zeros((n,), _I),) if stats else ())

    def walk(_):
        def cond(st):
            attempt, row, done, seen, qa, ra = st[:6]
            return jnp.logical_and(attempt < max_probes, ~jnp.all(done))

        def body(st):
            if stats:
                attempt, row, done, seen, qa, ra, plen = st
                plen = plen + (~done).astype(_I)
            else:
                attempt, row, done, seen, qa, ra = st
            win = ops.key_windows(store, row)
            if ops.quotient:
                tgt = probing.match_word(words, num_rows, attempt,
                                         quotient=True)
                match = (win[:, 0, :] == tgt[:, None]) & ~done[:, None]
            else:
                match = (jnp.all(win == keys[:, :, None], axis=1)
                         & ~done[:, None])
            has_empty = probing.vote_any(win[:, 0, :] == EMPTY_KEY)
            if collect:
                lanes = jax.lax.broadcasted_iota(_I, match.shape, 1)
                slot = row.astype(_I)[:, None] * w + lanes
                slot = jnp.where(match, slot, cap).reshape(-1)
                rank = jnp.cumsum(match.astype(_I), axis=1) - 1
                local = jnp.broadcast_to(seen[:, None] + rank, match.shape)
                qcol = jnp.broadcast_to(idx[:, None], match.shape)
                if packed:
                    qa = qa.at[slot].set(
                        (qcol * cap + local).reshape(-1), mode="drop")
                else:
                    qa = qa.at[slot].set(qcol.reshape(-1), mode="drop")
                    ra = ra.at[slot].set(local.reshape(-1), mode="drop")
            seen = seen + probing.vote_count(match)
            done = done | has_empty
            nrow = probing.advance_row(scheme, row, step, attempt, num_rows)
            out = (attempt + 1, jnp.where(done, row, nrow), done, seen, qa,
                   ra)
            return out + ((plen,) if stats else ())

        st = (jnp.zeros((), _I), row0, ~active, jnp.zeros((n,), _I), qa0, ra0)
        if stats:
            st = st + (jnp.zeros((n,), _I),)
        if max_probes <= UNROLL_PROBES:
            # bucketed walks have a static <= 2-window budget: unroll the
            # attempts so the walk costs the same at every load factor
            # (no early-exit all-done reduction; body is a no-op once an
            # element is done, so the outputs are identical)
            res = st
            for _ in range(max_probes):
                res = body(res)
        else:
            res = jax.lax.while_loop(cond, body, st)
        out = (res[3], res[4], res[5])
        return out + ((res[6],) if stats else ())

    if count is None:
        res = walk(None)
    else:
        res = jax.lax.cond(count == 0, empty, walk, None)
    cnt, qa, ra = res[:3]
    if packed:
        ra = jnp.where(qa >= 0, qa % cap, 0)
        qa = jnp.where(qa >= 0, qa // cap, n)
    if stats:
        return cnt, qa, ra, res[3]
    return cnt, qa, ra


# ---------------------------------------------------------------------------
# compaction — arena + counts -> the paper's (values, offsets, counts)
# ---------------------------------------------------------------------------

def _fan_out(rcnt, rep_of, live, n):
    """Per-query counts from representative counts (masked queries -> 0)."""
    safe = jnp.clip(rep_of, 0, max(n - 1, 0))
    return jnp.where(live, rcnt[safe], 0)


def rep_offsets(is_rep, rcnt):
    """Representative-dense base offsets, in batch order of representatives:
    ``rep_base[i]`` is where representative i's value segment starts in the
    dense slot list (garbage for non-representatives, never read)."""
    repc = jnp.where(is_rep, rcnt, 0)
    return jnp.cumsum(repc) - repc


def dense_capacity(cap, out_capacity) -> int:
    """Size of the representative-dense slot list.

    ``min(cap, out_capacity)`` suffices: every dense position a valid
    output element reads satisfies ``gpos <= j < out_capacity`` (each
    representative counted in ``rep_base`` has its first occurrence — and
    hence at least one full segment — before any query that reads it), and
    is also ``< cap`` (one slot per distinct stored value).  Writes past
    the truncation drop; truncated reads are zeroed by the valid mask.
    """
    return min(cap, max(int(out_capacity), 1))


def _emit(arena_values, cap, out_capacity, counts, is_rep, rep_of, rcnt,
          qarena, rank_arena):
    """Pack the walk's arena into the prefix-sum output layout.

    One scatter orders matched slots representative-dense (walk order
    within each representative), one gather fans the slot values out into
    every query's segment.  Entries past each segment — and everything
    past the true total when ``out_capacity`` truncates — stay zero,
    matching the reference's drop-scatter semantics bit for bit.

    The dense list is ``dense_capacity``-sized, NOT arena-sized: the
    scatter still reads the (cap,) arena once, but its target (and the
    whole downstream gather chain) shrinks to the output's own scale —
    the fix for pool-heavy stores whose arena dwarfs the batch.

    ``arena_values`` is the store's slot-arena hook (``slots -> (m, vw)``,
    cf. ``layouts.StoreOps.arena_values``) and ``cap`` its capacity: the
    open-addressing tables expose row*W+lane slot ids, the bucket-list
    table its value pool — either store shape rides this one compaction.
    """
    n = rep_of.shape[0]
    rep_base = rep_offsets(is_rep, rcnt)
    dcap = dense_capacity(cap, out_capacity)
    okslot = qarena < n
    safe_q = jnp.clip(qarena, 0, max(n - 1, 0))
    pos = jnp.where(okslot, rep_base[safe_q] + rank_arena, dcap)
    rep_dense = jnp.zeros((dcap,), _I).at[pos].set(
        jnp.arange(cap, dtype=_I), mode="drop")
    return _emit_dense(arena_values, cap, out_capacity, counts, rep_of,
                       rep_base, rep_dense)


def _emit_dense(arena_values, cap, out_capacity, counts, rep_of, rep_base,
                rep_dense):
    """Gather half of ``_emit``: fan a representative-dense slot list out
    into every query's prefix-sum segment.  ``rep_dense`` holds flat slot
    ids at ``rep_base[rep] + rank`` (walk order) — built either by
    ``_emit``'s arena scatter or stamped directly by a walk that knows its
    ranks up front (``bucket_list.chain_arena`` dense mode)."""
    n = rep_of.shape[0]
    dcap = rep_dense.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), _I), jnp.cumsum(counts)])
    j = jnp.arange(out_capacity, dtype=_I)
    seg = jnp.searchsorted(offsets[1:], j, side="right").astype(_I)
    segc = jnp.clip(seg, 0, max(n - 1, 0))
    local = j - offsets[segc]
    valid = j < offsets[n]
    gpos = jnp.clip(rep_base[jnp.clip(rep_of[segc], 0, max(n - 1, 0))] + local,
                    0, dcap - 1)
    slot = jnp.clip(rep_dense[gpos], 0, cap - 1)
    svals = arena_values(slot)                              # (out_capacity, vw)
    out = jnp.where(valid[:, None], svals, 0)
    return out, offsets, counts


def _emit_store(table, out_capacity, counts, is_rep, rep_of, rcnt, qarena,
                rank_arena):
    """_emit over an open-addressing table's own slot arena."""
    return _emit(lambda s: table.ops.arena_values(table.store, s),
                 table.ops.arena_capacity, out_capacity, counts, is_rep,
                 rep_of, rcnt, qarena, rank_arena)


# ---------------------------------------------------------------------------
# multi-value entry points
# ---------------------------------------------------------------------------

def _retrieval_stats(table, plen=None, active=None, tail_rounds=None):
    """TableStats for a pure retrieval walk (no statuses, no fixpoint)."""
    from repro.obs import metrics
    return metrics.table_stats(table.ops, table.store, plen=plen,
                               active=active, tail_rounds=tail_rounds)


def count_multi(table, keys, mask=None, stats=False):
    """Fused path for ``multi_value.count_values`` (dedup + one walk)."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    if n == 0:
        out = jnp.zeros((0,), _I)
        return (out, _retrieval_stats(table)) if stats else out
    live = jnp.ones((n,), bool) if mask is None else mask
    is_rep, rep_of = group_queries(keys, live)
    words = sv.probe_words(table, keys)
    fw = fused_walk(_tstatic(table), table.store, keys, words, is_rep,
                    collect=False, count=table.count, stats=stats)
    counts = _fan_out(fw[0], rep_of, live, n)
    if stats:
        return counts, _retrieval_stats(table, plen=fw[3], active=is_rep)
    return counts


def retrieve_all_multi(table, keys, out_capacity, mask=None, stats=False):
    """Fused path for ``multi_value.retrieve_all``: the single-walk
    count+gather this engine exists for."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    vw = table.value_words
    if n == 0:
        out = jnp.zeros((out_capacity, vw), _U)
        res = ((out[:, 0] if vw == 1 else out), jnp.zeros((1,), _I),
               jnp.zeros((0,), _I))
        return res + ((_retrieval_stats(table),) if stats else ())
    live = jnp.ones((n,), bool) if mask is None else mask
    is_rep, rep_of = group_queries(keys, live)
    words = sv.probe_words(table, keys)
    fw = fused_walk(
        _tstatic(table), table.store, keys, words, is_rep, collect=True,
        count=table.count, stats=stats)
    rcnt, qarena, rank_arena = fw[:3]
    counts = _fan_out(rcnt, rep_of, live, n)
    out, offsets, counts = _emit_store(table, out_capacity, counts, is_rep,
                                       rep_of, rcnt, qarena, rank_arena)
    res = ((out[:, 0] if vw == 1 else out), offsets, counts)
    if stats:
        return res + (_retrieval_stats(table, plen=fw[3], active=is_rep),)
    return res


def erase_multi(table, keys):
    """Fused path for ``multi_value.erase``: the walk's occupied-arena mask
    drives one dense batched tombstone write."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    if n == 0:
        return table, jnp.zeros((0,), _I)
    live = jnp.ones((n,), bool)
    is_rep, rep_of = group_queries(keys, live)
    words = sv.probe_words(table, keys)
    rcnt, qarena, _ = fused_walk(_tstatic(table), table.store, keys, words,
                                 is_rep, collect=True, count=table.count)
    store = table.ops.arena_tombstone(table.store, qarena < n)
    counts = _fan_out(rcnt, rep_of, live, n)
    erased = jnp.sum(jnp.where(is_rep, rcnt, 0), dtype=_I)
    return dataclasses.replace(table, store=store,
                               count=table.count - erased), counts


# ---------------------------------------------------------------------------
# single-value entry points (dedup + one located walk, shared with erase)
# ---------------------------------------------------------------------------

def _locate_reps(table, keys, stats=False):
    from repro.core import bulk
    from repro.core import single_value as sv
    n = keys.shape[0]
    live = jnp.ones((n,), bool)
    is_rep, rep_of = group_queries(keys, live)
    words = sv.probe_words(table, keys)
    pm = bulk.probe_matches(
        _tstatic(table), table.store, keys, words, is_rep, table.count,
        stats=stats)
    matched, mrow, mlane = pm[:3]
    out = (is_rep, rep_of, matched, mrow, mlane)
    return out + (pm[3:] if stats else ())


def retrieve_single(table, keys, stats=False):
    """Fused path for ``single_value.retrieve``: duplicate probe keys walk
    once; duplicates read their representative's slot."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    vw = table.value_words
    if n == 0:
        vals = jnp.zeros((0, vw), _U)
        res = ((vals[:, 0] if vw == 1 else vals), jnp.zeros((0,), bool))
        return res + ((_retrieval_stats(table),) if stats else ())
    lr = _locate_reps(table, keys, stats=stats)
    is_rep, rep_of, matched, mrow, mlane = lr[:5]
    vp = table.value_planes()                                 # (vw, p, W)
    rvals = vp[:, mrow, mlane].T                              # (n, vw)
    found = matched[rep_of]
    vals = jnp.where(found[:, None], rvals[rep_of], 0)
    res = ((vals[:, 0] if vw == 1 else vals), found)
    if stats:
        return res + (_retrieval_stats(table, plen=lr[5], active=is_rep,
                                       tail_rounds=lr[6]),)
    return res


def contains_single(table, keys):
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    if keys.shape[0] == 0:
        return jnp.zeros((0,), bool)
    _, rep_of, matched, _, _ = _locate_reps(table, keys)
    return matched[rep_of]


def erase_single(table, keys, mask=None):
    """Fused path for ``single_value.erase``: one representative walk, one
    batched tombstone scatter, count delta from the group structure (no
    separate distinct-count sort)."""
    from repro.core import bulk
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    if n == 0:
        return table, jnp.zeros((0,), bool)
    live = jnp.ones((n,), bool) if mask is None else mask
    is_rep, rep_of = group_queries(keys, live)
    words = sv.probe_words(table, keys)
    matched, mrow, mlane = bulk.probe_matches(
        _tstatic(table), table.store, keys, words, is_rep, table.count)
    hit = is_rep & matched
    srows = jnp.where(hit, mrow, _U(table.num_rows))
    store = table.ops.scatter_key_word(table.store, srows, mlane,
                                       TOMBSTONE_KEY)
    safe = jnp.clip(rep_of, 0, max(n - 1, 0))
    erased = live & matched[safe] & (rep_of < n)
    count = table.count - jnp.sum(hit, dtype=_I)
    return dataclasses.replace(table, store=store, count=count), erased
