"""Vectorized bulk-build engine — scatter-arbitration inserts.

The paper's headline number is *build* throughput (up to 1.6 G inserts/s);
the scan reference path in ``single_value`` / ``multi_value`` serializes the
batch with ``lax.scan`` (one probe walk per key, n sequential steps).  This
module replaces that with a constant number of **whole-batch vectorized
sweeps**, the bulk-synchronous build style of WarpSpeed (McCoy & Pandey
2025) and the NUMA pre-aggregation of Tripathy & Green (2021), mapped onto
the repo's single-writer-per-shard model (no CAS — all conflict resolution
happens *before* any store write):

1. **Dedup** — intra-batch duplicate keys are resolved in plain vector ops:
   sort-by-key groups equal keys, a segment-combine pre-aggregates the RMW
   operands (or picks the last writer for plain upsert), and exactly one
   *representative* per distinct key survives — the group's first live
   occurrence, carrying the group's combined operand.
2. **Probe** — representatives run one vectorized ``_locate``-style COPS
   walk against the (immutable, pre-batch) store.  Matches are final here:
   the batch inserts only keys *distinct* from every representative, so no
   store write can create or destroy a match.  Non-matches become
   *claimers*.  Building into an empty table — the paper's bulk-build
   benchmark — skips the walk entirely.
3. **Arbitrate** — claimers are placed by a *virtual-fill fixpoint* over a
   precomputed per-row free-lane count: claimers targeting a row are ranked
   by original batch position (scatter-min arbitration generalized from one
   slot to a whole probe window) and the k-th lowest-priority claimer takes
   the k-th lowest EMPTY/TOMBSTONE lane — exactly what k consecutive
   sequential inserts do to a window.  Claimers ranked past the row's free
   lanes are *bumped*: they advance their probe cursor to the next
   candidate row of their own probe sequence and re-enter the next sweep
   (possibly ousting a higher-priority tentative occupant there).  The
   fixpoint is the deferred-acceptance argument: by induction over
   priority, each claimer ends exactly where the sequential scan would have
   placed it.  Claimers that exhaust ``max_probes`` rows report FULL, like
   the scan.
4. **Apply** — one batched write phase: matched slots gather-old / fold /
   scatter (RMW) or scatter the pre-combined value (upsert); placed
   claimers scatter key + value.  Assignments are distinct by construction
   — (row, rank) pairs are unique — which the parity suite cross-checks
   with an explicit scatter-min arena (``arbitrate``).

Build complexity drops from n sequential probe walks to ~max_bump_chain
vectorized sweeps over a (num_rows,) count table, after a single
vectorized probe.

**Fast and general lanes.**  XLA's CPU sort has a fast payload-free form,
so the hot path (1-word keys) runs entirely in the original batch order:
group ids come from a bare key sort + ``searchsorted``, segment combines
are scatter-reductions (``.at[gid].add/min/max``) keyed by a per-word
combiner *spec* (e.g. ``("min", "add")``), and the per-sweep rank sort
packs (row, priority) into one u32.  Wide keys — u64 two-plane AND
composite ``key_words >= 2`` multi-column keys (``hashing.pack_columns``)
— and arbitrary user combiner *callables* take the general lane: one
stable MULTI-PLANE LEXICOGRAPHIC sort by (masked, key plane_{kw-1} ..
plane_0, batch index) (``_sort_batch``), with group segments bounded by
the all-plane adjacent-equality compare (``_group_structure``) — never a
single-plane compare, so composite keys differing only in a high word
occupy distinct groups.  Both lanes share probe / placement / apply
(which are plane-count agnostic: the probe word is the
``key_hash_word`` fold of every plane) and are bit-identical.

**Parity.**  The engine is bit-exact against the ``backend="scan"``
reference — same claimed slots, same table state, same per-element STATUS
codes — provided the RMW combine is associative and matches the sequential
fold (see ``update_single``).  ``tests/test_bulk.py`` asserts this across
duplicates, tombstone reuse, masks, near-full tables and u64 keys.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compat
from repro.core import probing
from repro.core.common import (
    EMPTY_KEY,
    STATUS_FULL,
    STATUS_INSERTED,
    STATUS_MASKED,
    STATUS_UPDATED,
    TOMBSTONE_KEY,
)

_U = jnp.uint32
_I = jnp.int32


def _tstatic(table):
    """(store protocol, scheme, seed, max_probes) — the engines' static tuple.

    ``max_probes`` is the COVERAGE-CLAMPED budget (``probing.
    effective_probes``): a walk never spends attempts revisiting rows its
    scheme cannot leave (quadratic reaches only (p+1)/2 distinct rows,
    bucketed exactly 2).  Identity for cops/linear with the default
    budget; for quadratic it is the spurious-FULL bugfix — revisited rows
    re-entered the claim fixpoint as fresh candidates and burned the
    budget the sequential reference spent on distinct rows.
    """
    return (table.ops, table.scheme, table.seed,
            probing.effective_probes(table.scheme, table.max_probes,
                                     table.ops.num_rows))


# ---------------------------------------------------------------------------
# combiner specs — scatter-reducible segment combines for the fast lane
# ---------------------------------------------------------------------------

#: per-word reducers usable as combiner specs: name -> (identity, pairwise)
COMBINE_OPS = {
    "add": (np.uint32(0), lambda a, b: a + b),
    "min": (np.uint32(0xFFFFFFFF), jnp.minimum),
    "max": (np.uint32(0), jnp.maximum),
    "or": (np.uint32(0), jnp.bitwise_or),
    "and": (np.uint32(0xFFFFFFFF), jnp.bitwise_and),
    "xor": (np.uint32(0), jnp.bitwise_xor),
}

#: specs with no native scatter-reduce method; folded via bit planes
_BITWISE = ("or", "and", "xor")


def combine_callable(spec: Sequence[str]) -> Callable:
    """Lift a per-word combiner spec into the general lane's callable form."""
    ops = [COMBINE_OPS[s][1] for s in spec]
    return lambda a, b: jnp.stack([op(a[w], b[w])
                                   for w, op in enumerate(ops)])


def _bitwise_scatter(name, gid, col, contrib, n):
    """Per-group bitwise or/and/xor of ``col[contrib]`` via ONE scatter-add.

    ``jnp.ndarray.at`` has no bitwise reducers, but every bitwise fold is a
    per-bit-plane popcount question: decompose the operands into a (n, 32)
    bit matrix, scatter-add it per group alongside the contributor count,
    and read each bit back as any (or), all (and) or parity (xor) of its
    plane.  Zero-contributor groups fall out as the op's identity (0 for
    or/xor, 0xFFFFFFFF for and) automatically.
    """
    shifts = jnp.arange(32, dtype=_U)
    bits = ((col[:, None] >> shifts[None, :]) & _U(1)).astype(_I)
    bits = jnp.where(contrib[:, None], bits, 0)
    acc = jnp.zeros((n, 32), _I).at[gid].add(bits)
    if name == "xor":
        plane = (acc & 1) > 0
    elif name == "or":
        plane = acc > 0
    else:  # and: every contributor set the bit
        cnt = jnp.zeros((n,), _I).at[gid].add(contrib.astype(_I))
        plane = acc == cnt[:, None]
    word = jnp.sum(jnp.where(plane, _U(1) << shifts[None, :], _U(0)), axis=1)
    return word[gid]


def _scatter_combine(spec, gid, vals, contrib):
    """Per-group combine of ``vals[contrib]`` via scatter-reduce -> (n, vw).

    Non-contributing elements scatter the op's identity, so each group cell
    holds exactly the fold over its contributors (the fast-lane rendering
    of the general lane's segmented scan).  add/min/max map directly onto
    ``.at[]`` reducers; the bitwise specs run the bit-plane scatter-add.
    """
    n = gid.shape[0]
    out = []
    for w, name in enumerate(spec):
        if name in _BITWISE:
            out.append(_bitwise_scatter(name, gid, vals[:, w], contrib, n))
            continue
        ident, _ = COMBINE_OPS[name]
        v = jnp.where(contrib, vals[:, w], ident)
        arena = jnp.full((n,), ident, _U)
        arena = getattr(arena.at[gid], name)(v)   # .add / .min / .max
        out.append(arena[gid])
    return jnp.stack(out, axis=1)


# ---------------------------------------------------------------------------
# dedup — fast lane (1-word keys, original batch order)
# ---------------------------------------------------------------------------

def _group_fast(keys1, live):
    """Group structure for 1-word keys without any payload sort.

    A bare value sort (XLA's fast path) + ``searchsorted`` yields a group
    id per element; first/last live occurrences come from scatter-min/max
    arenas, and both are skipped entirely when the sorted run has no
    adjacent duplicates.  Masked elements sort as EMPTY_KEY (no user key
    collides with a sentinel) and so never join a live group.

    Returns (is_rep, rep_of, lww_of, gid, has_dups) — all in batch order;
    ``rep_of``/``lww_of`` map every element to its group's first/last live
    element (itself when duplicate-free).
    """
    n = keys1.shape[0]
    idx = jnp.arange(n, dtype=_U)
    k = jnp.where(live, keys1, EMPTY_KEY)
    sk = jnp.sort(k)
    has_dups = jnp.any((sk[1:] == sk[:-1]) & (sk[:-1] != EMPTY_KEY))

    def with_dups(_):
        gid = jnp.searchsorted(sk, k).astype(_U)
        rep = jnp.full((n,), _U(n)).at[gid].min(jnp.where(live, idx, n))
        lww = jnp.zeros((n,), _U).at[gid].max(jnp.where(live, idx, 0))
        return gid, rep[gid], lww[gid]

    def without(_):
        return idx, idx, idx

    gid, rep_of, lww_of = jax.lax.cond(has_dups, with_dups, without, None)
    is_rep = live & (rep_of == idx)
    return is_rep, rep_of, lww_of, gid, has_dups


# ---------------------------------------------------------------------------
# dedup — general lane (wide keys / arbitrary combiners; sorted domain)
# ---------------------------------------------------------------------------

def _sort_batch(keys, mask, payload_cols):
    """Stable sort by (masked, key words, batch index).

    Masked elements cluster at the end (they never merge with live groups);
    within a live group elements keep batch order, so "first live
    occurrence" and "last writer" are positional.  Returns the sorted
    (masked_flag, key_words, orig_idx, payload_cols) tuple.

    **Packed u64 lane**: two-word keys (u64 two-plane and composite kw=2)
    fuse their planes into one ``plane0 << 32 | plane1`` sort word when
    XLA sorts genuine uint64 on this config (``compat.supports_u64_sort``
    — requires x64), cutting the comparator from 4 sort keys to 3.  The
    packed word compares exactly like the (plane0, plane1) lexicographic
    pair, and the planes are split back out of the sorted word, so the
    group structure and every downstream output are bit-identical to the
    two-plane path (asserted by ``tests/test_packed_sort.py``).
    """
    n = mask.shape[0]
    flag = (~mask).astype(_U)
    idx = jnp.arange(n, dtype=_U)
    kw = keys.shape[1]
    if kw == 2 and compat.supports_u64_sort():
        u64 = jnp.uint64
        word = (keys[:, 0].astype(u64) << u64(32)) | keys[:, 1].astype(u64)
        out = jax.lax.sort(tuple([flag, word, idx] + list(payload_cols)),
                           num_keys=3)
        sw = out[1]
        skeys = jnp.stack([(sw >> u64(32)).astype(_U),
                           (sw & u64(0xFFFFFFFF)).astype(_U)], axis=1)
        return out[0], skeys, out[2], out[3:]
    ops = [flag] + [keys[:, w] for w in range(kw)] + [idx] + list(payload_cols)
    out = jax.lax.sort(tuple(ops), num_keys=kw + 2)
    return out[0], jnp.stack(out[1:1 + kw], axis=1), out[1 + kw], out[2 + kw:]


def _group_structure(flag, skeys):
    """Segment layout of the sorted batch.

    Returns (live, is_rep, first_pos, last_pos): segments are maximal runs
    of equal live keys (each masked element is its own singleton segment,
    never read), ``is_rep`` marks the first live element of each live
    group, and first/last_pos give, per element, the sorted positions
    bounding its segment.
    """
    n = flag.shape[0]
    live = flag == 0
    same_key = jnp.all(skeys[1:] == skeys[:-1], axis=1)
    cont = jnp.concatenate([jnp.zeros((1,), bool),
                            same_key & live[1:] & live[:-1]])
    runstart = ~cont
    is_rep = live & runstart
    pos = jnp.arange(n, dtype=_I)
    first_pos = jax.lax.cummax(jnp.where(runstart, pos, -1))
    nxt = jnp.concatenate([runstart[1:], jnp.ones((1,), bool)])
    last_pos = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(nxt, pos, n))))
    return live, is_rep, first_pos, last_pos


def _segmented_combine(vals, reset, combine):
    """Inclusive segmented scan of ``vals`` (n, vw) with ``combine``.

    ``reset`` marks positions where accumulation restarts; the value at a
    segment's last position is the combine over [last reset .. last].
    """
    cmb = jax.vmap(combine)

    def op(a, b):
        fa, va = a
        fb, vb = b
        f = fa | fb
        v = jnp.where(fb[:, None], vb, cmb(va, vb))
        return f, v

    _, out = jax.lax.associative_scan(op, (reset, vals))
    return out


# ---------------------------------------------------------------------------
# step 2 — vectorized probe walk (batch version of _probe_for_insert)
# ---------------------------------------------------------------------------

#: each buffer of the compacted tail holds 1/_SHRINK of its parent's lanes.
#: At load 0.9 about 4% of the keys need a second window and 0.8% a third
#: (sv_lookup's key set): a sixteenth holds the first, and the second
#: overflows into a few cheap rounds.  On one v5e chip a 2^24-key lookup
#: took 1.560 s with 16 and 1.990 s with 4.
_SHRINK = 16


def _tail_sizes(n):
    """Buffer sizes of the compacted tail for a batch of ``n`` (static):
    n/16, n/256, ... while at least ``probing.COMPACT_LANES``; empty when
    the batch is too small to compact."""
    sizes = []
    m = n // _SHRINK
    while m >= probing.COMPACT_LANES:
        sizes.append(m)
        m //= _SHRINK
    return tuple(sizes)


def probe_matches(tstatic, store, keys, words, active, count=None,
                  stats=False):
    """One COPS walk for every active element against the current store.

    Returns (matched, row, lane) — the position of each key already
    present.  The walk stops at a match or a window containing EMPTY
    (absence proof), exactly like ``_locate``; candidate slots are NOT
    chosen here — claims are placed by the virtual-fill fixpoint, which
    owns the write-order semantics.  When ``count`` is given and zero (the
    bulk-build-from-fresh case), the walk is skipped: an empty table can
    hold no match even if erases left tombstones behind.

    **Compacted tail.**  Most keys stop in their first window (1.05
    windows per key at load 0.9), but a full-width loop runs until the
    slowest key stops.  So when the budget exceeds
    ``probing.UNROLL_PROBES`` and the batch holds at least 16 times
    ``probing.COMPACT_LANES`` elements, window 1 runs over the whole
    batch, and the keys still pending after it move into a static buffer
    of 1/16 of the batch (pending lanes first, in batch order, by one sort
    of their indices).  That buffer runs window 2, its pending keys move
    into a buffer of 1/256, and so on down to the last buffer of at least
    ``COMPACT_LANES`` lanes, which runs the while-loop to the end.  Each buffer's results scatter back to its parent by index.  A
    stage whose pending keys outnumber its buffer takes them in rounds,
    each round walking its keys to the end before the next; nothing is
    dropped or cut short.  The result is exact: every key's walk reads the
    same immutable store, visits the same rows in the same order and stops
    on the same rule whichever lanes share its window, so (matched, row,
    lane) and the probe length equal the full-width loop's.

    ``stats`` (static) additionally returns the per-element probe length
    — windows examined before the element's walk stopped — and the number
    of compacted rounds run (0 on the full-width paths) as a fourth and
    fifth output.  When False (default) the graph carries neither.
    """
    ops, scheme, seed, max_probes = tstatic
    num_rows, w = ops.num_rows, ops.window
    n = keys.shape[0]
    row0 = probing.initial_row(words, num_rows, seed, ops.quotient)
    step = probing.row_step(scheme, words, num_rows, seed, ops.quotient)
    zero = jnp.zeros((), _I)

    def empty(_):
        out = (jnp.zeros((n,), bool), row0, jnp.zeros((n,), _U))
        return out + ((jnp.zeros((n,), _I), zero) if stats else ())

    def window(keys, words, step):
        """Loop body: one probe window for every lane not yet done."""
        def body(st):
            if stats:
                attempt, row, done, mrow, mlane, matched, plen = st
                plen = plen + (~done).astype(_I)
            else:
                attempt, row, done, mrow, mlane, matched = st
            win = ops.key_windows(store, row)
            has_empty = probing.vote_any(win[:, 0, :] == EMPTY_KEY)
            if ops.quotient:
                # quotient stores hold q*2 + choice: the compare target is
                # attempt-dependent (choice == attempt on the bucketed walk)
                tgt = probing.match_word(words, num_rows, attempt,
                                         quotient=True)
                match = win[:, 0, :] == tgt[:, None]
            else:
                match = jnp.all(win == keys[:, :, None], axis=1)
            m_lane = probing.vote_lowest(match)
            hit = (m_lane < w) & ~done
            mrow = jnp.where(hit, row, mrow)
            mlane = jnp.where(hit, m_lane.astype(_U), mlane)
            matched = matched | hit
            done = done | hit | has_empty
            nrow = probing.advance_row(scheme, row, step, attempt, num_rows)
            out = (attempt + 1, jnp.where(done, row, nrow), done, mrow,
                   mlane, matched)
            return out + ((plen,) if stats else ())
        return body

    def cond(st):
        attempt, row, done, *_ = st
        return jnp.logical_and(attempt < max_probes, ~jnp.all(done))

    def start(attempt, row, done):
        """Walk state of lanes that have run ``attempt`` windows unmatched."""
        m = row.shape[0]
        z = jnp.zeros((m,), _U)
        st = (jnp.asarray(attempt, _I), row, done, z, z,
              jnp.zeros((m,), bool))
        return st + ((jnp.full((m,), attempt, _I),) if stats else ())

    def tail(k, sizes, keys, words, step, st):
        """Finish the walks of a buffer whose lanes have all run ``k``
        windows.  Returns the state, plus the rounds run under stats."""
        if k >= max_probes or not sizes:
            if k < max_probes:
                st = jax.lax.while_loop(cond, window(keys, words, step), st)
            return (st, zero) if stats else (st,)
        big, m, kw = st[1].shape[0], sizes[0], keys.shape[1]
        payload = jnp.concatenate([keys, words[:, None], st[1][:, None]],
                                  axis=1)
        lane = jnp.arange(big, dtype=_U)

        def put(a, sel, b):
            return a.at[sel].set(b, mode="drop", indices_are_sorted=True,
                                 unique_indices=True)

        def rbody(c):
            st = c[0]
            # pending lanes first, in batch order; the others sort past
            # ``big``, so the indices are sorted and distinct, and those
            # past ``big`` fill (gather) or drop (scatter)
            sel = jnp.sort(jnp.where(st[2], _U(big) + lane, lane))[:m]
            sub = payload.at[sel].get(mode="fill", fill_value=0,
                                      indices_are_sorted=True,
                                      unique_indices=True)
            ckeys, cwords = sub[:, :kw], sub[:, kw]
            cstep = probing.row_step(scheme, cwords, num_rows, seed,
                                     ops.quotient)
            cst = window(ckeys, cwords, cstep)(
                start(k, sub[:, kw + 1], sel >= big))
            cres = tail(k + 1, sizes[1:], ckeys, cwords, cstep, cst)
            cst = cres[0]
            st = st[:2] + (put(st[2], sel, jnp.ones((m,), bool)),) + tuple(
                put(a, sel, b) for a, b in zip(st[3:], cst[3:]))
            return (st,) + ((c[1] + 1 + cres[1],) if stats else ())

        c = (st,) + ((zero,) if stats else ())
        return jax.lax.while_loop(lambda c: ~jnp.all(c[0][2]), rbody, c)

    def walk(_):
        st = start(0, row0, ~active)
        body = window(keys, words, step)
        sizes = _tail_sizes(n)
        rounds = zero
        if max_probes <= probing.UNROLL_PROBES:
            # static <= 2-window budget (the bucketed walk): unroll so the
            # walk costs the same at every load factor; body is a no-op
            # once an element is done, so outputs are identical
            res = st
            for _ in range(max_probes):
                res = body(res)
        elif sizes:
            out = tail(1, sizes, keys, words, step, body(st))
            res = out[0]
            rounds = out[1] if stats else zero
        else:
            res = jax.lax.while_loop(cond, body, st)
        matched, mrow, mlane = res[5], res[3], res[4]
        if stats:
            return matched, mrow, mlane, res[6], rounds
        return matched, mrow, mlane

    if count is None:
        return walk(None)
    return jax.lax.cond(count == 0, empty, walk, None)


# ---------------------------------------------------------------------------
# step 3 — virtual-fill fixpoint (claim placement)
# ---------------------------------------------------------------------------

def _rank_by_row(row, prio, alive, num_rows, prio_is_iota):
    """Rank each alive claimer among same-row claimers by priority.

    Fast form: pack (row, prio) into one u32 and run XLA's payload-free
    sort; the element is recovered from the priority half of the packed
    word.  Falls back to a two-key sort when num_rows * n overflows u32.
    ``prio_is_iota`` (static) marks the batch-order case where the
    priority IS the element index, skipping the final permutation gather.
    """
    n = prio.shape[0]
    pos = jnp.arange(n, dtype=_I)
    if int(num_rows) * n < 2 ** 32:
        sent = _U(2 ** 32 - 1)
        packed = jnp.where(alive, row * _U(n) + prio, sent)
        sp = jnp.sort(packed)
        srow = sp // _U(n)
        tgt = jnp.where(sp == sent, _U(n), sp % _U(n))   # element id (prio)
    else:
        grp = jnp.where(alive, row, _U(num_rows))
        srow, sprio, _ = jax.lax.sort(
            (grp, prio, jnp.arange(n, dtype=_U)), num_keys=2)
        tgt = jnp.where(srow == _U(num_rows), _U(n), sprio)
    newrow = jnp.concatenate([jnp.ones((1,), bool), srow[1:] != srow[:-1]])
    rank_sorted = pos - jax.lax.cummax(jnp.where(newrow, pos, -1))
    by_prio = jnp.zeros((n,), _I).at[tgt].set(rank_sorted, mode="drop")
    return by_prio if prio_is_iota else by_prio[prio]


def _nth_set_lane(mask32, rank, window):
    """Lane index of the ``rank``-th set bit of a per-element u32 candidate
    bitmask — a 5-step popcount binary search, all (n,)-elementwise ops
    (the vector analogue of __fns on a ballot mask).  Requires W <= 32."""
    lane = jnp.zeros(rank.shape, _I)
    cur = mask32
    r = rank
    for shift in (16, 8, 4, 2, 1):
        if shift >= window:
            continue
        low = cur & _U((1 << shift) - 1)
        c = jax.lax.population_count(low).astype(_I)
        hi = r >= c
        r = r - jnp.where(hi, c, 0)
        lane = lane + jnp.where(hi, shift, 0)
        cur = jnp.where(hi, cur >> shift, low)
    return lane


def place_claims(tstatic, store, words, claim, prio, prio_is_iota=False,
                 stats=False):
    """Assign every claimer a slot — or FULL — via the virtual-fill fixpoint.

    Per sweep, claimers targeting a row are ranked by ``prio`` (original
    batch position = sequential insert order); rank k takes the k-th lowest
    free lane, ranks past the row's free-lane count bump to the next
    candidate row of their own probe sequence.  A bumped claimer may oust a
    higher-priority tentative occupant of its new row in the following
    sweep, so the fixpoint converges to the priority-greedy (= sequential)
    assignment.  Returns (placed, row, lane, full).

    ``stats`` (static) appends two telemetry outputs — the per-element
    final probe attempt (rows examined, = the claimer's probe length) and
    the number of fixpoint sweeps run — without touching the stats-off
    graph (the per-element attempt is already in the carry; only the sweep
    counter is added, gated on the python flag).
    """
    ops, scheme, seed, max_probes = tstatic
    num_rows, w = ops.num_rows, ops.window
    n = prio.shape[0]
    kp0 = ops.key_planes(store)[0]                            # (p, W)
    cand = (kp0 == EMPTY_KEY) | (kp0 == TOMBSTONE_KEY)
    if w <= 32:
        # pack each row's candidate lanes into one u32 ballot mask
        bits = jax.lax.broadcasted_iota(_U, cand.shape, 1)
        cmask = jnp.sum(jnp.where(cand, _U(1) << bits, _U(0)), axis=1)
        n_cand = jax.lax.population_count(cmask).astype(_I)   # (p,)
    else:
        cmask = None
        n_cand = jnp.sum(cand.astype(_I), axis=1)             # (p,)
    row0 = probing.initial_row(words, num_rows, seed, ops.quotient)
    step = probing.row_step(scheme, words, num_rows, seed, ops.quotient)

    def advance(attempt, row, move, full):
        """Advance bumped claimers to their next row with any free lane."""
        def cond(st):
            attempt, row, pending = st
            return jnp.any(pending)

        def body(st):
            attempt, row, pending = st
            # attempt is 1-based (examined rows); advance_row wants the
            # 0-based index of the row being left (quadratic increments).
            nrow = probing.advance_row(scheme, row, step, attempt - 1,
                                       num_rows)
            row = jnp.where(pending, nrow, row)
            attempt = attempt + pending.astype(_I)
            pending = pending & (attempt < max_probes) & (n_cand[row] == 0)
            return attempt, row, pending

        attempt, row, _ = jax.lax.while_loop(cond, body,
                                             (attempt, row, move & ~full))
        # a claimer may sit at attempt == max_probes (the scan examines
        # exactly max_probes rows); past that, or stranded on a
        # candidate-free row, it is FULL.
        full = full | (move & ((attempt > max_probes) | (n_cand[row] == 0)))
        return attempt, row, full

    def cond(st):
        attempt, row, full, rank, over = st[:5]
        return jnp.any(over)

    def body(st):
        if stats:
            attempt, row, full, rank, over, sweeps = st
        else:
            attempt, row, full, rank, over = st
        attempt, row, full = advance(attempt, row, over, full)
        alive = claim & ~full
        rank = _rank_by_row(row, prio, alive, num_rows, prio_is_iota)
        over = alive & (rank >= n_cand[row])
        out = (attempt, row, full, rank, over)
        return out + ((sweeps + 1,) if stats else ())

    attempt0 = jnp.ones((n,), _I)
    full0 = claim & (max_probes < 1)
    rank0 = _rank_by_row(row0, prio, claim & ~full0, num_rows, prio_is_iota)
    over0 = claim & ~full0 & (rank0 >= n_cand[row0])
    st = (attempt0, row0, full0, rank0, over0)
    if stats:
        st = st + (jnp.zeros((), _I),)
    res = jax.lax.while_loop(cond, body, st)
    attempt, row, full, rank = res[0], res[1], res[2], res[3]
    placed = claim & ~full
    # rank-th lowest free lane of the assigned row
    if cmask is not None:
        lane = _nth_set_lane(cmask[row], rank, w)
    else:
        crow = cand[row]                                      # (n, W)
        crank = jnp.cumsum(crow.astype(_I), axis=1) - 1
        lanes = jax.lax.broadcasted_iota(_I, crow.shape, 1)
        lane = jnp.min(jnp.where(crow & (crank == rank[:, None]), lanes,
                                 _I(w)), axis=1)
    out = (placed, row, jnp.where(placed, lane, 0).astype(_U), full)
    if stats:
        return out + (jnp.clip(attempt, 0, max_probes), res[5])
    return out


def arbitrate(row, lane, claim, prio, num_rows, window):
    """Scatter-min slot arbitration: at most one claimer wins each
    (row, lane) slot.  Virtual-fill assignments are distinct by
    construction — (row, rank) pairs are unique — so this arena is the
    cross-check the parity suite runs over every placement, rather than a
    hot-path pass."""
    cap = num_rows * window
    slot = jnp.where(claim, row.astype(_I) * window + lane.astype(_I), cap)
    arena = jnp.full((cap + 1,), EMPTY_KEY, _U).at[slot].min(prio)
    return claim & (arena[slot] == prio)


# ---------------------------------------------------------------------------
# step 4 — batched apply
# ---------------------------------------------------------------------------

def _apply(table, keys, matched, mrow, mlane, placed, crow, clane,
           matched_vals, claim_vals):
    """One write phase: matched value scatters + placed key/value scatters.

    The batched scatter itself lives in the store protocol
    (``StoreOps.scatter_batch``): SOA scatters flattened planes (XLA's 1-D
    fast path), AOS composes the per-kind scatters.
    """
    oor = _U(table.num_rows)
    row = jnp.where(matched, mrow, crow)
    lane = jnp.where(matched, mlane, clane)
    vals = jnp.where(matched[:, None], matched_vals, claim_vals)
    vrow = jnp.where(matched | placed, row, oor)
    store = table.ops.scatter_batch(table.store, vrow, lane, keys, vals,
                                    placed)
    return store, jnp.sum(placed, dtype=_I)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _write_keys(table, keys, words, crow):
    """Key planes to scatter for placed claimers.

    Raw keys for every store except quotient, which writes the encoded
    remainder ``q*2 + choice``; ``choice`` falls out of the claim row
    (claim_row != first bucket — the bucketed walk has only two rows).
    """
    if not table.ops.quotient:
        return keys
    p = table.ops.num_rows
    row0 = probing.initial_row(words, p, table.seed, quotient=True)
    choice = (crow != row0)
    return probing.stored_word(words, p, choice, quotient=True)[:, None]


def _walk_plen(matched, probe_plen, claim_attempt, max_probes):
    """Per-element walk length: match-walk windows for matched elements,
    final placement attempt for claimers (clipped to max_probes)."""
    return jnp.where(matched, probe_plen,
                     jnp.clip(claim_attempt, 0, max_probes))


def _build_stats(table, status, plen, active, sweeps, tail_rounds=None):
    """Assemble the in-graph TableStats for a build op (post-op table)."""
    from repro.obs import metrics
    return metrics.table_stats(table.ops, table.store, status=status,
                               plen=plen, active=active,
                               fixpoint_iters=sweeps,
                               tail_rounds=tail_rounds)


def _finish_fast(table, keys, live, is_rep, rep_of, matched, mrow, mlane,
                 placed, crow, clane, matched_vals, claim_vals):
    """Shared tail of the fast lane: apply + statuses in batch order."""
    store, claimed = _apply(table, keys, matched, mrow, mlane, placed, crow,
                            clane, matched_vals, claim_vals)
    rep_ok = (matched | placed)[rep_of]
    status = jnp.where(
        ~live, _I(STATUS_MASKED),
        jnp.where(matched, _I(STATUS_UPDATED),
                  jnp.where(placed, _I(STATUS_INSERTED),
                            jnp.where(is_rep, _I(STATUS_FULL),
                                      jnp.where(rep_ok, _I(STATUS_UPDATED),
                                                _I(STATUS_FULL))))))
    return dataclasses.replace(table, store=store,
                               count=table.count + claimed), status


def insert_single(table, keys, values, mask=None, stats=False):
    """Bulk path for ``single_value.insert`` (plain upsert, LWW dedup).

    ``stats=True`` (static) returns ``(table, status, TableStats)`` with
    the telemetry accumulated inside the same graph; the default graph is
    untouched."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    values = sv.normalize_words(values, table.value_words, "values")
    n = keys.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    tstat = _tstatic(table)
    if table.key_words != 1:
        return _insert_general(table, tstat, keys, values, mask, stats=stats)
    is_rep, rep_of, lww_of, _, _ = _group_fast(keys[:, 0], mask)
    words = sv.probe_words(table, keys)
    pm = probe_matches(tstat, table.store, keys, words, is_rep, table.count,
                       stats=stats)
    matched, mrow, mlane = pm[:3]
    pc = place_claims(tstat, table.store, words, is_rep & ~matched,
                      jnp.arange(n, dtype=_U), prio_is_iota=True, stats=stats)
    placed, crow, clane = pc[0], pc[1], pc[2]
    lww = values[lww_of]                         # group's last live writer
    wkeys = _write_keys(table, keys, words, crow)
    out = _finish_fast(table, wkeys, mask, is_rep, rep_of, matched, mrow,
                       mlane, placed, crow, clane, lww, lww)
    if not stats:
        return out
    ntable, status = out
    plen = _walk_plen(matched, pm[3], pc[4], tstat[3])
    return ntable, status, _build_stats(ntable, status, plen, is_rep, pc[5],
                                        pm[4])


def update_single(table, keys, update_fn, combine, init, values, mask=None,
                  stats=False):
    """Bulk path for ``single_value.update_values`` (RMW upsert).

    ``combine`` must be the associative pre-aggregation of the operand
    stream: ``update_fn(update_fn(x, k, a), k, b) ==
    update_fn(x, k, combine(a, b))`` — sum/min/max/saturating-count all
    qualify.  A per-word spec tuple (e.g. ``("min", "add")``) runs the
    scatter-reduce fast lane; a callable runs the general sorted lane.
    Groups fold their operands before any store access; absent keys write
    ``update_fn(init_first, k, tail)`` exactly as the sequential chain
    would.
    """
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    tstat = _tstatic(table)
    is_spec = not callable(combine)
    if table.key_words != 1 or not is_spec:
        cmb = combine_callable(combine) if is_spec else combine
        return _update_general(table, tstat, keys, update_fn, cmb, init,
                               values, mask, stats=stats)
    spec = tuple(combine)
    vw = table.value_words
    vfold = jax.vmap(update_fn)
    is_rep, rep_of, lww_of, gid, has_dups = _group_fast(keys[:, 0], mask)
    words = sv.probe_words(table, keys)
    pm = probe_matches(tstat, table.store, keys, words, is_rep, table.count,
                       stats=stats)
    matched, mrow, mlane = pm[:3]
    pc = place_claims(tstat, table.store, words, is_rep & ~matched,
                      jnp.arange(n, dtype=_U), prio_is_iota=True, stats=stats)
    placed, crow, clane = pc[0], pc[1], pc[2]

    def folded(_):
        # agg_all = fold of every live operand (applied to the stored value
        # on match); agg_tail = fold of all but the first (applied to the
        # first element's init on claim: sequentially the claim writes init
        # and later duplicates fold into it).
        agg_all = _scatter_combine(spec, gid, values, mask)
        agg_tail = _scatter_combine(spec, gid, values, mask & ~is_rep)
        has_tail = lww_of != rep_of
        claim_vals = jnp.where(has_tail[:, None],
                               vfold(init[rep_of], keys, agg_tail),
                               init[rep_of])
        return agg_all, claim_vals

    def plain(_):
        return values, init

    agg_all, claim_vals = jax.lax.cond(has_dups, folded, plain, None)
    old = table.ops.value_windows(table.store, mrow)           # (n, vw, W)
    old = jnp.take_along_axis(
        old, mlane.astype(_I)[:, None, None], axis=2)[:, :, 0]
    matched_vals = vfold(old, keys, agg_all)
    wkeys = _write_keys(table, keys, words, crow)
    out = _finish_fast(table, wkeys, mask, is_rep, rep_of, matched, mrow,
                       mlane, placed, crow, clane, matched_vals, claim_vals)
    if not stats:
        return out
    ntable, status = out
    plen = _walk_plen(matched, pm[3], pc[4], tstat[3])
    return ntable, status, _build_stats(ntable, status, plen, is_rep, pc[5],
                                        pm[4])


def insert_multi(table, keys, values, mask=None, stats=False):
    """Bulk path for ``multi_value.insert`` (append; no dedup — every live
    element is a claimer, duplicates of a key contend for slots and the
    fixpoint resolves them in batch order)."""
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    values = sv.normalize_words(values, table.value_words, "values")
    n = keys.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    words = sv.key_hash_word(keys)
    tstat = _tstatic(table)
    pc = place_claims(tstat, table.store, words, mask,
                      jnp.arange(n, dtype=_U), prio_is_iota=True, stats=stats)
    placed, row, lane = pc[0], pc[1], pc[2]
    wrow = jnp.where(placed, row, _U(table.num_rows))
    store = table.ops.scatter_batch(table.store, wrow, lane, keys, values,
                                    placed)
    status = jnp.where(~mask, _I(STATUS_MASKED),
                       jnp.where(placed, _I(STATUS_INSERTED),
                                 _I(STATUS_FULL)))
    ntable = dataclasses.replace(
        table, store=store, count=table.count + jnp.sum(placed, dtype=_I))
    if not stats:
        return ntable, status
    plen = jnp.clip(pc[4], 0, tstat[3])
    return ntable, status, _build_stats(ntable, status, plen, mask, pc[5])


# ---------------------------------------------------------------------------
# general lane (u64 two-plane keys, arbitrary combiner callables)
# ---------------------------------------------------------------------------

def _statuses_sorted(n, live, is_rep, first_pos, matched, placed, sidx):
    """Fast-lane statuses, but in the sorted domain + unsort scatter."""
    rep_ok = (matched | placed)[first_pos]
    rep_status = jnp.where(matched, _I(STATUS_UPDATED),
                           jnp.where(placed, _I(STATUS_INSERTED),
                                     _I(STATUS_FULL)))
    dup_status = jnp.where(rep_ok, _I(STATUS_UPDATED), _I(STATUS_FULL))
    status = jnp.where(~live, _I(STATUS_MASKED),
                       jnp.where(is_rep, rep_status, dup_status))
    return jnp.zeros((n,), _I).at[sidx].set(status)


def _insert_general(table, tstat, keys, values, mask, stats=False):
    from repro.core import single_value as sv
    n = keys.shape[0]
    vw = table.value_words
    flag, skeys, sidx, vcols = _sort_batch(
        keys, mask, [values[:, w] for w in range(vw)])
    svals = (jnp.stack(vcols, axis=1) if vw else jnp.zeros((n, 0), _U))
    live, is_rep, first_pos, last_pos = _group_structure(flag, skeys)
    lww = svals[last_pos]
    swords = sv.key_hash_word(skeys)
    pm = probe_matches(tstat, table.store, skeys, swords, is_rep,
                       table.count, stats=stats)
    matched, mrow, mlane = pm[:3]
    pc = place_claims(tstat, table.store, swords, is_rep & ~matched, sidx,
                      stats=stats)
    placed, crow, clane = pc[0], pc[1], pc[2]
    store, claimed = _apply(table, skeys, matched, mrow, mlane, placed,
                            crow, clane, lww, lww)
    status = _statuses_sorted(n, live, is_rep, first_pos, matched, placed,
                              sidx)
    ntable = dataclasses.replace(table, store=store,
                                 count=table.count + claimed)
    if not stats:
        return ntable, status
    plen = _walk_plen(matched, pm[3], pc[4], tstat[3])
    return ntable, status, _build_stats(ntable, status, plen, is_rep, pc[5],
                                        pm[4])


def _update_general(table, tstat, keys, update_fn, combine, init, values,
                    mask, stats=False):
    from repro.core import single_value as sv
    n = keys.shape[0]
    vw = table.value_words
    cols = ([values[:, w] for w in range(vw)]
            + [init[:, w] for w in range(vw)])
    flag, skeys, sidx, scols = _sort_batch(keys, mask, cols)
    svals = jnp.stack(scols[:vw], axis=1) if vw else jnp.zeros((n, 0), _U)
    sinit = jnp.stack(scols[vw:], axis=1) if vw else jnp.zeros((n, 0), _U)
    live, is_rep, first_pos, last_pos = _group_structure(flag, skeys)
    swords = sv.key_hash_word(skeys)
    vfold = jax.vmap(update_fn)

    runstart = jnp.arange(n, dtype=_I) == first_pos
    rank1 = jnp.concatenate([jnp.zeros((1,), bool), runstart[:-1]]) & ~runstart
    agg_all = _segmented_combine(svals, runstart, combine)[last_pos]
    agg_tail = _segmented_combine(svals, rank1, combine)[last_pos]
    group_m = last_pos - first_pos + 1
    claim_vals = jnp.where((group_m >= 2)[:, None],
                           vfold(sinit, skeys, agg_tail), sinit)
    claim_vals = claim_vals[first_pos]

    pm = probe_matches(tstat, table.store, skeys, swords, is_rep,
                       table.count, stats=stats)
    matched, mrow, mlane = pm[:3]
    pc = place_claims(tstat, table.store, swords, is_rep & ~matched, sidx,
                      stats=stats)
    placed, crow, clane = pc[0], pc[1], pc[2]
    old = table.ops.value_windows(table.store, mrow)
    old = jnp.take_along_axis(
        old, mlane.astype(_I)[:, None, None], axis=2)[:, :, 0]
    matched_vals = vfold(old, skeys, agg_all)
    store, claimed = _apply(table, skeys, matched, mrow, mlane, placed,
                            crow, clane, matched_vals, claim_vals)
    status = _statuses_sorted(n, live, is_rep, first_pos, matched, placed,
                              sidx)
    ntable = dataclasses.replace(table, store=store,
                                 count=table.count + claimed)
    if not stats:
        return ntable, status
    plen = _walk_plen(matched, pm[3], pc[4], tstat[3])
    return ntable, status, _build_stats(ntable, status, plen, is_rep, pc[5],
                                        pm[4])
