"""Hypothesis property tests: table semantics vs python reference models,
and the structural invariants the probing scheme relies on.

The engines are called through the jitted wrappers below, so that each
static configuration (geometry, scheme, backend) compiles once for the
whole sweep: eager calls recompile their inner loops on every call.
Drawn lists are padded to their strategy's ``max_size`` so that every
example has the same shapes: inserts and erases mask the padding off,
lookups and ``mv.erase`` (no mask) pad with ``PAD_KEY``, a key outside
every drawn universe, and the padded lanes are dropped before comparing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import bloom as bf
from repro.core import bucket_list as bl
from repro.core import hashing, layouts, migrate, probing
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.core.common import (
    EMPTY_KEY,
    STATUS_INSERTED,
    STATUS_UPDATED,
    TOMBSTONE_KEY,
)
from repro.serving import elastic

SETTINGS = settings(max_examples=20, deadline=None,
                    suppress_health_check=[HealthCheck.too_slow,
                                           HealthCheck.data_too_large])

KEYS_MAX = 80
keys_st = st.lists(st.integers(1, 0xFFFF00), min_size=1, max_size=KEYS_MAX)
vals_st = st.integers(0, 0xFFFFFFFF)
PAD_KEY = 0xFFFF01          # above every drawn key, and not a sentinel

sv_insert = jax.jit(sv.insert)
sv_erase = jax.jit(sv.erase)
sv_retrieve = jax.jit(sv.retrieve)
mv_insert = jax.jit(mv.insert)
mv_erase = jax.jit(mv.erase)
mv_count = jax.jit(mv.count_values)
mv_retrieve_all = jax.jit(mv.retrieve_all, static_argnames="out_capacity")
bl_insert = jax.jit(bl.insert)
bl_count = jax.jit(bl.count_values)
bl_retrieve_all = jax.jit(bl.retrieve_all, static_argnames="out_capacity")
bf_insert = jax.jit(bf.insert)
bf_contains = jax.jit(bf.contains)
el_insert = jax.jit(elastic.insert)
el_erase = jax.jit(elastic.erase)
el_lookup = jax.jit(elastic.lookup)
# Traced, compaction and migration skip their host-side live-count guard
# and registry counters; the sweeps compare the live set themselves.
el_compact_all = jax.jit(elastic.compact_all)
mig_grow = jax.jit(migrate.grow, static_argnames="new_capacity")
mig_compact = jax.jit(migrate.compact)


@jax.jit
def _advertised(filters, keys):
    """Whether each key is set in its owner shard's bloom filter."""
    words = sv.key_hash_word(sv.normalize_key_batch(keys, 1, "keys"))
    owners = hashing.hash_owner(words, len(filters))
    bits = jnp.stack([f.bits for f in filters])
    return bf.contains_stack(filters[0], bits, owners, words)


def _pad(xs, size, fill=PAD_KEY):
    """``xs`` as a u32 array of ``size`` lanes (the tail ``fill``), and the
    mask of its ``len(xs)`` live lanes."""
    a = np.full(size, fill, np.uint32)
    a[:len(xs)] = np.asarray(xs, np.uint32)
    return jnp.asarray(a), jnp.asarray(np.arange(size) < len(xs))


@st.composite
def ops_st(draw):
    """A sequence of (op, key, value) against a small key universe."""
    n = draw(st.integers(1, 60))
    ops = []
    for _ in range(n):
        op = draw(st.sampled_from(["insert", "insert", "insert", "erase"]))
        k = draw(st.integers(1, 40))
        v = draw(st.integers(0, 10 ** 6))
        ops.append((op, k, v))
    return ops


class TestSingleValueVsDict:
    @SETTINGS
    @given(ops=ops_st(), window=st.sampled_from([4, 16, 32]),
           scheme=st.sampled_from(["cops", "linear"]))
    def test_matches_dict_model(self, ops, window, scheme):
        t = sv.create(512, window=window, scheme=scheme)
        model = {}
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, stt = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
                code = int(stt[0])
                assert code == (STATUS_UPDATED if k in model
                                else STATUS_INSERTED)
                model[k] = v & 0xFFFFFFFF
            else:
                t, er = sv_erase(t, ka)
                assert bool(er[0]) == (k in model)
                model.pop(k, None)
        assert int(t.count) == len(model)
        universe = jnp.arange(1, 41, dtype=jnp.uint32)
        got, found = sv_retrieve(t, universe)
        for i, k in enumerate(range(1, 41)):
            assert bool(found[i]) == (k in model)
            if k in model:
                assert int(got[i]) == model[k]

    @SETTINGS
    @given(keys=keys_st)
    def test_cops_invariant_lowest_candidate(self, keys):
        """Every stored key sits at the lowest candidate position of its
        probe sequence (what makes stop-at-EMPTY retrieval sound)."""
        t = sv.create(256, window=8)
        u = np.unique(np.asarray(keys, np.uint32))
        ku, live = _pad(u, KEYS_MAX)
        t, _ = sv_insert(t, ku, ku, live)
        kp = np.asarray(t.key_planes()[0])          # (p, W)
        word = hashing.mix_murmur3(jnp.asarray(u))
        for k in u:
            row = int(probing.initial_row(jnp.uint32(k), t.num_rows, t.seed))
            step = int(probing.row_step("cops", jnp.uint32(k), t.num_rows,
                                        t.seed))
            for attempt in range(t.num_rows):
                win = kp[row]
                if (win == k).any():
                    lane = int(np.argmax(win == k))
                    before = win[:lane]
                    assert not (before == EMPTY_KEY).any(), \
                        f"key {k} not at lowest candidate lane"
                    break
                assert not (win == EMPTY_KEY).any(), \
                    f"EMPTY window before key {k} was found"
                row = (row + step) % t.num_rows


class TestMultiValueVsMultiDict:
    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 20),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=100))
    def test_multiset_semantics(self, pairs):
        t = mv.create(1024, window=16)
        model: dict = {}
        ks, live = _pad([p[0] for p in pairs], 100)
        vs, _ = _pad([p[1] for p in pairs], 100)
        for k, v in pairs:
            model.setdefault(k, []).append(v & 0xFFFFFFFF)
        t, stt = mv_insert(t, ks, vs, live)
        assert (np.asarray(stt)[:len(pairs)] == STATUS_INSERTED).all()
        q = jnp.arange(1, 21, dtype=jnp.uint32)
        cnt = mv_count(t, q)
        for i, k in enumerate(range(1, 21)):
            assert int(cnt[i]) == len(model.get(k, []))
        out, off, _ = mv_retrieve_all(t, q, out_capacity=100)
        out, off = np.asarray(out), np.asarray(off)
        for i, k in enumerate(range(1, 21)):
            got = sorted(out[off[i]:off[i + 1]].tolist())
            assert got == sorted(model.get(k, []))


class TestBucketListVsMultiDict:
    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 15),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=80),
           growth=st.sampled_from([1.0, 1.1, 2.0]),
           s0=st.sampled_from([1, 2, 4]))
    def test_matches_multidict(self, pairs, growth, s0):
        t = bl.create(256, pool_capacity=4096, s0=s0, growth=growth)
        model: dict = {}
        for k, v in pairs:
            model.setdefault(k, []).append(v & 0xFFFFFFFF)
        ks, live = _pad([p[0] for p in pairs], 80)
        vs, _ = _pad([p[1] for p in pairs], 80)
        t, stt = bl_insert(t, ks, vs, live)
        assert (np.asarray(stt)[:len(pairs)] == STATUS_INSERTED).all()
        q = jnp.arange(1, 16, dtype=jnp.uint32)
        out, off, cnt = bl_retrieve_all(t, q, out_capacity=80)
        out, off = np.asarray(out), np.asarray(off)
        for i, k in enumerate(range(1, 16)):
            assert int(cnt[i]) == len(model.get(k, []))
            # bucket-list preserves insertion order within a key
            assert out[off[i]:off[i + 1]].tolist() == model.get(k, [])


class TestBucketListRoundTrip:
    """insert -> count_values -> retrieve_all invariants across BOTH
    backends (the batched engine build and the sequential scan), over
    duplicates, masks, growth schedules and pool exhaustion: counts match
    the surviving model, values keep insertion order, both backends agree
    bit for bit on statuses, handles, pool planes and retrievals."""

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 15),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=80),
           growth=st.sampled_from([1.0, 1.1, 2.0]),
           s0=st.sampled_from([1, 2, 4]),
           pool_capacity=st.sampled_from([24, 128, 4096]),
           use_mask=st.booleans(),
           batches=st.integers(1, 2))
    def test_round_trip_invariants(self, pairs, growth, s0, pool_capacity,
                                   use_mask, batches):
        kw = dict(key_capacity=256, pool_capacity=pool_capacity,
                  s0=s0, growth=growth)
        tb = bl.create(backend="jax", **kw)
        ts = bl.create(backend="scan", **kw)
        n = len(pairs)
        ks, live = _pad([p[0] for p in pairs], 80)
        vs, _ = _pad([p[1] for p in pairs], 80)
        rng = np.random.default_rng(n)
        model: dict = {}
        for b in range(batches):
            mask = (live & jnp.asarray(np.pad(rng.random(n) < 0.7,
                                              (0, 80 - n)))
                    if use_mask else live)
            tb, stb = bl_insert(tb, ks, vs + b, mask)
            ts, sts = bl_insert(ts, ks, vs + b, mask)
            # backends bit-exact: statuses + handles + pool + allocator
            np.testing.assert_array_equal(np.asarray(stb)[:n],
                                          np.asarray(sts)[:n])
            for pb, ps in zip(jax.tree_util.tree_leaves(tb.key_store.store),
                              jax.tree_util.tree_leaves(ts.key_store.store)):
                np.testing.assert_array_equal(np.asarray(pb), np.asarray(ps))
            np.testing.assert_array_equal(np.asarray(tb.pool),
                                          np.asarray(ts.pool))
            assert int(tb.alloc_top) == int(ts.alloc_top)
            # model: statuses say exactly which writes landed (pool
            # exhaustion drops the tail of a key's stream, masks drop
            # elements) — INSERTED elements append in batch order
            for i, (k, v) in enumerate(pairs):
                if int(stb[i]) == STATUS_INSERTED:
                    model.setdefault(k, []).append((v + b) & 0xFFFFFFFF)
        q = jnp.arange(1, 16, dtype=jnp.uint32)
        cb = bl_count(tb, q)
        cs = bl_count(ts, q)
        np.testing.assert_array_equal(np.asarray(cb), np.asarray(cs))
        total = sum(map(len, model.values()))
        outb, offb, cntb = bl_retrieve_all(tb, q, out_capacity=2 * 80 + 1)
        outs, offs, cnts = bl_retrieve_all(ts, q, out_capacity=2 * 80 + 1)
        np.testing.assert_array_equal(np.asarray(outb), np.asarray(outs))
        np.testing.assert_array_equal(np.asarray(offb), np.asarray(offs))
        np.testing.assert_array_equal(np.asarray(cntb), np.asarray(cnts))
        outb, offb = np.asarray(outb), np.asarray(offb)
        for i, k in enumerate(range(1, 16)):
            assert int(cb[i]) == len(model.get(k, []))
            # bucket lists preserve insertion order within a key
            assert outb[offb[i]:offb[i + 1]].tolist() == model.get(k, [])
        # the allocator never hands out past the pool, and the handles'
        # counts sum to the model total (pool-exhaustion bookkeeping)
        assert int(tb.alloc_top) <= pool_capacity
        assert int(jnp.sum(tb._counts_all())) == total


class TestBloomProperties:
    @SETTINGS
    @given(keys=keys_st)
    def test_never_false_negative(self, keys):
        f = bf.create(1 << 10, k=3)
        ka, live = _pad(keys, KEYS_MAX)
        f = bf_insert(f, ka, live)
        assert bf_contains(f, ka)[:len(keys)].all()

    @SETTINGS
    @given(keys=keys_st)
    def test_insert_idempotent(self, keys):
        f = bf.create(1 << 10, k=3)
        ka, live = _pad(keys, KEYS_MAX)
        f1 = bf_insert(f, ka, live)
        f2 = bf_insert(f1, ka, live)
        assert (f1.bits == f2.bits).all()


class TestMultisplitProperties:
    @SETTINGS
    @given(keys=keys_st, parts=st.sampled_from([2, 4, 8]))
    def test_multisplit_is_stable_partition(self, keys, parts):
        from repro.core import distributed as dist
        ka = jnp.asarray(np.asarray(keys, np.uint32))
        owners = dist.owner_of(ka, parts, 1)
        so, counts, order, sk = dist.multisplit(owners, parts, ka)
        so, counts, sk = np.asarray(so), np.asarray(counts), np.asarray(sk)
        assert sorted(sk.tolist()) == sorted(np.asarray(ka).tolist())
        assert (np.diff(so) >= 0).all()             # grouped by owner
        assert counts.sum() == len(keys)
        # stability: equal-owner keys keep relative order
        for p in range(parts):
            orig = [k for k, o in zip(np.asarray(ka), np.asarray(owners))
                    if o == p]
            assert sk[so == p].tolist() == orig


class TestInsertEraseRetrieveRoundTrip:
    """Round-trip invariants across BOTH backends: after insert -> erase ->
    retrieve, erased keys retrieve empty, survivors keep their exact value
    multisets, and the live count matches the distinct live keys."""

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 25),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=80),
           erase_keys=st.lists(st.integers(1, 30), max_size=15),
           backend=st.sampled_from(["jax", "scan"]),
           window=st.sampled_from([4, 16]))
    def test_multi_value_round_trip(self, pairs, erase_keys, backend, window):
        t = mv.create(1024, window=window, backend=backend)
        model: dict = {}
        ks, live = _pad([p[0] for p in pairs], 80)
        vs, _ = _pad([p[1] for p in pairs], 80)
        for k, v in pairs:
            model.setdefault(k, []).append(v & 0xFFFFFFFF)
        t, _ = mv_insert(t, ks, vs, live)
        if erase_keys:
            ek, _ = _pad(erase_keys, 15)
            t, ecnt = mv_erase(t, ek)
            # every occurrence (duplicates included) reports the key's full
            # pre-erase multiplicity: the batch walk reads each window once
            for i, k in enumerate(erase_keys):
                assert int(ecnt[i]) == len(model.get(k, []))
            for k in erase_keys:
                model.pop(k, None)
        # live pair count == surviving multiset size
        assert int(t.count) == sum(map(len, model.values()))
        q = jnp.arange(1, 31, dtype=jnp.uint32)
        cnt = mv_count(t, q)
        for i, k in enumerate(range(1, 31)):
            assert int(cnt[i]) == len(model.get(k, []))
        out, off, _ = mv_retrieve_all(t, q, out_capacity=80 + 1)
        out, off = np.asarray(out), np.asarray(off)
        for i, k in enumerate(range(1, 31)):
            got = sorted(out[off[i]:off[i + 1]].tolist())
            assert got == sorted(model.get(k, [])), \
                f"key {k} multiset mismatch on backend={backend}"

    @SETTINGS
    @given(ops=ops_st(), backend=st.sampled_from(["jax", "scan"]),
           window=st.sampled_from([4, 16]))
    def test_single_value_round_trip(self, ops, backend, window):
        t = sv.create(512, window=window, backend=backend)
        model = {}
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, _ = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
                model[k] = v & 0xFFFFFFFF
            else:
                t, er = sv_erase(t, ka)
                assert bool(er[0]) == (k in model)
                model.pop(k, None)
        assert int(t.count) == len(model)   # live count == distinct live keys
        q = jnp.arange(1, 41, dtype=jnp.uint32)
        got, found = sv_retrieve(t, q)
        for i, k in enumerate(range(1, 41)):
            assert bool(found[i]) == (k in model)
            if k in model:
                assert int(got[i]) == model[k]

    @SETTINGS
    @given(keys=st.lists(st.integers(1, 40), min_size=1, max_size=60),
           backend=st.sampled_from(["jax", "scan"]))
    def test_erase_then_reinsert_recovers(self, keys, backend):
        """erase(k); insert(k, v') must behave as if k was never there."""
        u = np.unique(np.asarray(keys, np.uint32))
        n = len(u)
        ka, live = _pad(u, 60)
        t = sv.create(256, backend=backend)
        t, _ = sv_insert(t, ka, ka, live)
        t, er = sv_erase(t, ka, live)
        assert np.asarray(er)[:n].all()
        assert int(t.count) == 0
        _, found = sv_retrieve(t, ka)
        assert not np.asarray(found)[:n].any()  # erased keys retrieve empty
        t, stt = sv_insert(t, ka, ka * 2, live)
        assert (np.asarray(stt)[:n] == STATUS_INSERTED).all()
        got, found = sv_retrieve(t, ka)
        assert np.asarray(found)[:n].all()
        np.testing.assert_array_equal(np.asarray(got)[:n], u * 2)


class TestBucketedRoundTrip:
    """The two-choice bucketed lane (plain and quotient storage) against
    the dict model, across BOTH backends: insert/erase sequences preserve
    exact map semantics, and quotient decode never produces a false
    positive (the mixer is a bijection, so q*p + b1 recovers the key
    exactly)."""

    @SETTINGS
    @given(ops=ops_st(), backend=st.sampled_from(["jax", "scan"]),
           quotient=st.booleans())
    def test_single_value_bucketed_round_trip(self, ops, backend, quotient):
        t = sv.create(512, window=8, kind="bucketed", quotient=quotient,
                      backend=backend)
        model = {}
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, stt = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
                if int(stt[0]) <= STATUS_UPDATED:
                    assert int(stt[0]) == (STATUS_UPDATED if k in model
                                           else STATUS_INSERTED)
                    model[k] = v & 0xFFFFFFFF
            else:
                t, er = sv_erase(t, ka)
                assert bool(er[0]) == (k in model)
                model.pop(k, None)
        assert int(t.count) == len(model)
        q = jnp.arange(1, 41, dtype=jnp.uint32)
        got, found = sv_retrieve(t, q)
        for i, k in enumerate(range(1, 41)):
            assert bool(found[i]) == (k in model)
            if k in model:
                assert int(got[i]) == model[k]

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 20),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=80),
           erase_keys=st.lists(st.integers(1, 25), max_size=10),
           backend=st.sampled_from(["jax", "scan"]))
    def test_multi_value_bucketed_round_trip(self, pairs, erase_keys,
                                             backend):
        t = mv.create(1024, window=16, kind="bucketed", backend=backend)
        model: dict = {}
        ks, live = _pad([p[0] for p in pairs], 80)
        vs, _ = _pad([p[1] for p in pairs], 80)
        t, stt = mv_insert(t, ks, vs, live)
        for i, (k, v) in enumerate(pairs):
            if int(stt[i]) == STATUS_INSERTED:
                model.setdefault(k, []).append(v & 0xFFFFFFFF)
        if erase_keys:
            ek, _ = _pad(erase_keys, 10)
            t, ecnt = mv_erase(t, ek)
            for i, k in enumerate(erase_keys):
                assert int(ecnt[i]) == len(model.get(k, []))
            for k in erase_keys:
                model.pop(k, None)
        assert int(t.count) == sum(map(len, model.values()))
        q = jnp.arange(1, 26, dtype=jnp.uint32)
        cnt = mv_count(t, q)
        out, off, _ = mv_retrieve_all(t, q, out_capacity=80 + 1)
        out, off = np.asarray(out), np.asarray(off)
        for i, k in enumerate(range(1, 26)):
            assert int(cnt[i]) == len(model.get(k, []))
            got = sorted(out[off[i]:off[i + 1]].tolist())
            assert got == sorted(model.get(k, []))


class TestCompositeKeyRoundTrip:
    """Composite (multi-column) keys vs a dict-of-tuples model AND the
    u32-packed single-word rendering of the same columns: insert -> erase
    -> retrieve round-trips, with outputs bit-equal across the two
    representations (the packing never leaks into results)."""

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(0, 5), st.integers(1, 6),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=60),
           erase=st.lists(st.tuples(st.integers(0, 6), st.integers(1, 7)),
                          max_size=10),
           backend=st.sampled_from(["jax", "scan"]))
    def test_multi_value_composite_round_trip(self, pairs, erase, backend):
        # padding key (15, 15), packed 255: outside both drawn universes
        n = len(pairs)
        hi, live = _pad([p[0] for p in pairs], 60, 15)
        lo, _ = _pad([p[1] for p in pairs], 60, 15)
        vs, _ = _pad([p[2] for p in pairs], 60)
        packed = (hi << 4) | lo
        model: dict = {}
        for h, l, v in pairs:
            model.setdefault((h, l), []).append(v & 0xFFFFFFFF)
        tc = mv.create(512, key_words=2, backend=backend)
        tp = mv.create(512, key_words=1, backend=backend)
        tc, st_c = mv_insert(tc, (hi, lo), vs, live)
        tp, st_p = mv_insert(tp, packed, vs, live)
        np.testing.assert_array_equal(np.asarray(st_c)[:n],
                                      np.asarray(st_p)[:n])
        if erase:
            eh, _ = _pad([e[0] for e in erase], 10, 15)
            el, _ = _pad([e[1] for e in erase], 10, 15)
            tc, ec = mv_erase(tc, (eh, el))
            tp, ep = mv_erase(tp, (eh << 4) | el)
            np.testing.assert_array_equal(np.asarray(ec)[:len(erase)],
                                          np.asarray(ep)[:len(erase)])
            for h, l in erase:
                model.pop((h, l), None)
        assert int(tc.count) == sum(map(len, model.values()))
        assert int(tc.count) == int(tp.count)
        qh = jnp.asarray([h for h in range(6) for _ in range(1, 7)],
                         jnp.uint32)
        ql = jnp.asarray([l for _ in range(6) for l in range(1, 7)],
                         jnp.uint32)
        cap = 60 + 1
        out_c, off_c, cnt_c = mv_retrieve_all(tc, (qh, ql), cap)
        out_p, off_p, cnt_p = mv_retrieve_all(tp, (qh << 4) | ql, cap)
        np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p))
        np.testing.assert_array_equal(np.asarray(off_c), np.asarray(off_p))
        np.testing.assert_array_equal(np.asarray(cnt_c), np.asarray(cnt_p))
        out, off = np.asarray(out_c), np.asarray(off_c)
        for i, (h, l) in enumerate(zip(np.asarray(qh), np.asarray(ql))):
            got = sorted(out[off[i]:off[i + 1]].tolist())
            assert got == sorted(model.get((int(h), int(l)), [])), \
                f"key ({h},{l}) multiset mismatch on backend={backend}"

    @SETTINGS
    @given(ops=st.lists(st.tuples(st.sampled_from(["insert", "insert",
                                                   "erase"]),
                                  st.integers(0, 4), st.integers(1, 5),
                                  st.integers(0, 10 ** 6)),
                        min_size=1, max_size=50),
           backend=st.sampled_from(["jax", "scan"]))
    def test_single_value_composite_round_trip(self, ops, backend):
        t = sv.create(256, key_words=2, backend=backend)
        model = {}
        for op, h, l, v in ops:
            key = (jnp.asarray([h], jnp.uint32), jnp.asarray([l], jnp.uint32))
            if op == "insert":
                t, stt = sv_insert(t, key, jnp.asarray([v], jnp.uint32))
                assert int(stt[0]) == (STATUS_UPDATED if (h, l) in model
                                       else STATUS_INSERTED)
                model[(h, l)] = v & 0xFFFFFFFF
            else:
                t, er = sv_erase(t, key)
                assert bool(er[0]) == ((h, l) in model)
                model.pop((h, l), None)
        assert int(t.count) == len(model)
        qh = jnp.asarray([h for h in range(5) for _ in range(1, 6)],
                         jnp.uint32)
        ql = jnp.asarray([l for _ in range(5) for l in range(1, 6)],
                         jnp.uint32)
        got, found = sv_retrieve(t, (qh, ql))
        for i, (h, l) in enumerate(zip(np.asarray(qh), np.asarray(ql))):
            assert bool(found[i]) == ((int(h), int(l)) in model)
            if (int(h), int(l)) in model:
                assert int(got[i]) == model[(int(h), int(l))]


class TestMigrationRoundTrip:
    """insert -> erase -> grow -> compact -> retrieve preserves the exact
    live set for every table kind (repro.core.migrate): grown/compacted
    tables answer every query identically to the churned original, erased
    keys stay erased, and tombstones are gone after migration."""

    @SETTINGS
    @given(ops=ops_st(), window=st.sampled_from([4, 16]),
           new_capacity=st.sampled_from([600, 2048]))
    def test_single_value_migration(self, ops, window, new_capacity):
        from repro.obs import metrics
        t = sv.create(512, window=window)
        model = {}
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, _ = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
                model[k] = v & 0xFFFFFFFF
            else:
                t, _ = sv_erase(t, ka)
                model.pop(k, None)
        t = mig_compact(mig_grow(t, new_capacity))
        _, tomb, _ = metrics.slot_stats(t.ops, t.store)
        assert int(tomb) == 0                  # migration drops tombstones
        assert int(t.count) == len(model)
        q = jnp.arange(1, 41, dtype=jnp.uint32)
        got, found = sv_retrieve(t, q)
        for i, k in enumerate(range(1, 41)):
            assert bool(found[i]) == (k in model)
            if k in model:
                assert int(got[i]) == model[k]

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 20),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=80),
           erase_keys=st.lists(st.integers(1, 25), max_size=10))
    def test_multi_value_migration(self, pairs, erase_keys):
        t = mv.create(512, window=8)
        model: dict = {}
        ks, live = _pad([p[0] for p in pairs], 80)
        vs, _ = _pad([p[1] for p in pairs], 80)
        for k, v in pairs:
            model.setdefault(k, []).append(v & 0xFFFFFFFF)
        t, _ = mv_insert(t, ks, vs, live)
        if erase_keys:
            t, _ = mv_erase(t, _pad(erase_keys, 10)[0])
            for k in erase_keys:
                model.pop(k, None)
        t = mig_compact(mig_grow(t, 2048))
        assert int(t.count) == sum(map(len, model.values()))
        q = jnp.arange(1, 21, dtype=jnp.uint32)
        cnt = mv_count(t, q)
        out, off, _ = mv_retrieve_all(t, q, out_capacity=80 + 1)
        out, off = np.asarray(out), np.asarray(off)
        for i, k in enumerate(range(1, 21)):
            assert int(cnt[i]) == len(model.get(k, []))  # fan-out preserved
            got = sorted(out[off[i]:off[i + 1]].tolist())
            assert got == sorted(model.get(k, []))

    @SETTINGS
    @given(pairs=st.lists(st.tuples(st.integers(1, 12),
                                    st.integers(0, 10 ** 6)),
                          min_size=1, max_size=60),
           s0=st.sampled_from([1, 2]),
           growth=st.sampled_from([1.0, 1.5]))
    def test_bucket_list_migration(self, pairs, s0, growth):
        t = bl.create(128, pool_capacity=1024, s0=s0, growth=growth)
        ks, live = _pad([p[0] for p in pairs], 60)
        vs, _ = _pad([p[1] for p in pairs], 60)
        t, stt = bl_insert(t, ks, vs, live)
        assert (np.asarray(stt)[:len(pairs)] == STATUS_INSERTED).all()
        q = jnp.arange(1, 13, dtype=jnp.uint32)
        want = bl_retrieve_all(t, q, out_capacity=60)
        fresh = mig_compact(mig_grow(t, 512))
        got = bl_retrieve_all(fresh, q, out_capacity=60)
        # migration preserves per-key insertion order bit-exactly
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


class TestLayoutEquivalence:
    @SETTINGS
    @given(keys=keys_st, window=st.sampled_from([8, 32]))
    def test_all_layouts_same_results(self, keys, window):
        u = np.unique(np.asarray(keys, np.uint32))
        n = len(u)
        vals = (u * 31 + 7).astype(np.uint32)
        ku, live = _pad(u, KEYS_MAX)
        results = {}
        for layout in layouts.LAYOUTS:
            t = sv.create(512, window=window, layout=layout)
            t, _ = sv_insert(t, ku, _pad(vals, KEYS_MAX)[0], live)
            got, found = sv_retrieve(t, ku)
            results[layout] = (np.asarray(got)[:n], np.asarray(found)[:n])
        a = results["soa"]
        for layout in ("aos", "packed"):
            assert (results[layout][0] == a[0]).all()
            assert (results[layout][1] == a[1]).all()


class TestSnapshotProperties:
    """Checkpoint/restore round-trips under arbitrary op interleavings."""

    @SETTINGS
    @given(ops=ops_st(), layout=st.sampled_from(["soa", "aos", "packed"]))
    def test_insert_snapshot_erase_restore_retrieve(self, ops, layout):
        """Snapshot mid-sequence, keep mutating, restore: the restored
        table answers exactly as the table did at snapshot time."""
        from repro.core import snapshot
        t = sv.create(512, window=16, layout=layout)
        model = {}
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, _ = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
                model[k] = v & 0xFFFFFFFF
            else:
                t, _ = sv_erase(t, ka)
                model.pop(k, None)
        blob = snapshot.snapshot_bytes(t)
        frozen = dict(model)
        # post-snapshot mutations that must NOT leak into the restore
        for k in list(model)[: len(model) // 2]:
            t, _ = sv_erase(t, jnp.asarray([k], jnp.uint32))
        t, _ = sv_insert(t, jnp.asarray([41], jnp.uint32),
                         jnp.asarray([0], jnp.uint32))
        restored = snapshot.restore_bytes(blob)
        assert int(restored.count) == len(frozen)
        universe = jnp.arange(1, 42, dtype=jnp.uint32)
        got, found = sv_retrieve(restored, universe)
        for i, k in enumerate(range(1, 42)):
            assert bool(found[i]) == (k in frozen)
            if k in frozen:
                assert int(got[i]) == frozen[k]

    @SETTINGS
    @given(ops=ops_st())
    def test_snapshot_bytes_deterministic(self, ops):
        """Same table state => byte-identical snapshot (stable manifest
        ordering), so checksums are meaningful across processes."""
        from repro.core import snapshot
        t = sv.create(256, window=8)
        for op, k, v in ops:
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                t, _ = sv_insert(t, ka, jnp.asarray([v], jnp.uint32))
            else:
                t, _ = sv_erase(t, ka)
        assert snapshot.snapshot_bytes(t) == snapshot.snapshot_bytes(t)


class TestShardedBloomInvariant:
    """The elastic front-end's one-sided filter contract: every key live
    in a shard's table is contains=True in that shard's filter, across
    arbitrary insert/erase/compaction sequences."""

    @SETTINGS
    @given(ops=ops_st(), num_shards=st.sampled_from([2, 4]))
    def test_live_keys_always_advertised(self, ops, num_shards):
        st_ = elastic.create(num_shards, 512, window=16)
        model = {}
        compact_every = 7
        for i, (op, k, v) in enumerate(ops):
            ka = jnp.asarray([k], jnp.uint32)
            if op == "insert":
                st_, _ = el_insert(st_, ka, jnp.asarray([v], jnp.uint32))
                model[k] = v & 0xFFFFFFFF
            else:
                st_, _ = el_erase(st_, ka)
                model.pop(k, None)
            if i % compact_every == compact_every - 1:
                st_ = el_compact_all(st_)        # filter rebuild point
                assert int(elastic.count(st_)) == len(model)
            if not model:
                continue
            live, _ = _pad(sorted(model), 40)   # ops_st's universe
            admitted = _advertised(st_.filters, live)[:len(model)]
            assert bool(jnp.all(admitted)), \
                "live key not advertised by its owner's filter"
        # and the lookup path agrees with the dict model end-to-end
        universe = jnp.arange(1, 41, dtype=jnp.uint32)
        got, found, stats = el_lookup(st_, universe)
        assert int(stats["overflow"]) == 0
        for i, k in enumerate(range(1, 41)):
            assert bool(found[i]) == (k in model)
            if k in model:
                assert int(got[i]) == model[k]

    @SETTINGS
    @given(keys=keys_st)
    def test_rebuild_is_subset_of_incremental(self, keys):
        """rebuild_from_table never advertises MORE than the incremental
        filter: rebuilt bits are a subset (erase-staleness only shrinks)."""
        t = sv.create(512, window=16)
        u = np.unique(np.asarray(keys, np.uint32))
        ka, live = _pad(u, KEYS_MAX)
        t, _ = sv_insert(t, ka, ka, live)
        f_inc = bf_insert(bf.create(1 << 12), sv.key_hash_word(
            sv.normalize_key_batch(ka, 1, "keys")), live)
        half = jnp.arange(KEYS_MAX) < len(u) // 2
        if len(u) // 2:
            t, _ = sv_erase(t, ka, half)
        f_reb = bf.rebuild_from_table(f_inc, t)
        assert bool(jnp.all(f_inc.bits >= f_reb.bits)), \
            "rebuilt filter set a bit the incremental filter never did"
