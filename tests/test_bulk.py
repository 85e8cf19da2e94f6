"""Bulk-engine parity suite: the vectorized scatter-arbitration build
(repro.core.bulk, backend="jax") must be *bit-exact* against the
sequential-scan reference (backend="scan") — identical store planes,
identical live counts, identical per-element STATUS codes — across
duplicates-in-batch, tombstone reuse, masks, near-full tables, u64
(2-word) keys, and every probing scheme/window combination."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bulk
from repro.core import counting as ct
from repro.core import hashset as hs
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.relational import groupby as gb


def assert_tables_equal(tb, ts, stb=None, sts=None):
    """Bit-exact comparison: store planes, count, statuses."""
    for pb, ps in zip(jax.tree_util.tree_leaves(tb.store),
                      jax.tree_util.tree_leaves(ts.store)):
        np.testing.assert_array_equal(np.asarray(pb), np.asarray(ps))
    assert int(tb.count) == int(ts.count)
    if stb is not None:
        np.testing.assert_array_equal(np.asarray(stb), np.asarray(sts))


def _pair(create_fn, **kw):
    return create_fn(backend="jax", **kw), create_fn(backend="scan", **kw)


class TestInsertParity:
    def test_duplicates_and_masks(self):
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(1, 150, 400, dtype=np.uint32))
        vals = jnp.asarray(rng.integers(0, 2 ** 32 - 2, 400, dtype=np.uint32))
        mask = jnp.asarray(rng.random(400) < 0.8)
        tb, ts = _pair(lambda **kw: sv.create(1024, window=16, **kw))
        tb, stb = sv.insert(tb, keys, vals, mask)
        ts, sts = sv.insert(ts, keys, vals, mask)
        assert_tables_equal(tb, ts, stb, sts)

    def test_near_full_and_full_statuses(self):
        rng = np.random.default_rng(1)
        keys = jnp.asarray(rng.permutation(
            np.arange(1, 120, dtype=np.uint32)))
        tb, ts = _pair(lambda **kw: sv.create(64, window=8, max_probes=16,
                                              **kw))
        tb, stb = sv.insert(tb, keys, keys)
        ts, sts = sv.insert(ts, keys, keys)
        assert_tables_equal(tb, ts, stb, sts)

    def test_tombstone_reuse(self):
        keys = jnp.arange(1, 120, dtype=jnp.uint32)
        tb, ts = _pair(lambda **kw: sv.create(64, window=8, max_probes=16,
                                              **kw))
        tb, _ = sv.insert(tb, keys, keys)
        ts, _ = sv.insert(ts, keys, keys)
        tb, eb = sv.erase(tb, keys[:40])
        ts, es = sv.erase(ts, keys[:40])
        np.testing.assert_array_equal(np.asarray(eb), np.asarray(es))
        tb, stb = sv.insert(tb, keys[:80], keys[:80] ^ 7)
        ts, sts = sv.insert(ts, keys[:80], keys[:80] ^ 7)
        assert_tables_equal(tb, ts, stb, sts)

    def test_u64_two_word_keys(self):
        rng = np.random.default_rng(2)
        kk = rng.integers(0, 2 ** 32 - 2, (150, 2), dtype=np.uint32)
        kk = np.concatenate([kk, kk[:30]])           # duplicates
        vv = jnp.asarray(rng.integers(0, 2 ** 32 - 2, (180, 2),
                                      dtype=np.uint32))
        tb, ts = _pair(lambda **kw: sv.create(512, key_words=2, value_words=2,
                                              window=8, **kw))
        tb, stb = sv.insert(tb, jnp.asarray(kk), vv)
        ts, sts = sv.insert(ts, jnp.asarray(kk), vv)
        assert_tables_equal(tb, ts, stb, sts)

    @pytest.mark.parametrize("layout", ["soa", "aos", "packed"])
    def test_layouts(self, layout):
        rng = np.random.default_rng(3)
        keys = jnp.asarray(rng.integers(1, 100, 200, dtype=np.uint32))
        tb, ts = _pair(lambda **kw: sv.create(512, layout=layout, window=16,
                                              **kw))
        tb, stb = sv.insert(tb, keys, keys * 3)
        ts, sts = sv.insert(ts, keys, keys * 3)
        assert_tables_equal(tb, ts, stb, sts)

    def test_hashset_zero_value_words(self):
        keys = jnp.asarray([5, 9, 5, 11, 9, 5], jnp.uint32)
        sb, ss = _pair(lambda **kw: hs.create(128, **kw))
        sb, nb = hs.add(sb, keys)
        ss, ns = hs.add(ss, keys)
        np.testing.assert_array_equal(np.asarray(nb), np.asarray(ns))
        assert int(sb.count) == int(ss.count)


class TestMultiValueParity:
    def test_duplicate_keys_distinct_slots(self):
        rng = np.random.default_rng(4)
        keys = jnp.asarray(rng.integers(1, 20, 200, dtype=np.uint32))
        vals = jnp.arange(200, dtype=jnp.uint32)
        mask = jnp.asarray(rng.random(200) < 0.8)
        tb, ts = _pair(lambda **kw: mv.create(1024, window=16, **kw))
        tb, stb = mv.insert(tb, keys, vals, mask)
        ts, sts = mv.insert(ts, keys, vals, mask)
        assert_tables_equal(tb, ts, stb, sts)

    def test_near_full_heavy_duplicates(self):
        rng = np.random.default_rng(5)
        keys = jnp.asarray(rng.integers(1, 6, 100, dtype=np.uint32))
        tb, ts = _pair(lambda **kw: mv.create(64, window=8, max_probes=16,
                                              **kw))
        tb, stb = mv.insert(tb, keys, keys * 3)
        ts, sts = mv.insert(ts, keys, keys * 3)
        assert_tables_equal(tb, ts, stb, sts)


class TestRmwParity:
    def test_counting(self):
        rng = np.random.default_rng(6)
        keys = jnp.asarray(rng.integers(1, 50, 300, dtype=np.uint32))
        tb, ts = _pair(lambda **kw: ct.create(256, **kw))
        tb, stb = ct.insert(tb, keys)
        ts, sts = ct.insert(ts, keys)
        assert_tables_equal(tb, ts, stb, sts)

    @pytest.mark.parametrize("agg", gb.AGGS)
    def test_groupby_all_aggs(self, agg):
        rng = np.random.default_rng(7)
        keys = jnp.asarray(rng.integers(1, 40, 250, dtype=np.uint32))
        vals = jnp.asarray(rng.integers(0, 1 << 20, 250, dtype=np.uint32))
        mask = jnp.asarray(rng.random(250) < 0.8)
        tb, ts = _pair(lambda **kw: gb.create(256, **kw))
        tb, stb = gb.update(tb, agg, keys, vals, mask)
        ts, sts = gb.update(ts, agg, keys, vals, mask)
        assert_tables_equal(tb, ts, stb, sts)

    def test_second_batch_folds_into_existing(self):
        rng = np.random.default_rng(8)
        keys = jnp.asarray(rng.integers(1, 30, 150, dtype=np.uint32))
        vals = jnp.asarray(rng.integers(0, 1 << 16, 150, dtype=np.uint32))
        tb, ts = _pair(lambda **kw: gb.create(256, **kw))
        tb, _ = gb.update(tb, "min", keys[:70], vals[:70])
        ts, _ = gb.update(ts, "min", keys[:70], vals[:70])
        tb, stb = gb.update(tb, "min", keys, vals)
        ts, sts = gb.update(ts, "min", keys, vals)
        assert_tables_equal(tb, ts, stb, sts)

    @pytest.mark.parametrize("name,fold", [
        ("or", lambda old, key, new: old | new),
        ("and", lambda old, key, new: old & new),
        ("xor", lambda old, key, new: old ^ new),
    ])
    def test_bitwise_specs(self, name, fold):
        """("or",)/("and",)/("xor",) specs run the bit-plane scatter-reduce
        fast lane and must match the sequential fold bit for bit — the
        bloom-style value lane (set-union values via bitwise-or)."""
        rng = np.random.default_rng(hash(name) % 2 ** 31)
        n = 300
        keys = jnp.asarray(rng.integers(1, 25, n, dtype=np.uint32))
        vals = jnp.asarray(rng.integers(0, 2 ** 32 - 2, n, dtype=np.uint32))
        init = jnp.asarray(rng.integers(0, 2 ** 32 - 2, n, dtype=np.uint32))
        mask = jnp.asarray(rng.random(n) < 0.75)
        tb, ts = _pair(lambda **kw: sv.create(256, **kw))
        pre = keys[: n // 2]                  # existing keys exercise RMW
        tb, _ = sv.insert(tb, pre, pre)
        ts, _ = sv.insert(ts, pre, pre)
        tb, stb = sv.update_values(tb, keys, fold, init, mask=mask,
                                   values=vals, combine=(name,))
        ts, sts = sv.update_values(ts, keys, fold, init, mask=mask,
                                   values=vals)
        assert_tables_equal(tb, ts, stb, sts)

    def test_bitwise_spec_multiword(self):
        """Mixed per-word specs — ("or", "add") — on 2-word values."""
        rng = np.random.default_rng(11)
        n = 150
        keys = jnp.asarray(rng.integers(1, 20, n, dtype=np.uint32))
        vals = jnp.asarray(rng.integers(0, 2 ** 31, (n, 2), dtype=np.uint32))
        init = jnp.asarray(rng.integers(0, 2 ** 31, (n, 2), dtype=np.uint32))
        fold = lambda old, key, new: jnp.stack([old[0] | new[0],
                                                old[1] + new[1]])
        tb, ts = _pair(lambda **kw: sv.create(256, value_words=2, **kw))
        tb, stb = sv.update_values(tb, keys, fold, init, values=vals,
                                   combine=("or", "add"))
        ts, sts = sv.update_values(ts, keys, fold, init, values=vals)
        assert_tables_equal(tb, ts, stb, sts)

    def test_bitwise_combine_callable_roundtrip(self):
        """COMBINE_OPS entries for the bitwise specs lift into the general
        lane's callable form (combine_callable) with the same identity."""
        a = jnp.asarray([0b1010], jnp.uint32)
        b = jnp.asarray([0b0110], jnp.uint32)
        assert int(bulk.combine_callable(("or",))(a, b)[0]) == 0b1110
        assert int(bulk.combine_callable(("and",))(a, b)[0]) == 0b0010
        assert int(bulk.combine_callable(("xor",))(a, b)[0]) == 0b1100
        for name in ("or", "and", "xor"):
            ident, op = bulk.COMBINE_OPS[name]
            assert int(op(a, jnp.asarray([ident]))[0]) == int(a[0])

    def test_general_lane_callable_combine(self):
        """An arbitrary (associative) combiner callable takes the sorted
        general lane; same parity contract."""
        keys = jnp.asarray([3, 3, 7, 3, 9, 7], jnp.uint32)
        vals = jnp.asarray([1, 2, 3, 4, 5, 6], jnp.uint32)
        fold = lambda old, key, new: jnp.maximum(old, new)
        cmb = lambda a, b: jnp.maximum(a, b)
        tb, ts = _pair(lambda **kw: sv.create(128, **kw))
        tb, stb = sv.update_values(tb, keys, fold, jnp.uint32(0),
                                   values=vals, combine=cmb)
        ts, sts = sv.update_values(ts, keys, fold, jnp.uint32(0),
                                   values=vals)
        assert_tables_equal(tb, ts, stb, sts)


class TestEraseCountDelta:
    def test_duplicate_erase_counts_once(self):
        keys = jnp.arange(1, 51, dtype=jnp.uint32)
        t = sv.create(256)
        t, _ = sv.insert(t, keys, keys)
        dup = jnp.asarray([1, 1, 2, 2, 2, 3, 99], jnp.uint32)
        t, erased = sv.erase(t, dup)
        assert np.asarray(erased).tolist() == [True] * 6 + [False]
        assert int(t.count) == 47                    # 3 distinct keys erased

    def test_masked_erase_excluded_from_delta(self):
        keys = jnp.arange(1, 21, dtype=jnp.uint32)
        t = sv.create(128)
        t, _ = sv.insert(t, keys, keys)
        mask = jnp.asarray([True, False] * 5)
        t, erased = sv.erase(t, keys[:10], mask=mask)
        assert int(np.asarray(erased).sum()) == 5
        assert int(t.count) == 15


class TestArbitrationInvariant:
    def test_placements_are_distinct_slots(self):
        """The scatter-min arena must confirm every virtual-fill placement
        is a unique (row, lane) slot."""
        rng = np.random.default_rng(9)
        keys = sv.normalize_words(
            jnp.asarray(rng.integers(1, 5000, 600, dtype=np.uint32)), 1, "k")
        table = sv.create(1024, window=16)
        words = sv.key_hash_word(keys)
        claim = jnp.ones((600,), bool)
        prio = jnp.arange(600, dtype=jnp.uint32)
        placed, row, lane, _ = bulk.place_claims(
            bulk._tstatic(table), table.store, words, claim, prio)
        win = bulk.arbitrate(row, lane, placed, prio, table.num_rows,
                             table.window)
        np.testing.assert_array_equal(np.asarray(win), np.asarray(placed))


@pytest.mark.parametrize("seed", range(8))
def test_randomized_mixed_ops(seed):
    """Randomized end-to-end: insert(dups+mask) -> erase -> reinsert, plus a
    multi-value build, across schemes/windows/capacities — bit-exact."""
    r = np.random.default_rng(seed)
    n = int(r.integers(20, 200))
    keys = jnp.asarray(r.integers(1, int(r.integers(5, 100)), n,
                                  dtype=np.uint32))
    vals = jnp.asarray(r.integers(0, 2 ** 32 - 2, n, dtype=np.uint32))
    mask = jnp.asarray(r.random(n) < 0.7)
    window = int(r.choice([1, 4, 8, 32]))
    scheme = str(r.choice(["cops", "linear", "quadratic"]))
    cap = int(r.choice([64, 256]))
    mp = int(r.choice([8, 64]))
    mk = lambda **kw: sv.create(cap, window=window, scheme=scheme,
                                max_probes=mp, **kw)
    tb, ts = _pair(mk)
    tb, s1 = sv.insert(tb, keys, vals, mask)
    ts, s2 = sv.insert(ts, keys, vals, mask)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    tb, e1 = sv.erase(tb, keys[:n // 2])
    ts, e2 = sv.erase(ts, keys[:n // 2])
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    tb, s3 = sv.insert(tb, keys, vals ^ 99)
    ts, s4 = sv.insert(ts, keys, vals ^ 99)
    assert_tables_equal(tb, ts, s3, s4)
    mb, ms = _pair(lambda **kw: mv.create(cap, window=window, scheme=scheme,
                                          max_probes=mp, **kw))
    mb, s5 = mv.insert(mb, keys, vals, mask)
    ms, s6 = mv.insert(ms, keys, vals, mask)
    assert_tables_equal(mb, ms, s5, s6)


# one compile per (window, backend) for the whole hypothesis sweep
sv_insert = jax.jit(sv.insert)
sv_erase = jax.jit(sv.erase)


def test_hypothesis_property_parity():
    """Hypothesis sweep (skipped when hypothesis is absent): arbitrary
    op sequences agree between bulk and scan.  Each batch is padded to the
    strategy's max_size (25) and the padding masked off, so every example
    runs the same compiled programs."""
    pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(ops=st.lists(
        st.tuples(st.sampled_from(["insert", "insert", "erase"]),
                  st.lists(st.integers(1, 30), min_size=1, max_size=25)),
        min_size=1, max_size=4),
        window=st.sampled_from([4, 16]))
    def run(ops, window):
        tb, ts = _pair(lambda **kw: sv.create(128, window=window, **kw))
        for op, ks in ops:
            n = len(ks)
            ka = jnp.asarray(np.pad(np.asarray(ks, np.uint32), (0, 25 - n)))
            live = jnp.arange(25) < n
            if op == "insert":
                va = ka * 7
                tb, s1 = sv_insert(tb, ka, va, live)
                ts, s2 = sv_insert(ts, ka, va, live)
            else:
                tb, s1 = sv_erase(tb, ka, live)
                ts, s2 = sv_erase(ts, ka, live)
            np.testing.assert_array_equal(np.asarray(s1)[:n],
                                          np.asarray(s2)[:n])
        assert_tables_equal(tb, ts)

    run()
