"""Fused-retrieval parity suite: the single-walk bulk-retrieval engine
(repro.core.bulk_retrieve, backend="jax") must be *bit-exact* against the
two-walk count+gather reference (backend="scan") — identical values,
offsets, counts, found/erased masks, and post-erase store planes — across
duplicate probe keys, masks, tombstone-riddled tables, ``out_capacity``
overflow truncation, u64 (2-word) keys, empty batches and ``n=0`` /
``out_capacity=0`` edges.  The Pallas walk tile joins the same contract
where the kernel path applies."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bulk
from repro.core import bulk_retrieve as br
from repro.core import counting as ct
from repro.core import hashset as hs
from repro.core import multi_value as mv
from repro.core import probing
from repro.core import single_value as sv
from repro.relational import join

from conftest import unique_keys


def _pair(create_fn, **kw):
    return create_fn(backend="jax", **kw), create_fn(backend="scan", **kw)


def assert_same(*pairs):
    for a, b in pairs:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def assert_stores_equal(ta, tb):
    for pa, pb in zip(jax.tree_util.tree_leaves(ta.store),
                      jax.tree_util.tree_leaves(tb.store)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    assert int(ta.count) == int(tb.count)


def _mv_pair(n_pairs=300, key_hi=25, capacity=1024, window=16, seed=0, **kw):
    """Identical multi-value tables on both backends (insert parity is
    covered by test_bulk; here it just provides the fixture)."""
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(1, key_hi, n_pairs, dtype=np.uint32))
    vals = jnp.arange(n_pairs, dtype=jnp.uint32)
    tj, ts = _pair(lambda **k: mv.create(capacity, window=window, **k, **kw))
    tj, _ = mv.insert(tj, keys, vals)
    ts, _ = mv.insert(ts, keys, vals)
    assert_stores_equal(tj, ts)
    return tj, ts, rng


def _assert_retrieval_parity(tj, ts, q, out_capacity, mask=None):
    cj = mv.count_values(tj, q, mask)
    cs = mv.count_values(ts, q, mask)
    assert_same((cj, cs))
    vj, oj, c2j = mv.retrieve_all(tj, q, out_capacity, mask)
    vs, os_, c2s = mv.retrieve_all(ts, q, out_capacity, mask)
    assert_same((vj, vs), (oj, os_), (c2j, c2s))
    return vj, oj, cj


class TestRetrieveAllParity:
    def test_duplicate_probe_keys(self):
        """Duplicates walk once in the engine yet must fan out full copies."""
        tj, ts, _ = _mv_pair()
        q = jnp.asarray([3, 3, 3, 7, 3, 9, 7, 7, 3], jnp.uint32)
        _assert_retrieval_parity(tj, ts, q, out_capacity=200)

    def test_masks_drop_queries_entirely(self):
        tj, ts, rng = _mv_pair(seed=1)
        q = jnp.asarray(rng.integers(1, 40, 80, dtype=np.uint32))
        mask = jnp.asarray(rng.random(80) < 0.5)
        _, _, counts = _assert_retrieval_parity(tj, ts, q, 400, mask)
        assert (np.asarray(counts)[~np.asarray(mask)] == 0).all()

    @pytest.mark.parametrize("out_capacity", [0, 1, 7, 64])
    def test_out_capacity_overflow_truncation(self, out_capacity):
        """Truncation must drop exactly the tail and keep offsets/counts
        describing the UNtruncated layout on both backends."""
        tj, ts, rng = _mv_pair(n_pairs=200, key_hi=10, seed=2)
        q = jnp.asarray(rng.integers(1, 12, 30, dtype=np.uint32))
        vj, oj, cj = _assert_retrieval_parity(tj, ts, q, out_capacity)
        total = int(np.asarray(oj)[-1])
        assert total > out_capacity          # the case actually overflows
        assert int(np.asarray(cj).sum()) == total

    def test_tombstone_riddled_table(self):
        """Erase most keys, then query a mix of live, erased and absent
        keys — tombstones must not stop the walk on either backend."""
        tj, ts, _ = _mv_pair(n_pairs=400, key_hi=20, capacity=1024, seed=3)
        dead = jnp.arange(1, 15, dtype=jnp.uint32)
        tj, ej = mv.erase(tj, dead)
        ts, es = mv.erase(ts, dead)
        assert_same((ej, es))
        assert_stores_equal(tj, ts)
        q = jnp.asarray([1, 5, 14, 15, 16, 19, 99, 5, 15], jnp.uint32)
        _assert_retrieval_parity(tj, ts, q, 300)

    def test_u64_two_word_keys_and_values(self):
        rng = np.random.default_rng(4)
        kk = rng.integers(0, 2 ** 32 - 2, (60, 2), dtype=np.uint32)
        kk = np.concatenate([kk, kk[:20]])
        vv = jnp.asarray(rng.integers(0, 2 ** 32 - 2, (80, 2),
                                      dtype=np.uint32))
        tj, ts = _pair(lambda **k: mv.create(512, key_words=2, value_words=2,
                                             window=8, **k))
        tj, _ = mv.insert(tj, jnp.asarray(kk), vv)
        ts, _ = mv.insert(ts, jnp.asarray(kk), vv)
        q = jnp.asarray(np.concatenate([kk[:30], kk[:10],
                                        rng.integers(0, 2 ** 32 - 2, (10, 2),
                                                     dtype=np.uint32)]))
        qm = jnp.asarray(rng.random(50) < 0.8)
        _assert_retrieval_parity(tj, ts, q, 120, qm)

    def test_empty_batch(self):
        tj, ts, _ = _mv_pair(seed=5)
        q = jnp.zeros((0,), jnp.uint32)
        for oc in (0, 8):
            vj, oj, cj = _assert_retrieval_parity(tj, ts, q, oc)
            assert oj.shape == (1,) and int(oj[0]) == 0
            assert cj.shape == (0,)

    def test_empty_table_shortcut(self):
        """count==0 short-cuts the walk; results must still match the
        reference, which walks."""
        tj, ts = _pair(lambda **k: mv.create(256, **k))
        q = jnp.asarray([1, 2, 3, 1], jnp.uint32)
        _assert_retrieval_parity(tj, ts, q, 16)

    @pytest.mark.parametrize("layout", ["soa", "aos"])
    def test_layouts(self, layout):
        tj, ts, rng = _mv_pair(seed=6, layout=layout)
        q = jnp.asarray(rng.integers(1, 40, 60, dtype=np.uint32))
        _assert_retrieval_parity(tj, ts, q, 300)

    def test_max_probes_exhaustion(self):
        """A tiny max_probes truncates the walk identically on both paths."""
        rng = np.random.default_rng(7)
        keys = jnp.asarray(rng.integers(1, 8, 120, dtype=np.uint32))
        tj, ts = _pair(lambda **k: mv.create(64, window=4, max_probes=3, **k))
        tj, _ = mv.insert(tj, keys, keys * 5)
        ts, _ = mv.insert(ts, keys, keys * 5)
        q = jnp.asarray(rng.integers(1, 10, 40, dtype=np.uint32))
        _assert_retrieval_parity(tj, ts, q, 200)


class TestSingleValueRetrieveParity:
    def test_duplicates_and_missing(self):
        rng = np.random.default_rng(8)
        keys = jnp.asarray(rng.permutation(
            np.arange(1, 120, dtype=np.uint32)))
        tj, ts = _pair(lambda **k: sv.create(512, **k))
        tj, _ = sv.insert(tj, keys, keys * 3)
        ts, _ = sv.insert(ts, keys, keys * 3)
        q = jnp.asarray(rng.integers(1, 200, 150, dtype=np.uint32))
        assert_same(*zip(sv.retrieve(tj, q), sv.retrieve(ts, q)))
        assert_same((sv.contains(tj, q), sv.contains(ts, q)))

    def test_u64_keys_wide_values(self):
        rng = np.random.default_rng(9)
        kk = jnp.asarray(rng.integers(0, 2 ** 32 - 2, (70, 2),
                                      dtype=np.uint32))
        vv = jnp.asarray(rng.integers(0, 2 ** 32 - 2, (70, 2),
                                      dtype=np.uint32))
        tj, ts = _pair(lambda **k: sv.create(256, key_words=2, value_words=2,
                                             window=8, **k))
        tj, _ = sv.insert(tj, kk, vv)
        ts, _ = sv.insert(ts, kk, vv)
        q = jnp.concatenate([kk[:40], kk[:10]])
        assert_same(*zip(sv.retrieve(tj, q), sv.retrieve(ts, q)))

    def test_empty_batch(self):
        tj, ts = _pair(lambda **k: sv.create(128, **k))
        q = jnp.zeros((0,), jnp.uint32)
        vj, fj = sv.retrieve(tj, q)
        vs, fs = sv.retrieve(ts, q)
        assert_same((vj, vs), (fj, fs))
        assert vj.shape == (0,) and fj.shape == (0,)

    def test_counting_counts(self):
        rng = np.random.default_rng(10)
        keys = jnp.asarray(rng.integers(1, 30, 200, dtype=np.uint32))
        tj, ts = _pair(lambda **k: ct.create(256, **k))
        tj, _ = ct.insert(tj, keys)
        ts, _ = ct.insert(ts, keys)
        q = jnp.asarray(rng.integers(1, 40, 60, dtype=np.uint32))
        assert_same((ct.counts(tj, q), ct.counts(ts, q)))


class TestEraseParity:
    def test_single_value_duplicates_and_masks(self):
        rng = np.random.default_rng(11)
        keys = jnp.arange(1, 80, dtype=jnp.uint32)
        tj, ts = _pair(lambda **k: sv.create(256, **k))
        tj, _ = sv.insert(tj, keys, keys)
        ts, _ = sv.insert(ts, keys, keys)
        q = jnp.asarray([1, 1, 2, 2, 2, 3, 99, 4, 1], jnp.uint32)
        m = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0, 1], bool)
        tj, ej = sv.erase(tj, q, m)
        ts, es = sv.erase(ts, q, m)
        assert_same((ej, es))
        assert_stores_equal(tj, ts)

    def test_single_value_all_masked_group(self):
        """A key appearing only with mask=False must not be erased and must
        not disturb the walk (its group has no representative)."""
        keys = jnp.arange(1, 30, dtype=jnp.uint32)
        tj, ts = _pair(lambda **k: sv.create(128, **k))
        tj, _ = sv.insert(tj, keys, keys)
        ts, _ = sv.insert(ts, keys, keys)
        q = jnp.asarray([5, 6, 6, 7], jnp.uint32)
        m = jnp.asarray([True, False, False, True])
        tj, ej = sv.erase(tj, q, m)
        ts, es = sv.erase(ts, q, m)
        assert_same((ej, es))
        assert_stores_equal(tj, ts)
        assert bool(sv.contains(tj, jnp.asarray([6], jnp.uint32))[0])

    def test_multi_value_batched_tombstones(self):
        tj, ts, rng = _mv_pair(n_pairs=250, key_hi=15, seed=12)
        q = jnp.asarray([1, 3, 3, 5, 99, 1], jnp.uint32)
        tj, cj = mv.erase(tj, q)
        ts, cs = mv.erase(ts, q)
        assert_same((cj, cs))
        assert_stores_equal(tj, ts)
        # erased keys retrieve empty afterwards, on both backends
        _assert_retrieval_parity(tj, ts, q, 100)
        assert int(mv.count_values(tj, jnp.asarray([1, 3, 5], jnp.uint32)).sum()) == 0

    def test_multi_value_empty_batch(self):
        tj, ts, _ = _mv_pair(seed=13)
        q = jnp.zeros((0,), jnp.uint32)
        tj, cj = mv.erase(tj, q)
        ts, cs = mv.erase(ts, q)
        assert_same((cj, cs))
        assert_stores_equal(tj, ts)

    def test_hashset_remove(self):
        keys = jnp.asarray([5, 9, 11, 13], jnp.uint32)
        sj, ss = _pair(lambda **k: hs.create(128, **k))
        sj, _ = hs.add(sj, keys)
        ss, _ = hs.add(ss, keys)
        q = jnp.asarray([9, 9, 13, 7], jnp.uint32)
        sj, rj = hs.remove(sj, q)
        ss, rs = hs.remove(ss, q)
        assert_same((rj, rs))
        assert_stores_equal(sj, ss)


class TestPallasParity:
    def test_count_and_retrieve_all(self):
        rng = np.random.default_rng(14)
        keys = jnp.asarray(rng.integers(1, 20, 200, dtype=np.uint32))
        vals = jnp.arange(200, dtype=jnp.uint32)
        tp = mv.create(512, window=8, backend="pallas")
        ts = mv.create(512, window=8, backend="scan")
        tp, _ = mv.insert(tp, keys, vals)
        ts, _ = mv.insert(ts, keys, vals)
        assert_stores_equal(tp, ts)
        q = jnp.asarray(rng.integers(1, 30, 60, dtype=np.uint32))
        qm = jnp.asarray(rng.random(60) < 0.7)
        assert_same((mv.count_values(tp, q, qm), mv.count_values(ts, q, qm)))
        for oc in (0, 11, 300):
            a = mv.retrieve_all(tp, q, oc, qm)
            b = mv.retrieve_all(ts, q, oc, qm)
            assert_same(*zip(a, b))

    def test_single_value_lookup_dispatch(self):
        keys = jnp.arange(1, 60, dtype=jnp.uint32)
        tp = sv.create(256, backend="pallas")
        ts = sv.create(256, backend="scan")
        tp, _ = sv.insert(tp, keys, keys ^ 21)
        ts, _ = sv.insert(ts, keys, keys ^ 21)
        q = jnp.asarray([1, 1, 7, 99, 58], jnp.uint32)
        assert_same(*zip(sv.retrieve(tp, q), sv.retrieve(ts, q)))


class TestArenaInvariant:
    def test_arena_ranks_are_contiguous_per_representative(self):
        """Every representative's arena entries must carry ranks 0..cnt-1
        exactly once — the collision-free-placement invariant the
        compaction gather relies on."""
        tj, _, rng = _mv_pair(n_pairs=300, key_hi=12, seed=15)
        q = jnp.asarray(rng.integers(1, 15, 40, dtype=np.uint32))
        keys_n = sv.normalize_words(q, 1, "keys")
        live = jnp.ones((40,), bool)
        is_rep, rep_of = br.group_queries(keys_n, live)
        words = sv.key_hash_word(keys_n)
        cnt, qa, ra = br.fused_walk(br._tstatic(tj), tj.store, keys_n, words,
                                    is_rep, collect=True, count=tj.count)
        cnt, qa, ra = map(np.asarray, (cnt, qa, ra))
        is_rep = np.asarray(is_rep)
        for r in np.nonzero(is_rep)[0]:
            ranks = sorted(ra[qa == r].tolist())
            assert ranks == list(range(cnt[r])), f"rep {r}: {ranks}"


@pytest.mark.parametrize("seed", range(6))
def test_randomized_adversarial_parity(seed):
    """Randomized end-to-end: build (dups+mask) -> erase -> fused vs
    reference count/retrieve/erase across schemes, windows, layouts,
    capacities and out_capacity truncation — bit-exact."""
    r = np.random.default_rng(seed)
    n = int(r.integers(20, 200))
    key_hi = int(r.integers(4, 60))
    keys = jnp.asarray(r.integers(1, key_hi, n, dtype=np.uint32))
    vals = jnp.asarray(r.integers(0, 2 ** 32 - 2, n, dtype=np.uint32))
    mask = jnp.asarray(r.random(n) < 0.7)
    window = int(r.choice([1, 4, 8, 32]))
    scheme = str(r.choice(["cops", "linear", "quadratic"]))
    layout = str(r.choice(["soa", "aos"]))
    cap = int(r.choice([64, 256]))
    mp = int(r.choice([8, 64]))
    mk = lambda **kw: mv.create(cap, window=window, scheme=scheme,
                                layout=layout, max_probes=mp, **kw)
    tj, ts = _pair(mk)
    tj, _ = mv.insert(tj, keys, vals, mask)
    ts, _ = mv.insert(ts, keys, vals, mask)
    nq = int(r.integers(1, 80))
    q = jnp.asarray(r.integers(1, key_hi + 10, nq, dtype=np.uint32))
    qm = jnp.asarray(r.random(nq) < 0.8)
    total = int(np.asarray(mv.count_values(ts, q, qm)).sum())
    for oc in {0, max(total // 3, 1), total, total + 8}:
        _assert_retrieval_parity(tj, ts, q, oc, qm)
    tj, ej = mv.erase(tj, q[:nq // 2])
    ts, es = mv.erase(ts, q[:nq // 2])
    assert_same((ej, es))
    assert_stores_equal(tj, ts)
    _assert_retrieval_parity(tj, ts, q, max(total, 1))


class TestJoinLeftOuterTruncation:
    def test_left_outer_clip_keeps_valid_total_consistent(self):
        """Regression: the left-outer gather clips inner positions to
        out_capacity-1; when out_capacity < total the truncated result
        must still report the full total, mark exactly the first
        out_capacity rows valid, and agree with the scan backend."""
        bkeys = jnp.asarray([1, 1, 1, 2, 2, 3], jnp.uint32)
        pkeys = jnp.asarray([1, 9, 2, 1, 8], jnp.uint32)
        table_j, _ = join.build(bkeys, backend="jax")
        table_s, _ = join.build(bkeys, backend="scan")
        full = join.probe(table_j, pkeys, 32, how="left")
        total = int(full.total)              # 3 + 1 + 2 + 3 + 1 = 10
        assert total == 10
        for oc in (1, 4, total - 1):
            rj = join.probe(table_j, pkeys, oc, how="left")
            rs = join.probe(table_s, pkeys, oc, how="left")
            assert_same((rj.build_idx, rs.build_idx),
                        (rj.probe_idx, rs.probe_idx),
                        (rj.valid, rs.valid), (rj.matched, rs.matched),
                        (rj.total, rs.total))
            assert int(rj.total) == total    # truncation is silent but honest
            assert int(np.asarray(rj.valid).sum()) == min(oc, total)
            # valid rows must be a prefix and match the untruncated head
            np.testing.assert_array_equal(
                np.asarray(rj.build_idx)[:oc][np.asarray(rj.valid)],
                np.asarray(full.build_idx)[:oc][np.asarray(rj.valid)])

    @pytest.mark.parametrize("how", join.HOW)
    def test_all_flavors_fused_vs_scan(self, how):
        rng = np.random.default_rng(16)
        bkeys = jnp.asarray(rng.integers(1, 15, 80, dtype=np.uint32))
        pkeys = jnp.asarray(rng.integers(1, 25, 50, dtype=np.uint32))
        pm = jnp.asarray(rng.random(50) < 0.8)
        tb_j, _ = join.build(bkeys, backend="jax")
        tb_s, _ = join.build(bkeys, backend="scan")
        cj = join.count_matches(tb_j, pkeys, how, mask=pm)
        cs = join.count_matches(tb_s, pkeys, how, mask=pm)
        assert_same((cj, cs))
        oc = int(np.asarray(cj).sum())
        for cap2 in (max(oc // 2, 1), oc + 4):
            rj = join.probe(tb_j, pkeys, cap2, how=how, mask=pm)
            rs = join.probe(tb_s, pkeys, cap2, how=how, mask=pm)
            assert_same((rj.build_idx, rs.build_idx),
                        (rj.probe_idx, rs.probe_idx),
                        (rj.valid, rs.valid), (rj.matched, rs.matched),
                        (rj.total, rs.total))


# ---------------------------------------------------------------------------
# compacted probe walk: batches of at least 16 x COMPACT_LANES keys walk
# window 1 at full width, then shrinking buffers of the pending keys
# ---------------------------------------------------------------------------

N_COMPACT = bulk._SHRINK * probing.COMPACT_LANES   # smallest that compacts


def _compact_table(n_pairs, load=0.9, window=32, max_probes=None, seed=0,
                   erase_share=0.0):
    """A jax-backend table of ``n_pairs`` distinct keys at ``load``, with the
    first ``erase_share`` of them erased again (tombstones), and as many
    distinct keys that were never inserted."""
    rng = np.random.default_rng(seed)
    ks = (rng.choice(2 ** 31, 2 * n_pairs, replace=False) + 1).astype(
        np.uint32)
    present, absent = ks[:n_pairs], ks[n_pairs:]
    t = sv.create(int(n_pairs / load), window=window, max_probes=max_probes)
    t, _ = jax.jit(sv.insert)(t, jnp.asarray(present),
                              jnp.asarray(present * 3))
    gone = present[:int(n_pairs * erase_share)]
    if gone.size:
        t, _ = jax.jit(sv.erase)(t, jnp.asarray(gone))
    return t, present, absent, rng


def _compact_queries(case):
    """(table, queries) of one parity case; every batch compacts."""
    n = N_COMPACT
    if case == "present":
        t, p, _, rng = _compact_table(2 * n)
        q = rng.permutation(p)
    elif case == "absent90":
        t, p, a, rng = _compact_table(2 * n)
        q = rng.permutation(np.concatenate([p[:n // 10], a[:n - n // 10]]))
    elif case == "duplicates":
        t, p, a, rng = _compact_table(n)
        q = np.concatenate([rng.choice(p, 3 * n // 4), a[:n // 4]])
    elif case == "tombstones":
        t, p, a, rng = _compact_table(n, load=0.95, erase_share=0.4)
        q = rng.permutation(np.concatenate([p, a[:n // 2]]))
    elif case.startswith("max_probes"):
        t, p, a, rng = _compact_table(n, max_probes=int(case[-1]))
        q = rng.permutation(np.concatenate([p, a[:n // 4]]))
    else:                                  # "w4" / "w8": stages overflow
        t, p, a, rng = _compact_table(n, load=0.95, window=int(case[1:]))
        q = rng.permutation(np.concatenate([p, a[:n // 2]]))
    return t, jnp.asarray(q)


@pytest.fixture
def full_width(monkeypatch):
    """Trace and run ``fn`` with the compacted tail switched off: no buffer
    is large enough, so every walk is the full-width loop.  A fresh
    wrapper keeps jit from reusing a trace of the compacted walk."""
    def run(fn, *args):
        with monkeypatch.context() as m:
            m.setattr(probing, "COMPACT_LANES", 1 << 40)
            return jax.jit(lambda *a: fn(*a))(*args)
    return run


def _walk(t, q):
    """(matched, row, lane, plen, tail_rounds) of the representatives'
    walk, as ``retrieve`` runs it."""
    def fn(t, q):
        kb = sv.normalize_key_batch(q, 1, "keys")
        is_rep, _ = br.group_queries(kb, jnp.ones(q.shape, bool))
        return bulk.probe_matches(bulk._tstatic(t), t.store, kb,
                                  sv.probe_words(t, kb), is_rep, t.count,
                                  stats=True)
    return jax.jit(fn)(t, q)


class TestCompactedWalkParity:
    @pytest.mark.parametrize("case", [
        "present", "absent90", "duplicates", "tombstones", "max_probes1",
        "max_probes2", "max_probes3", "w4", "w8"])
    def test_matches_full_width_and_scan(self, case, full_width):
        t, q = _compact_queries(case)
        assert q.shape[0] >= N_COMPACT
        walk = _walk(t, q)
        ref = full_width(_walk, t, q)
        assert_same(*zip(walk[:4], ref[:4]))           # matched, row, lane, plen
        stats_fn = lambda t, q: sv.retrieve(t, q, stats=True)
        vj, fj, sj = jax.jit(stats_fn)(t, q)
        vf, ff, sf = full_width(stats_fn, t, q)
        vs, fs = jax.jit(sv.retrieve)(dataclasses.replace(t, backend="scan"), q)
        assert_same((vj, vs), (fj, fs), (vj, vf), (fj, ff),
                    (sj.probe_hist, sf.probe_hist),
                    (sj.probe_sum, sf.probe_sum), (sj.probe_n, sf.probe_n))
        assert_same((sv.contains(t, q), fs))
        assert int(sf.tail_rounds) == 0
        assert int(sj.tail_rounds) == int(walk[4])

    def test_erase_with_duplicates_and_mask(self, full_width):
        t, p, a, rng = _compact_table(N_COMPACT)
        q = jnp.asarray(np.concatenate([rng.choice(p, N_COMPACT // 2),
                                        a[:N_COMPACT // 2]]))
        m = jnp.asarray(rng.random(N_COMPACT) < 0.7)
        tj, ej = jax.jit(sv.erase)(t, q, m)
        tf, ef = full_width(sv.erase, t, q, m)
        ts, es = jax.jit(sv.erase)(dataclasses.replace(t, backend="scan"),
                                   q, m)
        assert_same((ej, es), (ej, ef))
        assert_stores_equal(tj, ts)
        assert_stores_equal(tj, tf)

    def test_insert_into_filled_table_with_mask(self, full_width):
        """Upserts into a non-empty table walk for their matches first."""
        t, p, a, rng = _compact_table(N_COMPACT, load=0.8)
        q = jnp.asarray(np.concatenate([p[:N_COMPACT // 2],
                                        a[:N_COMPACT // 2]]))
        m = jnp.asarray(rng.random(N_COMPACT) < 0.8)
        ins = lambda t, k, v, m: sv.insert(t, k, v, m, stats=True)
        tj, sj, stj = jax.jit(ins)(t, q, q + 7, m)
        tf, sf, stf = full_width(ins, t, q, q + 7, m)
        assert_same((sj, sf), (stj.probe_hist, stf.probe_hist),
                    (stj.probe_sum, stf.probe_sum))
        assert_stores_equal(tj, tf)
        assert int(stj.tail_rounds) > 0 and int(stf.tail_rounds) == 0


class TestTailRounds:
    def test_zero_when_every_key_stops_in_window_one(self):
        rng = np.random.default_rng(21)
        keys = jnp.asarray(unique_keys(rng, N_COMPACT))
        t = sv.create(8 * N_COMPACT, window=32)          # load 1/8
        t, _ = jax.jit(sv.insert)(t, keys, keys)
        st = jax.jit(lambda t, k: sv.retrieve(t, k, stats=True))(t, keys)[2]
        assert int(st.probe_hist[0]) == N_COMPACT
        assert int(st.tail_rounds) == 0

    def test_zero_below_the_size_threshold(self):
        t, p, _, _ = _compact_table(N_COMPACT)
        q = jnp.asarray(p[:N_COMPACT - 1])
        walk = _walk(t, q)
        assert int(jnp.max(walk[3])) > 1                 # keys took window 2+
        assert int(walk[4]) == 0

    @pytest.mark.parametrize("n_pairs,load,stages", [
        (4 * N_COMPACT, 0.9, 1), (16 * N_COMPACT, 0.8, 2)])
    def test_one_round_per_stage(self, n_pairs, load, stages):
        t, p, _, _ = _compact_table(n_pairs, load=load)
        walk = _walk(t, jnp.asarray(p))
        sizes = bulk._tail_sizes(n_pairs)
        assert len(sizes) == stages
        assert int(jnp.max(walk[3])) > stages + 1        # every one entered
        for k, m in enumerate(sizes, start=1):           # none overflows
            assert int(jnp.sum(walk[3] > k)) <= m
        assert int(walk[4]) == stages

    def test_more_rounds_when_a_stage_overflows(self):
        t, q = _compact_queries("w4")
        walk = _walk(t, q)
        pending = int(jnp.sum(walk[3] > 1))              # after window 1
        assert pending > bulk._tail_sizes(q.shape[0])[0]
        assert int(walk[4]) > len(bulk._tail_sizes(q.shape[0]))
