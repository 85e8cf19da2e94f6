"""Online growth + tombstone compaction (repro.core.migrate).

The robustness contract: no table hard-fails under sustained churn.
Covers the migration engine (grow/compact bit-exact on the live set for
all three table kinds), the policy layer (insert_or_grow retries FULL
after growth; maybe_migrate trips on load factor / tombstone density),
the registry counters, erase-slot bookkeeping exactness across both
backends, the kv-cache free-list fix (no aliasing on exhaustion), and
the pipeline dedup churn loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_list as bl
from repro.core import counting
from repro.core import migrate
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.core.common import (
    STATUS_FULL,
    STATUS_INSERTED,
    STATUS_POOL_FULL,
)
from repro.obs import metrics
from repro.obs.registry import REGISTRY

_U = jnp.uint32


def _keys(n, start=1):
    return jnp.arange(start, start + n, dtype=_U)


class TestGrowCompactSingleValue:
    def _churned(self):
        t = sv.create(256, window=8)
        t, _ = sv.insert(t, _keys(100), _keys(100) * 3)
        t, er = sv.erase(t, _keys(40))            # keys 1..40 tombstoned
        assert np.asarray(er).all()
        return t

    def test_compact_drops_tombstones_preserves_live(self):
        t = self._churned()
        _, tomb0, _ = metrics.slot_stats(t.ops, t.store)
        assert int(tomb0) == 40
        c = migrate.compact(t)
        assert c.capacity == t.capacity
        live, tomb, _ = metrics.slot_stats(c.ops, c.store)
        assert int(tomb) == 0 and int(live) == 60
        assert int(c.count) == 60
        got, found = sv.retrieve(c, _keys(100))
        np.testing.assert_array_equal(np.asarray(found),
                                      np.arange(1, 101) > 40)
        np.testing.assert_array_equal(np.asarray(got)[40:],
                                      np.arange(41, 101) * 3)

    def test_grow_preserves_live_set(self):
        t = self._churned()
        g = migrate.grow(t, 4096)
        assert g.capacity >= 4096 > t.capacity
        live, tomb, _ = metrics.slot_stats(g.ops, g.store)
        assert int(tomb) == 0 and int(live) == 60
        got, found = sv.retrieve(g, _keys(60, start=41))
        assert np.asarray(found).all()
        np.testing.assert_array_equal(np.asarray(got),
                                      np.arange(41, 101) * 3)
        # erased keys stay erased
        _, dfound = sv.retrieve(g, _keys(40))
        assert not np.asarray(dfound).any()

    def test_grow_shrink_guard(self):
        t = sv.create(1024, window=8)
        t, _ = sv.insert(t, _keys(500), _keys(500))
        with pytest.raises(ValueError):
            migrate.grow(t, 256)                  # would drop live keys

    def test_counters(self):
        t = self._churned()
        g0 = REGISTRY.counter("table.grows").value
        c0 = REGISTRY.counter("table.compactions").value
        m0 = REGISTRY.counter("table.migrated_slots").value
        t = migrate.grow(t, 2048)
        t = migrate.compact(t)
        assert REGISTRY.counter("table.grows").value == g0 + 1
        assert REGISTRY.counter("table.compactions").value == c0 + 1
        assert REGISTRY.counter("table.migrated_slots").value == m0 + 120


class TestGrowCompactMultiValue:
    def test_fanout_and_multisets_preserved(self):
        t = mv.create(512, window=8)
        ks = jnp.repeat(_keys(30), 3)             # 30 keys x 3 values
        vs = jnp.arange(90, dtype=_U) * 7
        t, _ = mv.insert(t, ks, vs)
        t, ecnt = mv.erase(t, _keys(10))          # drop keys 1..10 entirely
        np.testing.assert_array_equal(np.asarray(ecnt), 3)
        for fresh in (migrate.grow(t, 2048), migrate.compact(t)):
            cnt = mv.count_values(fresh, _keys(30))
            np.testing.assert_array_equal(
                np.asarray(cnt), [0] * 10 + [3] * 20)
            out, off, _ = mv.retrieve_all(fresh, _keys(30), out_capacity=90)
            out, off = np.asarray(out), np.asarray(off)
            for i in range(10, 30):
                got = sorted(out[off[i]:off[i + 1]].tolist())
                want = sorted((np.arange(3 * i, 3 * i + 3) * 7).tolist())
                assert got == want


class TestGrowCompactBucketList:
    def _filled(self):
        t = bl.create(128, pool_capacity=512, s0=2, growth=1.5)
        ks = jnp.repeat(_keys(20), 4)             # per-key insertion order
        vs = jnp.arange(80, dtype=_U) + 100
        t, stt = bl.insert(t, ks, vs)
        assert (np.asarray(stt) == STATUS_INSERTED).all()
        return t

    @pytest.mark.parametrize("op", ["grow", "compact"])
    def test_retrieval_bit_identical(self, op):
        t = self._filled()
        fresh = (migrate.grow(t, 512) if op == "grow"
                 else migrate.compact(t))
        q = _keys(20)
        out0, off0, cnt0 = bl.retrieve_all(t, q, out_capacity=80)
        out1, off1, cnt1 = bl.retrieve_all(fresh, q, out_capacity=80)
        # values keep per-key insertion order bit-exactly (the migration
        # replays the chains as an ordered stream)
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
        np.testing.assert_array_equal(np.asarray(off0), np.asarray(off1))
        np.testing.assert_array_equal(np.asarray(cnt0), np.asarray(cnt1))
        # migration replays the chains as one ordered stream, so the
        # rebuilt pool follows the same bucket schedule as the original
        # single-batch build — no extra slack accumulates across cycles
        assert int(fresh.alloc_top) == int(t.alloc_top)

    def test_grow_pool_only(self):
        t = self._filled()
        g = migrate.grow(t, t.key_store.capacity, new_pool_capacity=2048)
        assert g.pool_capacity >= 2048
        out0, off0, _ = bl.retrieve_all(t, _keys(20), out_capacity=80)
        out1, off1, _ = bl.retrieve_all(g, _keys(20), out_capacity=80)
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
        np.testing.assert_array_equal(np.asarray(off0), np.asarray(off1))


class TestInsertOrGrow:
    def test_single_value_never_full(self):
        t = sv.create(64, window=8)
        policy = migrate.GrowthPolicy(max_load_factor=0.8, growth_factor=2.0)
        for b in range(8):
            t, stt = sv.insert_or_grow(t, _keys(64, start=1 + b * 64),
                                       _keys(64, start=1 + b * 64),
                                       policy=policy)
            assert not bool(jnp.any(stt == STATUS_FULL))
        assert int(t.count) == 512
        assert t.capacity > 512 / 0.8 * 0.99      # grew past the threshold
        got, found = sv.retrieve(t, _keys(512))
        assert np.asarray(found).all()
        np.testing.assert_array_equal(np.asarray(got), np.arange(1, 513))

    def test_counting_counts_survive_growth(self):
        t = counting.create(32, window=8)
        policy = migrate.GrowthPolicy(max_load_factor=0.7)
        for _ in range(3):                        # 3 occurrences of each key
            t, stt = counting.insert_or_grow(t, _keys(100), policy=policy)
            assert not bool(jnp.any(stt == STATUS_FULL))
        got, found = sv.retrieve(t, _keys(100))
        assert np.asarray(found).all()
        np.testing.assert_array_equal(np.asarray(got), 3)

    def test_bucket_list_pool_growth(self):
        t = bl.create(64, pool_capacity=16, s0=1, growth=2.0)
        policy = migrate.GrowthPolicy(max_load_factor=0.8)
        for b in range(4):
            vs = jnp.arange(32, dtype=_U) + b * 32
            t, stt = bl.insert_or_grow(t, jnp.repeat(_keys(8), 4), vs,
                                       policy=policy)
            assert not bool(jnp.any(stt == STATUS_POOL_FULL))
            assert not bool(jnp.any(stt == STATUS_FULL))
        assert t.pool_capacity > 16
        cnt = bl.count_values(t, _keys(8))
        np.testing.assert_array_equal(np.asarray(cnt), 16)

    def test_compacts_at_max_capacity(self):
        # at the cap, reclaim tombstones instead of growing
        t = sv.create(128, window=8)
        cap = t.capacity
        policy = migrate.GrowthPolicy(max_load_factor=0.9,
                                      max_capacity=cap)
        t, _ = sv.insert(t, _keys(100), _keys(100))
        t, _ = sv.erase(t, _keys(60))
        c0 = REGISTRY.counter("table.compactions").value
        t, stt = sv.insert_or_grow(t, _keys(60, start=200),
                                   _keys(60, start=200), policy=policy)
        assert not bool(jnp.any(stt == STATUS_FULL))
        assert t.capacity == cap                  # never exceeded the cap
        assert REGISTRY.counter("table.compactions").value > c0


class TestMaybeMigrate:
    def test_tombstone_density_trigger(self):
        t = sv.create(256, window=8)
        t, _ = sv.insert(t, _keys(150), _keys(150))
        t, _ = sv.erase(t, _keys(100))
        policy = migrate.GrowthPolicy(max_tombstone_density=0.2)
        fresh = migrate.maybe_migrate(t, policy)
        assert fresh is not t
        assert fresh.capacity == t.capacity       # compaction, not growth
        _, tomb, _ = metrics.slot_stats(fresh.ops, fresh.store)
        assert int(tomb) == 0

    def test_below_thresholds_noop(self):
        t = sv.create(256, window=8)
        t, _ = sv.insert(t, _keys(50), _keys(50))
        assert migrate.maybe_migrate(t, migrate.DEFAULT_POLICY) is t

    def test_load_factor_trigger_grows(self):
        t = sv.create(64, window=8)
        t, _ = sv.insert(t, _keys(60), _keys(60))
        policy = migrate.GrowthPolicy(max_load_factor=0.5)
        fresh = migrate.maybe_migrate(t, policy)
        assert fresh.capacity > t.capacity


class TestEraseSlotBookkeeping:
    """Satellite: erase on tombstoned/absent keys leaves the live and
    tombstone censuses exact — double-erase and erase-then-reinsert do
    not drift the counters, on either backend."""

    @pytest.mark.parametrize("backend", ["jax", "scan"])
    def test_double_erase_exact(self, backend):
        t = sv.create(128, window=8, backend=backend)
        t, _ = sv.insert(t, _keys(30), _keys(30))
        t, er1 = sv.erase(t, _keys(30))
        assert np.asarray(er1).all()
        live1, tomb1, _ = metrics.slot_stats(t.ops, t.store)
        assert (int(live1), int(tomb1)) == (0, 30)
        assert int(t.count) == 0
        # erasing the same keys again: all report absent, census unchanged
        t, er2 = sv.erase(t, _keys(30))
        assert not np.asarray(er2).any()
        live2, tomb2, _ = metrics.slot_stats(t.ops, t.store)
        assert (int(live2), int(tomb2)) == (0, 30)
        assert int(t.count) == 0

    @pytest.mark.parametrize("backend", ["jax", "scan"])
    def test_erase_absent_key_exact(self, backend):
        t = sv.create(128, window=8, backend=backend)
        t, _ = sv.insert(t, _keys(10), _keys(10))
        t, er = sv.erase(t, _keys(10, start=500))  # never inserted
        assert not np.asarray(er).any()
        live, tomb, _ = metrics.slot_stats(t.ops, t.store)
        assert (int(live), int(tomb)) == (10, 0)
        assert int(t.count) == 10

    @pytest.mark.parametrize("backend", ["jax", "scan"])
    def test_erase_then_reinsert_exact(self, backend):
        t = sv.create(128, window=8, backend=backend)
        t, _ = sv.insert(t, _keys(20), _keys(20))
        t, _ = sv.erase(t, _keys(20))
        # reinsert reclaims each key's own tombstone: live back to 20,
        # tombstones back to 0 — no slot leaks in either direction
        t, stt = sv.insert(t, _keys(20), _keys(20) * 9)
        assert (np.asarray(stt) == STATUS_INSERTED).all()
        live, tomb, _ = metrics.slot_stats(t.ops, t.store)
        assert (int(live), int(tomb)) == (20, 0)
        assert int(t.count) == 20
        got, found = sv.retrieve(t, _keys(20))
        assert np.asarray(found).all()
        np.testing.assert_array_equal(np.asarray(got), np.arange(1, 21) * 9)

    @pytest.mark.parametrize("backend", ["jax", "scan"])
    def test_multi_value_double_erase_exact(self, backend):
        t = mv.create(256, window=8, backend=backend)
        t, _ = mv.insert(t, jnp.repeat(_keys(10), 2),
                         jnp.arange(20, dtype=_U))
        t, e1 = mv.erase(t, _keys(10))
        np.testing.assert_array_equal(np.asarray(e1), 2)
        live1, tomb1, _ = metrics.slot_stats(t.ops, t.store)
        t, e2 = mv.erase(t, _keys(10))
        np.testing.assert_array_equal(np.asarray(e2), 0)
        live2, tomb2, _ = metrics.slot_stats(t.ops, t.store)
        assert (int(live1), int(tomb1)) == (int(live2), int(tomb2)) == (0, 20)
        assert int(t.count) == 0


class TestKVCacheAllocation:
    """Satellite: free-list exhaustion reports per-key failures instead
    of aliasing everything onto the last physical page."""

    def test_exhaustion_no_aliasing(self):
        from repro.serving import kv_cache as pkv
        c = pkv.create(num_layers=1, num_pages=4, page_size=4,
                       num_kv_heads=1, head_dim=4)
        full0 = REGISTRY.counter("kv_cache.alloc_full").value
        seq = jnp.arange(6, dtype=jnp.int32) + 10  # 6 seqs, 4 pages
        c, phys, ok = pkv.allocate_pages(c, seq, jnp.zeros((6,), jnp.int32))
        np.testing.assert_array_equal(np.asarray(ok),
                                      [True] * 4 + [False] * 2)
        assert sorted(np.asarray(phys)[:4].tolist()) == [0, 1, 2, 3]
        assert int(c.free_top) == 4
        assert REGISTRY.counter("kv_cache.alloc_full").value == full0 + 2
        # the failed keys are NOT in the page table; a retry after a free
        # can still allocate them
        _, found = pkv.lookup_pages(c, seq, jnp.zeros((6,), jnp.int32))
        np.testing.assert_array_equal(np.asarray(found),
                                      [True] * 4 + [False] * 2)

    def test_duplicate_keys_one_draw(self):
        from repro.serving import kv_cache as pkv
        c = pkv.create(num_layers=1, num_pages=8, page_size=4,
                       num_kv_heads=1, head_dim=4)
        seq = jnp.asarray([7, 7, 7, 8], jnp.int32)
        c, phys, ok = pkv.allocate_pages(c, seq, jnp.zeros((4,), jnp.int32))
        assert bool(jnp.all(ok))
        p = np.asarray(phys)
        assert p[0] == p[1] == p[2] != p[3]
        assert int(c.free_top) == 2               # distinct keys only

    def test_policy_grows_page_table(self):
        from repro.serving import kv_cache as pkv
        policy = migrate.GrowthPolicy(max_load_factor=0.8)
        c = pkv.create(num_layers=1, num_pages=512, page_size=4,
                       num_kv_heads=1, head_dim=4, table_slack=0.125,
                       policy=policy)
        cap0 = c.page_table.capacity
        for wave in range(8):
            seq = jnp.arange(64, dtype=jnp.int32) + wave * 64
            c, _, ok = pkv.allocate_pages(c, seq, jnp.zeros((64,), jnp.int32))
            assert bool(jnp.all(ok)), f"allocation failed in wave {wave}"
        assert int(c.free_top) == 512
        assert c.page_table.capacity > cap0       # the table grew


class TestPipelineChurn:
    def test_dedup_churn_compacts_and_never_fails(self):
        from repro.data import pipeline
        cfg = pipeline.DataConfig(vocab_size=64, seq_len=16, global_batch=32)
        policy = migrate.GrowthPolicy(max_load_factor=0.7,
                                      max_tombstone_density=0.15)
        table = counting.create(64, window=8)
        # the filter grows and compacts on the host; the forget pass is one
        # program at one shape, compiled once for the loop
        forget = jax.jit(pipeline.dedup_forget)
        c0 = REGISTRY.counter("table.compactions").value
        window = []                               # sliding retention window
        for step in range(16):
            batch = pipeline.get_batch(cfg, step)
            table, keep = pipeline.dedup_filter(table, batch["tokens"],
                                                policy=policy)
            assert bool(jnp.any(keep))            # fresh data passes
            window.append(batch["tokens"])
            if len(window) > 3:
                table, _ = forget(table, window.pop(0))
        # churn produced tombstones; the policy compacted at least once
        assert REGISTRY.counter("table.compactions").value > c0
        # the surviving window is still deduplicated exactly
        _, keep_again = pipeline.dedup_filter(table, window[-1],
                                              policy=policy)
        assert not bool(jnp.any(keep_again))
