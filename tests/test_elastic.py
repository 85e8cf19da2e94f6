"""Fault-injection suite for checkpoint/restore + elastic sharded serving.

Three failure families, per ISSUE 10:

- **crash recovery** — snapshot a live table, keep mutating the original
  (simulating the work lost after the checkpoint), restore, and demand
  the restored table is BIT-EXACT against the checkpointed state: same
  treedef (probe geometry/statics), same store planes, same slot census,
  and retrieve parity on the live set.  Every table kind × geometry.
- **torn snapshots** — truncations at every layer (magic, header,
  payload) and payload bit-flips must raise ``SnapshotError`` with a
  clear diagnosis, never restore a silently wrong table.
- **elastic restore** — restoring onto a different shard count must
  replay the ownership exchange exactly: each shard ends with precisely
  its owned keys (``check_ownership``), nothing lost, lookup parity
  intact.  Host-simulated meshes here; the 8-device shard_map leg runs
  in subprocesses via the harness from ``test_distributed.py``, its
  ``shard_map`` programs called through ``jax.jit``.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bloom
from repro.core import bucket_list as bl
from repro.core import counting, hashing, migrate, snapshot
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.serving import elastic

_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": "src"}


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=100,
                       env=_ENV, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# Engines called through jax.jit: one compile per static configuration and
# shape, reused by every test of a worker.  Called eagerly, they compile
# their inner loops again on every call.
sv_insert = jax.jit(sv.insert)
sv_erase = jax.jit(sv.erase)
sv_retrieve = jax.jit(sv.retrieve)
mv_insert = jax.jit(mv.insert)
mv_retrieve_all = jax.jit(mv.retrieve_all, static_argnames="out_capacity")
bl_insert = jax.jit(bl.insert)
bl_retrieve_all = jax.jit(bl.retrieve_all, static_argnames="out_capacity")
counting_insert = jax.jit(counting.insert)
counting_counts = jax.jit(counting.counts)
elastic_insert = jax.jit(elastic.insert)
elastic_erase = jax.jit(elastic.erase)
elastic_lookup = jax.jit(elastic.lookup)


def _keys(n, seed=0, lo=1, span=1 << 18):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice(span, n, replace=False) + lo, jnp.uint32)


def _assert_bit_exact(a, b, what=""):
    """Same treedef (statics => probe geometry) and same plane bytes."""
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b), \
        f"{what}: treedef (static config) drifted through the snapshot"
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert la.dtype == lb.dtype and la.shape == lb.shape, what
        assert bool(jnp.array_equal(la, lb)), \
            f"{what}: store plane bytes differ after restore"


# every kind × geometry: (builder, insert, mutate-after-snapshot, verify)
def _sv_like(make):
    def build():
        t = make()
        ks, vs = _keys(150), _keys(150) * 7
        t, _ = sv_insert(t, ks, vs)
        return t, (ks, vs)

    def mutate(t, live):
        t, _ = sv_insert(t, _keys(60, seed=9, lo=1 << 20), _keys(60, seed=9))
        t, _ = sv_erase(t, live[0][:40])
        return t

    def verify(t, live):
        got, found = sv_retrieve(t, live[0])
        assert bool(jnp.all(found))
        assert bool(jnp.all(got == live[1]))
    return build, mutate, verify


def _mv_case():
    def build():
        t = mv.create(2048)
        ks = jnp.concatenate([_keys(100), _keys(100)])
        vs = jnp.concatenate([_keys(100) * 3, _keys(100) * 5])
        t, _ = mv_insert(t, ks, vs)
        return t, (ks, vs)

    def mutate(t, live):
        t, _ = mv_insert(t, _keys(50, seed=9, lo=1 << 20),
                         _keys(50, seed=9))
        return t

    def verify(t, live):
        _, _, cnt = mv_retrieve_all(t, live[0][:100], 400)
        assert bool(jnp.all(cnt == 2))
    return build, mutate, verify


def _mv_bucketed_case():
    b, m, v = _mv_case()

    def build():
        t = mv.create(2048, kind="bucketed")
        ks = jnp.concatenate([_keys(100), _keys(100)])
        vs = jnp.concatenate([_keys(100) * 3, _keys(100) * 5])
        t, _ = mv_insert(t, ks, vs)
        return t, (ks, vs)
    return build, m, v


def _counting_case():
    def build():
        t = counting.create(512)
        ks = _keys(80)
        t, _ = counting_insert(t, jnp.concatenate([ks, ks, ks[:40]]))
        return t, (ks,)

    def mutate(t, live):
        t, _ = counting_insert(t, live[0])
        return t

    def verify(t, live):
        c = counting_counts(t, live[0])
        assert bool(jnp.all(c[:40] == 3)) and bool(jnp.all(c[40:] == 2))
    return build, mutate, verify


def _bucket_list_case():
    def build():
        t = bl.create(256, 4096)
        ks = jnp.concatenate([_keys(80), _keys(80)])
        vs = jnp.arange(160, dtype=jnp.uint32)
        t, _ = bl_insert(t, ks, vs)
        return t, (ks, vs)

    def mutate(t, live):
        t, _ = bl_insert(t, _keys(40, seed=9, lo=1 << 20), _keys(40, seed=9))
        return t

    def verify(t, live):
        _, _, cnt = bl_retrieve_all(t, live[0][:80], 400)
        assert bool(jnp.all(cnt == 2))
    return build, mutate, verify


CASES = {
    "sv-soa": _sv_like(lambda: sv.create(1024)),
    "sv-aos": _sv_like(lambda: sv.create(1024, layout="aos")),
    "sv-packed": _sv_like(lambda: sv.create(1024, layout="packed")),
    "sv-bucketed": _sv_like(lambda: sv.create(1024, kind="bucketed")),
    "sv-quotient": _sv_like(
        lambda: sv.create(1024, kind="bucketed", quotient=True)),
    "sv-2word": _sv_like(lambda: sv.create(1024, key_words=2, value_words=2)),
    "mv-cops": _mv_case(),
    "mv-bucketed": _mv_bucketed_case(),
    "counting": _counting_case(),
    "bucket-list": _bucket_list_case(),
}


class TestCrashRecovery:
    """snapshot -> mutate original -> restore -> bit-exact + parity."""

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_round_trip_bit_exact(self, case, tmp_path):
        build, mutate, verify = CASES[case]
        if case == "sv-2word":
            # 2-word case feeds u32 pairs through the same sv path
            t = sv.create(1024, key_words=2, value_words=2)
            ks = jnp.stack([_keys(100), _keys(100, seed=3)], axis=1)
            vs = jnp.stack([_keys(100) * 3, _keys(100) * 5], axis=1)
            t, _ = sv_insert(t, ks, vs)
            live = (ks, vs)

            def mutate(tt, lv):
                tt, _ = sv_erase(tt, lv[0][:40])
                return tt

            def verify(tt, lv):
                got, found = sv_retrieve(tt, lv[0])
                assert bool(jnp.all(found))
                assert bool(jnp.all(got == lv[1]))
        else:
            t, live = build()
        path = tmp_path / f"{case}.snap"
        snapshot.save(t, str(path))
        checkpointed = t
        t = mutate(t, live)           # work lost after the checkpoint
        restored = snapshot.load(str(path))
        _assert_bit_exact(checkpointed, restored, case)
        verify(restored, live)        # retrieve parity on the live set

    @pytest.mark.parametrize("case", ["sv-soa", "sv-quotient", "bucket-list"])
    def test_census_preserved(self, case, tmp_path):
        build, _, _ = CASES[case]
        t, _ = build()
        restored = snapshot.restore_bytes(snapshot.snapshot_bytes(t))
        ka, _, la = migrate.live_entries(t)
        kb, _, lb = migrate.live_entries(restored)
        assert int(jnp.sum(la)) == int(jnp.sum(lb))
        assert bool(jnp.array_equal(jnp.where(la[:, None], ka, 0),
                                    jnp.where(lb[:, None], kb, 0)))


class TestTornSnapshots:
    """Damaged state must raise SnapshotError, never restore quietly."""

    def _blob(self):
        t, _ = CASES["sv-soa"][0]()
        return snapshot.snapshot_bytes(t)

    def test_bad_magic(self):
        with pytest.raises(snapshot.SnapshotError, match="magic"):
            snapshot.restore_bytes(b"NOTASNAP" + self._blob()[8:])

    def test_truncated_header(self):
        blob = self._blob()
        with pytest.raises(snapshot.SnapshotError, match="header"):
            snapshot.restore_bytes(blob[:20])

    def test_truncated_payload(self):
        blob = self._blob()
        with pytest.raises(snapshot.SnapshotError,
                           match="torn snapshot: payload"):
            snapshot.restore_bytes(blob[:-100])

    def test_corrupted_payload_bits(self):
        blob = bytearray(self._blob())
        blob[-40] ^= 0xFF             # flip bits deep in the payload
        with pytest.raises(snapshot.SnapshotError, match="sha256"):
            snapshot.restore_bytes(bytes(blob))

    def test_corrupted_header_json(self):
        blob = self._blob()
        nl = blob.find(b"\n", len(snapshot.MAGIC))
        bad = blob[:len(snapshot.MAGIC)] + b'{"version": ' + blob[nl:]
        with pytest.raises(snapshot.SnapshotError, match="header"):
            snapshot.restore_bytes(bad)

    def test_unknown_version(self):
        blob = self._blob()
        bad = blob.replace(b'"version": 1', b'"version": 99', 1)
        with pytest.raises(snapshot.SnapshotError, match="version"):
            snapshot.restore_bytes(bad)

    def test_empty_and_garbage_files(self, tmp_path):
        p = tmp_path / "x.snap"
        p.write_bytes(b"")
        with pytest.raises(snapshot.SnapshotError):
            snapshot.load(str(p))
        p.write_bytes(b"\x00" * 256)
        with pytest.raises(snapshot.SnapshotError):
            snapshot.load(str(p))

    def test_elastic_load_missing_manifest(self, tmp_path):
        with pytest.raises(snapshot.SnapshotError, match="manifest"):
            elastic.load(str(tmp_path))


class TestSnapshotWriter:
    """The async double-buffered writer."""

    def test_async_write_then_load(self, tmp_path):
        t, live = CASES["sv-soa"][0]()
        p = str(tmp_path / "w.snap")
        with snapshot.SnapshotWriter() as w:
            w.save(t, p)
            w.flush()
            restored = snapshot.load(p)
        _assert_bit_exact(t, restored, "writer")

    def test_donation_safe(self, tmp_path):
        """The host copy is taken synchronously in save(): donating the
        table's buffers immediately afterwards must not corrupt the
        queued snapshot."""
        t, live = CASES["sv-soa"][0]()
        p = str(tmp_path / "w.snap")
        donating = jax.jit(lambda tt, k, v: sv.insert(tt, k, v)[0],
                           donate_argnums=(0,))
        with snapshot.SnapshotWriter() as w:
            w.save(t, p)
            t2 = donating(t, _keys(50, seed=5, lo=1 << 21),
                          _keys(50, seed=5))   # invalidates t's buffers
            jax.block_until_ready(t2.count)
            w.flush()
        restored = snapshot.load(p)
        got, found = sv_retrieve(restored, live[0])
        assert bool(jnp.all(found)) and bool(jnp.all(got == live[1]))
        assert int(restored.count) == int(jnp.sum(
            jnp.ones_like(live[0], jnp.int32)))

    def test_latest_wins(self, tmp_path):
        """Queueing faster than the disk keeps only the freshest state."""
        t, _ = CASES["sv-soa"][0]()
        versions = [t]
        for i in range(4):
            t, _ = sv_insert(t, _keys(20, seed=10 + i, lo=(1 << 20) * (i + 2)),
                             _keys(20, seed=10 + i))
            versions.append(t)
        p = str(tmp_path / "w.snap")
        with snapshot.SnapshotWriter() as w:
            for v in versions:
                w.save(v, p)
            w.flush()
        restored = snapshot.load(p)
        assert int(restored.count) == int(versions[-1].count)
        _assert_bit_exact(versions[-1], restored, "latest-wins")

    def test_write_failure_surfaces(self, tmp_path):
        t, _ = CASES["sv-soa"][0]()
        w = snapshot.SnapshotWriter()
        w.save(t, str(tmp_path / "no" / "such" / "dir" / "x.snap"))
        with pytest.raises(OSError):
            w.flush()
        w.close()


class TestShardedServing:
    """The bloom-filtered sharded table vs a dict model."""

    def test_dict_model_parity(self):
        rng = np.random.default_rng(1)
        st = elastic.create(4, 2048)
        model = {}
        for step in range(4):
            ins = _keys(200, seed=20 + step, span=1 << 12)
            vs = jnp.asarray(rng.integers(0, 2 ** 31, 200), jnp.uint32)
            st, _ = elastic_insert(st, ins, vs)
            for k, v in zip(np.asarray(ins).tolist(), np.asarray(vs).tolist()):
                model[k] = v
            dels = _keys(60, seed=40 + step, span=1 << 12)
            st, erased = elastic_erase(st, dels)
            for i, k in enumerate(np.asarray(dels).tolist()):
                assert bool(erased[i]) == (k in model)
                model.pop(k, None)
        universe = jnp.asarray(sorted(set(np.asarray(
            _keys(4096, seed=99, span=1 << 12)).tolist())), jnp.uint32)
        got, found, stats = elastic_lookup(st, universe)
        for i, k in enumerate(np.asarray(universe).tolist()):
            assert bool(found[i]) == (k in model), f"key {k}"
            if k in model:
                assert int(got[i]) == model[k]
        assert int(elastic.count(st)) == len(model)

    def test_absent_keys_skip_exchange(self):
        st = elastic.create(4, 2048)
        st, _ = elastic_insert(st, _keys(500), _keys(500))
        absent = _keys(1000, seed=7, lo=1 << 20)
        _, found, stats = elastic_lookup(st, absent)
        assert not bool(jnp.any(found))
        frac = int(stats["skips"]) / 1000
        assert frac >= 0.5, \
            f"bloom front-end only skipped {frac:.0%} of absent traffic"

    def test_no_false_negatives_through_filter(self):
        """Every live key must pass its owner's filter (admission is
        exact for present keys — the one-sided bloom contract)."""
        st = elastic.create(4, 2048)
        ks = _keys(800)
        st, _ = elastic_insert(st, ks, ks)
        _, found, stats = elastic_lookup(st, ks)
        assert bool(jnp.all(found)), "filter produced a false negative"
        assert int(stats["skips"]) == 0

    def test_erase_staleness_and_compaction_rebuild(self):
        """Regression for the bloom staleness-after-erase fix: erase
        leaves the filter permissive; compact_all's rebuild stops
        advertising long-dead keys."""
        st = elastic.create(4, 2048)
        ks = _keys(600)
        st, _ = elastic_insert(st, ks, ks)
        dead, alive = ks[:500], ks[500:]
        st, _ = elastic_erase(st, dead)

        def advertised(s, keys):
            words = sv.key_hash_word(
                sv.normalize_key_batch(keys, 1, "keys"))
            owners = hashing.hash_owner(words, s.num_shards)
            bits = jnp.stack([f.bits for f in s.filters])
            return bloom.contains_stack(s.filters[0], bits, owners, words)

        stale = advertised(st, dead)
        assert bool(jnp.all(stale)), \
            "erase must leave the filter permissive (no bit clearing)"
        st = elastic.compact_all(st)
        stale_after = float(jnp.mean(advertised(st, dead)))
        assert stale_after < 0.1, \
            f"{stale_after:.0%} of long-dead keys still advertised " \
            "after compaction rebuild"
        # live keys must never be dropped by the rebuild
        assert bool(jnp.all(advertised(st, alive)))
        got, found, _ = elastic_lookup(st, alive)
        assert bool(jnp.all(found)) and bool(jnp.all(got == alive))

    def test_fill_fraction_only_grows_until_rebuild(self):
        st = elastic.create(2, 1024)
        fills = [float(bloom.fill_fraction(st.filters[0]))]
        for i in range(3):
            st, _ = elastic_insert(st, _keys(100, seed=i, lo=1 + (i << 12)),
                                   _keys(100, seed=i))
            st, _ = elastic_erase(st, _keys(50, seed=i, lo=1 + (i << 12)))
            fills.append(float(bloom.fill_fraction(st.filters[0])))
        assert all(b >= a for a, b in zip(fills, fills[1:])), fills


class TestElasticReshard:
    """Restore onto a different shard count: exact ownership replay."""

    @pytest.mark.parametrize("p_from,p_to", [(4, 8), (8, 3), (2, 7)])
    def test_reshard_ownership_exact(self, p_from, p_to):
        st = elastic.create(p_from, 4096)
        ks, vs = _keys(2000), _keys(2000) * 11
        st, _ = elastic_insert(st, ks, vs)
        st2 = elastic.reshard(st, p_to)
        assert st2.num_shards == p_to
        assert int(elastic.count(st2)) == 2000
        elastic.check_ownership(st2)
        got, found, _ = elastic_lookup(st2, ks)
        assert bool(jnp.all(found)) and bool(jnp.all(got == vs))

    def test_restore_onto_resized_mesh(self, tmp_path):
        st = elastic.create(4, 4096)
        ks, vs = _keys(1500), _keys(1500) * 13
        st, _ = elastic_insert(st, ks, vs)
        st, _ = elastic_erase(st, ks[:500])
        d = str(tmp_path / "ckpt")
        elastic.save(st, d)
        # same count -> bit-exact shard restore
        same = elastic.load(d)
        for a, b in zip(st.shards, same.shards):
            _assert_bit_exact(a, b, "same-count restore")
        # 2x count -> exact ownership under the new partition
        wide = elastic.load(d, num_shards=8)
        assert int(elastic.count(wide)) == 1000
        elastic.check_ownership(wide)
        got, found, _ = elastic_lookup(wide, ks[500:])
        assert bool(jnp.all(found)) and bool(jnp.all(got == vs[500:]))
        gone, gfound, _ = elastic_lookup(wide, ks[:500])
        assert not bool(jnp.any(gfound))

    def test_kill_restore_resume(self, tmp_path):
        """The fig12 leg in miniature: serve, checkpoint async, 'crash',
        restore, resume serving at parity."""
        rng = np.random.default_rng(3)

        def traffic(n):
            for _ in range(n):
                yield (jnp.asarray(rng.integers(1, 1 << 14, 128), jnp.uint32),
                       jnp.asarray(rng.integers(0, 2 ** 31, 128), jnp.uint32),
                       jnp.asarray(rng.integers(1, 1 << 16, 128), jnp.uint32),
                       jnp.asarray(rng.integers(1, 1 << 14, 64), jnp.uint32))

        st = elastic.create(4, 4096)
        st, _, _, _ = elastic.serve_traffic(st, traffic(4))
        d = str(tmp_path / "ckpt")
        with snapshot.SnapshotWriter() as w:
            elastic.save(st, d, writer=w)
            w.flush()
        sweeps = [migrate.live_entries(t) for t in st.shards]
        live_all = jnp.concatenate(
            [k[np.asarray(lv)] for k, _, lv in sweeps])
        pre_count = int(elastic.count(st))
        del st                                     # the crash
        st2 = elastic.load(d)
        assert int(elastic.count(st2)) == pre_count
        got, found, stats = elastic_lookup(st2, live_all)
        assert int(stats["overflow"]) == 0
        assert bool(jnp.all(found))
        st2, _, steps, _ = elastic.serve_traffic(st2, traffic(3))
        assert steps == 3


class TestElasticSubprocess:
    """8-device legs via the subprocess harness from test_distributed."""

    def test_mesh_shards_checkpoint_to_resized_service(self, tmp_path):
        """Build a REAL 8-shard mesh table, checkpoint each device's
        shard, restore as a 4-shard elastic service: ownership exact."""
        out = _run(f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import distributed as dist, snapshot, bloom
            from repro.core.compat import make_mesh_compat
            from repro.serving import elastic
            import dataclasses, json, os
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 2048, window=16)
            n = 8 * 400
            keys = jnp.asarray(np.random.default_rng(0).permutation(
                np.arange(1, n + 1, dtype=np.uint32)))
            vals = keys * 3
            table, status, ov = jax.jit(dist.shard_insert,
                                        static_argnums=(0, 1))(
                mesh, 'x', table, keys, vals)
            assert int(np.asarray(ov).sum()) == 0
            d = {str(tmp_path)!r}
            os.makedirs(d, exist_ok=True)
            shards = [jax.tree.map(lambda x: x[i], table) for i in range(8)]
            f0 = bloom.create(16 * shards[0].capacity)
            st = elastic.ShardedTable(
                shards=tuple(shards),
                filters=tuple(bloom.rebuild_from_table(f0, t)
                              for t in shards),
                num_shards=8, slack=2.0)
            elastic.check_ownership(st)   # mesh partition == elastic partition
            elastic.save(st, d)
            st4 = elastic.load(d, num_shards=4)
            assert st4.num_shards == 4
            assert int(elastic.count(st4)) == n
            elastic.check_ownership(st4)
            got, found, stats = elastic.lookup(st4, keys)
            assert bool(jnp.all(found))
            assert bool(jnp.all(got == vals))
            print('OK')
        """)
        assert "OK" in out

    def test_filtered_retrieve_in_mesh(self):
        """retrieve_distributed_filtered inside shard_map: parity on
        present keys, >=50% of absent traffic killed pre-all_to_all."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core import distributed as dist, bloom
            from repro.core.compat import make_mesh_compat, shard_map_compat
            mesh = make_mesh_compat((8,), ('x',))
            table = dist.create_sharded(mesh, 'x', 2048)
            rng = np.random.default_rng(0)
            n = 8 * 512
            keys = jnp.asarray(rng.choice(1 << 19, n, replace=False) + 1,
                               jnp.uint32)
            vals = keys * 5
            table, _, ov = jax.jit(dist.shard_insert, static_argnums=(0, 1))(
                mesh, 'x', table, keys, vals)
            assert int(np.asarray(ov).sum()) == 0
            proto = bloom.create(16 * 2048)
            spec = jax.tree.map(lambda _: P('x'), table)
            def mk(t):
                t0 = dist._local(t)
                return dist._relift(
                    bloom.rebuild_from_table(proto, t0).bits)
            fbits = jax.jit(shard_map_compat(mk, mesh, in_specs=(spec,),
                                             out_specs=P('x')))(table)
            import dataclasses
            def body(t, fb, k):
                f = dataclasses.replace(proto, bits=fb[0])
                v, fnd, sk, ov = dist.retrieve_distributed_filtered(
                    dist._local(t), f, k, 'x')
                return v, fnd, sk[None], ov[None]
            g = jax.jit(shard_map_compat(
                body, mesh, in_specs=(spec, P('x'), P('x')),
                out_specs=(P('x'), P('x'), P('x'), P('x'))))
            v, fnd, sk, ov = g(table, fbits, keys)
            assert bool(jnp.all(fnd)) and bool(jnp.all(v == vals))
            assert int(jnp.max(ov)) == 0
            absent = jnp.asarray(
                rng.choice(1 << 19, n, replace=False) + (1 << 21), jnp.uint32)
            v2, f2, sk2, _ = g(table, fbits, absent)
            assert not bool(jnp.any(f2))
            frac = int(jnp.sum(sk2)) / n
            assert frac >= 0.5, frac
            print('OK skip_frac=%.3f' % frac)
        """)
        assert "OK" in out
