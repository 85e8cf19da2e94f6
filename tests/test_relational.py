"""Relational operator tests: every join flavor and aggregate checked
against numpy/dict reference implementations, plus tombstoned build keys,
empty inputs, masks, and jax-vs-pallas backend agreement."""

import os
import subprocess
import sys
import textwrap
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import multi_value as mv
from repro.relational import distinct as rdistinct
from repro.relational import groupby as rgroupby
from repro.relational import join as rjoin


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def ref_join(build_keys, probe_keys, how, build_live=None, probe_live=None):
    """Dict-based reference: returns (sorted pair list, matched mask)."""
    build_live = np.ones(len(build_keys), bool) if build_live is None else build_live
    probe_live = np.ones(len(probe_keys), bool) if probe_live is None else probe_live
    d = defaultdict(list)
    for i, k in enumerate(build_keys):
        if build_live[i]:
            d[int(k)].append(i)
    pairs, matched = [], []
    for j, k in enumerate(probe_keys):
        hits = d.get(int(k), []) if probe_live[j] else []
        matched.append(bool(hits) and bool(probe_live[j]))
        if not probe_live[j]:
            continue
        if how == "inner":
            pairs += [(i, j) for i in hits]
        elif how == "left":
            pairs += [(i, j) for i in hits] if hits else [(-1, j)]
        elif how == "semi" and hits:
            pairs.append((-1, j))
        elif how == "anti" and not hits:
            pairs.append((-1, j))
    return sorted(pairs), np.array(matched, bool)


def result_pairs(res):
    return sorted((int(b), int(p)) for b, p, v in
                  zip(res.build_idx, res.probe_idx, res.valid) if v)


def ref_groupby(keys, values, agg):
    groups = defaultdict(list)
    for k, v in zip(keys, values):
        groups[int(k)].append(int(v))
    out = {}
    for k, vs in groups.items():
        if agg == "sum":
            out[k] = int(np.sum(np.asarray(vs, np.uint32), dtype=np.uint32))
        elif agg == "min":
            out[k] = min(vs)
        elif agg == "max":
            out[k] = max(vs)
        elif agg == "count":
            out[k] = len(vs)
        elif agg == "mean":
            out[k] = float(np.float32(np.sum(np.asarray(vs, np.uint32),
                                             dtype=np.uint32))
                           / np.float32(len(vs)))
    return out


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

class TestJoin:
    @pytest.mark.parametrize("how", rjoin.HOW)
    def test_matches_reference_with_duplicates(self, how, rng):
        # duplicate keys on BOTH sides -> N:M fan-out
        bk = rng.integers(1, 40, 120).astype(np.uint32)
        pk = rng.integers(1, 60, 200).astype(np.uint32)
        cap = 4096
        res = jax.jit(lambda b, p: rjoin.hash_join(b, p, cap, how))(
            jnp.asarray(bk), jnp.asarray(pk))
        pairs, matched = ref_join(bk, pk, how)
        assert result_pairs(res) == pairs
        assert int(res.total) == len(pairs)
        np.testing.assert_array_equal(np.asarray(res.matched), matched)

    @pytest.mark.parametrize("how", rjoin.HOW)
    def test_tombstoned_build_keys(self, how, rng):
        # erased build keys must act as absent in every flavor
        bk = rng.choice(np.arange(1, 600, dtype=np.uint32), 150, replace=False)
        pk = rng.choice(bk, 80, replace=False)
        erased = bk[:50]
        table, _ = rjoin.build(jnp.asarray(bk))
        table, _ = mv.erase(table, jnp.asarray(erased))
        res = rjoin.probe(table, jnp.asarray(pk), 512, how)
        live = ~np.isin(bk, erased)
        pairs, matched = ref_join(bk, pk, how, build_live=live)
        assert result_pairs(res) == pairs
        np.testing.assert_array_equal(np.asarray(res.matched), matched)

    @pytest.mark.parametrize("how", rjoin.HOW)
    def test_probe_mask(self, how, rng):
        bk = rng.integers(1, 30, 60).astype(np.uint32)
        pk = rng.integers(1, 50, 90).astype(np.uint32)
        mask = rng.random(90) < 0.6
        res = rjoin.hash_join(jnp.asarray(bk), jnp.asarray(pk), 2048, how,
                              probe_mask=jnp.asarray(mask))
        pairs, matched = ref_join(bk, pk, how, probe_live=mask)
        assert result_pairs(res) == pairs
        np.testing.assert_array_equal(np.asarray(res.matched), matched)

    @pytest.mark.parametrize("how", rjoin.HOW)
    def test_empty_inputs(self, how):
        e = jnp.zeros((0,), jnp.uint32)
        ks = jnp.asarray([1, 2, 3], jnp.uint32)
        # empty build: inner/semi emit nothing, left/anti emit probe rows
        res = rjoin.hash_join(e, ks, 16, how)
        pairs, _ = ref_join(np.zeros(0, np.uint32), np.asarray(ks), how)
        assert result_pairs(res) == pairs
        # empty probe: nothing out
        res = rjoin.hash_join(ks, e, 16, how)
        assert int(res.total) == 0 and not bool(res.valid.any())
        # both empty
        res = rjoin.hash_join(e, e, 4, how)
        assert int(res.total) == 0

    def test_out_capacity_overflow_reports_total(self, rng):
        bk = np.repeat(np.arange(1, 11, dtype=np.uint32), 8)   # 10 keys x8
        pk = np.arange(1, 11, dtype=np.uint32)
        res = rjoin.hash_join(jnp.asarray(bk), jnp.asarray(pk), 24, "inner")
        assert int(res.total) == 80                  # true size via counting pass
        assert int(res.valid.sum()) == 24            # capacity-bounded output

    def test_count_matches_sizes_output(self, rng):
        bk = rng.integers(1, 20, 64).astype(np.uint32)
        pk = rng.integers(1, 30, 48).astype(np.uint32)
        table, _ = rjoin.build(jnp.asarray(bk))
        for how in rjoin.HOW:
            want = int(rjoin.count_matches(table, jnp.asarray(pk), how).sum())
            res = rjoin.probe(table, jnp.asarray(pk), max(want, 1), how)
            assert int(res.total) == want

    def test_gather_payload(self, rng):
        bk = np.asarray([1, 2, 3], np.uint32)
        bv = np.asarray([10, 20, 30], np.uint32)
        pk = np.asarray([2, 9, 1], np.uint32)
        pv = np.asarray([5, 6, 7], np.uint32)
        res = rjoin.hash_join(jnp.asarray(bk), jnp.asarray(pk), 8, "inner")
        bcols, pcols = rjoin.gather_payload(res, jnp.asarray(bv),
                                            jnp.asarray(pv))
        got = sorted((int(a), int(b)) for a, b, v in
                     zip(bcols, pcols, res.valid) if v)
        assert got == [(10, 7), (20, 5)]

    def test_backend_agreement_jax_vs_pallas(self, rng):
        bk = rng.integers(1, 50, 100).astype(np.uint32)
        pk = rng.integers(1, 80, 100).astype(np.uint32)
        for how in rjoin.HOW:
            a = rjoin.hash_join(jnp.asarray(bk), jnp.asarray(pk), 512, how,
                                backend="jax")
            b = rjoin.hash_join(jnp.asarray(bk), jnp.asarray(pk), 512, how,
                                backend="pallas")
            assert result_pairs(a) == result_pairs(b)
            assert int(a.total) == int(b.total)
            np.testing.assert_array_equal(np.asarray(a.matched),
                                          np.asarray(b.matched))


# ---------------------------------------------------------------------------
# group-by
# ---------------------------------------------------------------------------

class TestGroupBy:
    @pytest.mark.parametrize("agg", rgroupby.AGGS)
    def test_matches_reference(self, agg, rng):
        keys = rng.integers(1, 25, 300).astype(np.uint32)
        vals = rng.integers(0, 1 << 20, 300).astype(np.uint32)
        gk, out, live, table = jax.jit(
            lambda k, v, agg=agg: rgroupby.aggregate(k, v, 128, agg))(
                jnp.asarray(keys), jnp.asarray(vals))
        got = {int(k): (float(v) if agg == "mean" else int(v))
               for k, v, l in zip(gk, out, live) if l}
        ref = ref_groupby(keys, vals, agg)
        if agg == "mean":
            assert got.keys() == ref.keys()
            for k in ref:
                assert got[k] == pytest.approx(ref[k], rel=1e-5)
        else:
            assert got == ref
        assert int(table.count) == len(ref)

    def test_sum_wraps_u32(self):
        keys = jnp.asarray([5, 5], jnp.uint32)
        vals = jnp.asarray([0xFFFFFFFF, 2], jnp.uint32)
        _, out, live, _ = rgroupby.aggregate(keys, vals, 64, "sum")
        assert int(out[np.asarray(live)][0]) == 1   # mod 2^32

    def test_streaming_updates_and_lookup(self, rng):
        keys = rng.integers(1, 10, 200).astype(np.uint32)
        vals = rng.integers(0, 1000, 200).astype(np.uint32)
        table = rgroupby.create(64)
        for lo in range(0, 200, 50):                # 4 batches, same table
            table, _ = rgroupby.update(table, "sum",
                                       jnp.asarray(keys[lo:lo + 50]),
                                       jnp.asarray(vals[lo:lo + 50]))
        ref = ref_groupby(keys, vals, "sum")
        q = np.asarray(sorted(ref), np.uint32)
        got, found = rgroupby.lookup(table, "sum", jnp.asarray(q))
        assert found.all()
        assert [int(v) for v in got] == [ref[int(k)] for k in q]

    def test_mask_and_empty(self, rng):
        keys = rng.integers(1, 8, 60).astype(np.uint32)
        vals = rng.integers(0, 100, 60).astype(np.uint32)
        mask = rng.random(60) < 0.5
        gk, out, live, _ = rgroupby.aggregate(
            jnp.asarray(keys), jnp.asarray(vals), 64, "sum",
            mask=jnp.asarray(mask))
        ref = ref_groupby(keys[mask], vals[mask], "sum")
        got = {int(k): int(v) for k, v, l in zip(gk, out, live) if l}
        assert got == ref
        e = jnp.zeros((0,), jnp.uint32)
        _, _, live, table = rgroupby.aggregate(e, e, 32, "count")
        assert int(live.sum()) == 0 and int(table.count) == 0

    def test_backend_agreement_jax_vs_pallas(self, rng):
        keys = rng.integers(1, 30, 150).astype(np.uint32)
        vals = rng.integers(0, 1 << 16, 150).astype(np.uint32)
        for agg in rgroupby.AGGS:
            ga = rgroupby.aggregate(jnp.asarray(keys), jnp.asarray(vals),
                                    128, agg, backend="jax")[:3]
            gb = rgroupby.aggregate(jnp.asarray(keys), jnp.asarray(vals),
                                    128, agg, backend="pallas")[:3]
            for a, b in zip(ga, gb):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# distinct
# ---------------------------------------------------------------------------

class TestDistinct:
    def test_matches_reference(self, rng):
        keys = rng.integers(1, 50, 400).astype(np.uint32)
        uniq, n_uniq, first = jax.jit(
            lambda k: rdistinct.distinct(k, 128))(jnp.asarray(keys))
        _, ref_first = np.unique(keys, return_index=True)
        ref_mask = np.zeros(len(keys), bool)
        ref_mask[ref_first] = True
        np.testing.assert_array_equal(np.asarray(first), ref_mask)
        assert int(n_uniq) == len(ref_first)
        # first-occurrence order
        assert [int(u) for u in np.asarray(uniq)[:int(n_uniq)]] == \
            [int(k) for k in keys[ref_mask]]

    def test_streaming_across_batches(self, rng):
        dset = rdistinct.create(256)
        a = np.asarray([1, 2, 3, 2], np.uint32)
        b = np.asarray([3, 4, 1, 5], np.uint32)
        dset, fa = rdistinct.first_occurrence(dset, jnp.asarray(a))
        dset, fb = rdistinct.first_occurrence(dset, jnp.asarray(b))
        assert np.asarray(fa).tolist() == [True, True, True, False]
        assert np.asarray(fb).tolist() == [False, True, False, True]
        assert int(dset.count) == 5

    def test_empty_and_backend(self):
        e = jnp.zeros((0,), jnp.uint32)
        _, n, _ = rdistinct.distinct(e, 4)
        assert int(n) == 0
        k = jnp.asarray([7, 7, 8], jnp.uint32)
        for backend in ("jax", "pallas"):
            u, n, f = rdistinct.distinct(k, 4, backend=backend)
            assert int(n) == 2 and np.asarray(f).tolist() == [True, False,
                                                              True]


# ---------------------------------------------------------------------------
# pipeline stage + sharded join (subprocess: needs 8 host devices)
# ---------------------------------------------------------------------------

class TestPipelineStage:
    def test_dedup_join_aggregate(self):
        from repro.core import counting
        from repro.data import pipeline as dp
        cfg = dp.DataConfig(vocab_size=64, seq_len=16, global_batch=8, seed=1)
        toks = dp.synthetic_batch(cfg, 0)["tokens"]
        toks = jnp.concatenate([toks, toks[:2]])        # 2 duplicate seqs
        table = counting.create(1024)
        tracked = jnp.asarray([3, 7, 11], jnp.uint32)
        table, keep, hits = jax.jit(
            lambda t, x: dp.relational_stage(t, x, tracked))(table, toks)
        kn = np.asarray(keep)
        assert kn[:8].all() and not kn[8:].any()
        tn = np.asarray(toks)
        ref = np.array([int(np.isin(tn[i], [3, 7, 11]).sum())
                        for i in range(tn.shape[0])])
        np.testing.assert_array_equal(np.asarray(hits),
                                      np.where(kn, ref, 0))
        # prebuilt watchlist (hot path) + duplicate watchlist entries
        wl = dp.build_watchlist(jnp.asarray([3, 3, 7, 11, 7], jnp.uint32))
        table2 = counting.create(1024)
        table2, keep2, hits2 = jax.jit(
            lambda t, x, w: dp.relational_stage(t, x, w))(table2, toks, wl)
        np.testing.assert_array_equal(np.asarray(keep2), kn)
        np.testing.assert_array_equal(np.asarray(hits2),
                                      np.where(kn, ref, 0))


class TestLazyImportInsideJit:
    def test_first_import_inside_trace_no_tracer_leak(self):
        # repro.relational is imported lazily inside jitted pipeline code;
        # a module-level jnp constant would be created as a tracer on the
        # first trace and leak into the second jit call (fresh process so
        # the module is really first-imported inside the trace).
        env = {**os.environ, "PYTHONPATH": "src"}
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp
            from repro.core import counting
            from repro.data import pipeline as dp
            toks = jnp.ones((2, 8), jnp.int32)
            tracked = jnp.asarray([1, 2], jnp.uint32)
            t1 = counting.create(64)
            t1, _, _ = jax.jit(
                lambda t, x: dp.relational_stage(t, x, tracked))(t1, toks)
            t2 = counting.create(64)
            t2, _, _ = jax.jit(
                lambda t, x: dp.relational_stage(t, x, tracked))(t2, toks)
            print('OK')
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=100, env=env,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, f"STDERR:\n{r.stderr[-3000:]}"
        assert "OK" in r.stdout


class TestShardedJoin:
    def test_partitioned_matches_reference(self):
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "PYTHONPATH": "src"}
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from collections import defaultdict
            from repro.relational import join
            mesh = jax.make_mesh((8,), ('x',))
            # jitted: an eager shard_map compiles each primitive on its own
            shard_join = jax.jit(join.shard_join, static_argnums=(0, 1, 4, 5),
                                 static_argnames='slack')
            rng = np.random.default_rng(0)
            bk = rng.integers(1, 300, 8 * 64).astype(np.uint32)
            pk = rng.integers(1, 400, 8 * 128).astype(np.uint32)
            d = defaultdict(list)
            for i, k in enumerate(bk):
                d[int(k)].append(i)
            refm = np.array([int(k) in d for k in pk])
            ref = sorted((i, j) for j, k in enumerate(pk)
                         for i in d.get(int(k), []))
            out = shard_join(mesh, 'x', jnp.asarray(bk),
                             jnp.asarray(pk), 2048, 'inner', slack=4.0)
            assert int(np.asarray(out['overflow']).sum()) == 0
            got = sorted((int(b), int(p)) for b, p, v in
                         zip(out['build_idx'], out['probe_idx'],
                             out['valid']) if v)
            assert got == ref, 'pair mismatch'
            assert (np.asarray(out['matched']) == refm).all()
            for how, expect in (('semi', int(refm.sum())),
                                ('anti', int((~refm).sum()))):
                o = shard_join(mesh, 'x', jnp.asarray(bk),
                               jnp.asarray(pk), 2048, how, slack=4.0)
                assert int(np.asarray(o['total']).sum()) == expect, how
            # composite two-column keys ride the same ownership exchange:
            # (hi, lo) tuples vs the equivalent u32-packed single words.
            # Output order is per-owner-shard and the two representations
            # hash to different owners, so compare the order-independent
            # contract: the global (build, probe) pair set, the
            # input-aligned matched mask, and the total
            bh, bl = bk >> 4, (bk & 15) | 1
            ph, plo = pk >> 4, (pk & 15) | 1
            oc = shard_join(mesh, 'x',
                            (jnp.asarray(bh), jnp.asarray(bl)),
                            (jnp.asarray(ph), jnp.asarray(plo)),
                            2048, 'inner', slack=4.0)
            op = shard_join(mesh, 'x',
                            jnp.asarray((bh << 4) | bl),
                            jnp.asarray((ph << 4) | plo),
                            2048, 'inner', slack=4.0)
            def pairs(o):
                return sorted((int(b), int(p)) for b, p, v in
                              zip(o['build_idx'], o['probe_idx'],
                                  o['valid']) if v)
            assert pairs(oc) == pairs(op), 'composite pair set mismatch'
            assert (np.asarray(oc['matched'])
                    == np.asarray(op['matched'])).all()
            assert (int(np.asarray(oc['total']).sum())
                    == int(np.asarray(op['total']).sum()))
            assert int(np.asarray(oc['overflow']).sum()) == 0
            print('OK')
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=100, env=env,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
        assert "OK" in r.stdout
