"""Observability suite (docs/OBSERVABILITY.md).

Four gates:

1. **Stats parity** — every engine's *outputs* are bit-exact with
   ``stats=True`` vs ``stats=False``, across the jax / scan / pallas
   backends: telemetry must be a pure observer.
2. **Probe-histogram recount** — the in-graph probe-length histogram of a
   retrieval matches an independent python re-walk of the probe sequence
   against the same store (small tables, exhaustive).
3. **HLO identity** — with ``stats=False`` the compiled graph of bulk
   insert and fused retrieve is byte-identical to the default call (and
   the hlo_census byte/flop counts agree); ``stats=True`` must differ.
4. **Host-side plumbing** — registry counters/gauges/histograms, tracer
   spans + JSONL schema, report guards, BENCH schema validator.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_list as bl
from repro.core import bulk
from repro.core import counting, probing
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.core.common import EMPTY_KEY
from repro.launch import hlo_census
from repro.obs import metrics
from repro.obs import trace as obtrace
from repro.obs.registry import REGISTRY, Registry

from conftest import unique_keys

BACKENDS = ("jax", "scan", "pallas")


def _keys_vals(rng, n):
    ks = jnp.asarray(unique_keys(rng, n))
    return ks, ks ^ jnp.uint32(0x5A5A)


def _trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# 1. stats parity: outputs bit-exact with stats on/off, all backends
# ---------------------------------------------------------------------------

class TestStatsParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_single_value(self, rng, backend):
        keys, vals = _keys_vals(rng, 64)
        t0 = sv.create(128, window=8, backend=backend)
        t_off, s_off = jax.jit(lambda t, k, v: sv.insert(t, k, v))(
            t0, keys, vals)
        t_on, s_on, st = jax.jit(
            lambda t, k, v: sv.insert(t, k, v, stats=True))(t0, keys, vals)
        assert _trees_equal(t_off.store, t_on.store)
        assert bool(jnp.array_equal(s_off, s_on))
        assert int(jnp.sum(st.status_hist)) == 64
        assert int(st.live_slots) == 64

        r_off = jax.jit(lambda t, k: sv.retrieve(t, k))(t_off, keys)
        r_on = jax.jit(lambda t, k: sv.retrieve(t, k, stats=True))(
            t_on, keys)
        assert bool(jnp.array_equal(r_off[0], r_on[0]))
        assert bool(jnp.array_equal(r_off[1], r_on[1]))
        rst = r_on[2]
        assert int(rst.probe_n) == 64 and rst.mean_probe_len() >= 1.0

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_multi_value(self, rng, backend):
        keys, vals = _keys_vals(rng, 48)
        mkeys = jnp.concatenate([keys, keys[:16]])       # multiplicity 2 head
        mvals = jnp.arange(64, dtype=jnp.uint32)
        t0 = mv.create(192, window=8, backend=backend)
        t_off, s_off = jax.jit(lambda t, k, v: mv.insert(t, k, v))(
            t0, mkeys, mvals)
        t_on, s_on, _ = jax.jit(
            lambda t, k, v: mv.insert(t, k, v, stats=True))(t0, mkeys, mvals)
        assert _trees_equal(t_off.store, t_on.store)
        assert bool(jnp.array_equal(s_off, s_on))

        c_off = jax.jit(lambda t, k: mv.count_values(t, k))(t_off, keys)
        c_on, cst = jax.jit(
            lambda t, k: mv.count_values(t, k, stats=True))(t_on, keys)
        assert bool(jnp.array_equal(c_off, c_on))
        assert int(cst.probe_n) > 0

        cap = int(jnp.sum(c_off))
        r_off = jax.jit(lambda t, k: mv.retrieve_all(t, k, cap))(t_off, keys)
        r_on = jax.jit(lambda t, k: mv.retrieve_all(t, k, cap, stats=True))(
            t_on, keys)
        for a, b in zip(r_off, r_on[:3]):
            assert bool(jnp.array_equal(a, b))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_counting(self, rng, backend):
        keys = jnp.asarray(unique_keys(rng, 32))
        batch = jnp.concatenate([keys, keys, keys[:8]])
        t0 = counting.create(128, backend=backend)
        t_off, s_off = jax.jit(lambda t, k: counting.insert(t, k))(t0, batch)
        t_on, s_on, st = jax.jit(
            lambda t, k: counting.insert(t, k, stats=True))(t0, batch)
        assert _trees_equal(t_off.store, t_on.store)
        assert bool(jnp.array_equal(s_off, s_on))
        assert int(jnp.sum(st.status_hist)) == batch.shape[0]

        c_off = jax.jit(lambda t, k: counting.counts(t, k))(t_off, keys)
        c_on, _ = jax.jit(
            lambda t, k: counting.counts(t, k, stats=True))(t_on, keys)
        assert bool(jnp.array_equal(c_off, c_on))

    @pytest.mark.parametrize("backend", ("jax", "scan"))
    def test_bucket_list(self, rng, backend):
        keys = jnp.asarray(unique_keys(rng, 24))
        mkeys = jnp.concatenate([keys, keys[:12]])
        mvals = jnp.arange(36, dtype=jnp.uint32)
        t0 = bl.create(96, 256, window=8, backend=backend)
        t_off, s_off = jax.jit(lambda t, k, v: bl.insert(t, k, v))(
            t0, mkeys, mvals)
        t_on, s_on, st = jax.jit(
            lambda t, k, v: bl.insert(t, k, v, stats=True))(t0, mkeys, mvals)
        assert _trees_equal(t_off.key_store.store, t_on.key_store.store)
        assert bool(jnp.array_equal(t_off.pool, t_on.pool))
        assert bool(jnp.array_equal(s_off, s_on))
        assert int(st.live_slots) == 24

        c_off = jax.jit(lambda t, k: bl.count_values(t, k))(t_off, keys)
        c_on, _ = jax.jit(
            lambda t, k: bl.count_values(t, k, stats=True))(t_on, keys)
        assert bool(jnp.array_equal(c_off, c_on))

        cap = int(jnp.sum(c_off))
        r_off = jax.jit(lambda t, k: bl.retrieve_all(t, k, cap))(t_off, keys)
        r_on = jax.jit(lambda t, k: bl.retrieve_all(t, k, cap, stats=True))(
            t_on, keys)
        for a, b in zip(r_off, r_on[:3]):
            assert bool(jnp.array_equal(a, b))


# ---------------------------------------------------------------------------
# 2. probe-length histogram vs independent python recount
# ---------------------------------------------------------------------------

def _ref_probe_lengths(table, keys) -> np.ndarray:
    """Independent probe-length recount: replay each key's probe sequence
    in python against the store, counting windows until a match or an
    EMPTY-containing window (the walk's absence proof) — the same stop
    rule as ``bulk.probe_matches`` but none of its while-loop plumbing."""
    kb = sv.normalize_key_batch(keys, table.key_words, "keys")
    words = sv.key_hash_word(kb)
    num_rows = table.ops.num_rows
    row0 = np.asarray(probing.initial_row(words, num_rows, table.seed))
    step = np.asarray(probing.row_step(table.scheme, words, num_rows,
                                       table.seed))
    kb_np = np.asarray(kb)
    out = []
    for i in range(kb_np.shape[0]):
        row = np.uint32(row0[i])
        plen = 0
        for attempt in range(table.max_probes):
            win = np.asarray(table.ops.key_windows(
                table.store, jnp.asarray([row], jnp.uint32)))[0]   # (kw, W)
            plen += 1
            if bool((win == kb_np[i][:, None]).all(axis=0).any()):
                break
            if bool((win[0] == EMPTY_KEY).any()):
                break
            row = np.uint32(np.asarray(probing.advance_row(
                table.scheme, jnp.asarray([row], jnp.uint32),
                jnp.asarray([step[i]], jnp.uint32),
                jnp.asarray(attempt, jnp.int32), num_rows))[0])
        out.append(plen)
    return np.asarray(out, np.int32)


def _ref_hist(plens: np.ndarray) -> np.ndarray:
    edges = 2 ** np.arange(metrics.NUM_PROBE_BINS)
    b = np.searchsorted(edges, plens, side="left")
    return np.bincount(np.clip(b, 0, metrics.NUM_PROBE_BINS - 1),
                       minlength=metrics.NUM_PROBE_BINS).astype(np.int64)


class TestProbeHistRecount:
    @pytest.mark.parametrize("density", (0.5, 0.9))
    def test_retrieve_hist_matches_recount(self, rng, density):
        n = 48
        keys, vals = _keys_vals(rng, n)
        t = sv.create(int(n / density), window=4, max_probes=64)
        t, _ = sv.insert(t, keys, vals)
        missing = jnp.asarray(
            unique_keys(rng, 16, lo=0x7000_0000).astype(np.uint32))
        queries = jnp.concatenate([keys, missing])       # all distinct
        _, _, st = jax.jit(lambda tt, k: sv.retrieve(tt, k, stats=True))(
            t, queries)
        ref = _ref_probe_lengths(t, queries)
        assert int(st.probe_n) == queries.shape[0]
        assert int(st.probe_sum) == int(ref.sum())
        np.testing.assert_array_equal(np.asarray(st.probe_hist), _ref_hist(ref))
        # histogram-derived quantiles are upper bin edges of the recount
        assert st.probe_quantile(0.50) >= float(np.median(ref))

    def test_sparse_table_all_length_one(self, rng):
        # tiny load, wide windows: no bumping, so every key sits in the
        # first window of its probe sequence -> all probe lengths are 1
        keys = jnp.asarray(unique_keys(rng, 8))
        t = sv.create(1, window=32)
        t, _ = sv.insert(t, keys, keys)
        _, _, st = sv.retrieve(t, keys, stats=True)
        assert int(st.probe_hist[0]) == 8
        assert st.mean_probe_len() == 1.0


# ---------------------------------------------------------------------------
# 3. HLO identity: stats=False is byte-identical to the default graph
# ---------------------------------------------------------------------------

def _compiled_text(fn, *args) -> str:
    """Compiled HLO without its source locations: JAX records the file line
    of every traced call, which differs between otherwise equal programs."""
    def entry(*a):                    # same jit name for every candidate
        return fn(*a)
    text = jax.jit(entry).lower(*args).compile().as_text()
    text = re.sub(r" stack_frame_id=\d+", "", text)
    return re.sub(r"\nFileNames\n.*?\nStackFrames\n(?:\d+ \{[^}]*\}\n)*",
                  "\n", text, flags=re.S)


class TestHloIdentity:
    def test_bulk_insert_stats_off_identical(self, rng):
        keys, vals = _keys_vals(rng, 64)
        t0 = sv.create(128, window=8)
        default = _compiled_text(lambda t, k, v: sv.insert(t, k, v),
                                 t0, keys, vals)
        off = _compiled_text(lambda t, k, v: sv.insert(t, k, v, stats=False),
                             t0, keys, vals)
        on = _compiled_text(lambda t, k, v: sv.insert(t, k, v, stats=True),
                            t0, keys, vals)
        assert default == off                     # byte-identical HLO
        assert default != on                      # telemetry is real
        ca, cb = hlo_census.census(default), hlo_census.census(off)
        assert ca.bytes_moved == cb.bytes_moved and ca.flops == cb.flops

    def test_fused_retrieve_stats_off_identical(self, rng):
        keys, _ = _keys_vals(rng, 48)
        mkeys = jnp.concatenate([keys, keys[:16]])
        t0 = mv.create(192, window=8)
        t0, _ = mv.insert(t0, mkeys, jnp.arange(64, dtype=jnp.uint32))
        cap = int(jnp.sum(mv.count_values(t0, keys)))
        default = _compiled_text(lambda t, k: mv.retrieve_all(t, k, cap),
                                 t0, keys)
        off = _compiled_text(
            lambda t, k: mv.retrieve_all(t, k, cap, stats=False), t0, keys)
        on = _compiled_text(
            lambda t, k: mv.retrieve_all(t, k, cap, stats=True), t0, keys)
        assert default == off
        assert default != on
        ca, cb = hlo_census.census(default), hlo_census.census(off)
        assert ca.bytes_moved == cb.bytes_moved and ca.flops == cb.flops

    def test_compacted_retrieve_stats_off_identical(self, rng):
        # a batch large enough for the probe walk's compacted tail
        n = bulk._SHRINK * probing.COMPACT_LANES
        keys, vals = _keys_vals(rng, n)
        t0 = sv.create(n + n // 8, window=8)
        t0, _ = sv.insert(t0, keys, vals)
        default = _compiled_text(lambda t, k: sv.retrieve(t, k), t0, keys)
        off = _compiled_text(lambda t, k: sv.retrieve(t, k, stats=False),
                             t0, keys)
        on = _compiled_text(lambda t, k: sv.retrieve(t, k, stats=True),
                            t0, keys)
        assert default == off
        assert default != on
        ca, cb = hlo_census.census(default), hlo_census.census(off)
        assert ca.bytes_moved == cb.bytes_moved and ca.flops == cb.flops


# ---------------------------------------------------------------------------
# 4. host-side plumbing: registry / tracer / report / schema
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = Registry()
        r.counter("c").inc()
        r.counter("c").inc(4)
        r.gauge("g").set(2.5)
        for v in (0.1, 0.2, 0.3):
            r.histogram("h").record(v)
        snap = r.snapshot()
        assert snap["c"] == 5.0 and snap["g"] == 2.5
        assert snap["h"]["count"] == 3
        assert abs(r.histogram("h").percentile(50) - 0.2) < 1e-9
        assert "c: 5" in r.render()

    def test_kind_rebinding_raises(self):
        r = Registry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_tracer_values_are_noops_under_jit(self):
        r = Registry()

        @jax.jit
        def f(x):
            r.counter("jit.c").inc(x)          # x is a tracer here
            r.gauge("jit.g").set(x)
            return x + 1

        f(jnp.ones(()))
        assert r.counter("jit.c").value == 0.0
        assert np.isnan(r.gauge("jit.g").value)
        r.counter("jit.c").inc(jnp.asarray(3.0))   # concrete: records
        assert r.counter("jit.c").value == 3.0

    def test_kv_cache_counters(self):
        from repro.serving import kv_cache as pkv
        alloc0 = REGISTRY.counter("kv_cache.pages_allocated").value
        evict0 = REGISTRY.counter("kv_cache.pages_evicted").value
        c = pkv.create(num_layers=1, num_pages=16, page_size=4,
                       num_kv_heads=1, head_dim=4)
        seq = jnp.asarray([1, 2], jnp.int32)
        c, _, _ = pkv.allocate_pages(c, seq, jnp.zeros((2,), jnp.int32))
        c, _ = pkv.free_sequences(c, seq[:1], max_pages=2)
        assert REGISTRY.counter("kv_cache.pages_allocated").value == alloc0 + 2
        assert REGISTRY.counter("kv_cache.pages_evicted").value == evict0 + 1


class TestTracer:
    def test_spans_jsonl_and_percentiles(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tr = obtrace.Tracer(registry=Registry(), jsonl_path=path)
        with tr.span("unit.work", idx=0):
            pass
        with tr.span("unit.work", idx=1):
            pass
        tr.event("unit.marker", note=1)
        tr.close()
        events = obtrace.load_events(path)
        assert [e["event"] for e in events] == ["unit.work", "unit.work",
                                               "unit.marker"]
        for e in events:
            assert obtrace.is_event(e)
            obtrace.validate_event(e)
        p = tr.percentiles("unit.work")
        assert p["count"] == 2 and p["p50_s"] >= 0.0

    def test_disabled_tracer_is_silent(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tr = obtrace.Tracer(registry=Registry(), jsonl_path=path,
                            enabled=False)
        with tr.span("nope"):
            pass
        tr.event("nope")
        tr.close()
        assert not (tmp_path / "t.jsonl").exists()

    def test_pipeline_stage_spans(self, tmp_path):
        from repro.data import pipeline as dp
        cfg = dp.DataConfig(vocab_size=64, seq_len=16, global_batch=4, seed=1)
        toks = dp.synthetic_batch(cfg, 0)["tokens"]
        table = counting.create(512)
        path = str(tmp_path / "pipe.jsonl")
        tr = obtrace.Tracer(registry=Registry(), jsonl_path=path)
        tracked = jnp.asarray([3, 7, 11], jnp.uint32)
        _, keep, hits = dp.relational_stage(table, toks, tracked, tracer=tr)
        tr.close()
        names = {e["event"] for e in obtrace.load_events(path)}
        assert names == {"pipeline.dedup", "pipeline.join",
                         "pipeline.aggregate"}
        # traced run computes the same outputs as the untraced one
        _, keep2, hits2 = dp.relational_stage(counting.create(512), toks,
                                              tracked)
        assert bool(jnp.array_equal(keep, keep2))
        assert bool(jnp.array_equal(hits, hits2))


class TestReportGuards:
    def test_load_skips_malformed_and_trace_lines(self, tmp_path):
        from repro.launch import report
        p = tmp_path / "recs.jsonl"
        lines = [
            {"arch": "a", "shape": "s", "mesh": "2x2", "kind": "fwd"},
            {"event": "serve.decode_step", "t_s": 0.0, "dur_s": 0.001},
            {"arch": "b"},                              # missing identity
            {"arch": "a", "shape": "s", "mesh": "4x4", "chips": 16,
             "compile_s": 1.0, "roofline": {
                 "flops_per_device": 1e9, "bytes_per_device": 1e6,
                 "wire_bytes": 0.0, "collectives": {},
                 "compute_s": 0.1, "memory_s": 0.2, "collective_s": 0.0,
                 "bottleneck": "memory", "model_flops": 1e9,
                 "useful_ratio": 0.5}},
        ]
        p.write_text("\n".join(json.dumps(r) for r in lines) + "\n")
        recs = report.load(str(p))
        assert len(recs) == 2
        assert report.meshes(recs) == ["2x2", "4x4"]
        # record without roofline/compile_s renders with placeholders
        table = report.dryrun_table(recs)
        assert "—" in table and "KeyError" not in table
        rt = report.roofline_table(recs, "4x4")
        assert "memory" in rt

    def test_table_metrics_section(self, tmp_path):
        from repro.launch import report
        bench = {"fig5": [
            {"name": "fig5.insert.wc-cops.rho0.5", "us_per_call": 10.0,
             "ops_per_s": 1e8, "probe_len_p50": 1.0, "probe_len_p99": 2.0,
             "load_factor": 0.5, "pct_of_roofline": 7.5, "spread": 0.05},
            {"name": "fig5.insert.pydict", "us_per_call": 50.0},
        ]}
        p = tmp_path / "BENCH_t.json"
        p.write_text(json.dumps(bench))
        sec = report.table_metrics_section(str(p))
        assert "fig5.insert.wc-cops.rho0.5" in sec
        assert "fig5.insert.pydict" not in sec          # no metric cols


class TestBenchSchema:
    def test_valid_bench_passes(self):
        from benchmarks import validate
        with open(validate.default_schema_path()) as f:
            schema = json.load(f)
        bench = {"fig5": [{"name": "r", "us_per_call": 1.0,
                           "ops_per_s": 2e6, "extra": "ok=1",
                           "load_factor": 0.9, "probe_len_p99": 4.0}]}
        assert validate.validate(bench, schema) == []

    def test_invalid_rows_fail(self):
        from benchmarks import validate
        with open(validate.default_schema_path()) as f:
            schema = json.load(f)
        missing = {"fig5": [{"us_per_call": 1.0}]}
        assert any("missing required" in e
                   for e in validate.validate(missing, schema))
        bad_type = {"fig5": [{"name": "r", "us_per_call": "fast"}]}
        assert any("expected number" in e
                   for e in validate.validate(bad_type, schema))
        bad_range = {"fig5": [{"name": "r", "us_per_call": 1.0,
                               "load_factor": 1.5}]}
        assert any("maximum" in e
                   for e in validate.validate(bad_range, schema))
        stray = {"fig5": [{"name": "r", "us_per_call": 1.0,
                           "custom": "not-a-number"}]}
        assert any("expected number" in e
                   for e in validate.validate(stray, schema))

    def test_parse_row_lifts_numeric_extras(self):
        from benchmarks.run import parse_row
        e = parse_row("fig5.x,12.5,8.00Mops/s,ok=1,probe_len_p99=4,note=abc")
        assert e["ops_per_s"] == 8e6
        assert e["probe_len_p99"] == 4.0
        assert "note" not in e and "note=abc" in e["extra"]


class TestServeLoopTraced:
    def test_generate_traced_records_latencies(self):
        from repro import configs
        from repro.models import model_zoo as zoo
        from repro.serving import serve_loop
        cfg = configs.get_smoke_config("smollm-360m")
        model = zoo.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
        tr = obtrace.Tracer(registry=Registry())
        toks, tr = serve_loop.generate_traced(model, params, prompts, 5,
                                              tracer=tr)
        assert toks.shape == (2, 5)
        p = tr.percentiles("serve.decode_step")
        assert p["count"] == 5
        assert tr.percentiles("serve.prefill")["count"] == 1
        assert p["p50_s"] >= 0.0 and p["p99_s"] >= p["p50_s"]
        # traced decode == the scan-path generate (same sampling rule)
        import dataclasses as _dc
        ref = serve_loop.generate(_dc.replace(model, prefill=None), params,
                                  prompts, 5)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))
