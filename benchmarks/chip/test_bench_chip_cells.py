"""CPU rehearsal of the chip benchmark at tiny sizes.

Each cell's unit runs under ``JAX_PLATFORMS=cpu`` with its configuration
cut to a few hundred pairs (``dist4_build``, and a sharded lookup, on four
virtual devices, in a child process), and the result line is checked for
form.  The timed path is
broken underneath in each way the cell can be, and ``correct`` has to come
out false; so does the control.  These call ``harness.run_cell`` directly,
past ``run.py``'s look for a TPU, which has tests of its own here.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import bytes as floors
import control
import harness
import program
import traffic
from repro.core import counting, distributed, single_value
from repro.obs import metrics

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PAIRS = 512
SEED = 2**31 + 12345
CPU_PEAK = {"hbm_bytes_per_s": 1.0}       # a stand-in; no device number
ONE_CHIP = ("sv_build", "sv_lookup")


def make_root(dest: Path, pairs: int = PAIRS) -> Path:
    """A checkout holding the benchmark with every configuration cut to
    ``pairs`` pairs per chip, and every mix to batches of at most 256
    keys, 4 batches."""
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(HERE, dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*",
                                                  "testdata"))
    for f in (dest / "benchmarks" / "chip" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["pairs_per_chip"] = pairs
        f.write_text(json.dumps(cfg))
    for f in (dest / "benchmarks" / "chip" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        if mix["keys_per_call"] != "table":
            mix["keys_per_call"] = min(mix["keys_per_call"], 256)
            mix["calls"] = min(mix["calls"], 4)
        f.write_text(json.dumps(mix))
    return dest


def run(root: Path, name: str, trace: bool = False, seed: int = SEED):
    cell = harness.load_cell(root, name, root / "benchmarks" / "chip")
    return harness.run_cell(cell, seed, 0.0, trace, jax.devices(), CPU_PEAK,
                            time.perf_counter(), root / ".bench_trace" / name)


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)


def _assert_line(line: dict, cell) -> None:
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    json.dumps(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == cell.chips
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("name", ONE_CHIP)
def test_cell_runs_and_is_correct(root, name):
    cell = harness.load_cell(root, name, root / "benchmarks" / "chip")
    line = run(root, name)
    _assert_line(line, cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == set(cell.end_to_end)
    for m in line["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("name", ONE_CHIP)
def test_traced_run_reports_per_layer_metrics(root, name):
    cell = harness.load_cell(root, name, root / "benchmarks" / "chip")
    line = run(root, name, trace=True)
    _assert_line(line, cell)
    assert set(line["metrics"]) == set(cell.per_layer)
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert line["breakdown"]["device_ops"]
    assert {g[0] for g in line["breakdown"]["idle_gaps"]} <= {
        "bench.window", "bench.dispatch", "bench.wait"}


def test_added_config_mix_and_metric_are_picked_up(root):
    """A later PR adds a configuration, a mix and metrics as files and
    entries only; no code changes.  A split name (``device_idle.sv16``)
    is read by its quantity's reader."""
    bench_dir = root / "benchmarks" / "chip"
    cfg = json.loads((bench_dir / "configs" / "sv_u32_w32.json").read_text())
    cfg.update(name="sv_u32_w16", window=16, load=0.5)
    (bench_dir / "configs" / "sv_u32_w16.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "half_absent.json").write_text(json.dumps(
        {"op": "retrieve", "keys_per_call": 256, "calls": 3,
         "present_share": 0.5, "order": "uniform"}))
    (bench_dir / "metrics" / "lookup.calls.py").write_text(
        "def read(run):\n    return run['window']['calls']\n")
    (bench_dir / "metrics" / "lookup_p95_ms.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n    return 1e3 * float("
        "np.percentile(run['window']['latencies_s'], 95))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "sv_u32_w16", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/chip/configs/sv_u32_w16.json"})
    bench["workloads"].append({
        "name": "sv16_absent", "config": "sv_u32_w16",
        "traffic": "half_absent", "chips": 1, "why": "test"})
    bench["end_to_end"].append({
        "name": "lookup_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": ["sv16_absent"]})
    for m in bench["per_layer"]:
        if m["name"] == "walk.probe_windows":
            m["workloads"].append("sv16_absent")
    for name, unit in (("lookup.calls", "calls"), ("device_idle.sv16", "%")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "host_clock", "layer": "device",
            "moves": "lookup_p95_ms", "workloads": ["sv16_absent"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = run(root, "sv16_absent")
    assert line["correct"] is True
    assert line["attempted"] == 3 * 256      # a window of 0 s: one round
    assert set(line["metrics"]) == {"lookup_p95_ms", "setup_s"}
    line = run(root, "sv16_absent", trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"lookup.calls", "walk.probe_windows",
                                    "device_idle.sv16"}
    assert line["metrics"]["lookup.calls"]["value"] == 3


def test_a_split_metric_is_read_by_its_quantity(root):
    bench_dir = root / "benchmarks" / "chip"
    for name in ("device_idle.build", "device_idle.lookup"):
        assert harness.reader_path(bench_dir, name).name == "device_idle.py"
    assert harness.reader_path(
        bench_dir, "walk.probe_windows").name == "walk.probe_windows.py"
    with pytest.raises(harness.CellError):
        harness.reader_path(bench_dir, "no_such.metric")


# A table kind that no file of the benchmark names: a counting table built
# from every key repeated ``multiplicity`` times, judged by each key's count.
COUNT_UNIT = '''"""A whole build of a counting table from repeated keys."""

import jax
import jax.numpy as jnp

import harness
import traffic
from repro.core import counting


def make(cell, devices, seed):
    cfg = cell.config
    n, r = cfg["keys"], cfg["multiplicity"]
    distinct = traffic.keys_of(jnp.arange(n, dtype=jnp.uint32))
    keys = jax.random.permutation(traffic.rng(seed), jnp.tile(distinct, r))

    def build(keys):
        table = counting.create(round(n / cfg["load"]), window=cfg["window"])
        return counting.insert(table, keys)[0]

    build, foot = harness.compile_unit("build", jax.jit(build), keys)
    return Unit(build, foot, keys, distinct, r)


class Unit:
    slots, floor, tally = 1, 0, None

    def __init__(self, build, foot, keys, distinct, r):
        self.build, self.keys = build, keys
        self.distinct, self.r = distinct, r
        self.footprints = [foot]
        self.per_call = keys.shape[0]

    def call(self, _slot):
        return self.build(self.keys)

    def stats(self):
        return None

    def check(self, w, seed):
        got = counting.counts(w.kept[0], self.distinct)
        wrong = int(jnp.sum(got != self.r))
        return harness.limit0({"counts_wrong": wrong}), 0
'''


def _tree(root: Path) -> dict:
    return {f: f.read_bytes() for f in root.rglob("*") if f.is_file()}


def test_added_table_kind_is_files_only(root, monkeypatch):
    """A later PR adds a table kind and an op as one unit file, a
    configuration, a mix, a reader and entries; no existing file but
    ``BENCHMARK.json`` changes, and the kind runs, is judged, and has no
    control."""
    bench_dir = root / "benchmarks" / "chip"
    before = _tree(root)
    (bench_dir / "units" / "occurrence_counter.count_all.py").write_text(
        COUNT_UNIT)
    (bench_dir / "configs" / "occ_r3.json").write_text(json.dumps(
        {"name": "occ_r3", "table": "occurrence_counter", "chips": 1,
         "keys": 128, "multiplicity": 3, "load": 0.5, "window": 32}))
    (bench_dir / "traffic" / "repeated.json").write_text(json.dumps(
        {"op": "count_all"}))
    (bench_dir / "metrics" / "count_rate.py").write_text(
        "def read(run):\n    w = run['window']\n"
        "    return w['keys'] / w['elapsed_s']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "occ_r3", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/chip/configs/occ_r3.json"})
    bench["workloads"].append({"name": "occ_build", "config": "occ_r3",
                               "traffic": "repeated", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({
        "name": "count_rate", "unit": "keys/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["occ_build"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _tree(root)
    assert {f for f in before if before[f] != after[f]} == {
        root / "BENCHMARK.json"}

    line = run(root, "occ_build")
    assert line["correct"] is True
    assert line["attempted"] == 3 * 128
    assert set(line["metrics"]) == {"count_rate", "setup_s"}
    assert line["checks"] == {"counts_wrong": {"value": 0, "limit": 0}}

    real = counting.insert
    monkeypatch.setattr(counting, "insert", lambda table, keys, mask=None,
                        stats=False: real(table, keys[:keys.shape[0] // 2]))
    line = run(root, "occ_build")
    assert line["correct"] is False
    assert line["checks"]["counts_wrong"]["value"] > 0

    cell = harness.load_cell(root, "occ_build", bench_dir)
    with pytest.raises(harness.CellError, match="no control"):
        control.readings(cell, SEED, jax.devices(), control.CONTROL_PROBES)


@pytest.mark.parametrize("field,value,unit", [
    ("table", "no_such_kind", "units/no_such_kind.insert.py"),
    ("op", "no_such_op", "units/single_value.no_such_op.py")])
def test_unknown_kind_or_op_names_its_unit_file(root, field, value, unit):
    bench_dir = root / "benchmarks" / "chip"
    path = bench_dir / ("configs/sv_u32_w32.json" if field == "table"
                        else "traffic/bulk_build.json")
    data = json.loads(path.read_text())
    data[field] = value
    path.write_text(json.dumps(data))
    with pytest.raises(harness.CellError, match=unit):
        harness.load_cell(root, "sv_build", bench_dir)


def test_stats_carry_every_table_stats_field():
    got = program.stats_dict(metrics.empty())
    assert set(got) == {f.name for f in dataclasses.fields(metrics.TableStats)}
    json.dumps(got)
    assert got["tail_rounds"] == 0 and got["load_factor"] == 0.0
    assert isinstance(got["probe_hist"], list)


# -- faults planted in the timed path ------------------------------------------

_insert, _retrieve = single_value.insert, single_value.retrieve


def _insert_unchanged(table, keys, values, mask=None, stats=False):
    return table, jnp.zeros(keys.shape[0], jnp.int32)


def _insert_half(table, keys, values, mask=None, stats=False):
    h = keys.shape[0] // 2
    table, _ = _insert(table, keys[:h], values[:h], None if mask is None
                       else mask[:h])
    return table, jnp.zeros(keys.shape[0], jnp.int32)


def _insert_altered(table, keys, values, mask=None, stats=False):
    return _insert(table, keys, values.at[0].add(1), mask)


def _insert_scattered(table, keys, values, mask=None, stats=False):
    """Every key and value stored, but in the row mirrored from its own:
    the same slots hold the same pairs, outside their probe windows."""
    table, status = _insert(table, keys, values, mask)
    store = {k: v[:, ::-1, :] for k, v in table.store.items()}
    return dataclasses.replace(table, store=store), status


def _retrieve_half(table, keys, stats=False):
    h = keys.shape[0] // 2
    vals, found = _retrieve(table, keys[:h])
    pad = keys.shape[0] - h
    return (jnp.concatenate([vals, jnp.zeros(pad, vals.dtype)]),
            jnp.concatenate([found, jnp.zeros(pad, bool)]))


def _retrieve_altered(table, keys, stats=False):
    vals, found = _retrieve(table, keys)
    return vals.at[0].add(1), found


FAULTS = {
    "state_unchanged": ("insert", _insert_unchanged),
    "half_batch_insert": ("insert", _insert_half),
    "value_altered_insert": ("insert", _insert_altered),
    "scattered_insert": ("insert", _insert_scattered),
    "half_batch_lookup": ("retrieve", _retrieve_half),
    "answer_altered_lookup": ("retrieve", _retrieve_altered),
}
CELL_FAULTS = [("sv_build", f) for f in ("state_unchanged",
                                         "half_batch_insert",
                                         "value_altered_insert",
                                         "scattered_insert")] + [
    ("sv_lookup", f) for f in ("half_batch_lookup", "answer_altered_lookup")]


@pytest.mark.parametrize("name,fault", CELL_FAULTS)
def test_fault_in_timed_path_is_not_correct(root, monkeypatch, name, fault):
    attr, fake = FAULTS[fault]
    monkeypatch.setattr(single_value, attr, fake)
    line = run(root, name)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_scattered_build_fails_the_read_back_alone(root, monkeypatch):
    """Slot contents cannot see keys stored outside their probe windows;
    reading every key back through the read path does."""
    monkeypatch.setattr(single_value, "insert", _insert_scattered)
    checks = run(root, "sv_build")["checks"]
    read = {k for k in checks if k.startswith("read_")}
    assert read == {"read_found_wrong", "read_values_wrong"}
    assert all(checks[k]["value"] == 0 for k in checks if k not in read)
    assert checks["read_found_wrong"]["value"] > PAIRS // 2


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(root, name):
    cell = harness.load_cell(root, name, root / "benchmarks" / "chip")
    sound = control.readings(cell, SEED, jax.devices())
    ctl = control.readings(cell, SEED, jax.devices(), control.CONTROL_PROBES)
    assert max(sound.values()) == 0
    assert max(ctl.values()) > 0


# -- four chips, on four virtual CPU devices ------------------------------------

def _dist_child(dest: str) -> None:
    """Run in a child with four virtual devices: the sound run, each fault
    and the control; print one JSON object."""
    root = make_root(Path(dest))
    out = {"devices": len(jax.devices()), "sound": run(root, "dist4_build")}

    def local_insert(table, keys, values, axis, slack=2.0, insert_fn=None):
        table, status = single_value.insert(table, keys, values)
        return table, status, jnp.zeros((), jnp.int32)

    for fault, (mod, attr, fake) in {
            "state_unchanged": (single_value, "insert", _insert_unchanged),
            "half_batch": (single_value, "insert", _insert_half),
            "value_altered": (single_value, "insert", _insert_altered),
            "scattered": (single_value, "insert", _insert_scattered),
            "exchange_left_out": (distributed, "insert_distributed",
                                  local_insert)}.items():
        real = getattr(mod, attr)
        setattr(mod, attr, fake)
        try:
            out[fault] = run(root, "dist4_build")
        finally:
            setattr(mod, attr, real)
    cell = harness.load_cell(root, "dist4_build", root / "benchmarks" / "chip")
    out["control"] = control.readings(cell, SEED, jax.devices(),
                                      control.CONTROL_PROBES)

    # the retrieve leg: the sharded table looked up through shard_retrieve
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "dist4_lookup",
                               "config": "dist4_sv_u32_w32",
                               "traffic": "bulk_lookup", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "lookup_rate":
            m["workloads"].append("dist4_lookup")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out["lookup_sound"] = run(root, "dist4_lookup")

    def local_retrieve(table, keys, axis, slack=2.0):
        return (*single_value.retrieve(table, keys), jnp.zeros((), jnp.int32))

    for fault, (mod, attr, fake) in {
            "lookup_half_batch": (single_value, "retrieve", _retrieve_half),
            "lookup_answer_altered": (single_value, "retrieve",
                                      _retrieve_altered),
            "lookup_exchange_left_out": (distributed, "retrieve_distributed",
                                         local_retrieve)}.items():
        real = getattr(mod, attr)
        setattr(mod, attr, fake)
        try:
            out[fault] = run(root, "dist4_lookup")
        finally:
            setattr(mod, attr, real)
    cell = harness.load_cell(root, "dist4_lookup",
                             root / "benchmarks" / "chip")
    out["lookup_control"] = control.readings(cell, SEED, jax.devices(),
                                             control.CONTROL_PROBES)
    print(json.dumps(out))


def test_dist4_build_on_four_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path[:0] = [sys.argv[1]]; "
            "import test_bench_chip_cells as t; t._dist_child(sys.argv[2])")
    proc = subprocess.run([sys.executable, "-c", code, str(HERE),
                           str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    sound = out["sound"]
    assert sound["correct"] is True and sound["device"]["count"] == 4
    assert sound["checks"]["misplaced"]["value"] == 0
    assert sound["checks"]["read_found_wrong"]["value"] == 0
    for fault in ("state_unchanged", "half_batch", "value_altered",
                  "scattered", "exchange_left_out"):
        assert out[fault]["correct"] is False, fault
    assert out["exchange_left_out"]["checks"]["misplaced"]["value"] > 0
    assert out["scattered"]["checks"]["read_found_wrong"]["value"] > 0
    assert max(out["control"].values()) > 0
    sound = out["lookup_sound"]
    assert sound["correct"] is True and sound["device"]["count"] == 4
    assert set(sound["metrics"]) == {"lookup_rate", "setup_s"}
    assert sound["attempted"] == 4 * PAIRS
    for fault in ("lookup_half_batch", "lookup_answer_altered",
                  "lookup_exchange_left_out"):
        assert out[fault]["correct"] is False, fault
    assert out["lookup_exchange_left_out"]["checks"]["found_wrong"][
        "value"] > 0
    assert max(out["lookup_control"].values()) > 0


def test_seed_draws_order_and_values_not_the_work():
    mix = {"keys_per_call": 64, "calls": 8, "present_share": 0.75,
           "order": "uniform"}
    a, b = (traffic.query_indices(mix, 1000, s) for s in (1, 2**31 + 9))
    assert (a != b).any()
    assert sorted(map(sorted, a.tolist())) == sorted(map(sorted, b.tolist()))
    assert int((a < 1000).sum()) == 8 * 48
    (ka, va), (kb, vb) = (traffic.pairs(100, traffic.salt(s)) for s in (1, 2))
    assert (ka == kb).all() and (va != vb).all()
    assert len(set(ka.tolist())) == 100
    pairs = jax.jit(traffic.pairs, static_argnums=0)
    for s in (1, 2**33 + 5):                # a new seed compiles nothing
        pairs(100, traffic.salt(s))
    assert pairs._cache_size() == 1


# -- peaks, byte floors and the command's refusals ------------------------------

def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.CellError):
        harness.load_peak("TPU v99 imaginary")
    assert harness.load_peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_byte_floors_match_hand_counts():
    # 1000 u32 pairs into 1200 slots: 1200 * 4 B of empty keys written;
    # per key 8 B read, a 4 B status and the 8 B slot written
    assert floors.insert_floor(1000, 1200) == 4800 + 1000 * 20
    # 700 present, 300 absent: per query 4 B key read, 4 B value and a
    # 1 B flag written; each present key's 8 B slot read
    assert floors.lookup_floor(700, 300) == 1000 * 9 + 700 * 8
    # two-word keys and values
    assert floors.insert_floor(10, 20, 2, 2) == 20 * 8 + 10 * 36


def _command(cwd: Path, *extra: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "sv_build",
         "--seed", "1", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_command_refuses_a_cpu():
    proc = _command(ROOT)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_refuses_a_checkout_without_the_library(tmp_path):
    make_root(tmp_path)
    proc = _command(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
