"""One run of one benchmark cell, found by name.

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration and a
traffic mix; ``configs/<config>.json`` holds the table's sizes and
guarantees, ``traffic/<traffic>.json`` the mix, and ``metrics/<metric>.py``
one reader per metric (or per quantity, ``reader_path``).  Adding a cell,
a configuration, a mix or a metric adds files and entries; no code here
names one.

A run: make the inputs on the device from the seed, compile and warm up
the unit, time whole units back to back for ``seconds`` (the window ends
with the first unit that finishes after it and completes a round over the
mix's distinct query batches), then judge every retained answer against
``reference`` (a build also through the library's read path, untimed) and
read the cell's metrics.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

import bytes as floors
import program
import reference
import trace_reduce
import traffic

HERE = Path(__file__).resolve().parent
SPAN = "bench."


class CellError(RuntimeError):
    """The cell cannot run as declared (unknown name, bad data file)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]
    units: dict[str, str]
    bench_dir: Path = HERE


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise CellError(f"{path}: {e}") from e


def load_cell(root: Path, name: str, bench_dir: Path = HERE) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its data files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    mix = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if int(config["chips"]) != int(w["chips"]):
        raise CellError(f"{name}: config {w['config']} is for "
                        f"{config['chips']} chips, the cell asks {w['chips']}")

    def mine(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m["name"] for m in bench["end_to_end"] if mine(m) is not False]
    layer = [m["name"] for m in bench["per_layer"]
             if mine(m) or (mine(m) is None and m["moves"] in e2e)]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    return Cell(name, int(w["chips"]), config, mix, e2e, layer, units,
                bench_dir)


def load_peak(device_kind: str, bench_dir: Path = HERE) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    peaks = _read_json(bench_dir / "peaks.json")
    if device_kind not in peaks:
        raise CellError(f"device kind {device_kind!r} is not in peaks.json")
    return peaks[device_kind]


def reader_path(bench_dir: Path, name: str) -> Path:
    """``metrics/<name>.py``; for a name with no file of its own, the file
    of its longest dotted prefix: ``device_idle.build`` and
    ``device_idle.lookup`` are one quantity, named apart by the end-to-end
    metric each moves, and ``metrics/device_idle.py`` reads both."""
    parts = name.split(".")
    for end in range(len(parts), 0, -1):
        path = bench_dir / "metrics" / (".".join(parts[:end]) + ".py")
        if path.is_file():
            return path
    raise CellError(f"no reader for {name!r} under {bench_dir / 'metrics'}")


def read_metric(bench_dir: Path, name: str, run: dict):
    """``read(run)`` of the metric's reader (``reader_path``); None when it
    finds nothing to read in this run."""
    path = reader_path(bench_dir, name)
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Window:
    calls: int = 0
    elapsed_s: float = 0.0
    latencies_s: list = field(default_factory=list)
    kept: dict = field(default_factory=dict)      # call slot -> last output
    tallies: list = field(default_factory=list)   # tally(output) per call


def _span(tracing: bool, name: str):
    return (jax.profiler.TraceAnnotation(SPAN + name) if tracing
            else contextlib.nullcontext())


def run_window(call, slots: int, seconds: float, tracing: bool = False,
               tally=None) -> Window:
    """Closed loop, one caller: ``call(i % slots)`` in whole rounds over
    the ``slots`` distinct inputs, until ``seconds`` have passed (at least
    one round).

    Each call is timed from dispatch to ``block_until_ready``; the last
    output of each slot is kept for the check, and ``tally(output)`` of
    every call where given."""
    w = Window()
    with _span(tracing, "window"):
        t0 = time.perf_counter()
        while True:
            slot = w.calls % slots
            ts = time.perf_counter()
            with _span(tracing, "dispatch"):
                out = call(slot)
            with _span(tracing, "wait"):
                jax.block_until_ready(out)
            te = time.perf_counter()
            w.latencies_s.append(te - ts)
            w.kept[slot] = out
            if tally is not None:
                w.tallies.append(tally(out))
            w.calls += 1
            if te - t0 >= seconds and w.calls % slots == 0:
                break
    w.elapsed_s = te - t0
    return w


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _compile(name: str, fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    foot = 0
    if m is not None:
        foot = (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        _log(f"compile {name}: {secs:.2f} s; temp_bytes="
             f"{m.temp_size_in_bytes} argument_bytes="
             f"{m.argument_size_in_bytes} output_bytes="
             f"{m.output_size_in_bytes}")
    else:
        _log(f"compile {name}: {secs:.2f} s; memory unreported")
    return compiled, foot


def _memory_peak(devices, footprints) -> int:
    """The larger of the allocator's peak on the fullest chip and the
    largest compiled footprint (the allocator may not count XLA's
    temporaries)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks + list(footprints)))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, t_start: float, trace_dir: Path | None = None
             ) -> dict:
    """Run ``cell`` once; return the contract line as a dict."""
    cfg, mix = cell.config, cell.traffic
    prog = program.Program(cfg, devices)
    devices, n, op = prog.devices, prog.pairs, mix["op"]
    _log(f"cell {cell.name}: {op} pairs={n} chips={prog.chips} "
         f"slots_per_chip={prog.capacity_per_chip} seed={seed}")

    pairs = jax.jit(traffic.pairs, static_argnums=0,
                    out_shardings=prog.batch_sharding)
    keys, values = pairs(n, traffic.salt(seed))
    build, build_foot = _compile("build", prog.build_fn(), keys, values)
    footprints = [build_foot]
    if op == "insert":
        unit = BuildUnit(prog, build, keys, values)
    elif op == "retrieve":
        unit = LookupUnit(prog, build, keys, values, mix, seed)
        del keys, values
        footprints.append(unit.footprint)
    else:
        raise CellError(f"unknown op {op!r}")

    jax.block_until_ready(unit.call(0))                  # warm-up unit
    setup_s = time.perf_counter() - t_start
    _log(f"setup_s: {setup_s:.4f}")

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_reduce.options())
    try:
        w = run_window(unit.call, unit.slots, seconds, trace, unit.tally)
    finally:
        if trace:
            jax.profiler.stop_trace()
    slowest = sorted(range(w.calls), key=lambda i: -w.latencies_s[i])[:3]
    _log(f"window: {w.calls} calls of {unit.per_call} keys in "
         f"{w.elapsed_s:.4f} s; slowest calls (index, ms): "
         f"{[(i, round(w.latencies_s[i] * 1e3, 3)) for i in slowest]}")
    memory_peak = _memory_peak(devices, footprints)

    reduced = stats = None
    if trace:
        reduced = trace_reduce.reduce(
            trace_reduce.find(trace_dir), SPAN,
            host_ops=devices[0].platform == "cpu")
        shutil.rmtree(trace_dir, ignore_errors=True)
        stats = unit.stats()
    checks, failed = unit.check(w, seed)

    run = {"cell": cell.name, "op": op, "chips": prog.chips,
           "setup_s": setup_s, "peak": peak, "trace": reduced,
           "stats": stats, "floor_bytes": unit.floor * w.calls,
           "window": {"calls": w.calls, "keys": unit.per_call * w.calls,
                      "elapsed_s": w.elapsed_s,
                      "latencies_s": w.latencies_s}}
    metrics = {}
    for name in (cell.per_layer if trace else cell.end_to_end):
        value = read_metric(cell.bench_dir, name, run)
        if value is None and not trace:
            raise CellError(f"{cell.name}: no value for {name}")
        if value is not None:
            metrics[name] = {"value": float(value), "unit": cell.units[name]}

    for name, c in checks.items():
        _log(f"check {name}: {c['value']} limit {c['limit']}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": prog.chips, "memory_peak_bytes": memory_peak}
    line = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": unit.per_call * w.calls, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return line


def _limit0(values: dict) -> dict:
    """Exact comparisons: every count of disagreements has the limit 0."""
    return {k: {"value": int(v), "limit": 0} for k, v in values.items()}


class BuildUnit:
    """A unit is a whole build: create a fresh table, insert every pair."""

    slots = 1

    def __init__(self, prog, build, keys, values):
        self.prog, self.build = prog, build
        self.keys, self.values = keys, values
        self.per_call = prog.pairs
        cfg = prog.cfg
        self.floor = floors.insert_floor(
            prog.pairs, prog.capacity_per_chip * prog.chips,
            cfg["key_words"], cfg["value_words"])

    def call(self, _slot):
        return self.build(self.keys, self.values)

    @staticmethod
    def tally(out):
        return out[1]              # per shard: [inserted, full, overflow]

    def stats(self):
        fn = self.prog.build_stats_fn()
        return None if fn is None else _stats_dict(fn(self.keys, self.values))

    def check(self, w: Window, seed: int):
        tallies = np.asarray(jax.device_get(w.tallies)).reshape(-1, 3)
        refused = int(len(w.tallies) * self.per_call - tallies[:, 0].sum())
        table = w.kept[0][0]
        slot_keys, slot_values, count = program.Program.slots(table)
        home = self.prog.devices[0]
        got = reference.table_disagreements(
            jax.device_put(slot_keys, home), jax.device_put(slot_values, home),
            jax.device_put(count, home), traffic.salt(seed),
            n=self.prog.pairs)
        got = dict(jax.device_get(got))
        got.update(self._read_back(table, seed))
        got["refused"] = refused
        if self.prog.chips > 1:
            got["overflow"] = int(tallies[:, 2].sum())
            got["misplaced"] = int(self.prog.misplaced_fn()(table))
        return _limit0(got), refused


    def _read_back(self, table, seed: int) -> dict:
        """Every inserted key and ``n // 64`` absent ones, read from the
        window's last table through the library's own read path (after the
        window, untimed), against the truth: a key stored outside the slots
        its probe sequence reaches reads as missing."""
        prog = self.prog
        idx = traffic.readback_indices(prog.pairs)
        keys = jax.jit(traffic.keys_of,
                       out_shardings=prog.batch_sharding)(idx)
        t0 = time.perf_counter()
        vals, found = jax.block_until_ready(prog.readback_fn()(table, keys))
        _log(f"read-back: {idx.shape[0]} keys in "
             f"{time.perf_counter() - t0:.2f} s (compile included)")
        home = prog.devices[0]
        got = reference.answer_disagreements(
            jax.device_put(idx, home), jax.device_put(vals, home),
            jax.device_put(found, home), traffic.salt(seed), n=prog.pairs)
        return {"read_" + k: int(v) for k, v in jax.device_get(got).items()}


class LookupUnit:
    """The table is built in set-up; a unit is one lookup call."""

    def __init__(self, prog, build, keys, values, mix, seed):
        self.prog = prog
        self.table, _ = jax.block_until_ready(build(keys, values))
        n = prog.pairs
        self.idx = traffic.query_indices(mix, n, seed)
        self.slots, self.per_call = traffic.batch_shape(mix, n)
        self.batches = [traffic.keys_of(self.idx[i])
                        for i in range(self.slots)]
        self.lookup, self.footprint = _compile(
            "lookup", prog.lookup_fn(), self.table, self.batches[0])
        n_present = round(self.per_call * float(mix["present_share"]))
        cfg = prog.cfg
        self.floor = floors.lookup_floor(
            n_present, self.per_call - n_present, cfg["key_words"],
            cfg["value_words"])

    tally = None

    def call(self, slot):
        return self.lookup(self.table, self.batches[slot])

    def stats(self):
        fn = self.prog.lookup_stats_fn()
        return _stats_dict(fn(self.table, self.batches[0]))

    def check(self, w: Window, seed: int):
        total = {"found_wrong": 0, "values_wrong": 0}
        for slot, (vals, found) in w.kept.items():
            got = reference.answer_disagreements(
                self.idx[slot], vals, found, traffic.salt(seed),
                n=self.prog.pairs)
            for k, v in jax.device_get(got).items():
                total[k] += int(v)
        return _limit0(total), 0


def _stats_dict(s) -> dict:
    s = jax.device_get(s)
    return {"fixpoint_iters": int(s.fixpoint_iters),
            "probe_sum": int(s.probe_sum), "probe_n": int(s.probe_n)}
