"""One run of one benchmark cell, found by name.

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration and a
traffic mix; ``configs/<config>.json`` holds the table's sizes and
guarantees, ``traffic/<traffic>.json`` the mix, ``units/<table>.<op>.py``
the timed unit of the configuration's ``table`` kind under the mix's
``op`` (``unit_path``), and ``metrics/<metric>.py`` one reader per metric
(or per quantity, ``reader_path``).  Adding a cell, a configuration, a
mix, a table kind, an op or a metric adds files and entries; no code here
names one.

A run: the unit file's ``make`` makes the inputs on the device from the
seed and compiles the unit (for a lookup, after building its table); one
warm-up unit runs; then whole units are timed back to back for
``seconds`` (the window ends with the first unit that finishes after it
and completes a round over the unit's distinct inputs), every retained
answer is judged by the unit's ``check`` against the plain reference, and
the cell's metrics are read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import inspect
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax

import trace_reduce

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
    unit_file: Path
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
    unit_file = unit_path(bench_dir, config["table"], mix["op"])
    return Cell(name, int(w["chips"]), config, mix, e2e, layer, units,
                unit_file, bench_dir)


def unit_path(bench_dir: Path, table: str, op: str) -> Path:
    """``units/<table>.<op>.py``: the timed unit of a configuration's
    ``table`` kind under a mix's ``op``.  A kind or op with no file is an
    error that names the file looked for."""
    path = bench_dir / "units" / f"{table}.{op}.py"
    if not path.is_file():
        raise CellError(f"no unit for table {table!r} and op {op!r}: "
                        f"{path} is missing")
    return path


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_unit(cell: Cell, devices, seed: int, max_probes: int | None = None):
    """The cell's timed unit: ``make(cell, devices, seed)`` of its unit
    file, which makes the inputs on the device and compiles (and, where
    the unit needs one, builds its table).  The unit has ``slots`` and
    ``per_call``, ``floor`` (the byte floor of one unit, ``bytes.py``),
    ``footprints`` (compiled footprints), ``call(slot)``, ``tally`` (a
    function of one call's output, or None), ``stats()`` (a dict of plain
    numbers, or None) and ``check(window, seed) -> (checks, failed)``.

    ``max_probes`` asks for the control (``control.py``); a unit file
    whose ``make`` takes no ``max_probes`` has none."""
    path = cell.unit_file
    make = _load(path, f"unit_{path.stem}").make
    if max_probes is None:
        return make(cell, devices, seed)
    if "max_probes" not in inspect.signature(make).parameters:
        raise CellError(f"{path} has no control: its make takes no "
                        "max_probes")
    return make(cell, devices, seed, max_probes=max_probes)


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
    return _load(reader_path(bench_dir, name), f"metric_{name}").read(run)


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

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compile_unit(name: str, fn, *args):
    """(compiled ``fn`` for ``args``, its compiled footprint in bytes), with
    the compile's seconds and memory logged."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    foot = 0
    if m is not None:
        foot = (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        log(f"compile {name}: {secs:.2f} s; temp_bytes="
            f"{m.temp_size_in_bytes} argument_bytes="
            f"{m.argument_size_in_bytes} output_bytes="
            f"{m.output_size_in_bytes}")
    else:
        log(f"compile {name}: {secs:.2f} s; memory unreported")
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
    devices, op = list(devices)[:cell.chips], cell.traffic["op"]
    log(f"cell {cell.name}: {cell.unit_file.name} chips={cell.chips} "
        f"seed={seed}")
    unit = make_unit(cell, devices, seed)

    jax.block_until_ready(unit.call(0))                  # warm-up unit
    setup_s = time.perf_counter() - t_start
    log(f"setup_s: {setup_s:.4f}")

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
    log(f"window: {w.calls} calls of {unit.per_call} keys in "
        f"{w.elapsed_s:.4f} s; slowest calls (index, ms): "
        f"{[(i, round(w.latencies_s[i] * 1e3, 3)) for i in slowest]}")
    memory_peak = _memory_peak(devices, unit.footprints)

    reduced = stats = None
    if trace:
        reduced = trace_reduce.reduce(
            trace_reduce.find(trace_dir), SPAN,
            host_ops=devices[0].platform == "cpu")
        shutil.rmtree(trace_dir, ignore_errors=True)
        stats = unit.stats()
    checks, failed = unit.check(w, seed)

    run = {"cell": cell.name, "op": op, "chips": cell.chips,
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
        log(f"check {name}: {c['value']} limit {c['limit']}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
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


def limit0(values: dict) -> dict:
    """Exact comparisons: every count of disagreements has the limit 0."""
    return {k: {"value": int(v), "limit": 0} for k, v in values.items()}
