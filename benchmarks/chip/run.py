#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload sv_build --seed 7 \\
        --seconds 10 --trace 0

Run from the root of a checkout that holds ``BENCHMARK.json``.  The run
holds every chip it uses in this one process.  It exits non-zero, with no
result line, when JAX finds no TPU, when the device kind has no entry in
``peaks.json``, when there are fewer chips than the cell asks for, or when
the library (``src/repro``) is not in the checkout.

Earlier lines (standard error) give sizes, compile seconds, compiled
memory, set-up seconds, the window and each number compared with its
limit.  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each compared number with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"run.py: no library under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(src), str(HERE)]

    import jax
    import harness
    from repro.launch import compile_cache

    try:
        cell = harness.load_cell(ROOT, args.workload)
    except harness.CellError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU; JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    try:
        peak = harness.load_peak(devices[0].device_kind)
    except harness.CellError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.configure()}", file=sys.stderr)
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            devices, peak, T_START, TRACE_DIR / cell.name)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
