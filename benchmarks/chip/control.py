#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program and its control.

    python3 benchmarks/chip/control.py --workload sv_lookup --seeds 11,12,13

For each seed, builds the cell's inputs as a run does and prints one JSON
line per side with every number a run compares:

- ``program``: the library as the configuration states it, each unit of
  the cell run once (every query batch of a lookup mix);
- ``control``: the library's own bounded-probing path switched on
  (``max_probes=1``: a table that gives up on a key after its first
  window), which breaks the guarantee that a build to the stated load
  places every key and that every inserted key is found.  The cell's unit
  file (``harness.make_unit``) says where it applies: for a lookup only
  the lookup is bounded, and the table is built soundly.  A unit file
  whose ``make`` takes no ``max_probes`` has no control.

The benchmark's runs never run this.  It holds the chips in this process
and, like ``run.py``, runs only on a TPU; the tests call ``readings`` at
tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CONTROL_PROBES = 1


def readings(cell, seed: int, devices, max_probes=None) -> dict:
    """Every compared number of one pass over the cell's units."""
    import harness

    unit = harness.make_unit(cell, devices, seed, max_probes)
    w = harness.run_window(unit.call, unit.slots, 0.0, tally=unit.tally)
    checks, _ = unit.check(w, seed)
    return {name: c["value"] for name, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    import harness
    from repro.launch import compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control.py: no TPU; JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 1
    compile_cache.configure()
    for seed in (int(s) for s in args.seeds.split(",")):
        for side, probes in (("program", None), ("control", CONTROL_PROBES)):
            got = readings(cell, seed, devices, probes)
            print(json.dumps({"workload": cell.name, "side": side,
                              "seed": seed, "checks": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
