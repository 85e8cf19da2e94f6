"""The timed unit of a single-value table under the ``insert`` op: a whole
build, ``create`` and ``insert`` of every pair of the configuration into a
fresh table (one chip, or sharded through the ownership exchange).

Its check reads the window's last table slot by slot, and reads every
inserted key and ``n // 64`` absent ones back through the library's own
read path after the window, untimed.  ``max_probes`` gives the control:
a table that gives up on a key after that many probe windows.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import bytes as floors
import harness
import program
import reference
import traffic


def make(cell, devices, seed: int, max_probes: int | None = None):
    prog = program.Program(cell.config, devices, max_probes=max_probes)
    harness.log(f"{prog.pairs} pairs, {prog.capacity_per_chip} slots per "
                "chip")
    keys, values = traffic.device_pairs(prog.pairs, seed,
                                        prog.batch_sharding)
    build, foot = harness.compile_unit("build", prog.build_fn(), keys, values)
    return BuildUnit(prog, build, foot, keys, values)


class BuildUnit:
    """A unit is a whole build: create a fresh table, insert every pair."""

    slots = 1

    def __init__(self, prog, build, foot, keys, values):
        self.prog, self.build = prog, build
        self.keys, self.values = keys, values
        self.footprints = [foot]
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
        return None if fn is None else program.stats_dict(
            fn(self.keys, self.values))

    def check(self, w, seed: int):
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
        return harness.limit0(got), refused

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
        vals, found = jax.block_until_ready(prog.lookup_fn()(table, keys))
        harness.log(f"read-back: {idx.shape[0]} keys in "
                    f"{time.perf_counter() - t0:.2f} s (compile included)")
        home = prog.devices[0]
        got = reference.answer_disagreements(
            jax.device_put(idx, home), jax.device_put(vals, home),
            jax.device_put(found, home), traffic.salt(seed), n=prog.pairs)
        return {"read_" + k: int(v) for k, v in jax.device_get(got).items()}
