"""The timed unit of a single-value table under the ``retrieve`` op: one
lookup call over a batch of the mix's keys, against a table of every pair
of the configuration built in set-up (one chip, or sharded, the queries
then going through the ownership exchange to their owner shards).

Its check compares the last answer of every distinct batch with the
truth.  ``max_probes`` gives the control: the table is built soundly and
the lookup gives up on a key after that many probe windows.
"""

from __future__ import annotations

import jax

import bytes as floors
import harness
import program
import reference
import traffic


def make(cell, devices, seed: int, max_probes: int | None = None):
    sound = program.Program(cell.config, devices)
    prog = (sound if max_probes is None else
            program.Program(cell.config, devices, max_probes=max_probes))
    harness.log(f"{prog.pairs} pairs, {prog.capacity_per_chip} slots per "
                "chip")
    keys, values = traffic.device_pairs(prog.pairs, seed,
                                        prog.batch_sharding)
    build, foot = harness.compile_unit("build", sound.build_fn(), keys,
                                       values)
    return LookupUnit(prog, build, foot, keys, values, cell.traffic, seed)


class LookupUnit:
    """The table is built in set-up; a unit is one lookup call."""

    def __init__(self, prog, build, build_foot, keys, values, mix, seed):
        self.prog = prog
        self.table, _ = jax.block_until_ready(build(keys, values))
        n = prog.pairs
        self.idx = traffic.query_indices(mix, n, seed)
        self.slots, self.per_call = traffic.batch_shape(mix, n)
        self.batches = [traffic.keys_of(self.idx[i])
                        for i in range(self.slots)]
        if prog.mesh is not None:
            # placed by shard only across chips: on one chip a committed
            # batch would add a sharding annotation to the lookup program
            self.batches = jax.device_put(self.batches, prog.batch_sharding)
        self.lookup, foot = harness.compile_unit(
            "lookup", prog.lookup_fn(), self.table, self.batches[0])
        self.footprints = [build_foot, foot]
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
        return None if fn is None else program.stats_dict(
            fn(self.table, self.batches[0]))

    def check(self, w, seed: int):
        total = {"found_wrong": 0, "values_wrong": 0}
        home = self.prog.devices[0]
        for slot, (vals, found) in w.kept.items():
            got = reference.answer_disagreements(
                self.idx[slot], jax.device_put(vals, home),
                jax.device_put(found, home), traffic.salt(seed),
                n=self.prog.pairs)
            for k, v in jax.device_get(got).items():
                total[k] += int(v)
        return harness.limit0(total), 0
