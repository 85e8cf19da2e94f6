"""The single-value table under test, as the benchmark drives it.

Every call of the library that the single-value units
(``units/single_value.insert.py``, ``units/single_value.retrieve.py``)
make is here: the table a configuration names, the timed programs (a
whole build of a fresh table; one lookup call, which also reads a built
table back for a build's check), the ``stats=True`` twins that the traced
run reads counters from, and the program's own ownership invariant for
sharded tables.  The harness times the units; ``reference`` judges what
they return.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as dist
from repro.core import single_value as sv
from repro.core.common import STATUS_FULL, STATUS_INSERTED
from repro.core.compat import make_mesh_compat

AXIS = "x"
_I = jnp.int32


class Program:
    """One configuration's table on ``chips`` devices.

    ``max_probes`` switches on the library's bounded probing, the path the
    control runs (a table that gives up on a key after that many windows).
    """

    def __init__(self, cfg: dict, devices, max_probes: int | None = None):
        self.cfg = cfg
        self.chips = int(cfg["chips"])
        self.devices = list(devices)[:self.chips]
        self.pairs = int(cfg["pairs_per_chip"]) * self.chips
        self.capacity_per_chip = math.ceil(cfg["pairs_per_chip"] / cfg["load"])
        self.max_probes = max_probes
        self.mesh = (make_mesh_compat((self.chips,), (AXIS,),
                                      devices=self.devices)
                     if self.chips > 1 else None)

    # -- placement ----------------------------------------------------------
    @property
    def batch_sharding(self):
        if self.mesh is None:
            return jax.sharding.SingleDeviceSharding(self.devices[0])
        return NamedSharding(self.mesh, P(AXIS))

    def _create_kwargs(self) -> dict:
        c = self.cfg
        return dict(window=c["window"], key_words=c["key_words"],
                    value_words=c["value_words"], scheme=c["scheme"],
                    backend=c["engine"], max_probes=self.max_probes)

    def create(self):
        if self.mesh is None:
            return sv.create(self.capacity_per_chip, **self._create_kwargs())
        return dist.create_sharded(self.mesh, AXIS, self.capacity_per_chip,
                                   **self._create_kwargs())

    # -- timed units ----------------------------------------------------------
    def _insert(self, keys, values, stats: bool = False):
        """(table, status, overflow per shard, stats) of a fresh build."""
        table = self.create()
        if self.mesh is None:
            out = sv.insert(table, keys, values, stats=stats)
            return out[0], out[1], jnp.zeros((1,), _I), out[2:]
        table, status, overflow = dist.shard_insert(
            self.mesh, AXIS, table, keys, values,
            slack=self.cfg["exchange_slack"])
        return table, status, overflow, ()

    def build_fn(self):
        """jit: (keys, values) -> (table, (chips, 3) i32 of [inserted,
        full, overflow] per shard).  Counts stay per shard, so the unit
        adds no collective of its own to the exchange's."""
        chips = self.chips

        def unit(keys, values):
            table, status, overflow, _ = self._insert(keys, values)
            status = status.reshape(chips, -1)
            return table, jnp.stack([
                jnp.sum(status == STATUS_INSERTED, axis=1, dtype=_I),
                jnp.sum(status == STATUS_FULL, axis=1, dtype=_I),
                overflow.astype(_I)], axis=1)
        return jax.jit(unit)

    def lookup_fn(self):
        """jit: (table, keys) -> (values, found) through the library's read
        path: ``retrieve`` on one chip, ``shard_retrieve`` (the ownership
        exchange, then the local walk) across chips, with the keys placed
        by ``batch_sharding``."""
        max_probes, mesh = self.max_probes, self.mesh
        slack = self.cfg["exchange_slack"]

        def unit(table, keys):
            if max_probes is not None:
                table = dataclasses.replace(table, max_probes=max_probes)
            if mesh is None:
                return sv.retrieve(table, keys)
            return dist.shard_retrieve(mesh, AXIS, table, keys,
                                       slack=slack)[:2]
        return jax.jit(unit)

    # -- counters for the traced run (never timed) ------------------------------
    def build_stats_fn(self):
        """jit: (keys, values) -> TableStats of one build, or None when the
        sharded path carries none."""
        if self.mesh is not None:
            return None
        return jax.jit(lambda k, v: self._insert(k, v, stats=True)[3][0])

    def lookup_stats_fn(self):
        """jit: (table, keys) -> TableStats of one lookup, or None when the
        sharded path carries none."""
        if self.mesh is not None:
            return None
        return jax.jit(lambda t, k: sv.retrieve(t, k, stats=True)[2])

    # -- reading a built table ----------------------------------------------
    @staticmethod
    def slots(table):
        """(key word 0, value word 0, count) of every slot of every shard."""
        keys = table.key_planes()
        vals = table.value_planes()
        return keys[..., 0, :, :], vals[..., 0, :, :], table.count

    def misplaced_fn(self):
        """jit: sharded table -> live keys held off their owner shard, by
        the library's own ``owner_of`` (0 on one chip)."""
        chips = self.chips

        def misplaced(table):
            keys = table.key_planes()[:, 0].reshape(chips, -1)
            live = (keys != jnp.uint32(0xFFFFFFFF)) & (
                keys != jnp.uint32(0xFFFFFFFE))
            owner = dist.owner_of(keys.reshape(-1), chips,
                                  1).reshape(chips, -1)
            shard = jnp.arange(chips, dtype=owner.dtype)[:, None]
            return jnp.sum(live & (owner != shard), dtype=_I)
        return jax.jit(misplaced)


def stats_dict(stats) -> dict:
    """Every ``TableStats`` field as plain numbers (a histogram as a list),
    for the metric readers."""
    stats = jax.device_get(stats)
    return {f.name: np.asarray(getattr(stats, f.name)).tolist()
            for f in dataclasses.fields(stats)}
