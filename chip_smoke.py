#!/usr/bin/env python3
"""Bring-up check: drive the hash-table library's main path once on a TPU.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: the distributed phase alone

One chip runs two phases through the public table API:

- ``insert_retrieve`` — a ``single_value`` table (default ``jax`` engine,
  u32 keys and values, window 32, COPS probing) sized for 2^26 pairs at
  load factor 0.9.  It inserts all pairs in one batch, upserts a 1/8
  subset with new values, then retrieves the 2^26 present keys and 2^26
  absent keys, one batch each.
- ``pallas`` — the COPS Pallas insert and lookup tiles on the largest table
  they accept (``kernels.cops.kernel.VMEM_RESIDENT_LIMIT``), bit-exact
  against the ``jax`` engine on the same keys, with the kernel asserted in
  the compiled program.

``--chips 4`` runs ``distributed``: ``core.distributed`` over a four-chip
mesh, 2^28 pairs in all (2^26 per chip), inserted, upserted and retrieved
in sharded batches of 2^25 keys per chip through the ownership exchange.

Keys are made on the device from ``--seed``: a seeded bijection of the
index, so distinct indices give distinct keys and none is a sentinel.
Values are a fixed function of the key.  Every answer is compared with that
plain truth; one mismatch fails the run.

Earlier lines give sizes, compile seconds and run seconds per phase (set-up
and wall time, not speed) and the peak device memory.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
N the chips the run used.  The sizes are fixed; only ``--seed`` varies
the data.
The script exits non-zero without that line when JAX finds no TPU, when the
repository's ``src/`` is not beside it, or when any check fails.  It holds
the chip in this one process.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {SRC}")
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.core import single_value as sv  # noqa: E402
from repro.core.common import (  # noqa: E402
    EMPTY_KEY, STATUS_INSERTED, STATUS_UPDATED, TOMBSTONE_KEY, is_prime)
from repro.core.compat import make_mesh_compat, shard_map_compat  # noqa: E402
from repro.kernels.cops import kernel as cops_kernel  # noqa: E402

_U = jnp.uint32
WINDOW = 32
LOAD = 0.9
# The sizes the run certifies.  One chip's HBM holds the insert and the
# retrieve of 2^26 pairs in one batch, not of 2^27; four chips hold the
# paper's 2^28.  Each chip's exchange hands its local insert up to twice
# its share of a sharded batch, so a batch of 2^25 keys per chip is the
# largest that fits.
PAIRS = 1 << 26
PALLAS_KEYS = 1 << 20
DIST_BATCH_PER_CHIP = 1 << 25


class CheckFailed(RuntimeError):
    """An answer differed from the plain truth."""


def check(what: str, got, want) -> None:
    got = np.asarray(got).tolist()
    if got != want:
        raise CheckFailed(f"{what}: got {got}, want {want}")


# ---------------------------------------------------------------------------
# the plain truth: keys and values as functions of an index
# ---------------------------------------------------------------------------

def _seed_consts(seed: int) -> tuple[int, int]:
    a = ((seed * 2 + 1) * 0x9E3779B1 | 1) & 0xFFFFFFFF     # odd: invertible
    b = (seed * 0x85EBCA6B + 0x27D4EB2F) & 0xFFFFFFFF
    return a, b


def keys_of(idx, seed: int):
    """Distinct u32 keys for distinct indices below 2^32 - 2.

    An odd multiply-add and murmur3's finalizer make a bijection of u32;
    walking it again from the (at most two) indices it sends onto the
    EMPTY/TOMBSTONE sentinels keeps it a bijection that avoids them.
    """
    a, b = _seed_consts(seed)

    def perm(x):
        return hashing.mix_murmur3(x * _U(a) + _U(b))

    x = perm(idx.astype(_U))
    for _ in range(2):
        x = jnp.where(x >= TOMBSTONE_KEY, perm(x), x)
    return x


def values_of(keys, salt):
    """The value stored for each key; a new ``salt`` changes every value."""
    return keys * _U(0x9E3779B1) + salt.astype(_U) * _U(0x7F4A7C15) + _U(1)


def _index(lo, n):
    return lo.astype(_U) + jnp.arange(n, dtype=_U)


# ---------------------------------------------------------------------------
# one chip: the jax engines at deployment size
# ---------------------------------------------------------------------------

def _insert_step(table, lo, salt, *, n, seed):
    keys = keys_of(_index(lo, n), seed)
    table, status = sv.insert(table, keys, values_of(keys, salt))
    return table, jnp.stack([jnp.sum(status == STATUS_INSERTED),
                             jnp.sum(status == STATUS_UPDATED)])


def _retrieve_check_step(table, lo, *, n, seed, n_present, n_updated):
    """Retrieve keys lo..lo+n-1; count answers that differ from the truth:
    (found != present, present with a wrong value)."""
    idx = _index(lo, n)
    keys = keys_of(idx, seed)
    vals, found = sv.retrieve(table, keys)
    present = idx < _U(n_present)
    want = values_of(keys, (idx < _U(n_updated)).astype(_U))
    return jnp.stack([jnp.sum(found != present),
                      jnp.sum(present & (vals != want))])


def _timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _timed_run(compiled, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _memory(compiled) -> str:
    m = compiled.memory_analysis()
    if m is None:
        return "memory=unreported"
    return (f"temp_bytes={m.temp_size_in_bytes} "
            f"argument_bytes={m.argument_size_in_bytes} "
            f"output_bytes={m.output_size_in_bytes}")


def phase_insert_retrieve(n_pairs: int, seed: int, report=print) -> None:
    """Insert, upsert 1/8, retrieve present and absent keys; check all."""
    table = sv.create(math.ceil(n_pairs / LOAD), window=WINDOW, scheme="cops")
    n_upd = n_pairs // 8
    report(f"insert_retrieve: pairs={n_pairs} rows={table.num_rows} "
           f"window={WINDOW} slots={table.capacity} "
           f"load={n_pairs / table.capacity:.4f}")
    zero, one, lo_absent = np.uint32(0), np.uint32(1), np.uint32(n_pairs)

    def step(name, fn, *args):
        compiled, c_s = _timed_compile(fn, *args)
        out, r_s = _timed_run(compiled, *args)
        report(f"  {name}: compile_s={c_s:.2f} run_s={r_s:.4f} "
               f"{_memory(compiled)}")
        return out

    insert = jax.jit(functools.partial(_insert_step, n=n_pairs, seed=seed),
                     donate_argnums=0)
    table, counts = step("insert", insert, table, zero, zero)
    check("insert statuses (inserted, updated)", counts, [n_pairs, 0])

    upsert = jax.jit(functools.partial(_insert_step, n=n_upd, seed=seed),
                     donate_argnums=0)
    table, counts = step("upsert", upsert, table, zero, one)
    check("upsert statuses (inserted, updated)", counts, [0, n_upd])
    check("table.count", table.count, n_pairs)

    retrieve = jax.jit(functools.partial(
        _retrieve_check_step, n=n_pairs, seed=seed, n_present=n_pairs,
        n_updated=n_upd))
    bad = step("retrieve_present", retrieve, table, zero)
    check("present keys (found wrong, value wrong)", bad, [0, 0])
    bad = step("retrieve_absent", retrieve, table, lo_absent)
    check("absent keys (found wrong, value wrong)", bad, [0, 0])


# ---------------------------------------------------------------------------
# one chip: the Pallas tiles against the jax engine
# ---------------------------------------------------------------------------

def largest_pallas_rows() -> int:
    """Largest prime row count whose key and value planes the insert and
    lookup tiles hold in VMEM."""
    rows = cops_kernel.VMEM_RESIDENT_LIMIT // (
        2 * cops_kernel.resident_bytes((8, WINDOW)) // 8)
    while not is_prime(rows):
        rows -= 1
    return rows


def _pallas_insert(table, *, n, seed):
    keys = keys_of(jnp.arange(n, dtype=_U), seed)
    return sv.insert(table, keys, values_of(keys, jnp.uint32(0)))


def _pallas_retrieve(table, lo, *, n, seed):
    return sv.retrieve(table, keys_of(_index(lo, n), seed))


def _differ(a, b):
    return sum(int(jnp.sum(x != y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def phase_pallas(n_keys: int, seed: int, rows: int | None = None,
                 require_kernel: bool = True, report=print) -> None:
    """backend='pallas' insert + retrieve, bit-exact with backend='jax'."""
    rows = rows or largest_pallas_rows()
    tj = sv.create(rows * WINDOW, window=WINDOW, scheme="cops")
    tp = sv.create(rows * WINDOW, window=WINDOW, scheme="cops",
                   backend="pallas")
    report(f"pallas: keys={n_keys} rows={tj.num_rows} window={WINDOW} "
           f"slots={tj.capacity} load={n_keys / tj.capacity:.4f} "
           f"resident_bytes="
           f"{cops_kernel.resident_bytes(*[(rows, WINDOW)] * 2)}")
    insert = jax.jit(functools.partial(_pallas_insert, n=n_keys, seed=seed))
    retrieve = jax.jit(functools.partial(_pallas_retrieve, n=n_keys,
                                         seed=seed))
    zero, lo_absent = np.uint32(0), np.uint32(n_keys)
    results = {}
    for name, table in (("jax", tj), ("pallas", tp)):
        ins_c, ins_cs = _timed_compile(insert, table)
        ret_c, ret_cs = _timed_compile(retrieve, table, zero)
        for what, c in (("insert", ins_c), ("retrieve", ret_c)):
            if (name == "pallas" and require_kernel
                    and "tpu_custom_call" not in c.as_text()):
                raise CheckFailed(f"pallas {what}: no tpu_custom_call in the "
                                  "compiled program")
        (table, status), ins_s = _timed_run(ins_c, table)
        present, pre_s = _timed_run(ret_c, table, zero)
        absent, abs_s = _timed_run(ret_c, table, lo_absent)
        report(f"  {name}: compile_s={ins_cs + ret_cs:.2f} "
               f"insert_run_s={ins_s:.4f} retrieve_run_s={pre_s + abs_s:.4f}")
        results[name] = (table.store, status, present, absent)
    (sj, stj, pj, aj), (sp, stp, pp, ap) = results["jax"], results["pallas"]
    check("pallas statuses all inserted",
          int(jnp.sum(stp == STATUS_INSERTED)), n_keys)
    check("pallas vs jax: table planes differing", _differ(sp, sj), 0)
    check("pallas vs jax: statuses differing", _differ(stp, stj), 0)
    check("pallas vs jax: present answers differing", _differ(pp, pj), 0)
    check("pallas vs jax: absent answers differing", _differ(ap, aj), 0)
    want = values_of(keys_of(jnp.arange(n_keys, dtype=_U), seed),
                      jnp.uint32(0))
    check("pallas present (not found, wrong value)",
          [int(jnp.sum(~pp[1])), int(jnp.sum(pp[0] != want))], [0, 0])
    check("pallas absent keys found", int(jnp.sum(ap[1])), 0)


# ---------------------------------------------------------------------------
# four chips: the distributed tables over a mesh
# ---------------------------------------------------------------------------

AXIS = "x"


def distributed_steps(mesh, n_batch: int, seed: int, n_present: int,
                      n_updated: int):
    """Jitted (keygen, insert, retrieve, ownership) steps over ``mesh`` for
    global batches of ``n_batch`` keys."""
    num = int(mesh.shape[AXIS])
    sharded = NamedSharding(mesh, P(AXIS))

    @functools.partial(jax.jit, out_shardings=sharded)
    def keygen(lo):
        return keys_of(_index(lo, n_batch), seed)

    @functools.partial(jax.jit, donate_argnums=0)
    def insert(table, keys, salt):
        table, status, ov = dist.shard_insert(
            mesh, AXIS, table, keys, values_of(keys, salt))
        return table, jnp.stack([jnp.sum(status == STATUS_INSERTED),
                                 jnp.sum(status == STATUS_UPDATED),
                                 jnp.sum(ov)])

    @jax.jit
    def retrieve(table, keys, lo):
        vals, found, ov = dist.shard_retrieve(mesh, AXIS, table, keys)
        idx = _index(lo, n_batch)
        present = idx < _U(n_present)
        want = values_of(keys, (idx < _U(n_updated)).astype(_U))
        return jnp.stack([jnp.sum(found != present),
                          jnp.sum(present & (vals != want)), jnp.sum(ov)])

    spec = P(AXIS)

    @jax.jit
    def ownership(table):
        def body(t):
            t = jax.tree.map(lambda x: x[0], t)
            kp = t.store["keys"][0].reshape(-1)
            live = (kp != EMPTY_KEY) & (kp != TOMBSTONE_KEY)
            mine = dist.owner_of(kp, num, 1) == jax.lax.axis_index(AXIS)
            return jnp.stack([jnp.sum(live & ~mine), jnp.sum(live),
                              t.count])[None]
        tspec = jax.tree.map(lambda _: spec, table)
        return shard_map_compat(body, mesh, in_specs=(tspec,),
                                out_specs=spec)(table)

    return keygen, insert, retrieve, ownership


def phase_distributed(mesh, n_batch: int, batches: int, seed: int,
                      report=print) -> None:
    """Insert ``batches`` global batches of ``n_batch`` keys, upsert the
    first batch, retrieve every batch and one absent batch; check the
    answers, key ownership, live counts and exchange overflow."""
    num = int(mesh.shape[AXIS])
    total = n_batch * batches
    keygen, insert, retrieve, ownership = distributed_steps(
        mesh, n_batch, seed, n_present=total, n_updated=n_batch)
    table = dist.create_sharded(mesh, AXIS, math.ceil(total / num / LOAD),
                                window=WINDOW, scheme="cops")
    rows = table.num_rows
    report(f"distributed: shards={num} pairs={total} batch={n_batch} "
           f"rows_per_shard={rows} slots_per_shard={rows * WINDOW} "
           f"load={total / (num * rows * WINDOW):.4f}")
    keys0 = keygen(np.uint32(0))
    zero, one = np.uint32(0), np.uint32(1)
    ins_c, ins_cs = _timed_compile(insert, table, keys0, zero)
    ret_c, ret_cs = _timed_compile(retrieve, table, keys0, zero)
    own_c, own_cs = _timed_compile(ownership, table)
    report(f"  compile_s insert={ins_cs:.2f} retrieve={ret_cs:.2f} "
           f"ownership={own_cs:.2f}; insert {_memory(ins_c)}; "
           f"retrieve {_memory(ret_c)}")

    def batch_keys(b):
        return keygen(np.uint32(b * n_batch))

    for b in range(batches):
        (table, counts), r_s = _timed_run(ins_c, table, batch_keys(b), zero)
        report(f"  insert batch {b}: run_s={r_s:.4f}")
        check(f"insert batch {b} (inserted, updated, overflow)", counts,
              [n_batch, 0, 0])
    (table, counts), r_s = _timed_run(ins_c, table, batch_keys(0), one)
    report(f"  upsert batch 0: run_s={r_s:.4f}")
    check("upsert batch 0 (inserted, updated, overflow)", counts,
          [0, n_batch, 0])
    for b in range(batches + 1):          # the last batch is absent keys
        bad, r_s = _timed_run(ret_c, table, batch_keys(b),
                              np.uint32(b * n_batch))
        report(f"  retrieve batch {b}{' (absent)' * (b == batches)}: "
               f"run_s={r_s:.4f}")
        check(f"retrieve batch {b} (found wrong, value wrong, overflow)",
              bad, [0, 0, 0])
    per_shard = np.asarray(own_c(table))
    report(f"  per shard (misplaced, live, count): {per_shard.tolist()}")
    check("keys off their owner shard", int(per_shard[:, 0].sum()), 0)
    check("live slots over all shards", int(per_shard[:, 1].sum()), total)
    check("table.count over all shards", int(per_shard[:, 2].sum()), total)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.configure()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    print("times: compile_s is set-up, run_s is the wall time of one call "
          "until its result is ready; neither is a speed result")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh = make_mesh_compat((4,), (AXIS,), devices=devices[:4])
            phase_distributed(mesh, 4 * DIST_BATCH_PER_CHIP,
                              PAIRS // DIST_BATCH_PER_CHIP, args.seed)
        else:
            phase_insert_retrieve(PAIRS, args.seed)
            phase_pallas(PALLAS_KEYS, args.seed)
    except Exception as e:  # noqa: BLE001 — any failure fails the run
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(f"peak_bytes_in_use: {_peak_bytes(devices[:args.chips])}")
    print(f"total_s: {time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
