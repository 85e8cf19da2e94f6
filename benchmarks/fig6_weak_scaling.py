"""Fig. 6 — weak scaling of distributed-mode insertion with runtime
breakdown (partition / exchange / insert) and efficiency.

The paper scales 1..8 GPUs on a DGX-1 with 2 GB per GPU; we scale over
1, 2, 4, ... of the devices this process has, with a fixed per-shard batch
(weak scaling), reporting the same phase breakdown.  It runs in the calling
process: on a chip host a child process could not reach the chips the
parent holds.  For several CPU devices, launch the process with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``.
Any failure raises.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core import single_value as sv
from repro.core.compat import (axis_size_compat, make_mesh_compat,
                               shard_map_compat)


def bench(num_shards, per_shard):
    mesh = make_mesh_compat((num_shards,), ("x",),
                            devices=jax.devices()[:num_shards])
    table = dist.create_sharded(mesh, "x", per_shard * 2, window=32)
    n = num_shards * per_shard
    keys = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n + 1, dtype=np.uint32)))
    vals = keys * 3

    # phase 1+2: partition (multisplit) + all_to_all exchange only
    def route(k, v):
        num = axis_size_compat("x")
        k2 = sv.normalize_words(k, 1, "k")
        owners = dist.owner_of(k2, num, 1)
        cap = int(np.ceil(k.shape[0] / num * 2.0))
        plan = dist.make_plan(owners, num, cap)
        kb = dist.scatter_to_buffer(plan, k2, num)
        vb = dist.scatter_to_buffer(plan, sv.normalize_words(v, 1, "v"), num)
        return dist.exchange(kb, "x"), dist.exchange(vb, "x")

    froute = jax.jit(shard_map_compat(route, mesh,
                                      in_specs=(P("x"), P("x")),
                                      out_specs=(P("x"), P("x"))))
    fall = jax.jit(lambda t, k, v: dist.shard_insert(mesh, "x", t, k, v))

    def t(f, *a, iters=3):
        jax.block_until_ready(f(*a))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*a))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        med = ts[len(ts) // 2]
        return med, (med - ts[0]) / med if med > 0 else 0.0

    t_route, _ = t(froute, keys, vals)
    t_total, spread = t(fall, table, keys, vals)
    _, _, overflow = fall(table, keys, vals)
    if int(jnp.sum(overflow)):
        raise RuntimeError(f"fig6: exchange overflow at {num_shards} shards")
    return dict(shards=num_shards, n=n, t_route=t_route,
                t_insert=max(t_total - t_route, 0.0), t_total=t_total,
                spread=spread)


def run(out=print):
    count = len(jax.devices())
    data = [bench(s, 1 << 12) for s in (1, 2, 4, 8) if s <= count]
    t1 = data[0]["t_total"]
    for d in data:
        # on fake CPU devices all "devices" share the host's cores, so ideal
        # weak scaling is t_N = N * t_1; eff_1core = N*t1/tN isolates the
        # per-shard overhead added by multisplit + all_to_all (the paper's
        # Fig-6 breakdown)
        eff = d["shards"] * t1 / d["t_total"]
        route_frac = d["t_route"] / d["t_total"]
        spread = d.get("spread", 0.0)
        out(f"fig6.insert.shards{d['shards']},{d['t_total']*1e6:.0f},"
            f"{d['n']/d['t_total']/1e6:.3f}Mops/s,"
            f"route_frac={route_frac:.2f},eff_1core={eff:.2f},"
            f"spread={spread:.4g},noisy={int(spread > 0.20)}")


if __name__ == "__main__":
    run()
