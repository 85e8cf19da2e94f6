"""Compile every Pallas tile for a TPU v5e that is described, not attached.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes the tiling rejects, scalar stores to VMEM, more VMEM than the chip
has).  Each case here lowers one tile with ``interpret=False`` for device 0
of a ``v5e:2x2`` topology at the largest table ``VMEM_RESIDENT_LIMIT``
admits, and checks that the compiled program holds the kernel.  The
topology is described inside a fixture, never at import: only one process
may load the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bloom import kernel as BK
from repro.kernels.cops import kernel as K
from repro.kernels.cops import ops as cops_ops
from repro.kernels.minhash import kernel as MK

_U, _I = jnp.uint32, jnp.int32
W = 32                      # the default probe window
G, T = 16, K.DEFAULT_TILE   # 16 tiles of keys per call


def max_rows(planes: int, lanes: int) -> int:
    """Most rows (a multiple of 8) for which `planes` resident (rows, lanes)
    planes stay within the tile limit."""
    per_row = K.resident_bytes((8, lanes)) // 8
    return (K.VMEM_RESIDENT_LIMIT // (planes * per_row)) // 8 * 8


def _case_insert():
    p = max_rows(2, W)
    f = functools.partial(K.insert_call, seed=7, max_probes=p, interpret=False)
    return f, [((p, W), _U)] * 2 + [((G, 1, T), _U)] * 2 + [((G, 1, T), _I)]


def _case_insert64():
    p = max_rows(3, W)
    f = functools.partial(K.insert64_call, seed=7, max_probes=p,
                          interpret=False)
    return f, [((p, W), _U)] * 3 + [((G, 1, T), _U)] * 3 + [((G, 1, T), _I)]


def _case_lookup():
    p = max_rows(2, W)
    f = functools.partial(K.lookup_call, seed=7, max_probes=p, interpret=False)
    return f, [((p, W), _U)] * 2 + [((G, 1, T), _U)]


def _case_lookup64():
    p = max_rows(3, W)
    f = functools.partial(K.lookup64_call, seed=7, max_probes=p,
                          interpret=False)
    return f, [((p, W), _U)] * 3 + [((G, 1, T), _U)] * 2


def _case_update():
    p = max_rows(3, W)
    f = functools.partial(K.update_call, seed=7, max_probes=p, agg="sum",
                          interpret=False)
    return f, [((p, W), _U)] * 3 + [((G, 1, T), _U)] * 2 + [((G, 1, T), _I)]


def _case_retrieve_multi():
    p = max_rows(3, W)
    f = functools.partial(K.retrieve_multi_call, seed=7, max_probes=p,
                          interpret=False)
    return f, ([((p, W), _U)] + [((p, W), _I)] * 2 + [((G, 1, T), _U)]
               + [((G, 1, T), _I)])


def _case_retrieve_multi64():
    p = max_rows(4, W)
    f = functools.partial(K.retrieve_multi64_call, seed=7, max_probes=p,
                          interpret=False)
    return f, ([((p, W), _U)] * 2 + [((p, W), _I)] * 2 + [((G, 1, T), _U)] * 2
               + [((G, 1, T), _I)])


def _case_bucket_walk():
    r = max_rows(3, K.POOL_LANES)
    f = functools.partial(K.bucket_walk_call, interpret=False)
    sched = 24
    return f, ([((r, K.POOL_LANES), _U)] + [((r, K.POOL_LANES), _I)] * 2
               + [((G, 1, T), _I)] * 4
               + [((1, sched), _I), ((1, sched + 1), _I)])


def _case_bloom_insert():
    blocks = max_rows(1, 16)
    f = functools.partial(BK.insert_call, k_hashes=4, seed=7, interpret=False)
    return f, [((blocks, 16), _U), ((G, 1, T), _U), ((G, 1, T), _I)]


def _case_bloom_query():
    blocks = max_rows(1, 16)
    f = functools.partial(BK.query_call, k_hashes=4, seed=7, interpret=False)
    return f, [((blocks, 16), _U), ((G, 1, T), _U)]


def _case_minhash():
    k, tile = 16, 512            # fig8's k-mer width, the kernel's tile
    f = functools.partial(MK.kmer_hash_call, k=k, interpret=False)
    return f, [((G, 1, tile + k - 1), jnp.uint8)]


CASES = {
    "insert": _case_insert,
    "insert64": _case_insert64,
    "lookup": _case_lookup,
    "lookup64": _case_lookup64,
    "update": _case_update,
    "retrieve_multi": _case_retrieve_multi,
    "retrieve_multi64": _case_retrieve_multi64,
    "bucket_walk": _case_bucket_walk,
    "bloom_insert": _case_bloom_insert,
    "bloom_query": _case_bloom_query,
    "minhash_kmer": _case_minhash,
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("name", sorted(CASES))
def test_tile_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, specs = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_pallas_dispatch_refuses_tables_above_the_limit():
    from repro.core import single_value as sv
    p = max_rows(2, W)
    cops_ops.check_resident("insert", (p, W), (p, W))
    with pytest.raises(cops_ops.TableTooLargeForKernel):
        cops_ops.check_resident("insert", (p + 8, W), (p + 8, W))
    keys = jnp.arange(1, 9, dtype=_U)
    big = sv.create((p + 64) * W, window=W, backend="pallas")
    with pytest.raises(cops_ops.TableTooLargeForKernel):
        sv.insert(big, keys, keys)
    with pytest.raises(cops_ops.TableTooLargeForKernel):
        sv.retrieve(big, keys)
