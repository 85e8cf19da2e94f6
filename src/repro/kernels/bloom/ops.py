"""Jitted wrappers for the blocked-bloom Pallas kernel (packed u32 words)."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import bloom as bloom_core
from repro.kernels.bloom import kernel as K
from repro.kernels.cops.ops import _tile_batch as _tile
from repro.kernels.cops.ops import check_resident, should_interpret

_U = jnp.uint32
_I = jnp.int32


@functools.partial(jax.jit, static_argnames=("k_hashes", "seed", "tile", "interpret"))
def insert_words(filt_words, keys, mask, *, k_hashes, seed, tile=K.DEFAULT_TILE,
                 interpret=True):
    """Insert keys into a packed (num_blocks, words) u32 filter."""
    k2, _ = _tile(keys.astype(_U), tile, 0)
    m2, _ = _tile(mask.astype(_I), tile, 0)
    return K.insert_call(filt_words, k2, m2, k_hashes=k_hashes, seed=seed,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k_hashes", "seed", "tile", "interpret"))
def query_words(filt_words, keys, *, k_hashes, seed, tile=K.DEFAULT_TILE,
                interpret=True):
    k2, n = _tile(keys.astype(_U), tile, 0)
    out = K.query_call(filt_words, k2, k_hashes=k_hashes, seed=seed,
                       interpret=interpret)
    return out.reshape(-1)[:n] != 0


def insert(f: bloom_core.BloomFilter, keys, mask=None) -> bloom_core.BloomFilter:
    """BloomFilter insert via the Pallas kernel (state stays bit-plane typed)."""
    keys = jnp.asarray(keys)
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    words = bloom_core.pack_words(f)
    check_resident("bloom insert", words.shape)
    words = insert_words(words, keys, mask, k_hashes=f.k, seed=f.seed,
                         interpret=should_interpret())
    return bloom_core.unpack_words(words, f.block_bits, f.k, f.seed)


def contains(f: bloom_core.BloomFilter, keys) -> jax.Array:
    words = bloom_core.pack_words(f)
    check_resident("bloom contains", words.shape)
    return query_words(words, jnp.asarray(keys), k_hashes=f.k, seed=f.seed,
                       interpret=should_interpret())
