"""Pallas TPU kernel for the blocked bloom filter (paper §IV: BloomFilter).

The filter is (num_blocks, words) packed u32 bit-words; each key touches
exactly one block (one vector row — the "one memory transaction" property of
blocked bloom filters, preserved on TPU as one VMEM row access).  Insert is
a row read-OR-write; since the whole filter is VMEM-resident and grid steps
are sequential, read-modify-write is race-free.  Query needs no
serialization at all but uses the same row-gather structure.  Keys and
per-key results stream as (G, 1, T) arrays in SMEM blocks, as in the
COPS tiles (``repro.kernels.cops.kernel``), whose block helpers this
module shares.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hashing
from repro.kernels.cops.kernel import (COMPILER_PARAMS, IN_PLACE,
                                       load_resident, per_key_spec,
                                       resident_spec)

_U = jnp.uint32
_I = jnp.int32

DEFAULT_TILE = 256


def _key_pattern(k, num_blocks, words, k_hashes, seed):
    """(block_row, (words,) u32 OR-pattern) for one key."""
    block = hashing.mix_murmur3(k ^ _U(seed)) % _U(num_blocks)
    h = hashing.mix_xxhash(k ^ _U(seed))
    g = hashing.mix_murmur3(k + _U(0x61C88647))
    bits = words * 32
    word_iota = jax.lax.broadcasted_iota(_U, (1, words), 1)[0]
    pattern = jnp.zeros((words,), _U)
    for i in range(k_hashes):
        pos = (h + _U(i) * g) % _U(bits)
        widx = pos // _U(32)
        bit = pos % _U(32)
        contrib = jnp.where(word_iota == widx,
                            jax.lax.shift_left(_U(1), bit), _U(0))
        pattern = pattern | contrib
    return block, pattern


def _insert_kernel(keys_ref, mask_ref, filt_in_ref, filt_ref,
                   *, num_blocks, words, k_hashes, seed):
    load_resident((filt_in_ref, filt_ref))
    tile = keys_ref.shape[1]

    def one_key(j, _):
        k = keys_ref[0, j]
        m = mask_ref[0, j] != 0
        block, pattern = _key_pattern(k, num_blocks, words, k_hashes, seed)

        @pl.when(m)
        def _():
            row = filt_ref[pl.ds(block.astype(_I), 1), :][0]
            filt_ref[pl.ds(block.astype(_I), 1), :] = (row | pattern)[None, :]

        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def insert_call(filt, keys3d, mask3d, *, k_hashes, seed, interpret=True):
    """keys3d/mask3d: (G, 1, T). Returns the updated (blocks, words) filter."""
    num_blocks, words = filt.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(_insert_kernel, num_blocks=num_blocks, words=words,
                             k_hashes=k_hashes, seed=seed)
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, row_tile, IN_PLACE],
        out_specs=resident_spec((num_blocks, words)),
        out_shape=jax.ShapeDtypeStruct((num_blocks, words), _U),
        input_output_aliases={2: 0},
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, mask3d, filt)


def _query_kernel(keys_ref, filt_ref, out_ref,
                  *, num_blocks, words, k_hashes, seed):
    tile = keys_ref.shape[1]

    def one_key(j, _):
        k = keys_ref[0, j]
        block, pattern = _key_pattern(k, num_blocks, words, k_hashes, seed)
        row = filt_ref[pl.ds(block.astype(_I), 1), :][0]
        hit = jnp.all((row & pattern) == pattern)
        out_ref[0, j] = hit.astype(_I)
        return 0

    jax.lax.fori_loop(0, tile, one_key, 0)


def query_call(filt, keys3d, *, k_hashes, seed, interpret=True):
    """keys3d: (G, 1, T). Returns (G, 1, T) i32 hit flags."""
    num_blocks, words = filt.shape
    g, _, tile = keys3d.shape
    kern = functools.partial(_query_kernel, num_blocks=num_blocks, words=words,
                             k_hashes=k_hashes, seed=seed)
    row_tile = per_key_spec(tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[row_tile, resident_spec((num_blocks, words))],
        out_specs=row_tile,
        out_shape=jax.ShapeDtypeStruct((g, 1, tile), _I),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(keys3d, filt)
