"""Pallas TPU kernel: canonical k-mer extraction + hashing (paper §V-C).

The metagenomics use case stores minhash-subsampled k-mers in a multi-value
table.  k-mer generation is the bandwidth-bound front half of that pipeline
(the paper ports it to CUDA for the same reason); it is a perfect VPU
workload: per output position, k unrolled shift-or steps over 2-bit base
codes — no gathers, no serialization.

Input: 2-bit base codes (0..3; >=4 marks N/invalid) in overlapped
(G, 1, T+k-1) tiles.  Output: (G, 1, T) u32 hashes of the *canonical* k-mer
(min of forward and reverse-complement encodings, as MetaCache/Kraken do),
with INVALID (0xFFFFFFFF) where the window contains an invalid base or runs
off the read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import hashing

_U = jnp.uint32
_I = jnp.int32

INVALID = np.uint32(0xFFFFFFFF)
DEFAULT_TILE = 512


def _kmer_kernel(bases_ref, out_ref, *, k, tile):
    fwd = jnp.zeros((tile,), _U)
    rev = jnp.zeros((tile,), _U)
    bad = jnp.zeros((tile,), bool)
    for j in range(k):                                # k static, unrolled
        b = bases_ref[0, pl.ds(j, tile)].astype(_U)   # bases j .. j+tile-1
        bad = bad | (b > _U(3))
        fwd = (fwd << _U(2)) | (b & _U(3))
        comp = _U(3) - (b & _U(3))
        rev = rev | (comp << _U(2 * j))
    canon = jnp.where(fwd < rev, fwd, rev)            # no unsigned min on-chip
    h = hashing.mix_murmur3(canon)
    out_ref[0, :] = jnp.where(bad, INVALID, h)


def kmer_hash_call(bases3d, *, k, interpret=True):
    """bases3d: (G, 1, T + k - 1) overlapped tiles -> (G, 1, T) canonical
    k-mer hashes."""
    g, _, padded = bases3d.shape
    tile = padded - (k - 1)
    kern = functools.partial(_kmer_kernel, k=k, tile=tile)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[pl.BlockSpec((None, 1, padded), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, tile), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 1, tile), _U),
        interpret=interpret,
    )(bases3d)
