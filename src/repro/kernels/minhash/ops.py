"""Jitted wrappers for k-mer hashing + minhash sketching.

``sketch_reads`` is the full front half of the paper's metagenomics pipeline
(§V-C): reads -> canonical k-mer hashes (Pallas kernel) -> per-read minhash
sketch (s smallest distinct hashes).  The sketches feed straight into a
MultiValue/BucketList table insert — the same fusion the paper gets from
its device-sided interface.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.cops.ops import should_interpret
from repro.kernels.minhash import kernel as K
from repro.kernels.minhash.ref import INVALID, minhash_sketch

_U = jnp.uint32


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def kmer_hashes(bases, *, k, tile=K.DEFAULT_TILE, interpret=True):
    """(L,) base codes -> (L - k + 1,) canonical k-mer hashes via the kernel."""
    bases = jnp.asarray(bases)
    n_out = bases.shape[0] - k + 1
    g = max(1, -(-n_out // tile))
    # build overlapped (G, tile + k - 1) tiles; pad tail with invalid bases
    padded_len = g * tile + k - 1
    bases = jnp.pad(bases, ((0, padded_len - bases.shape[0]),), constant_values=255)
    starts = jnp.arange(g) * tile
    idx = starts[:, None] + jnp.arange(tile + k - 1)[None, :]
    tiles = bases[idx][:, None, :]
    out = K.kmer_hash_call(tiles, k=k, interpret=interpret)
    return out.reshape(-1)[:n_out]


@functools.partial(jax.jit, static_argnames=("k", "s", "interpret"))
def sketch_reads(reads, *, k, s, interpret=True):
    """(R, L) base-code reads -> (R, s) minhash sketches (INVALID-padded)."""
    reads = jnp.asarray(reads)
    hashes = jax.vmap(lambda r: kmer_hashes(r, k=k, interpret=interpret))(reads)
    return jax.vmap(lambda h: minhash_sketch(h, s))(hashes)


def sketch_reads_auto(reads, *, k, s):
    return sketch_reads(reads, k=k, s=s, interpret=should_interpret())
