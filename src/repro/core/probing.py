"""Probing schemes over the (p, W) row layout — COPS and baselines.

The table is a 2-D array of ``p`` rows (p prime) by ``W`` lanes.  One *probe
window* is one row: the whole row is examined with vector ops — the TPU
analogue of the paper's warp-cooperative window (§IV-B.2).  The *outer*
scheme walks rows; the *inner* scheme is always "linear over the W lanes of
the row", resolved by a vectorized vote (see ``vote_*`` below).

Schemes (outer walk), all incremental to stay u32-overflow-safe:

- ``"cops"``   — double hashing over rows: row_{l+1} = (row_l + g(k)) mod p.
                 This is the paper's COPS (DH outer + LP inner).  With W=1 it
                 degenerates to scalar double hashing (cuDPP-style baseline).
- ``"linear"`` — row_{l+1} = (row_l + 1) mod p.  With W=1 this is the
                 one-thread-per-key linear probing baseline (cuDF-style);
                 with W>1 it is "blocked LP".  Exhibits primary clustering.
- ``"quadratic"`` — row_{l+1} = (row_l + 2l + 1) mod p (incremental l^2).
- ``"bucketed"`` — two-choice bucket placement (Compact Parallel Hash
                 Tables, PAPERS.md): a key has exactly TWO candidate
                 buckets, b1 = h1(k) mod p and b2 = (b1 + g(k)) mod p with
                 g in [1, p-1] (so b2 != b1 always).  The walk is a COPS
                 walk truncated to two rows; the insert path adds bounded
                 cuckoo eviction on top (see ``core.cuckoo``).  Constant
                 probe length makes retrieval throughput flat in the load
                 factor — the high-rho lane.

Each key's walk starts at ``h1(k) mod p`` and runs at most ``max_probes``
attempts (default p: DH/LP visit every row exactly once, the paper's abort
criterion "all slots visited").

**Coverage clamp** (:func:`scheme_coverage` / :func:`effective_probes`):
a scheme only ever reaches ``scheme_coverage(scheme, p)`` *distinct* rows —
p for cops/linear, (p+1)/2 for quadratic (the quadratic residues
``l^2 mod p`` repeat as soon as ``l > (p-1)/2`` since ``l^2 = (p-l)^2``),
2 for bucketed.  Walks beyond that budget revisit rows: retrieval wastes
probes, multi-value counting double-counts, and the bulk engine's
claim fixpoint gives revisited rows a second chance the sequential
reference never takes.  Every engine clamps its per-walk budget to
``effective_probes`` so all walks are revisit-free by construction.

**Quotient storage** (``quotient=True`` store geometries): the bucketed
lane can store ``q*2 + choice`` instead of the key, where ``h = mix(k ^
seed)``, ``b1 = h mod p``, ``q = h // p`` and ``choice`` says whether the
slot is the key's first or second bucket.  ``g`` is derived from ``q``
alone so the full hash (and hence the key — the mixer is a bijection) is
recoverable from (row, stored word); see ``hashing.unmix_murmur3``.  The
helpers below (:func:`initial_row` / :func:`row_step` with
``quotient=True``, :func:`match_word`, :func:`stored_word`) let the
engines treat the pre-mixed hash as the "key word": the probe compare
target becomes attempt-dependent (``q*2 + attempt``), everything else is
unchanged.  Stored words satisfy ``q*2+1 < TOMBSTONE_KEY`` for p >= 3, so
the in-band sentinels stay unambiguous.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing

_U = jnp.uint32

SCHEMES = ("cops", "linear", "quadratic", "bucketed")

#: walks whose clamped budget is at most this many windows are unrolled by
#: the bulk engines instead of run as an early-exit while_loop — the
#: bucketed two-choice walk (budget 2) then costs the same at every load
#: factor, which is what keeps its retrieve throughput flat in rho
UNROLL_PROBES = 2

#: smallest buffer of the probe walk's compacted tail (``bulk.
#: probe_matches``): the keys still pending after window 1 move into a
#: buffer of 1/16 of the batch, then 1/256, ... while a buffer keeps at
#: least this many lanes; batches under 16 times this walk at full width
COMPACT_LANES = 1024


def scheme_coverage(scheme: str, num_rows: int) -> int:
    """Number of DISTINCT rows a scheme's walk can ever reach (static).

    cops/linear generate Z_p (full coverage); quadratic reaches only the
    (p+1)/2 quadratic residues (``l^2 mod p`` collides for l and p-l);
    bucketed is two-choice by definition.
    """
    if scheme == "quadratic":
        return (num_rows + 1) // 2
    if scheme == "bucketed":
        return min(2, num_rows)
    if scheme in ("cops", "linear"):
        return num_rows
    raise ValueError(f"unknown scheme {scheme!r}")


def effective_probes(scheme: str, max_probes: int, num_rows: int) -> int:
    """Per-walk probe budget clamped to the scheme's distinct-row coverage.

    The coverage-clamp bugfix: walking past the coverage revisits rows —
    spurious FULL/absent reports on quadratic (budget burnt on repeats),
    double-counted matches in multi-value counting, and jax/scan fixpoint
    divergence.  Semantics-preserving for cops/linear (clamp is a no-op).
    """
    return max(1, min(int(max_probes), scheme_coverage(scheme, num_rows)))


def stops_at_empty(scheme: str) -> bool:
    """Whether a walk may stop at the first window containing EMPTY.

    True for every scheme: inserts always claim the earliest candidate row
    of their probe sequence and deletes write TOMBSTONE (never EMPTY), so
    "window has EMPTY => key cannot live in any later row" is an invariant
    even under bucketed cuckoo eviction (victims move OUT of full buckets,
    and their vacated slot becomes a TOMBSTONE).  Kept as an explicit
    predicate so future schemes that break the invariant have one switch
    to flip.
    """
    return True


def initial_row(key_word: jax.Array, num_rows: int, seed: int,
                quotient: bool = False) -> jax.Array:
    """First probe row.  With ``quotient=True`` the engine's "key word" is
    already the full mixed hash ``h`` (see module docstring): the row is
    plainly ``h mod p`` — re-mixing would lose invertibility."""
    if quotient:
        return (key_word.astype(_U) % _U(num_rows)).astype(_U)
    return hashing.hash_rows(key_word, num_rows, seed)


def row_step(scheme: str, key_word: jax.Array, num_rows: int, seed: int,
             quotient: bool = False) -> jax.Array:
    """Per-key row increment (constant across attempts for cops/linear)."""
    if scheme in ("cops", "bucketed"):
        if quotient:
            # step must be a function of q = h // p ONLY so that decoding
            # a stored word (which keeps q but drops b1) can re-derive it
            return hashing.hash_step(key_word.astype(_U) // _U(num_rows),
                                     num_rows, seed)
        return hashing.hash_step(key_word, num_rows, seed)
    if scheme == "linear":
        return jnp.ones_like(key_word)
    if scheme == "quadratic":
        # placeholder; quadratic uses the attempt counter, see advance_row
        return jnp.ones_like(key_word)
    raise ValueError(f"unknown scheme {scheme!r}")


def advance_row(scheme: str, row: jax.Array, step: jax.Array, attempt: jax.Array,
                num_rows: int) -> jax.Array:
    """Next row after ``attempt`` completed probes (attempt counts from 0)."""
    p = _U(num_rows)
    if scheme == "quadratic":
        # (l+1)^2 - l^2 = 2l + 1
        inc = (_U(2) * attempt.astype(_U) + _U(1)) % p
    else:
        inc = step
    return (row + inc) % p


# ---------------------------------------------------------------------------
# quotient-store helpers (bucketed lane, key_words == 1)
# ---------------------------------------------------------------------------

def match_word(key_word: jax.Array, num_rows: int, attempt,
               quotient: bool = False) -> jax.Array:
    """Probe-compare target at ``attempt`` (0 = first bucket).

    Non-quotient stores compare the raw key word (attempt-independent).
    Quotient stores hold ``q*2 + choice``; a probe at attempt ``a``
    matches exactly the stored word ``q*2 + a``.
    """
    if not quotient:
        return key_word
    q = key_word.astype(_U) // _U(num_rows)
    a = attempt if isinstance(attempt, int) else attempt.astype(_U)
    return q * _U(2) + _U(1) * a


def stored_word(key_word: jax.Array, num_rows: int, choice,
                quotient: bool = False) -> jax.Array:
    """Word written into the key plane when a claim lands.

    ``choice`` is 0 when the slot's row is the key's first bucket, 1 for
    the second (for quotient stores; ignored otherwise).
    """
    if not quotient:
        return key_word
    q = key_word.astype(_U) // _U(num_rows)
    c = choice if isinstance(choice, int) else choice.astype(_U)
    return q * _U(2) + _U(1) * c


# ---------------------------------------------------------------------------
# In-window votes — the vector analogue of __ballot_sync + __ffs (paper step 3/4)
# ---------------------------------------------------------------------------

def vote_lowest(mask: jax.Array) -> jax.Array:
    """Index of the lowest set lane, or W if none.  mask: (..., W) bool."""
    w = mask.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    return jnp.min(jnp.where(mask, lanes, jnp.int32(w)), axis=-1)


def vote_any(mask: jax.Array) -> jax.Array:
    """Group-any over the window lanes."""
    return jnp.any(mask, axis=-1)


def vote_count(mask: jax.Array) -> jax.Array:
    """Population count over the window lanes (multi-value counting pass).
    Pinned to i32 (bare integer sums promote to i64 under x64)."""
    return jnp.sum(mask, axis=-1, dtype=jnp.int32)
