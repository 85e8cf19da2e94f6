"""Seeded inputs for the chip benchmark, made on the device.

Keys are a bijection of an index, so distinct indices give distinct keys
and none is one of the table's two sentinels; the value of a key is a
function of the key and the seed.  That is the plain truth every answer is
compared with: index ``i < pairs`` is present, any other index is absent.

The keys of a build, their order, and which keys each query batch holds
are the same for every seed: the bulk build's arbitration sweeps and the
walk's loop trips are maxima over a key set and its order, and when the
seed drew them the time of a 2^24-pair build moved by a whole sweep (about
6%) from seed to seed.  The seed draws the values, the order of the keys
within each query batch and the order of the batches.

``keys_of`` is copied from ``chip_smoke.py`` together with the murmur3
finalizer it uses, so that a change to the library cannot move the
yardstick.

A traffic mix (``traffic/<name>.json``) is data that ``query_indices``
reads:

- ``op``: ``"insert"`` (a whole build of a fresh table) or ``"retrieve"``;
- ``keys_per_call``: ``"table"`` (all the configuration's pairs) or a count;
- ``calls``: how many distinct query batches are made (retrieve only); the
  window runs whole rounds over them;
- ``present_share``: the share of each batch drawn from present keys; the
  count is fixed, so every seed sees the same sizes;
- ``order``: ``"permutation"`` (distinct indices, seeded order) or
  ``"uniform"`` (drawn with replacement).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_U = jnp.uint32
EMPTY_KEY = np.uint32(0xFFFFFFFF)
TOMBSTONE_KEY = np.uint32(0xFFFFFFFE)


def _fmix32(h):
    """murmur3's 32-bit finalizer (a bijection of u32)."""
    h = h ^ (h >> 16)
    h = h * _U(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _U(0xC2B2AE35)
    return h ^ (h >> 16)


def _seed_consts(seed: int) -> tuple[int, int]:
    a = ((seed * 2 + 1) * 0x9E3779B1 | 1) & 0xFFFFFFFF     # odd: invertible
    b = (seed * 0x85EBCA6B + 0x27D4EB2F) & 0xFFFFFFFF
    return a, b


KEY_SET = 0x5EED


def keys_of(idx, key_set: int = KEY_SET):
    """Distinct u32 keys for distinct indices below 2^32 - 2.

    An odd multiply-add and the finalizer make a bijection of u32; walking
    it again from the (at most two) indices it sends onto the sentinels
    keeps it a bijection that avoids them.
    """
    a, b = _seed_consts(key_set)

    def perm(x):
        return _fmix32(x * _U(a) + _U(b))

    x = perm(idx.astype(_U))
    for _ in range(2):
        x = jnp.where(x >= TOMBSTONE_KEY, perm(x), x)
    return x


def salt(seed: int) -> np.uint32:
    """The seed's share of every value; passed to programs as data, so a
    new seed compiles nothing."""
    return np.uint32((seed * 0x7F4A7C15 + 1) & 0xFFFFFFFF)


def values_of(keys, salt):
    """The value stored for each key under a seed's ``salt``."""
    return keys * _U(0x9E3779B1) + salt


def rng(seed: int):
    """A JAX PRNG key for any non-negative whole ``seed``."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def pairs(n: int, salt):
    """The ``n`` (key, value) pairs of a build, in index order."""
    keys = keys_of(jnp.arange(n, dtype=_U))
    return keys, values_of(keys, salt)


def device_pairs(n: int, seed: int, sharding):
    """``pairs(n, salt(seed))`` made on the device in one jitted call and
    placed by ``sharding``."""
    return jax.jit(pairs, static_argnums=0, out_shardings=sharding)(
        n, salt(seed))


def readback_indices(n: int):
    """The indices a build's check reads back: every present one
    (``0..n-1``) and ``n // 64`` absent ones after them."""
    return jnp.arange(n + n // 64, dtype=_U)


def batch_shape(traffic: dict, n_pairs: int) -> tuple[int, int]:
    """(calls, keys per call) of a retrieve mix over ``n_pairs`` pairs."""
    per_call = traffic["keys_per_call"]
    per_call = n_pairs if per_call == "table" else int(per_call)
    return int(traffic.get("calls", 1)), per_call


def query_indices(traffic: dict, n_pairs: int, seed: int):
    """(calls, keys_per_call) u32 indices; ``i < n_pairs`` is present.

    Which keys each batch holds is drawn once for every seed (``KEY_SET``):
    a batch's time follows the longest probe sequence among its keys, so
    batches drawn per seed moved the 95th percentile by 5% between seeds.
    The seed shuffles the keys within each batch and the batches' order."""
    calls, per_call = batch_shape(traffic, n_pairs)
    n_present = round(per_call * float(traffic["present_share"]))
    k_present, k_absent = jax.random.split(rng(KEY_SET))
    order = traffic["order"]
    if order == "permutation":
        if calls * n_present > n_pairs:
            raise ValueError("a permutation cannot draw more present keys "
                             "than the table holds")
        present = jax.random.permutation(k_present, n_pairs)
        present = present[:calls * n_present]
    elif order == "uniform":
        present = jax.random.randint(k_present, (calls * n_present,), 0,
                                     n_pairs)
    else:
        raise ValueError(f"unknown key order {order!r}")
    absent = n_pairs + jax.random.randint(
        k_absent, (calls * (per_call - n_present),), 0, n_pairs)
    idx = jnp.concatenate([present.reshape(calls, n_present),
                           absent.reshape(calls, per_call - n_present)],
                          axis=1).astype(_U)
    k_keys, k_calls = jax.random.split(rng(seed))
    idx = jax.random.permutation(k_keys, idx, axis=1, independent=True)
    return jax.random.permutation(k_calls, idx, axis=0)
