"""The plain reference: what a correct table answers, from the truth alone.

Imports nothing of the library and takes nothing it made except the
answers under test.  The truth is ``traffic``'s key bijection: the build of
``n`` pairs inserts the keys of indices ``0..n-1`` with ``values_of`` of
each under the run's seed; a query for index ``i`` is present iff
``i < n``.

Every function returns exact counts of disagreements; a correct table
gives 0 for each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import traffic

_I = jnp.int32


@functools.partial(jax.jit, static_argnames=("n",))
def table_disagreements(slot_keys, slot_values, count, salt, *, n: int):
    """A table built from the ``n`` pairs of a seed's ``salt``
    (``traffic.salt``), read as raw slots.

    ``slot_keys`` / ``slot_values`` hold every slot of every shard (the
    first key and value word).  Returns the disagreements with the pairs:

    - ``live_gap``: |live slots - n|;
    - ``count_gap``: |the table's own count - n|;
    - ``keys_missing``: keys of the pairs not found among the live slots;
    - ``values_wrong``: live slots whose value is not the key's value.
    """
    keys = slot_keys.reshape(-1)
    vals = slot_values.reshape(-1)
    live = (keys != traffic.EMPTY_KEY) & (keys != traffic.TOMBSTONE_KEY)
    n_live = jnp.sum(live, dtype=_I)
    held = jnp.sort(jnp.where(live, keys, traffic.EMPTY_KEY))
    want, _ = traffic.pairs(n, salt)
    at = jnp.clip(jnp.searchsorted(held, want), 0, held.shape[0] - 1)
    found = (held[at] == want) & (want != traffic.EMPTY_KEY)
    return {
        "live_gap": jnp.abs(n_live - n),
        "count_gap": jnp.abs(jnp.sum(count, dtype=_I) - n),
        "keys_missing": n - jnp.sum(found, dtype=_I),
        "values_wrong": jnp.sum(
            live & (vals != traffic.values_of(keys, salt)), dtype=_I),
    }


@functools.partial(jax.jit, static_argnames=("n",))
def answer_disagreements(idx, values, found, salt, *, n: int):
    """Lookup answers for query indices ``idx`` against the truth:
    ``found_wrong`` (present reported absent or absent reported present)
    and ``values_wrong`` (a present key answered with another value)."""
    present = idx < jnp.uint32(n)
    want = traffic.values_of(traffic.keys_of(idx), salt)
    values = values.reshape(idx.shape)
    return {
        "found_wrong": jnp.sum(found.reshape(idx.shape) != present, dtype=_I),
        "values_wrong": jnp.sum(present & (values != want), dtype=_I),
    }
