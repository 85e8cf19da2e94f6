"""Byte floors: the HBM bytes any implementation of the same table op moves.

A floor counts what the op's semantics force, never a probe window that a
particular engine happens to walk, so a share of the HBM roofline built on
it reads the same work whatever implements it and cannot pass 100%:

- each key and value the caller passes in is read once;
- each result is written once: an insert's per-key status (i32), a
  lookup's value and found flag (u32 + bool);
- the one slot that a present key occupies is written (insert) or read
  (lookup): its key and value words;
- a fresh table's key plane is written once with the empty sentinel
  (values need no initial write).

Sizes are in 32-bit words as the configuration states them.
"""

from __future__ import annotations

WORD = 4
STATUS = 4
FOUND = 1


def insert_floor(n_keys: int, capacity: int, key_words: int = 1,
                 value_words: int = 1) -> int:
    """Create a table of ``capacity`` slots and insert ``n_keys`` keys."""
    pair = (key_words + value_words) * WORD
    return capacity * key_words * WORD + n_keys * (pair + STATUS + pair)


def lookup_floor(n_present: int, n_absent: int, key_words: int = 1,
                 value_words: int = 1) -> int:
    """Look up ``n_present`` present and ``n_absent`` absent keys."""
    pair = (key_words + value_words) * WORD
    per_query = key_words * WORD + value_words * WORD + FOUND
    return (n_present + n_absent) * per_query + n_present * pair
