"""Packed-u64 sort lane parity (``bulk._sort_batch``).

Two-word keys (u64 two-plane and composite kw=2) fuse their sort planes
into one ``plane0 << 32 | plane1`` uint64 word when the config sorts
genuine uint64 (``compat.supports_u64_sort`` — x64 on).  The packed word
compares exactly like the two-plane lexicographic pair, so EVERYTHING
downstream of the general dedup lane — group structure, insert/update
table state, statuses, fused retrieval layout, join pairs — must be
bit-identical between the two lanes.  These tests run each op on the
default config (two-plane lane) and again under ``jax.enable_x64(True)``
(packed lane) and diff the u32 outputs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bulk
from repro.core import compat
from repro.core import multi_value as mv
from repro.core import single_value as sv
from repro.relational import join as rjoin

_U = jnp.uint32


def _x64():
    return jax.enable_x64(True)


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def test_lane_detection_tracks_x64():
    assert not compat.supports_u64_sort()
    with _x64():
        assert compat.supports_u64_sort()
    assert not compat.supports_u64_sort()


def test_sort_batch_bit_exact():
    rng = np.random.default_rng(7)
    n = 4096
    # tiny universes: heavy duplicate groups, shared-lo and shared-hi keys
    keys = rng.integers(0, 40, size=(n, 2)).astype(np.uint32)
    mask = rng.random(n) < 0.85
    pay = rng.integers(0, 2**31, size=(n,)).astype(np.uint32)
    args = (jnp.asarray(keys), jnp.asarray(mask), [jnp.asarray(pay)])
    ref = _np(bulk._sort_batch(*args))
    with _x64():
        got = _np(bulk._sort_batch(*args))
    for r, g in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(r, g)


def _composite_batch(rng, n):
    hi = rng.integers(0, 5, n).astype(np.uint32)
    lo = rng.integers(1, 9, n).astype(np.uint32)
    vals = rng.integers(0, 2**31, n).astype(np.uint32)
    return (jnp.asarray(hi), jnp.asarray(lo)), jnp.asarray(vals)


def test_single_value_insert_update_bit_exact():
    rng = np.random.default_rng(11)
    keys, vals = _composite_batch(rng, 600)
    mask = jnp.asarray(rng.random(600) < 0.9)

    def run():
        t = sv.create(2048, key_words=2)
        t, st = sv.insert(t, keys, vals, mask=mask)
        t, st2 = sv.update_values(t, keys, lambda old, k, v: old + v, 0,
                                  values=vals, combine=("add",))
        got, found = sv.retrieve(t, keys)
        return _np((t.store, t.count, st, st2, got, found))

    ref = run()
    with _x64():
        got = run()
    for r, g in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(r, g)


def test_multi_value_retrieve_bit_exact():
    rng = np.random.default_rng(13)
    keys, vals = _composite_batch(rng, 500)

    def run():
        t = mv.create(2048, key_words=2)
        t, st = mv.insert(t, keys, vals)
        out, off, cnt = mv.retrieve_all(t, keys, out_capacity=2048)
        return _np((st, out, off, cnt))

    ref = run()
    with _x64():
        got = run()
    for r, g in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(r, g)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_composite_join_bit_exact(how):
    rng = np.random.default_rng(17)
    bkeys, bvals = _composite_batch(rng, 400)
    pkeys, _ = _composite_batch(rng, 300)

    def run():
        t, _ = rjoin.build(bkeys, capacity=2048, key_words=2)
        res = rjoin.probe(t, pkeys, 4096, how)
        return _np((res.build_idx, res.probe_idx, res.valid, res.matched,
                    res.total))

    ref = run()
    with _x64():
        got = run()
    for r, g in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(r, g)
