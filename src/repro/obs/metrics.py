"""In-graph table telemetry: the ``TableStats`` pytree.

Every engine entry point (``single_value``/``multi_value``/``counting``/
``bucket_list`` insert and retrieval, and the bulk engines underneath)
accepts ``stats: bool = False``.  The flag is **static**: when False the
traced graph is exactly the pre-telemetry graph (byte-identical HLO,
census-asserted by ``tests/test_obs.py``); when True the walk loops carry
a few extra i32 vectors and the op returns a ``TableStats`` alongside its
usual results — all accumulated inside the compiled graph, no host
round-trips.

Conventions
-----------

- **probe length** = probe *windows examined* by an element's walk: a key
  found in its first window has probe length 1; a claimer placed on its
  k-th row has probe length k; FULL elements report ``max_probes``.  Only
  elements that actually walk (representatives after dedup, live claimers)
  contribute — masked and duplicate elements count 0 and are excluded.
- **probe histogram** bins are fixed powers of two: bin i counts lengths
  in ``(2^(i-1), 2^i]`` (bin 0 = length 1), the last bin is open-ended.
  ``probe_sum``/``probe_n`` carry the exact first moment so the roofline
  bytes model can use the true mean rather than a bin midpoint.
- **status histogram** is indexed by the STATUS_* codes (INSERTED=0,
  UPDATED=1, FULL=2, MASKED=3, POOL_FULL=4).  Pure retrieval ops have no
  statuses and leave it zero.
- **fixpoint_iters** counts virtual-fill arbitration sweeps
  (``bulk.place_claims``) — 0 for ops that never place.
- **tail_rounds** counts the compacted rounds of the probe walk
  (``bulk.probe_matches``): one per buffer entered, more where a buffer
  overflows; 0 when every key stops in its first window or the batch is
  too small to compact.
- **live/tombstone slots + load factor** are a census of key plane 0 of
  the post-op store: exactly the signals a growth/compaction policy
  triggers on (ROADMAP).

Backends: ``backend="jax"`` threads the counters through the engine loops
themselves.  The scan/pallas backends run their op *unchanged* (outputs
stay bit-exact with ``stats=False`` — the parity suite asserts it) and
derive probe lengths from a measurement walk against the post-op table,
traced into the same graph (``measure_probe_lengths``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.common import (
    EMPTY_KEY,
    TOMBSTONE_KEY,
    register_struct,
)

_U = jnp.uint32
_I = jnp.int32
_F = jnp.float32

NUM_STATUS = 5                       # INSERTED/UPDATED/FULL/MASKED/POOL_FULL
NUM_PROBE_BINS = 16                  # bin i <=> probe length in (2^(i-1), 2^i]
_EDGES = (2 ** np.arange(NUM_PROBE_BINS)).astype(np.int32)   # 1,2,4,...,2^15


@register_struct
@dataclasses.dataclass
class TableStats:
    """Per-op telemetry accumulated inside the compiled graph."""
    status_hist: jax.Array           # (NUM_STATUS,) i32
    probe_hist: jax.Array            # (NUM_PROBE_BINS,) i32
    probe_sum: jax.Array             # i32 — sum of probe lengths
    probe_n: jax.Array               # i32 — number of walking elements
    fixpoint_iters: jax.Array        # i32 — arbitration sweeps
    tail_rounds: jax.Array           # i32 — compacted probe-walk rounds
    live_slots: jax.Array            # i32
    tombstone_slots: jax.Array       # i32
    load_factor: jax.Array           # f32 — live / capacity

    # -- host-side readers ---------------------------------------------------
    def mean_probe_len(self) -> float:
        n = int(self.probe_n)
        return float(self.probe_sum) / n if n else 0.0

    def probe_quantile(self, q: float) -> float:
        """Approximate quantile from the histogram (upper bin edge)."""
        hist = np.asarray(self.probe_hist)
        total = int(hist.sum())
        if total == 0:
            return 0.0
        cum = np.cumsum(hist)
        i = int(np.searchsorted(cum, q * total, side="left"))
        return float(_EDGES[min(i, NUM_PROBE_BINS - 1)])

    def as_dict(self) -> dict:
        """Plain-python rendering (for JSON rows / report tables)."""
        return {
            "status_hist": [int(x) for x in np.asarray(self.status_hist)],
            "probe_len_mean": self.mean_probe_len(),
            "probe_len_p50": self.probe_quantile(0.50),
            "probe_len_p99": self.probe_quantile(0.99),
            "fixpoint_iters": int(self.fixpoint_iters),
            "tail_rounds": int(self.tail_rounds),
            "live_slots": int(self.live_slots),
            "tombstone_slots": int(self.tombstone_slots),
            "load_factor": float(self.load_factor),
        }


def empty() -> TableStats:
    z = jnp.zeros((), _I)
    return TableStats(
        status_hist=jnp.zeros((NUM_STATUS,), _I),
        probe_hist=jnp.zeros((NUM_PROBE_BINS,), _I),
        probe_sum=z, probe_n=z, fixpoint_iters=z, tail_rounds=z,
        live_slots=z, tombstone_slots=z, load_factor=jnp.zeros((), _F))


@register_struct
@dataclasses.dataclass
class StreamCounters:
    """Streaming-ingestion telemetry carried *inside* a ``lax.scan``.

    The streaming engine (``repro.data.stream``) threads one of these
    through its scan carry, so a whole stream's counters accumulate in
    the compiled graph — zero host round-trips mid-stream, read once at
    the end.  All fields are scalar i32 (same dtype under x64, so the
    carry is stable across the packed-sort lane toggle).
    """
    chunks: jax.Array            # chunks processed
    kept: jax.Array              # sequences surviving dedup
    hits: jax.Array              # watchlist join hits (aggregated)
    erased: jax.Array            # fingerprints forgotten (ring expiry)
    compactions: jax.Array       # in-graph compactions fired
    live_slots: jax.Array        # dedup-table census after last chunk
    tombstone_slots: jax.Array   # ditto

    def as_dict(self) -> dict:
        return {f.name: int(getattr(self, f.name))
                for f in dataclasses.fields(self)}


def stream_counters_empty() -> StreamCounters:
    # one zeros() call PER field: the stream carry is donated, and two
    # pytree leaves sharing one buffer make donation reject the call
    # ("attempt to donate the same buffer twice")
    z = lambda: jnp.zeros((), _I)
    return StreamCounters(chunks=z(), kept=z(), hits=z(), erased=z(),
                          compactions=z(), live_slots=z(),
                          tombstone_slots=z())


def status_hist(status: jax.Array) -> jax.Array:
    """(n,) STATUS_* codes -> (NUM_STATUS,) counts."""
    idx = jnp.clip(status.astype(_I), 0, NUM_STATUS - 1)
    return jnp.zeros((NUM_STATUS,), _I).at[idx].add(1)


def probe_hist(plen: jax.Array, active: jax.Array):
    """Bin probe lengths of ``active`` elements into the power-of-two
    histogram.  Returns (hist, probe_sum, probe_n)."""
    plen = plen.astype(_I)
    counted = active & (plen > 0)
    edges = jnp.asarray(_EDGES, _I)
    # bin = first i with plen <= 2^i  (length 1 -> bin 0)
    b = jnp.searchsorted(edges, plen, side="left").astype(_I)
    b = jnp.where(counted, jnp.clip(b, 0, NUM_PROBE_BINS - 1), NUM_PROBE_BINS)
    hist = jnp.zeros((NUM_PROBE_BINS,), _I).at[b].add(1, mode="drop")
    return (hist, jnp.sum(jnp.where(counted, plen, 0), dtype=_I),
            jnp.sum(counted, dtype=_I))


def slot_stats(ops, store):
    """Census of key plane 0: (live, tombstones, load_factor)."""
    kp0 = ops.key_planes(store)[0]
    live = jnp.sum((kp0 != EMPTY_KEY) & (kp0 != TOMBSTONE_KEY), dtype=_I)
    tomb = jnp.sum(kp0 == TOMBSTONE_KEY, dtype=_I)
    lf = live.astype(_F) / _F(max(ops.num_rows * ops.window, 1))
    return live, tomb, lf


def table_stats(ops, store, *, status=None, plen=None, active=None,
                fixpoint_iters=None, tail_rounds=None) -> TableStats:
    """Assemble a ``TableStats`` from whatever an op measured.

    ``store`` is the *post-op* store (slot census); any of the walk-level
    inputs may be omitted (pure retrieval has no statuses, scan backends
    have no fixpoint)."""
    st = empty()
    live, tomb, lf = slot_stats(ops, store)
    sh = st.status_hist if status is None else status_hist(status)
    if plen is not None:
        act = jnp.ones(plen.shape, bool) if active is None else active
        ph, ps, pn = probe_hist(plen, act)
    else:
        ph, ps, pn = st.probe_hist, st.probe_sum, st.probe_n
    fx = st.fixpoint_iters if fixpoint_iters is None else \
        jnp.asarray(fixpoint_iters, _I)
    tr = st.tail_rounds if tail_rounds is None else \
        jnp.asarray(tail_rounds, _I)
    return TableStats(status_hist=sh, probe_hist=ph, probe_sum=ps,
                      probe_n=pn, fixpoint_iters=fx, tail_rounds=tr,
                      live_slots=live, tombstone_slots=tomb, load_factor=lf)


def merge(a: TableStats, b: TableStats) -> TableStats:
    """Accumulate two ops' stats (slot census / load factor taken from b,
    the later op)."""
    return TableStats(
        status_hist=a.status_hist + b.status_hist,
        probe_hist=a.probe_hist + b.probe_hist,
        probe_sum=a.probe_sum + b.probe_sum,
        probe_n=a.probe_n + b.probe_n,
        fixpoint_iters=a.fixpoint_iters + b.fixpoint_iters,
        tail_rounds=a.tail_rounds + b.tail_rounds,
        live_slots=b.live_slots, tombstone_slots=b.tombstone_slots,
        load_factor=b.load_factor)


def measure_probe_lengths(tstatic, store, keys, active,
                          words=None) -> jax.Array:
    """Bolt-on probe-length measurement: one stats-enabled match walk
    against ``store`` (windows examined to hit the key or its EMPTY
    frontier).  Used by the scan/pallas backends, whose op itself is kept
    untouched — the measurement is an extra read-only walk traced into
    the same graph.  ``words`` overrides the probe words (quotient tables
    probe by the full hash, not the raw key word)."""
    from repro.core import bulk
    from repro.core import single_value as sv
    if words is None:
        words = sv.key_hash_word(keys)
    return bulk.probe_matches(tstatic, store, keys, words, active,
                              stats=True)[3]


def bolt_on_stats(table, keys, status=None, mask=None) -> TableStats:
    """TableStats for an op that ran *unchanged* (scan/pallas backends).

    Dedups the batch like the bulk engines (one walking representative
    per distinct live key) and measures probe lengths with a read-only
    walk against the post-op store; status histogram and slot census come
    from the op's own outputs/state.  Traced into the caller's graph."""
    from repro.core import bulk_retrieve
    from repro.core import single_value as sv
    keys = sv.normalize_key_batch(keys, table.key_words, "keys")
    n = keys.shape[0]
    if n == 0:
        return table_stats(table.ops, table.store, status=status)
    live = jnp.ones((n,), bool) if mask is None else mask
    is_rep, _ = bulk_retrieve.group_queries(keys, live)
    from repro.core import probing
    tstatic = (table.ops, table.scheme, table.seed,
               probing.effective_probes(table.scheme, table.max_probes,
                                        table.num_rows))
    plen = measure_probe_lengths(tstatic, table.store, keys, is_rep,
                                 words=sv.probe_words(table, keys))
    return table_stats(table.ops, table.store, status=status, plen=plen,
                       active=is_rep)
