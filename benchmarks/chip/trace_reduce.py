"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

- **Device ops**: the events of each device plane's ``XLA Ops`` line (on a
  TPU, planes ``/device:TPU:<n>``), named by their HLO instruction
  (``fusion.3``).  A trace with no device plane is an error, unless the
  caller asks for ``host_ops``: then (XLA's CPU backend, in the tests) the
  host events that carry an ``hlo_op`` stat stand in, as one device.
- **Window**: the host span ``<prefix>window`` that the harness wraps
  around its measured loop; everything is clipped to it.
- **Busy**: the union of a device's op intervals in the window; ``busy_s``
  is its length averaged over devices, ``idle_share`` is 1 - busy/window.
- **Top ops**: self time per op name in the window (an op's time less that
  of the ops nested in it), averaged over devices.
- **Collective time**: the union of the intervals of collective ops, by
  name
  (all-to-all, all-reduce, all-gather, reduce-scatter, collective-permute,
  send/recv), averaged over devices.
- **Idle gaps**: each stretch of the window in which a device runs
  nothing is named by the innermost host span (``<prefix>*``) that covers
  its midpoint, ``<prefix>window`` itself when no narrower span does;
  seconds per name, averaged over devices.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

COLLECTIVE = re.compile(                 # XLA:TPU writes all_to_all.31
    r"all[-_]to[-_]all|all[-_]reduce|all[-_]gather|reduce[-_]scatter|"
    r"collective[-_]permute|\bsend\b|\brecv\b|send[-_]done|recv[-_]done",
    re.I)
TOP = 10


def find(trace_dir: Path) -> Path:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``fusion.3`` from a TPU op event's ``%fusion.3 = u32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _has_hlo_op(e) -> bool:
    return any(k == "hlo_op" for k, _ in e.stats)


def read_trace(path: Path, host_ops: bool = False):
    """(device name -> lines of ops [(start_ns, end_ns, op)], host spans
    [(start_ns, end_ns, name)]).  With ``host_ops``, a trace with no
    device plane takes its host events that carry ``hlo_op`` instead."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: dict[str, list] = {}
    hlo_ops: list = []
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = [[(int(e.start_ns), int(e.start_ns + e.duration_ns),
                       op_name(e.name)) for e in line.events]
                     for line in plane.lines if line.name == "XLA Ops"]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ops = []
                for e in line.events:
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    spans.append((s, t, e.name))
                    if t > s and _has_hlo_op(e):
                        ops.append((s, t, e.name))
                if ops:
                    hlo_ops.append(ops)
    if not devices:
        if not host_ops:
            raise ValueError(f"{path}: no device plane with XLA ops")
        devices = {"host": hlo_ops}
    return devices, spans


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in intervals
            if t > lo and s < hi]


def _total(intervals) -> int:
    return sum(t - s for s, t in intervals)


def self_times(ops) -> list[tuple[str, int]]:
    """(op, self ns) of one line of ops, where an op may contain others
    (a ``while`` holds the ops of its body): its time less its children's."""
    out: list[list] = []
    stack: list[int] = []
    for s, t, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(t, parent[2]) - s
        out.append([n, t - s, t])
        stack.append(len(out) - 1)
    return [(n, d) for n, d, _ in out]


def options():
    """Profiler options for the traced window: host spans and device ops,
    without the Python tracer (it slows the host) or HLO protos (large)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def reduce(path: Path, prefix: str = "bench.", host_ops: bool = False
           ) -> dict:
    """The device numbers of the window in the trace at ``path``."""
    return reduce_events(*read_trace(path, host_ops), prefix=prefix)


def reduce_events(devices: dict, spans: list, prefix: str = "bench.") -> dict:
    """``reduce`` on events already read: ``devices`` maps a device name to
    its lines of ops ``(start_ns, end_ns, name)``; ``spans`` are host spans
    alike."""
    windows = [(s, t) for s, t, n in spans if n == prefix + "window"]
    if not windows:
        raise ValueError(f"no host span {prefix}window")
    lo, hi = windows[0]
    inner = sorted((s, t, n) for s, t, n in spans
                   if n.startswith(prefix) and n != prefix + "window")
    starts = [s for s, _, _ in inner]

    def host_doing(mid):
        # the latest-starting span that covers mid is the innermost one
        i = bisect.bisect_right(starts, mid)
        for s, t, n in reversed(inner[max(0, i - 8):i]):
            if s <= mid <= t:
                return n
        return prefix + "window"

    ndev = len(devices)
    busy = coll = 0
    per_op: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    for lines in devices.values():
        lines = [[(max(s, lo), min(t, hi), n) for s, t, n in ops
                  if t > lo and s < hi] for ops in lines]
        flat = [op for ops in lines for op in ops]
        merged = union((s, t) for s, t, _ in flat)
        busy += _total(merged)
        coll += _total(union((s, t) for s, t, n in flat
                             if COLLECTIVE.search(n)))
        for ops in lines:
            for n, d in self_times(ops):
                per_op[n] += d / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps[host_doing((g0 + g1) / 2)] += (g1 - g0) / 1e9
    window_s = (hi - lo) / 1e9
    busy_s = busy / 1e9 / max(ndev, 1)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "collective_s": coll / 1e9 / max(ndev, 1),
        "devices": ndev,
        "top_ops": _top({k: v / ndev for k, v in per_op.items()}),
        "idle_gaps": _top({k: v / ndev for k, v in gaps.items()}),
    }


def _top(d: dict) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
