"""From a device trace's event list to busy time, gaps and per-op totals.

Events are [plane, line, name, start_ns, duration_ns] (`trace_extract`).
The device's operations are the events of the line `OP_LINE` on planes
`/device:TPU:<n>`: XLA's own op names, one event an executed op. Busy
time is the union of those intervals, a device at a time, averaged over
the devices; everything else of the traced window is idle.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
NAME_CHARS = 160     # an op's name is its whole HLO text: keep its head


def device_ops(events) -> Dict[str, List[Tuple[int, int, str]]]:
    """plane -> [(start_ns, end_ns, name)] of its op line, by start."""
    out: Dict[str, list] = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE_PREFIX) and line == OP_LINE:
            out.setdefault(plane, []).append((start, start + dur, name))
    for ops in out.values():
        ops.sort()
    return out


def union(intervals) -> List[Tuple[int, int]]:
    """Sorted (start, end) pairs -> the disjoint intervals they cover."""
    merged: List[list] = []
    for start, end in intervals:
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    per_plane = [sum(b - a for a, b in union((s, e) for s, e, _n in ops))
                 for ops in device_ops(events).values()]
    return sum(per_plane) / len(per_plane) / 1e9 if per_plane else 0.0


def op_totals(events, top: int = 10) -> List[list]:
    """[[op name, seconds]] of the ops that took most time."""
    totals: Dict[str, int] = {}
    for ops in device_ops(events).values():
        for start, end, name in ops:
            totals[name] = totals.get(name, 0) + (end - start)
    ranked = sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[name[:NAME_CHARS], ns / 1e9] for name, ns in ranked[:top]]


def idle_gaps(events, top: int = 10) -> List[list]:
    """[[what the host was doing, seconds]] of the longest gaps between
    ops on the first device. The host's spans are not on the profiler's
    clock yet, so every gap is `unattributed`."""
    planes = device_ops(events)
    if not planes:
        return []
    covered = union((s, e) for s, e, _n in planes[sorted(planes)[0]])
    gaps = sorted((b[0] - a[1] for a, b in zip(covered, covered[1:])),
                  reverse=True)
    return [["unattributed", ns / 1e9] for ns in gaps[:top]]
