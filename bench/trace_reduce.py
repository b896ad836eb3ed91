"""From a profiler trace to the device's busy time and where it idled.

``read_xplane`` takes what the reduction needs out of an ``.xplane.pb``:
the operations each TPU ran (its "XLA Ops" line) and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``).
``reduce`` works on plain tuples, so it is checked on synthetic traces.

Busy time is the union of a device's operation intervals inside the
window, averaged over the devices; idle is the rest of the window.  Each
idle stretch is charged to the innermost host span open at that moment
(what the host was doing while the chip waited), or to ``"no bench span"``.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

HOST_PREFIX = "bench."
NO_SPAN = "no bench span"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def read_xplane(path: str):
    """(ops per device {plane: [(name, start_s, end_s)]}, host spans
    [(name, start_s, end_s)]) from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return ops, spans


def busy_intervals(ops, t0: float, t1: float) -> np.ndarray:
    """Disjoint, sorted (start, end) rows covering the ops inside [t0, t1]."""
    if not ops:
        return np.zeros((0, 2))
    a = np.array([(s, e) for _, s, e in ops], float)
    a = np.clip(a, t0, t1)
    a = a[a[:, 1] > a[:, 0]]
    if not len(a):
        return np.zeros((0, 2))
    a = a[np.argsort(a[:, 0], kind="stable")]
    reach = np.maximum.accumulate(a[:, 1])
    new = np.ones(len(a), bool)
    new[1:] = a[1:, 0] > reach[:-1]
    starts = a[new, 0]
    idx = np.flatnonzero(new)
    ends = reach[np.append(idx[1:] - 1, len(a) - 1)]
    return np.stack([starts, ends], axis=1)


def gaps(busy: np.ndarray, t0: float, t1: float) -> np.ndarray:
    """The stretches of [t0, t1] that ``busy`` leaves uncovered."""
    edges = np.concatenate([[t0], busy.reshape(-1), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def innermost_segments(spans, t0: float, t1: float):
    """Nested host spans flattened into disjoint (start, end, label)
    pieces, each labelled with the innermost span open there."""
    spans = [x for x in spans if x[0] != HOST_PREFIX + "window"]
    # at one instant: ends before starts, and the longer span opens first
    events = sorted([(s, 1, s - e, i) for i, (_, s, e) in enumerate(spans)]
                    + [(e, 0, 0.0, i) for i, (_, s, e) in enumerate(spans)])
    out, stack, prev = [], [], t0

    def label():
        return spans[stack[-1]][0] if stack else NO_SPAN

    for t, opens, _, i in events:
        t = min(max(t, t0), t1)
        if t > prev:
            out.append((prev, t, label()))
            prev = t
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
    if t1 > prev:
        out.append((prev, t1, label()))
    return out


def reduce(ops_by_device: dict, spans, window, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, and the breakdown lists:
    the operations that took most device time, and idle seconds by the
    host span open while the first device idled."""
    t0, t1 = window
    if not ops_by_device or t1 <= t0:
        return {}
    busy = {d: busy_intervals(o, t0, t1) for d, o in ops_by_device.items()}
    busy_s = float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in busy.values()]))
    by_op = defaultdict(float)
    for o in ops_by_device.values():
        for name, s, e in o:
            by_op[name] += max(0.0, min(e, t1) - max(s, t0))
    first = sorted(busy)[0]
    idle = defaultdict(float)
    segs = innermost_segments([x for x in spans if x[2] > t0 and x[1] < t1],
                              t0, t1)
    j = 0
    for g0, g1 in gaps(busy[first], t0, t1):
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, label = segs[k]
            idle[label] += min(b, g1) - max(a, g0)
            k += 1
    n = len(ops_by_device)
    return {
        "busy_s": busy_s,
        "window_s": t1 - t0,
        "device_ops": sorted(([k, v / n] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }
