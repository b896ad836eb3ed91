"""Latency arithmetic over the per-request records of one window.

A record (``bench.serve.Record``) carries the request's due time, its
prompt length, and its deliveries: ``(t, n)`` pairs, n new output tokens
handed back at host time t.  The first delivery holds the first token.
All times are seconds on one host clock.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """q-th percentile, linear between order statistics; None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def ttft(records, t0: float, t1: float) -> list[float]:
    """Time to first token of every request due in [t0, t1), from its due
    time.  A request not answered by t1 counts with its wait so far."""
    out = []
    for r in records:
        if not t0 <= r.due < t1:
            continue
        first = r.deliveries[0][0] if r.deliveries else None
        out.append((first if first is not None and first <= t1 else t1)
                   - r.due)
    return out


def itl(records, t0: float, t1: float) -> list[float]:
    """Inter-token gaps of every output token after a request's first,
    delivered in [t0, t1]: the time since that request's previous delivery,
    shared evenly by the n tokens of one delivery."""
    out = []
    for r in records:
        for (tp, _), (t, n) in zip(r.deliveries, r.deliveries[1:]):
            if t0 <= t <= t1 and n > 0:
                out.extend([(t - tp) / n] * n)
    return out


def tokens_served(records, t0: float, t1: float) -> int:
    """Prompt tokens of requests whose first token came in [t0, t1], plus
    output tokens delivered in [t0, t1]."""
    total = 0
    for r in records:
        if r.deliveries and t0 <= r.deliveries[0][0] <= t1:
            total += r.prompt_len
        total += sum(n for t, n in r.deliveries if t0 <= t <= t1)
    return total
