"""Median wait from a request's due time to its first prefill chunk (the
program's ``prefill.chunk`` span), over the requests due in the window;
one never admitted counts with its wait so far."""
from bench import tails

UNIT = "s"


def read(rec):
    if rec.get("spans") is None:
        return None
    t0, t1 = rec["window"]
    start = {}
    for e in rec["spans"]:
        rid = (e.get("args") or {}).get("rid")
        if e["name"] == "prefill.chunk" and rid is not None:
            start.setdefault(rid, rec["span_clock0"] + e["ts"] * 1e-6)
    waits = [min(start.get(r.rid, t1), t1) - r.due
             for r in rec["records"] if t0 <= r.due < t1]
    return tails.percentile(waits, 50)
