"""95th percentile of the gap before each output token after a request's
first, over the tokens handed back in the window."""
from bench import tails

UNIT = "ms"


def read(rec):
    t0, t1 = rec["window"]
    p = tails.percentile(tails.itl(rec["records"], t0, t1), 95)
    return None if p is None else 1e3 * p
