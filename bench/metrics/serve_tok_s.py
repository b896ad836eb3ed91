"""Prompt tokens of requests first answered in the window plus output
tokens handed back in it, per second of window."""
from bench import tails

UNIT = "tokens/s"


def read(rec):
    t0, t1 = rec["window"]
    return tails.tokens_served(rec["records"], t0, t1) / (t1 - t0)
