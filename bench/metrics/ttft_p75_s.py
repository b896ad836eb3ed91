"""75th percentile of time to first token over every request due in the
window, from its due time; one still waiting counts with its wait so far.
At the chat cell's ~40 requests a window, p75 is the highest percentile
with ten requests beyond it."""
from bench import tails

UNIT = "s"


def read(rec):
    t0, t1 = rec["window"]
    return tails.percentile(tails.ttft(rec["records"], t0, t1), 75)
