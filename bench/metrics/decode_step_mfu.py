"""The decode steps' share of the chip's peak at their roofline: the least
time their work needs (every weight read once at bf16 plus the live K/V
rows, or their FLOPs at peak, whichever is longer; ``bench/work.py``) over
the engine's decode device seconds, in %."""
from bench import work

UNIT = "%"


def read(rec):
    dev_s, peak = rec["counters"]["decode_device_s"], rec.get("peak")
    if not dev_s or not peak:
        return None
    w = rec["work"]
    return 100.0 * work.least_time(w["decode_flops"], w["decode_bytes"],
                                   peak) / dev_s
