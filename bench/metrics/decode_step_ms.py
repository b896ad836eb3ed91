"""Engine decode device seconds per decode step (the program's counters at
its jit boundary), in ms."""
UNIT = "ms"


def read(rec):
    c = rec["counters"]
    return 1e3 * c["decode_device_s"] / c["decode_steps"] \
        if c["decode_steps"] else None
