"""Engine prefill device seconds per 1000 prefilled tokens (the program's
counters), in a cell judged by tokens served per second."""
UNIT = "ms"


def read(rec):
    c = rec["counters"]
    return 1e6 * c["prefill_device_s"] / c["prefill_tokens"] \
        if c["prefill_tokens"] else None
