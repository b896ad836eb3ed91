"""Host time per decode step spent copying each block's tokens and logits
off the device and scanning them for NaNs: the program's ``decode.fetch``
and ``decode.guard`` spans inside the window, summed, over the engine's
decode steps, in ms."""
from bench import program_trace

UNIT = "ms"


def read(rec):
    s = program_trace.span_seconds(rec, {"decode.fetch", "decode.guard"})
    steps = rec["counters"]["decode_steps"]
    return None if s is None or not steps else 1e3 * s / steps
