"""Share of the traced window in which the device idled while the innermost
open program span was ``decode.fetch`` or ``decode.guard`` (the host
copying a decode block's output and scanning it), from the profiler trace,
in %."""
from bench import program_trace

UNIT = "%"


def read(rec):
    t = program_trace.load(rec)
    if not t or not t["n_spans"]:
        return None
    idle = t["idle_by_span"]
    return 100.0 * (idle.get("decode.fetch", 0.0)
                    + idle.get("decode.guard", 0.0)) / t["window_s"]
