"""Device time per decode step of the decode program's operations under
the model's ``attention`` scope (projections, cache write, attention over
the gathered view, output projection), from the profiler trace, each
instant charged to the innermost operation; in ms."""
from bench import program_trace

UNIT = "ms"


def read(rec):
    t = program_trace.load(rec)
    steps = rec["counters"]["decode_steps"]
    if not t or not steps:
        return None
    s = program_trace.module_scope_seconds(t, "jit_engine_decode",
                                           "attention")
    return None if s is None else 1e3 * s / steps
