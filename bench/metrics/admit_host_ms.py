"""Host time per admission outside the device calls: the self time of the
program's ``engine.admit`` span (its duration less its ``prefill.chunk``
and ``admit.first_token`` children), averaged over the admissions inside
the window, in ms."""
import statistics

from bench import program_trace

UNIT = "ms"


def read(rec):
    own = program_trace.self_seconds(rec, "engine.admit")
    return 1e3 * statistics.fmean(own) if own else None
