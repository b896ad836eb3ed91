"""Low-overhead request-lifecycle + engine-span trace recorder.

The paper's whole method is *observing* where kernels spend their time and
choosing coarsening degrees from that evidence; this module is the serving
stack's equivalent for scheduler/engine time.  A ``TraceRecorder`` collects
instant events (request lifecycle transitions: QUEUED -> ADMITTED -> ... ->
exactly one terminal state) and spans (prefill chunks, decode blocks, verify
passes, swap gather/scatter) into a fixed-capacity ring buffer, and exports
them as Chrome trace-event JSON that Perfetto / chrome://tracing load
directly.

Two clocks ride on every event: the wall clock (``ts``, microseconds since
recorder start — what Perfetto renders) and the scheduler's logical-quantum
clock (``args["q"]`` — what the deterministic tests compare, because wall
time is not replayable).  The scheduler stamps ``recorder.quantum`` once per
step; everything recorded inside that step inherits it.

Cost model:

* **disabled** (the default everywhere): ``event``/``begin``/``end`` return
  on the first instruction and allocate NOTHING (pinned by a tracemalloc
  test); ``span`` returns one shared no-op context manager.  Instrumented
  code needs no ``if tracing:`` guards.
* **enabled**: one tuple append into a ``deque(maxlen=capacity)`` per
  event — the ring drops the oldest events once full (``dropped`` counts
  them) so a long-running server holds bounded memory.  Each span also
  opens a ``jax.profiler.TraceAnnotation`` named ``repro.<span name>``
  (closed by ``end``), so under ``jax.profiler`` the spans sit on the
  profiler's clock beside the device's operations; instant events stay in
  the recorder only.

Track layout (Chrome ``tid``): engine slots trace as tid 0..slots-1,
batch-level engine work (decode blocks, verify passes) on ``ENGINE_TRACK``,
the scheduler's quantum spans on ``SCHED_TRACK``, and each request's
lifecycle as instant events on ``REQ_TRACK_BASE + rid``.  ``to_chrome``
emits thread-name metadata so Perfetto labels the tracks.
"""
from __future__ import annotations

import json
import time
from collections import deque

from jax.profiler import TraceAnnotation

# prefix of the profiler annotation each span opens
ANNOTATION_PREFIX = "repro."

# Chrome trace-event phases this recorder emits: X complete, i instant,
# M metadata.  validate_chrome accepts exactly these.
PHASES = ("X", "i", "M")

ENGINE_TRACK = 998          # batch-level engine spans (decode/verify)
SCHED_TRACK = 999           # scheduler quantum spans
REQ_TRACK_BASE = 1000       # request rid r traces on tid REQ_TRACK_BASE + r

# request lifecycle states an instant event may carry (scheduler states
# plus the admission-side transitions)
LIFECYCLE = ("QUEUED", "ADMITTED", "RESUMED", "SUSPENDED", "PREEMPTED",
             "FINISHED", "CANCELLED", "REJECTED", "FAILED")
TERMINAL_STATES = frozenset({"FINISHED", "CANCELLED", "REJECTED", "FAILED"})


class _NullSpan:
    """Shared no-op context manager the disabled recorder hands out.
    Explicit __exit__ parameters: *args would pack a tuple per call and the
    disabled path is pinned to allocate nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("rec", "tid")

    def __init__(self, rec: "TraceRecorder", tid: int):
        self.rec, self.tid = rec, tid

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.rec.end(self.tid)
        return False


class TraceRecorder:
    """Ring-buffered span/event recorder, zero-cost when disabled."""

    def __init__(self, capacity: int = 1 << 16, enabled: bool = True,
                 clock=time.perf_counter):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self._clock = clock
        self._t0 = clock()
        self._buf: deque = deque(maxlen=self.capacity)
        self._open: dict[int, list] = {}   # tid -> stack of open spans
        self.quantum = 0      # the scheduler's logical clock (stamped on
        self._seq = 0         # every event next to the wall timestamp)
        self.dropped = 0      # events the ring overwrote

    def __bool__(self) -> bool:
        return self.enabled

    def __len__(self) -> int:
        return len(self._buf)

    # -- recording -----------------------------------------------------------

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _push(self, name, ph, cat, tid, ts, dur, args) -> None:
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._seq += 1
        self._buf.append((self._seq, name, ph, cat, int(tid), ts, dur,
                          self.quantum, args))

    def event(self, name: str, cat: str = "event", tid: int = 0,
              args: dict | None = None) -> None:
        """Record an instant event.  First instruction returns when the
        recorder is disabled — call sites need no guard."""
        if not self.enabled:
            return
        self._push(name, "i", cat, tid, self._now_us(), 0.0, args)

    def lifecycle(self, rid: int, state: str,
                  args: dict | None = None) -> None:
        """Record one request-lifecycle transition on the request's own
        track.  ``state`` should be a LIFECYCLE name."""
        if not self.enabled:
            return
        a = {"rid": int(rid)}
        if args:
            a.update(args)
        self._push(state, "i", "request", REQ_TRACK_BASE + int(rid),
                   self._now_us(), 0.0, a)

    def begin(self, name: str, cat: str = "engine", tid: int = 0,
              args: dict | None = None) -> None:
        """Open a span on ``tid``.  Spans per tid form a stack, so recorded
        spans always nest and never overlap within a track."""
        if not self.enabled:
            return
        ann = TraceAnnotation(ANNOTATION_PREFIX + name)
        ann.__enter__()
        self._open.setdefault(int(tid), []).append(
            (name, cat, self._now_us(), self.quantum, args, ann))

    def end(self, tid: int = 0) -> None:
        """Close the innermost open span on ``tid`` as a complete event."""
        if not self.enabled:
            return
        stack = self._open.get(int(tid))
        if not stack:
            raise RuntimeError(f"end() with no open span on tid {tid}")
        name, cat, ts, q, args, ann = stack.pop()
        # the span keeps its OPENING quantum: that is the step it belongs to
        seq = self._seq = self._seq + 1
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._buf.append((seq, name, "X", cat, int(tid), ts,
                          max(0.0, self._now_us() - ts), q, args))
        ann.__exit__(None, None, None)

    def span(self, name: str, cat: str = "engine", tid: int = 0,
             args: dict | None = None):
        """Context manager recording one complete span; the disabled path
        returns a shared no-op object."""
        if not self.enabled:
            return _NULL_SPAN
        self.begin(name, cat, tid, args)
        return _Span(self, tid)

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict]:
        """The buffer as dicts, in record order (oldest first)."""
        out = []
        for seq, name, ph, cat, tid, ts, dur, q, args in self._buf:
            e = {"seq": seq, "name": name, "ph": ph, "cat": cat, "tid": tid,
                 "ts": ts, "q": q}
            if ph == "X":
                e["dur"] = dur
            if args:
                e["args"] = args
            out.append(e)
        return out

    def signature(self) -> list[tuple]:
        """The deterministic projection of the trace: everything except the
        wall clock.  Two runs of the same seeded scenario must produce
        equal signatures — this is what the replay tests compare."""
        return [(name, ph, cat, tid, q,
                 tuple(sorted(args.items())) if args else ())
                for _, name, ph, cat, tid, ts, dur, q, args in self._buf]

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (Perfetto / chrome://tracing loadable):
        X/i events with both clocks in args, plus thread-name metadata so
        tracks read as "slot 0", "engine", "scheduler", "req 3"."""
        events, tids = [], set()
        for seq, name, ph, cat, tid, ts, dur, q, args in self._buf:
            tids.add(tid)
            a = dict(args) if args else {}
            a["q"] = q
            e = {"name": name, "ph": ph, "cat": cat, "pid": 1, "tid": tid,
                 "ts": round(ts, 3), "args": a}
            if ph == "X":
                e["dur"] = round(dur, 3)
            if ph == "i":
                e["s"] = "t"        # instant scope: thread
            events.append(e)
        meta = []
        for tid in sorted(tids):
            if tid >= REQ_TRACK_BASE:
                label = f"req {tid - REQ_TRACK_BASE}"
            elif tid == SCHED_TRACK:
                label = "scheduler"
            elif tid == ENGINE_TRACK:
                label = "engine"
            else:
                label = f"slot {tid}"
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": label}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"recorded": len(self._buf),
                              "dropped": self.dropped}}

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# the shared disabled recorder: instrument against this by default so the
# un-observed hot path costs one attribute load + one branch per site
NULL_TRACER = TraceRecorder(capacity=1, enabled=False)


def validate_chrome(blob) -> None:
    """Raise ValueError unless ``blob`` is a well-formed Chrome trace-event
    JSON object of the shape ``to_chrome`` emits.  CI runs this over the
    serve smoke's TRACE_serve.json and fails the build on violations."""
    def bad(msg):
        raise ValueError(f"malformed chrome trace: {msg}")

    if not isinstance(blob, dict):
        bad(f"top level must be an object, got {type(blob).__name__}")
    events = blob.get("traceEvents")
    if not isinstance(events, list):
        bad("traceEvents must be a list")
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            bad(f"event {i} is not an object")
        if not isinstance(e.get("name"), str) or not e["name"]:
            bad(f"event {i} needs a non-empty string name")
        ph = e.get("ph")
        if ph not in PHASES:
            bad(f"event {i} has phase {ph!r}, expected one of {PHASES}")
        if not isinstance(e.get("pid"), int):
            bad(f"event {i} needs an int pid")
        if not isinstance(e.get("tid"), int):
            bad(f"event {i} needs an int tid")
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            bad(f"event {i} needs a numeric ts >= 0, got {ts!r}")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                bad(f"complete event {i} needs a numeric dur >= 0")
        args = e.get("args")
        if args is not None and not isinstance(args, dict):
            bad(f"event {i} args must be an object")
        if not isinstance(args, dict) or "q" not in args:
            bad(f"event {i} is missing the logical-quantum clock args['q']")
