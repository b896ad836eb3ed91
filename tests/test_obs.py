"""Observability suite: the repro.obs registry + trace recorder, and their
wiring through the serving stack.

Layer 1 — the instruments alone: counter/gauge/histogram semantics, the
Prometheus text exposition, the JSON snapshot, the disabled recorder's
zero-allocation fast path, span nesting per track, ring-buffer drops, and
the Chrome trace-event schema (``validate_chrome`` accepts what
``to_chrome`` emits and rejects malformed blobs).

Layer 2 — a FakeEngine (with suspend/resume so the swap path traces) under
forced preemption and a seeded FaultPlan: every submitted request's trace
track starts at QUEUED and ends at exactly ONE terminal state, and the
trace ``signature()`` (the wall-clock-free projection) replays bit-equal
for the same seeds.

Layer 3 — thin-view parity: the legacy counter attributes on SwapStore,
FaultPlan and TuningCache are views over registry counters and can never
drift from them; plus one real-PagedEngine acceptance run (reduced qwen3,
undersized pool, fault injection, trace enabled) pinning the --trace-out
contract: complete lifecycles, a schema-valid Perfetto-loadable export,
and registry values bitwise equal to the engine's legacy attributes."""
import json
import tracemalloc

import numpy as np
import pytest

from repro.obs import (ANNOTATION_PREFIX, ENGINE_TRACK, QUANTA_BUCKETS,
                       REQ_TRACK_BASE, SCHED_TRACK, TERMINAL_STATES, Counter,
                       Gauge, Histogram, NULL_TRACER, Registry, TraceRecorder,
                       validate_chrome)
from repro.serve import (BlockTables, FaultPlan, FaultyEngine, PagePool,
                         PoolExhausted, Request, Scheduler, State,
                         SwapStore, pages_needed)


# ---------------------------------------------------------------------------
# layer 1: instruments
# ---------------------------------------------------------------------------

def test_counter_semantics():
    reg = Registry()
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(41)
    assert c.value == 42 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create: same (name, labels) is the same instrument
    assert reg.counter("reqs_total") is c
    assert reg.counter("reqs_total", state="ok") is not c
    assert reg.value("reqs_total") == 42


def test_gauge_watermarks():
    g = Registry().gauge("free_pages")
    for v in (7, 2, 9, 4):
        g.set(v)
    assert g.value == 4
    assert g.lo == 2 and g.hi == 9      # lifetime water marks survive sets
    g.inc(3)
    g.dec(1)
    assert g.value == 6


def test_histogram_buckets_and_quantile():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.6, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [1, 2, 1, 1]      # last = +inf overflow
    assert h.count == 5 and h.sum == pytest.approx(106.6)
    assert h.quantile(0.5) == 2.0
    assert h.quantile(1.0) == 4.0        # +inf clamps to last finite bound
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))


def test_registry_type_mismatch_and_value_default():
    reg = Registry()
    reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    with pytest.raises(KeyError):
        reg.value("nope")
    assert reg.value("nope", default=0) == 0
    assert "x_total" in reg and len(reg) == 1


def test_prometheus_exposition():
    reg = Registry()
    reg.counter("req_total", "served requests", state="ok").inc(3)
    reg.gauge("pool_free").set(5)
    reg.gauge("pool_free").set(2)
    h = reg.histogram("wait_q", QUANTA_BUCKETS)
    h.observe(0)
    h.observe(3)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert "# HELP req_total served requests" in text
    assert 'req_total{state="ok"} 3' in text
    assert "pool_free 2" in text
    assert "pool_free_lo 2" in text and "pool_free_hi 5" in text
    # cumulative buckets + the implicit +Inf
    assert 'wait_q_bucket{le="0.0"} 1' in text
    assert 'wait_q_bucket{le="4.0"} 2' in text
    assert 'wait_q_bucket{le="+Inf"} 2' in text
    assert "wait_q_count 2" in text


def test_snapshot_is_jsonable():
    reg = Registry()
    reg.counter("c_total").inc()
    reg.gauge("g").set(1.5)
    reg.histogram("h", (1.0, 2.0)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c_total"] == 1
    assert snap["gauges"]["g"]["value"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    assert "p50" in snap["histograms"]["h"]
    # untouched gauge watermarks serialize as null, not Infinity
    reg2 = Registry()
    reg2.gauge("never_set")
    assert json.loads(json.dumps(reg2.snapshot()))["gauges"][
        "never_set"]["lo"] is None


# ---------------------------------------------------------------------------
# layer 1: trace recorder
# ---------------------------------------------------------------------------

def test_disabled_recorder_allocates_nothing():
    """20k disabled calls must allocate no per-call memory: the traced
    peak stays under a small constant (interpreter/pytest-internal noise —
    method caches, GC bookkeeping — lands in the ~1 KiB range regardless
    of call count; one tuple-per-call would be >1 MiB here) and nothing is
    retained in the buffer."""
    rec = TraceRecorder(capacity=8, enabled=False)
    assert not rec and not NULL_TRACER
    # warm up attribute/bytecode caches before measuring
    rec.event("w")
    rec.begin("w")
    rec.end()
    with rec.span("w"):
        pass
    rec.lifecycle(0, "QUEUED")
    rid = 7
    tracemalloc.start()
    i = 0
    while i < 20000:    # small ints are interned: the loop itself is free
        rec.event("e")
        rec.begin("b")
        rec.end()
        with rec.span("s"):
            pass
        rec.lifecycle(1, "FINISHED")
        # the engine's call sites: args built only when the recorder is on
        with rec.span("engine.admit", "engine", 3,
                      {"rid": rid} if rec else None):
            with rec.span("admit.first_token", "engine", 3):
                pass
        with rec.span("decode.block", "engine", ENGINE_TRACK,
                      {"slots": 2, "n": 4} if rec else None):
            with rec.span("decode.launch", "engine", ENGINE_TRACK):
                pass
            with rec.span("decode.fetch", "engine", ENGINE_TRACK):
                pass
            with rec.span("decode.guard", "engine", ENGINE_TRACK):
                pass
        i += 1
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 16384, f"disabled recorder allocated {peak} bytes peak"
    assert current < 16384, f"disabled recorder retained {current} bytes"
    assert len(rec) == 0


def test_span_nesting_per_track_and_quantum_stamp():
    rec = TraceRecorder(clock=iter(range(1000)).__next__)
    rec.quantum = 3
    rec.begin("outer", tid=0)
    rec.quantum = 4
    rec.event("inner.mark", tid=0)
    rec.begin("inner", tid=0)
    rec.begin("other-track", tid=1)     # stacks are independent per tid
    rec.end(1)
    rec.end(0)                          # closes inner
    rec.end(0)                          # closes outer
    evs = rec.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["q"] == 3       # span keeps its OPENING quantum
    assert by_name["inner"]["q"] == 4
    assert by_name["inner.mark"]["q"] == 4
    # nesting: inner closed before outer, both complete events
    assert by_name["inner"]["ph"] == by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    with pytest.raises(RuntimeError):
        rec.end(0)                      # nothing left open on this track


def test_enabled_recorder_annotates_the_profiler(tmp_path):
    """Under jax.profiler an enabled recorder's spans appear as host events
    named ``repro.<span>`` that nest as the spans do; instant events stay
    in the recorder."""
    import jax
    from jax.profiler import ProfileData
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("outer", tid=0):
            rec.event("mark", tid=0)
            with rec.span("inner", tid=0):
                with rec.span("other", tid=1):
                    pass
            with rec.span("inner", tid=0):
                pass
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(ANNOTATION_PREFIX)]
    names = sorted(n for n, _, _ in host)
    assert names == ["repro.inner", "repro.inner", "repro.other",
                     "repro.outer"]
    (_, o0, o1), = [h for h in host if h[0] == "repro.outer"]
    inner = [h for h in host if h[0] == "repro.inner"]
    (_, x0, x1), = [h for h in host if h[0] == "repro.other"]
    assert all(o0 <= a <= b <= o1 for _, a, b in inner)
    assert inner[0][1] <= x0 <= x1 <= inner[0][2]
    assert not inner[0][2] > inner[1][1] > inner[0][1]   # siblings
    # the recorder's own view is unchanged
    assert [e["name"] for e in rec.events()] == [
        "mark", "other", "inner", "inner", "outer"]


def test_ring_buffer_drops_oldest():
    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.event(f"e{i}")
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [e["name"] for e in rec.events()] == ["e6", "e7", "e8", "e9"]


def test_chrome_export_schema_and_validation():
    rec = TraceRecorder()
    rec.quantum = 1
    rec.lifecycle(3, "QUEUED", {"prompt": 5, "gen": 2})
    with rec.span("decode.block", "engine", ENGINE_TRACK, {"n": 4}):
        pass
    with rec.span("sched.quantum", "sched", SCHED_TRACK):
        pass
    blob = json.loads(json.dumps(rec.to_chrome()))   # full JSON round trip
    validate_chrome(blob)
    names = {e["tid"]: e["args"]["name"] for e in blob["traceEvents"]
             if e["ph"] == "M"}
    assert names[ENGINE_TRACK] == "engine"
    assert names[SCHED_TRACK] == "scheduler"
    assert names[REQ_TRACK_BASE + 3] == "req 3"
    inst = [e for e in blob["traceEvents"] if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" and "q" in e["args"] for e in inst)
    # rejections
    for bad in (
        [],                                              # not an object
        {"traceEvents": {}},                             # not a list
        {"traceEvents": [{"name": "x", "ph": "Z", "pid": 1, "tid": 0}]},
        {"traceEvents": [{"name": "x", "ph": "i", "pid": 1, "tid": 0,
                          "ts": -1.0, "args": {"q": 0}}]},
        {"traceEvents": [{"name": "x", "ph": "i", "pid": 1, "tid": 0,
                          "ts": 0.0, "args": {}}]},      # missing q
    ):
        with pytest.raises(ValueError):
            validate_chrome(bad)


# ---------------------------------------------------------------------------
# layer 2: lifecycle completeness + deterministic replay on a fake engine
# ---------------------------------------------------------------------------

class _Susp:
    """Fake suspension: enough state to restore the slot, plus the nbytes
    the SwapStore accounts."""

    def __init__(self, req, written, emitted):
        self.req, self.written, self.emitted = req, written, emitted
        self.n_tokens = written
        self.nbytes = written * 4


class SwappableFakeEngine:
    """Engine-protocol fake over a real PagePool, with deterministic tokens
    (token j of request r is ``(r.rid * 1009 + j) % 65521``) and the
    suspend/resume extension so the scheduler's swap path traces."""

    def __init__(self, *, slots=3, num_pages=10, page_size=4, max_len=64,
                 decode_block=4):
        self.slots = slots
        self.page_size = page_size
        self.max_len = max_len
        self.decode_block = decode_block
        self.pool = PagePool(num_pages, page_size)
        self.pool_capacity = self.pool.capacity
        self.bt = BlockTables(slots, pages_needed(max_len, page_size))
        self.state: dict[int, list] = {}  # slot -> [req, written, emitted]

    @staticmethod
    def tok(req: Request, j: int) -> int:
        return (req.rid * 1009 + j) % 65521

    def admit(self, slot, req):
        assert slot not in self.state
        pages = self.pool.alloc(pages_needed(len(req.prompt),
                                             self.page_size))
        self.bt.append(slot, pages)
        self.state[slot] = [req, len(req.prompt), 1]
        return self.tok(req, 0)

    def decode(self, slots):
        slots = [s for s in slots if s in self.state]
        if not slots:
            return {}
        n = max(1, min([self.decode_block]
                       + [st[0].gen - st[2] for st in
                          (self.state[s] for s in slots)]))
        for s in slots:
            req, written, _ = self.state[s]
            need = pages_needed(written + n, self.page_size) \
                - self.bt.num_pages(s)
            if need > 0:
                self.bt.append(s, self.pool.alloc(need))
        out = {}
        for s in slots:
            st = self.state[s]
            out[s] = [self.tok(st[0], st[2] + k) for k in range(n)]
            st[1] += n
            st[2] += n
        return out

    def _drop(self, slot):
        self.pool.release(self.bt.drop(slot))
        del self.state[slot]

    def finish(self, slot):
        self._drop(slot)

    def preempt(self, slot):
        self._drop(slot)

    # -- the swap extension --------------------------------------------------

    def suspend_bytes(self, slot) -> int:
        return self.state[slot][1] * 4

    def suspend(self, slot) -> _Susp:
        req, written, emitted = self.state[slot]
        self._drop(slot)
        return _Susp(req, written, emitted)

    def resume(self, slot, susp: _Susp) -> None:
        assert slot not in self.state
        pages = self.pool.alloc(pages_needed(susp.written, self.page_size))
        self.bt.append(slot, pages)
        self.state[slot] = [susp.req, susp.written, susp.emitted]


def _run_faulty_trace(seed: int):
    """An undersized pool + a seeded FaultPlan, fully traced; returns the
    (scheduler, trace, registry, done) tuple."""
    reg = Registry()
    trace = TraceRecorder()
    eng = SwappableFakeEngine(slots=3, num_pages=9, page_size=4, max_len=48)
    plan = FaultPlan(seed, p_admit=0.15, p_growth=0.1, p_transient=0.1,
                     metrics=reg, trace=trace)
    sched = Scheduler(FaultyEngine(eng, plan), host_swap_bytes=None,
                      metrics=reg, trace=trace)
    rng = np.random.default_rng(seed)
    for _ in range(9):
        gen = int(rng.integers(4, 20))
        plen = int(rng.integers(2, 12))
        sched.submit([int(t) for t in rng.integers(1, 1000, plen)], gen)
    done = sched.run_until_done()
    assert eng.pool.num_live == 0
    eng.pool.check()
    return sched, trace, reg, done


@pytest.mark.parametrize("seed", [0, 3])
def test_every_request_reaches_exactly_one_terminal_state(seed):
    sched, trace, reg, done = _run_faulty_trace(seed)
    tracks: dict[int, list[str]] = {}
    for name, ph, cat, tid, q, args in trace.signature():
        if tid >= REQ_TRACK_BASE:
            tracks.setdefault(tid - REQ_TRACK_BASE, []).append(name)
    # every submitted request has a track, starting QUEUED, ending at its
    # single terminal transition — no request vanishes, none dies twice
    assert set(tracks) == {r.rid for r in done}
    for rid, names in tracks.items():
        assert names[0] == "QUEUED", (rid, names)
        terminal = [n for n in names if n in TERMINAL_STATES]
        assert len(terminal) == 1, (rid, names)
        assert names[-1] == terminal[0], (rid, names)
    # the pool pressure + swap budget actually exercised the paths the
    # trace claims to cover
    flat = [n for names in tracks.values() for n in names]
    assert "SUSPENDED" in flat and "RESUMED" in flat
    assert int(reg.value("sched_preemptions_total")) > 0
    # terminal counters agree with the trace
    for s, n in ((s, int(reg.value("sched_requests_total", state=s.value)))
                 for s in (State.FINISHED, State.FAILED)):
        assert n == sum(1 for names in tracks.values()
                        if names[-1] == s.name)


def test_trace_signature_replays_deterministically():
    _, t1, r1, d1 = _run_faulty_trace(5)
    _, t2, r2, d2 = _run_faulty_trace(5)
    assert t1.signature() == t2.signature()
    assert [r.output for r in d1] == [r.output for r in d2]
    assert r1.snapshot()["counters"] == r2.snapshot()["counters"]
    # sanity: a different seed produces a different fault/evict history
    _, t3, _, _ = _run_faulty_trace(6)
    assert t1.signature() != t3.signature()


def test_scheduler_quantum_clock_on_every_event():
    _, trace, _, _ = _run_faulty_trace(0)
    sig = trace.signature()
    qs = [q for _, _, _, _, q, _ in sig]
    assert max(qs) > 1                       # the logical clock advanced
    assert all(isinstance(q, int) and q >= 0 for q in qs)
    # quantum spans land on the scheduler track, one per step, q strictly
    # increasing (each span keeps the quantum it opened under)
    sched_q = [q for name, ph, _, tid, q, _ in sig
               if tid == SCHED_TRACK and name == "sched.quantum"]
    assert sched_q == sorted(sched_q)
    assert len(set(sched_q)) == len(sched_q)


# ---------------------------------------------------------------------------
# layer 3: thin-view parity + the real-engine acceptance run
# ---------------------------------------------------------------------------

def test_swapstore_views_are_registry_counters():
    reg = Registry()
    sw = SwapStore(budget_bytes=100, metrics=reg)
    sw.put(1, "susp", 60)
    assert not sw.fits(60)                   # 60 + 60 > 100: refused
    sw.pop(1)
    sw.put(2, "susp", 30)
    sw.drop(2)
    assert sw.swapped_out == int(reg.value("swap_out_total")) == 2
    assert sw.swapped_in == int(reg.value("swap_in_total")) == 1
    assert sw.dropped == int(reg.value("swap_dropped_total")) == 1
    assert sw.refused == int(reg.value("swap_refused_total")) == 1
    assert sw.used_bytes == int(reg.value("swap_used_bytes")) == 0
    assert isinstance(sw.used_bytes, int)    # byte accounting stays exact


def test_faultplan_views_are_registry_counters():
    reg = Registry()
    plan = FaultPlan(0, p_admit=1.0, p_nan=1.0, metrics=reg)
    with pytest.raises(PoolExhausted):
        plan.on_admit()
    lg = np.zeros((4, 8), np.float32)
    plan.corrupt_logits(lg, "decode")
    st = plan.stats()
    assert st["admit_faults"] == int(reg.value("fault_admit_total")) == 1
    assert st["nan_rows"] == int(reg.value("fault_nan_rows_total")) == 4
    assert plan.total == 5


def test_tuningcache_stats_are_registry_counters(tmp_path):
    from repro.core.coarsening import CoarseningConfig
    from repro.tune import KernelSpec, TuningCache
    reg = Registry()
    cache = TuningCache(path=str(tmp_path / "t.json"), autoload=False,
                        metrics=reg)
    spec = KernelSpec.make("ew_stream", (4096,), block=256)
    assert cache.get(spec) is None
    cache.put(spec, CoarseningConfig(), modeled_s=1e-3, persist=False)
    assert cache.get(spec) is not None
    assert cache.stats == {"hits": 1, "misses": 1}
    assert int(reg.value("tune_cache_hits_total")) == 1
    assert int(reg.value("tune_cache_misses_total")) == 1


def test_real_engine_traced_fault_run(tmp_path):
    """The --trace-out acceptance pin: a fault-injected serve run on the
    real PagedEngine produces a schema-valid Chrome trace with complete
    request lifecycles and engine spans, and the registry's numbers are
    bitwise the legacy engine attributes."""
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import PagedEngine

    cfg = get_config("qwen3-0.6b").reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    reg = Registry()
    trace = TraceRecorder()
    eng = PagedEngine(cfg, params, slots=2, num_pages=8, page_size=8,
                      max_len=32, chunk=8, decode_block=4, metrics=reg,
                      trace=trace)
    plan = FaultPlan(7, p_transient=0.1, p_nan=0.05, metrics=reg,
                     trace=trace)
    sched = Scheduler(FaultyEngine(eng, plan), metrics=reg, trace=trace)
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit([int(t) for t in rng.integers(1, cfg.vocab, 6)], 8)
    done = sched.run_until_done()
    assert len(done) == 3
    assert all(r.state is State.FINISHED for r in done)
    eng.pool.check()

    # registry <-> legacy-attribute parity, bitwise
    assert int(reg.value("engine_prefill_steps_total")) == eng.prefill_steps
    assert int(reg.value("engine_decode_steps_total")) == eng.decode_steps
    assert int(reg.value("engine_prefill_tokens_total")) \
        == eng.prefill_tokens
    assert int(reg.value("engine_decode_tokens_total")) \
        == eng.decoded_tokens
    assert int(reg.value("engine_nan_rescues_total")) == eng.nan_rescues
    assert int(reg.value("sched_decode_faults_total")) == sched.decode_faults
    # device timers exist and are bounded by something sane
    assert eng.prefill_device_s > 0 and eng.decode_device_s > 0

    # lifecycle completeness on the real stack
    tracks: dict[int, list[str]] = {}
    for name, ph, cat, tid, q, args in trace.signature():
        if tid >= REQ_TRACK_BASE:
            tracks.setdefault(tid - REQ_TRACK_BASE, []).append(name)
    assert set(tracks) == {0, 1, 2}
    for names in tracks.values():
        assert names[0] == "QUEUED" and names[-1] == "FINISHED"
        assert sum(n in TERMINAL_STATES for n in names) == 1

    # engine spans made it onto the engine/slot tracks
    span_names = {name for name, ph, *_ in trace.signature() if ph == "X"}
    assert "prefill.chunk" in span_names
    assert "decode.block" in span_names
    assert "sched.quantum" in span_names

    # the dumped file is a valid, Perfetto-loadable Chrome trace
    out = tmp_path / "TRACE_serve.json"
    trace.dump(str(out))
    validate_chrome(json.loads(out.read_text()))


def test_engine_spans_time_the_host_work():
    """A traced tiny engine's admission and decode record the spans the
    benchmark reads: ``engine.admit`` holding its ``prefill.chunk`` and
    ``admit.first_token`` children, and per decode attempt one
    ``decode.launch`` (whose total is the decode device-seconds counter),
    ``decode.fetch`` and ``decode.guard`` inside ``decode.block``; a NaN
    rescue opens a second set."""
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import PagedEngine

    cfg = get_config("qwen3-0.6b").reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    trace = TraceRecorder()
    eng = PagedEngine(cfg, params, slots=2, num_pages=12, page_size=8,
                      max_len=48, chunk=8, decode_block=4, trace=trace)

    class PoisonOnce:           # the fault hook's seam: one NaN block
        done = False

        def corrupt_logits(self, lg, site):
            if self.done:
                return lg
            self.done = True
            lg = lg.copy()
            lg[0, 0, 0] = np.nan
            return lg

    eng.admit(0, Request(rid=5, prompt=list(range(1, 20)), gen=9))
    eng.decode([0])
    eng.fault_hook = PoisonOnce()
    eng.decode([0])
    assert eng.nan_rescues == 1
    spans = [e for e in trace.events() if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    for n in ("engine.admit", "admit.first_token", "decode.launch",
              "decode.fetch", "decode.guard"):
        assert n in names, n
    # two blocks, one rescued: three attempts of launch, fetch and guard
    assert names.count("decode.block") == 2
    assert [names.count(n) for n in
            ("decode.launch", "decode.fetch", "decode.guard")] == [3, 3, 3]

    def inside(child, parent):
        return parent["tid"] == child["tid"] and parent["ts"] <= child["ts"] \
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]

    admit, = [e for e in spans if e["name"] == "engine.admit"]
    assert admit["tid"] == 0 and admit["args"] == {"rid": 5}
    kids = [e for e in spans if e["name"] in ("prefill.chunk",
                                              "admit.first_token")]
    assert len(kids) == 4 and all(inside(k, admit) for k in kids)
    blocks = [e for e in spans if e["name"] == "decode.block"]
    for e in spans:
        if e["name"].startswith("decode.") and e["name"] != "decode.block":
            assert e["tid"] == ENGINE_TRACK
            assert any(inside(e, b) for b in blocks)
    launch_s = 1e-6 * sum(e["dur"] for e in spans
                          if e["name"] == "decode.launch")
    assert launch_s == pytest.approx(eng.decode_device_s, rel=0.01)
