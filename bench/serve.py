"""Serving cells: the program's ``Scheduler`` over its ``PagedEngine``,
fed by the generated traffic for one window, then checked against the
plain reference.

Set-up makes the weights from the seed, builds the engine and warms every
program the window will run (one prefill chunk shape, decode blocks of 1
to ``decode_block`` steps).  The window then submits each request when it
falls due and steps the scheduler; a thin tap between scheduler and engine
stamps every token handed back on the host clock and counts the work each
decode step needs.  After the window the program's state is freed and the
reference re-reads a sample of the finished requests.

Weights, the program's parameters, the reference and the work counts all
come from the configuration's family module (``cell.family``; see
``bench.registry.load_family``).
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import gen

STATE_FAILED = ("FAILED", "REJECTED", "CANCELLED")


@dataclasses.dataclass
class Record:
    """One request of the window, as the user sees it."""
    rid: int
    due: float                 # host time it fell due
    prompt_len: int
    gen: int
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n)
    high: int = 0              # output tokens handed back so far
    slot: int | None = None    # engine slot of its last admission
    state: str = "WAITING"        # after the running ones were finished
    state_at_close: str = "WAITING"
    prompt: list | None = None
    output: list | None = None


class Tap:
    """Stands between the scheduler and the engine and passes every call
    through.  Around ``admit`` and ``decode`` it opens a host span, stamps
    the tokens handed back, and adds up the work the decode steps need."""

    def __init__(self, engine, family, sizes, records: dict):
        self._engine, self.family, self.sizes = engine, family, sizes
        self.records = records
        self.sched = None
        self.decode_flops = self.decode_bytes = self.prefill_flops = 0
        self._prefilled: set = set()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _deliver(self, rec: Record, reach: int, t: float) -> None:
        if reach > rec.high:        # re-served tokens after a preemption
            rec.deliveries.append((t, reach - rec.high))
            rec.high = reach

    def admit(self, slot, req):
        with TraceAnnotation("bench.admit"):
            first = self._engine.admit(slot, req)
        t = time.perf_counter()
        if req.rid not in self._prefilled:
            self._prefilled.add(req.rid)
            self.prefill_flops += self.family.prefill_flops(
                self.sizes, len(req.prompt))
        rec = self.records[req.rid]
        rec.slot = slot
        self._deliver(rec, 1, t)
        return first

    def decode(self, slots):
        running = self.sched.running
        ctx = {s: len(running[s].prompt) + len(running[s].output)
               for s in slots}
        with TraceAnnotation("bench.decode"):
            out = self._engine.decode(slots)
        t = time.perf_counter()
        for s, toks in out.items():
            req = running[s]
            self._deliver(self.records[req.rid], len(req.output) + len(toks),
                          t)
        n = max((len(v) for v in out.values()), default=0)
        for k in range(n):
            f, b = self.family.decode_step_work(self.sizes,
                                                [ctx[s] + k for s in out])
            self.decode_flops += f
            self.decode_bytes += b
        return out


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) while
    ``open`` is set: none should fall inside the measured window."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.open, self.count, self.seconds = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT and self.open:
            self.count += 1
            self.seconds += duration


class _Clock:
    """perf_counter that remembers its first reading, so the program's
    trace recorder (microseconds since its start) maps onto host time."""

    def __init__(self):
        self.first = None

    def __call__(self):
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


COUNTERS = ("prefill_device_s", "decode_device_s", "prefill_steps",
            "decode_steps", "prefill_tokens", "decoded_tokens")


def _warm(engine, engine_kw: dict, multiple: int) -> None:
    """Run every program shape the window can use, one request at a time:
    a last prefill chunk of each length the traffic's prompts can leave
    (multiples of ``multiple`` up to the chunk), and decode blocks of each
    length 1..decode_block (a lone request of gen n + 1 decodes n)."""
    from repro.serve import Scheduler
    sched = Scheduler(engine)
    lengths = list(range(multiple, engine_kw["chunk"] + 1, multiple))
    blocks = list(range(1, engine_kw["decode_block"] + 1))
    for i in range(max(len(lengths), len(blocks))):
        sched.submit([1] * lengths[min(i, len(lengths) - 1)],
                     blocks[i % len(blocks)] + 1)
        sched.run_until_done()


@dataclasses.dataclass
class Served:
    """A set-up engine, ready for windows."""
    engine: object
    family: object             # the configuration's family module
    sizes: object              # its sizes
    recorder: object
    clock: _Clock
    max_len: int
    swap: int | None


def setup(cell, *, seed: int, trace: bool) -> Served:
    """Weights from the seed, the engine, and every program warmed."""
    from repro.obs import Registry, TraceRecorder
    from repro.serve import PagedEngine

    fam = cell.family
    s = fam.sizes(cell.config)
    ekw = dict(cell.workload["engine"])
    swap = ekw.pop("host_swap_bytes", None)
    cfg = fam.model_config(cell.config)
    params = fam.program_params(seed, s, cfg)
    clock = _Clock()
    recorder = TraceRecorder(capacity=1 << 20, enabled=trace, clock=clock)
    engine = PagedEngine(cfg, params, metrics=Registry(), trace=recorder,
                         **ekw)
    _warm(engine, ekw, int(cell.traffic["prompt"].get("multiple", 1)))
    return Served(engine=engine, family=fam, sizes=s, recorder=recorder,
                  clock=clock, max_len=ekw["max_len"], swap=swap)


def measure(st: Served, traffic: dict, *, seed: int, seconds: float,
            profile_dir: str | None = None, finish_s: float = 90.0) -> dict:
    """One measured window over a fresh scheduler; returns plain data.

    When the window closes, requests still waiting are dropped and those
    running are served to their end (for at most ``finish_s``, untimed),
    so the check can read what the window started."""
    from repro.serve import Scheduler
    engine, s, recorder = st.engine, st.sizes, st.recorder
    due = gen.schedule(traffic, seconds=seconds, seed=seed, vocab=s.vocab)
    compiles = CompileCounter()
    records: dict[int, Record] = {}
    tap = Tap(engine, st.family, s, records)
    sched = Scheduler(tap, host_swap_bytes=st.swap, trace=recorder)
    tap.sched = sched
    before = {k: getattr(engine, k) for k in COUNTERS}
    n_events = len(recorder)
    if profile_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)

    i, reqs = 0, {}
    compiles.open = True
    t0 = time.perf_counter()
    end = t0 + seconds
    with TraceAnnotation("bench.window"):
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while i < len(due) and t0 + due[i].at <= now:
                with TraceAnnotation("bench.submit"):
                    req = sched.submit(due[i].prompt, due[i].gen)
                records[req.rid] = Record(
                    rid=req.rid, due=t0 + due[i].at,
                    prompt_len=len(due[i].prompt), gen=due[i].gen)
                reqs[req.rid] = req
                i += 1
            if sched.waiting or sched.running:
                with TraceAnnotation("bench.step"):
                    sched.step()
            else:
                nxt = t0 + due[i].at if i < len(due) else end
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, end) - now))
    t1 = time.perf_counter()
    compiles.open = False
    if profile_dir:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    counters = {k: getattr(engine, k) - before[k] for k in COUNTERS}
    work_done = dict(decode_flops=tap.decode_flops,
                     decode_bytes=tap.decode_bytes,
                     prefill_flops=tap.prefill_flops)
    at_close = {rid: req.state.name for rid, req in reqs.items()}
    waiting, sched.waiting = len(sched.waiting), []
    while sched.running and time.perf_counter() < t1 + finish_s:
        sched.step()
    for rid, req in reqs.items():
        r = records[rid]
        r.state, r.state_at_close = req.state.name, at_close[rid]
        r.prompt, r.output = list(req.prompt), list(req.output)
    sched.drain()
    return dict(kind="serve", family=st.family, sizes=s, window=(t0, t1),
                records=list(records.values()), counters=counters,
                work=work_done,
                spans=recorder.events()[n_events:] if recorder else None,
                span_clock0=st.clock.first, memory_peak_bytes=int(mem),
                compiles=compiles.count, compile_s=compiles.seconds,
                waiting_at_end=waiting, generator_lag_s=_lag(records, reqs),
                max_len=st.max_len, max_gen=int(traffic["output"]["max"]))


def window(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
           profile_dir: str | None):
    """Set-up and one measured window; returns plain data only, so every
    device buffer of the program is free once it returns."""
    st = setup(cell, seed=seed, trace=trace)
    setup_s = time.perf_counter() - t_start
    rec = measure(st, cell.traffic, seed=seed, seconds=seconds,
                  profile_dir=profile_dir)
    rec["setup_s"] = setup_s
    return rec


def _lag(records, reqs) -> float:
    """How late the loop submitted requests after they fell due, at most
    (a submit waits for the scheduler step in progress)."""
    return 0.0 if not records else max(
        0.0, *(reqs[r].submitted_wall - records[r].due for r in records))


def sample(rec: dict, seed: int, want_tokens: int) -> list:
    """Finished requests to check, drawn from the seed: the longest, then
    one from each engine slot not yet covered, then others until
    ``want_tokens`` served tokens are in."""
    done = [r for r in rec["records"] if r.state == "FINISHED"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.gen, r.rid))
    rng = np.random.default_rng([int(seed), 1])
    rest = [done[j] for j in rng.permutation(len(done))
            if done[j] is not longest]
    out, slots = [longest], {longest.slot}
    for r in rest:
        if r.slot not in slots:
            out.append(r)
            slots.add(r.slot)
    for r in rest:
        if sum(len(x.output) for x in out) >= want_tokens:
            break
        if r not in out:
            out.append(r)
    return out


def check(rec: dict, *, seed: int, limits: dict, control: bool = False):
    """Re-read a sample of finished requests with the reference: the
    widest gap by which a served token's logit lies below the reference's
    best at that position.  Returns {name: (value, limit)}, plus the
    control's widest gap when ``control``."""
    fam, s = rec["family"], rec["sizes"]
    picked = sample(rec, seed, int(limits["sample_tokens"]))
    short = sum(len(r.output) != r.gen for r in picked)
    w = fam.bench_weights(seed, s)
    rows, length = rec["max_gen"], rec["max_len"]
    worst = worst_ctl = 0.0
    for r in picked:
        toks = np.zeros(length, np.int32)
        seq = r.prompt + r.output
        toks[:len(seq)] = seq
        served = np.zeros(rows, np.int32)
        served[:len(r.output)] = r.output
        got = fam.served_gaps(w, toks, r.prompt_len - 1, served, s=s,
                              rows=rows, control=control)
        if control:
            got, ctl = got
            worst_ctl = max(worst_ctl, float(np.max(ctl[:len(r.output)])))
        worst = max(worst, float(np.max(np.asarray(got)[:len(r.output)])))
    out = {"served_gap_max": (worst, limits["served_gap_max"]),
           "served_tokens": (sum(len(r.output) for r in picked),
                             limits["sample_tokens"]),
           "short_outputs": (short, 0)}
    if control:
        out["control_gap_max"] = (worst_ctl, limits["served_gap_max"])
    return out


def passed(checks: dict) -> bool:
    gap, tokens, short = (checks["served_gap_max"], checks["served_tokens"],
                          checks["short_outputs"])
    return gap[0] <= gap[1] and tokens[0] >= tokens[1] and short[0] == 0


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        profile_dir: str | None = None):
    """(record for the metric readers, checks, correct)."""
    rec = window(cell, seed=seed, seconds=seconds, trace=trace,
                 t_start=t_start, profile_dir=profile_dir)
    gc.collect()
    checks = check(rec, seed=seed, limits=cell.workload["check"])
    t0, t1 = rec["window"]
    due = [r for r in rec["records"] if r.due < t1]
    rec["attempted"] = len(due)
    rec["failed"] = sum(r.state_at_close in STATE_FAILED for r in due)
    return rec, checks, passed(checks)
