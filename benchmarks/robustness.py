"""Robust-serving table: what graceful degradation buys under pressure.

Rows (all CPU interpret-scale; trends, not absolute numbers):

  robustness,preempt,<policy>   the SAME undersized-pool trace served with
                                swap-resume eviction vs recompute eviction.
                                ``recovered_tokens`` counts cache rows
                                restored from host without recompute;
                                ``redone_tokens`` counts the rows a policy
                                re-paid (re-prefilled prompt rows plus
                                re-decoded output rows).  recovery_x =
                                recovered / max(1, redone) for the run:
                                recompute recovers nothing and redoes
                                everything at stake (x = 0); the PR gate is
                                recovery_x >= 2 on the swap row — swap
                                recovers at least 2x more useful tokens
                                than it re-pays, where recompute re-pays
                                all of them.
  robustness,deadline,...       oversubscribed trace under deadlines +
                                queue-wait bounds: terminal-state mix and
                                goodput (completed output tokens per
                                scheduler quantum) vs the unbounded run.
  robustness,swap_overhead      wall us of one suspend+resume round trip
                                vs re-running the prefill it avoids, and
                                the host bytes one suspension holds.
  robustness,faults             a seeded FaultPlan trace (admit + growth
                                exhaustion, transient decode faults, NaN
                                rows) vs the fault-free run: injected-fault
                                counts, bitwise_equal flag, pages leaked.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit

SLOTS, PAGE, MAX_LEN, CHUNK = 3, 8, 32, 8


def _model():
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, M.lm_init(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, num_pages, metrics=None):
    from repro.serve import PagedEngine
    return PagedEngine(cfg, params, slots=SLOTS, num_pages=num_pages,
                       page_size=PAGE, max_len=MAX_LEN, chunk=CHUNK,
                       decode_block=4, metrics=metrics)


def _trace(cfg, n, plen, rng):
    return [list(map(int, rng.integers(1, cfg.vocab, plen)))
            for _ in range(n)]


def preempt_rows(cfg, params) -> None:
    """Undersized pool (forces eviction every few quanta), long gens (lots
    of work at stake per eviction): swap vs recompute on the same trace."""
    from repro.obs import Registry
    from repro.serve import Scheduler
    prompts = _trace(cfg, 3, 6, np.random.default_rng(0))
    gen = 22
    stats = {}
    for policy, budget in (("swap", None), ("recompute", 0)):
        reg = Registry()
        eng = _engine(cfg, params, num_pages=8, metrics=reg)
        sched = Scheduler(eng, host_swap_bytes=budget, metrics=reg)
        for p in prompts:
            sched.submit(p, gen)
        t0 = time.perf_counter()
        done = sched.run_until_done()
        dt = time.perf_counter() - t0
        useful = sum(len(r.output) for r in done)
        # swap/preemption numbers come from the obs registry; the legacy
        # engine attributes are views over the same counters, asserted
        # bitwise so the two reporting paths can never drift
        recovered = int(reg.value("engine_swapped_tokens_total"))
        preempts = int(reg.value("sched_preemptions_total"))
        prefill_tok = int(reg.value("engine_prefill_tokens_total"))
        decode_tok = int(reg.value("engine_decode_tokens_total"))
        assert recovered == eng.swapped_out_tokens
        assert preempts == sum(r.preemptions for r in done)
        assert prefill_tok == eng.prefill_tokens
        assert decode_tok == eng.decoded_tokens
        # work this policy re-paid because of evictions: prompt rows
        # prefilled again + tokens emitted more than once.  Every admission
        # emits one token from the prefill logits (a recompute eviction
        # re-admits; a swap resume does not), the rest come from decode.
        admits = len(done) + sum(r.preemptions - r.swaps for r in done)
        redone = (prefill_tok - sum(len(p) for p in prompts)) \
            + (decode_tok + admits - useful)
        stats[policy] = dict(
            completed=len([r for r in done if not r.error]),
            preemptions=preempts,
            recovered_tokens=recovered,
            redone_tokens=redone,
            recovery_x=round(recovered / max(1, redone), 2),
            prefill_steps=int(reg.value("engine_prefill_steps_total")),
            decode_steps=int(reg.value("engine_decode_steps_total")),
            outputs=[r.output for r in sorted(done, key=lambda r: r.rid)],
            wall_s=dt)
        assert eng.pool.num_live == 0
        eng.pool.check()
    assert stats["swap"]["outputs"] == stats["recompute"]["outputs"], \
        "eviction policy changed a greedy stream"
    assert stats["recompute"]["redone_tokens"] > 0, \
        "trace failed to force a recompute re-prefill — weaken the pool"
    assert stats["swap"]["recovery_x"] >= 2, \
        f"swap recovery below the 2x gate: {stats['swap']}"
    for policy in ("swap", "recompute"):
        st = stats[policy]
        emit(f"robustness,preempt,{policy}", st["wall_s"] * 1e6, -1.0,
             completed=st["completed"], preemptions=st["preemptions"],
             recovered_tokens=st["recovered_tokens"],
             redone_tokens=st["redone_tokens"],
             recovery_x=st["recovery_x"],
             prefill_steps=st["prefill_steps"],
             decode_steps=st["decode_steps"])


def deadline_rows(cfg, params) -> None:
    """2x oversubscription: without bounds everything eventually finishes
    (high latency); with deadlines + queue-wait bounds the scheduler sheds
    the tail and spends its quanta on requests that can still make it."""
    from repro.obs import Registry
    from repro.serve import Scheduler, State
    prompts = _trace(cfg, 6, 6, np.random.default_rng(1))
    gen = 14
    for label, kw in (("unbounded", {}),
                      ("bounded", dict(deadline=8, max_queue_wait=3))):
        reg = Registry()
        eng = _engine(cfg, params, num_pages=10, metrics=reg)
        sched = Scheduler(eng, metrics=reg)
        for p in prompts:
            sched.submit(p, gen, **kw)
        done = sched.run_until_done()
        out_tokens = sum(len(r.output) for r in done
                         if r.state is State.FINISHED)
        # terminal-state mix from the registry, pinned against the request
        # list so the counters and the objects cannot disagree
        by = {s: int(reg.value("sched_requests_total", state=s.value))
              for s in (State.FINISHED, State.CANCELLED, State.REJECTED)}
        for s, n in by.items():
            assert n == sum(r.state is s for r in done)
        quanta = int(reg.value("sched_quanta_total"))
        assert quanta == sched.time
        emit(f"robustness,deadline,{label}", -1.0, -1.0,
             finished=by[State.FINISHED], cancelled=by[State.CANCELLED],
             rejected=by[State.REJECTED], quanta=quanta,
             goodput=round(out_tokens / max(1, quanta), 2))
        assert eng.pool.num_live == 0
        eng.pool.check()


def swap_overhead_row(cfg, params) -> None:
    """One suspend+resume round trip vs the prefill it replaces."""
    from repro.serve import Request
    eng = _engine(cfg, params, num_pages=16)
    prompt = list(map(int, np.random.default_rng(2).integers(
        1, cfg.vocab, 16)))
    req = Request(rid=0, prompt=prompt, gen=12)
    eng.admit(0, req)
    eng.decode([0])
    prefill_us = eng.prefill_device_s * 1e6    # what recompute re-pays
    t0 = time.perf_counter()
    susp = eng.suspend(0)
    eng.resume(1, susp)
    swap_us = (time.perf_counter() - t0) * 1e6
    emit("robustness,swap_overhead", swap_us, -1.0,
         prefill_us=round(prefill_us, 1),
         suspension_kib=round(susp.nbytes / 1024, 1),
         tokens=susp.n_tokens)
    eng.finish(1)
    eng.pool.check()


def fault_row(cfg, params) -> None:
    from repro.obs import Registry
    from repro.serve import FaultPlan, FaultyEngine, Scheduler
    prompts = _trace(cfg, 4, 6, np.random.default_rng(3))
    gen = 10

    def run(wrap, reg=None):
        eng = _engine(cfg, params, num_pages=10, metrics=reg)
        sched = Scheduler(wrap(eng), metrics=reg)
        for p in prompts:
            sched.submit(p, gen)
        done = sched.run_until_done()
        assert eng.pool.num_live == 0
        eng.pool.check()
        return eng, [r.output for r in sorted(done, key=lambda r: r.rid)]

    _, ref = run(lambda e: e)
    reg = Registry()
    plan = FaultPlan(7, p_admit=0.7, p_growth=0.2, p_transient=0.15,
                     p_nan=0.03, metrics=reg)
    eng, out = run(lambda e: FaultyEngine(e, plan), reg=reg)
    # fault numbers come from the shared obs registry; plan.stats() reads
    # the same counters, asserted bitwise so the views cannot drift
    faults = {k: int(reg.value(f"fault_{k}_total"))
              for k in ("admit", "growth", "transient")}
    faults["nan_rows"] = int(reg.value("fault_nan_rows_total"))
    st = plan.stats()
    assert faults["admit"] == st["admit_faults"]
    assert faults["growth"] == st["growth_faults"]
    assert faults["transient"] == st["transient_faults"]
    assert faults["nan_rows"] == st["nan_rows"]
    rescues = int(reg.value("engine_nan_rescues_total"))
    assert rescues == eng.nan_rescues
    emit("robustness,faults", -1.0, -1.0,
         bitwise_equal=int(out == ref), pages_leaked=eng.pool.num_live,
         nan_rescues=rescues, seed=st["seed"],
         admit_faults=faults["admit"], growth_faults=faults["growth"],
         transient_faults=faults["transient"], nan_rows=faults["nan_rows"])


def main() -> None:
    cfg, params = _model()
    preempt_rows(cfg, params)
    deadline_rows(cfg, params)
    swap_overhead_row(cfg, params)
    fault_row(cfg, params)


if __name__ == "__main__":
    main()
