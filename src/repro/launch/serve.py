"""Batched serving driver: fixed-slot continuous batching with CHUNKED
PREFILL and on-device decode blocks.

Admission runs the prompt through `lm_prefill` in seq-chunks — each chunk is
one batched model step that fills the admitted slot's K/V + recurrent caches
(other slots' caches are mask-protected) — so a request costs
``ceil(prompt_len/chunk) + gen_tokens`` model steps instead of
``prompt_len + gen_tokens``.  Decode then runs up to ``decode_block`` steps
fully on-device (a jitted lax.scan over `lm_decode_step` with in-loop
sampling) between host syncs.  Decode attention dispatches to the coarsened
split-KV kernel when the model config selects ``decode_backend='pallas'``.

``--quant int8|int4`` serves weight-only-quantized params (repro.quant;
dequant-fused kernels where the geometry allows, dense-dequant fallback
elsewhere) and ``--kv-quant int8`` switches the K/V cache to int8 payloads
with per-(token, kv-head) scales, quantized on append — together they
roughly double the slots*max_len a host can hold; the driver prints the
weight/cache memory next to tok/s.

``--cache paged`` swaps the fixed-stride per-slot cache for the paged KV
cache (repro.serve): a global page pool + per-slot block tables, FCFS
admission with preemption on pool exhaustion, and shared-prefix page
refcounting.  The driver then runs as a streaming front-end — requests are
submitted to the Scheduler, which admits/preempts/retires against the
PagedEngine (examples/serve_batched.py is a client of the same API).

``--spec-k K`` (paged only) turns on speculative decoding: a draft model
(``--draft-config``: an arch name, ``self``, or the default `draft_of`
shrink) proposes K tokens per slot per step and the target scores all K+1
positions in one batched pass through the short-q coarsened verify kernel,
accepting the longest matching prefix and rolling rejected pages back; the
driver reports the acceptance rate next to tok/s.

  PYTHONPATH=src python -m repro.launch.serve --cache paged --spec-k 4 \
      --draft-config self --slots 3 --requests 6 --gen-tokens 24

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --slots 4 --requests 8 --prompt-len 32 --chunk 16 --gen-tokens 16 \
      --quant int8 --kv-quant int8

  PYTHONPATH=src python -m repro.launch.serve --cache paged --num-pages 24 \
      --page-size 16 --slots 4 --requests 8 --gen-tokens 16

Robustness (paged only): ``--host-swap-mib`` bounds the host budget for
swap-out eviction (suspend/resume instead of recompute), ``--deadline`` /
``--max-queue-wait`` / ``--max-waiting`` set the cancellation and
backpressure contract, and ``--fault-seed --fault-admit/-decode/-transient/
-nan`` run the whole trace under deterministic fault injection
(repro.serve.faults) — completed outputs stay bitwise identical and a page
leak assertion runs at shutdown.  Ctrl-C drains gracefully on both paths.

Observability (paged only): ``--trace-out`` records the run and writes a
Chrome trace-event JSON (request lifecycles on per-request tracks, engine /
scheduler spans on their own tracks; load in Perfetto or chrome://tracing),
``--metrics-out`` dumps the metrics registry at exit (JSON snapshot, or
Prometheus text for ``.prom`` paths), and ``--metrics-every N`` prints a
compact registry line every N scheduler quanta.  Reported tok/s is over
device time (jitted calls + sync); scheduler/host time prints separately.
See repro.obs.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.config import ModelConfig


@functools.partial(jax.jit, donate_argnums=(0,))
def _slot_reset(cache, slot):
    """Zero one slot's rows across every cache leaf in a single jitted
    scatter (stacked block leaves carry batch on axis 1, tail on axis 0) —
    no whole-tree re-materialization per admission.  Zeros are scattered in
    each leaf's own dtype (int8 payloads of a quantized KV cache included)."""
    return {
        "blocks": [jax.tree.map(
            lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)), c)
            for c in cache["blocks"]],
        "tail": [jax.tree.map(
            lambda a: a.at[slot].set(jnp.zeros((), a.dtype)), c)
            for c in cache["tail"]],
    }


def device_name() -> str:
    """What the tok/s lines were measured on: platform, kind and count."""
    devs = jax.devices()
    return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"


def _tree_mib(tree) -> float:
    """Total leaf bytes of a pytree (concrete or eval_shape structs), MiB."""
    return sum(int(x.size) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "dtype")) / 2**20


class BatchedServer:
    def __init__(self, cfg: ModelConfig, params, *, slots: int, max_len: int,
                 chunk: int = 16, decode_block: int = 1,
                 temperature: float = 0.0, seed: int = 0,
                 tune: str | None = None, decode_backend: str | None = None,
                 moe_backend: str | None = None, quant: str | None = None,
                 kv_quant: str | None = None):
        if decode_backend is not None:
            cfg = dataclasses.replace(cfg, decode_backend=decode_backend)
        if moe_backend is not None:
            cfg = dataclasses.replace(cfg, moe_backend=moe_backend)
        if quant is not None:
            cfg = dataclasses.replace(cfg, quant=quant)
        if kv_quant is not None:
            cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
        self.weight_mib_dense = _tree_mib(params)
        self.quant_report = None
        if cfg.quant in ("int8", "int4"):
            from repro.quant import quantize_params
            params, self.quant_report = quantize_params(
                params, cfg.quant, group=cfg.quant_group)
        if tune:
            # pre-tune the kernel families this server's hot loops hit: the
            # ops-level streams at prompt-ingest scale plus the split-KV
            # decode-attention family at the allocated cache length
            from repro.tune import warm_from_flag
            warm_from_flag(cfg, tune, seq=max_len, batch=slots)
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.chunk, self.decode_block = chunk, decode_block
        self.temperature = temperature
        self.weight_mib = _tree_mib(params)
        self.cache = M.lm_init_cache(cfg, slots, max_len,
                                     enc_len=min(max_len, 64))
        # the serving headline: quantized weights + int8 KV cut the bytes
        # that bound slots*max_len per host — report both against dense
        self.cache_mib = _tree_mib(self.cache)
        dense_cfg = dataclasses.replace(cfg, kv_quant="none")
        self.cache_mib_dense = _tree_mib(jax.eval_shape(
            lambda: M.lm_init_cache(dense_cfg, slots, max_len,
                                    enc_len=min(max_len, 64))))
        self.pos = np.zeros((slots,), np.int32)
        self.active = np.zeros((slots,), bool)
        self.outputs: list[list[int]] = [[] for _ in range(slots)]
        self.completed: list[list[int]] = []   # archived finished sequences
        self.budget = np.zeros((slots,), np.int32)
        self.key = jax.random.PRNGKey(seed)
        # perf accounting (prefill and decode reported separately); the
        # *_device_s timers cover only the jitted model calls + the sync, so
        # tok/s reflects device-step time and host bookkeeping is reported
        # as overhead, not smeared into throughput
        self.prefill_steps = self.decode_steps = 0
        self.prefill_tokens = self.decoded_tokens = 0
        self.prefill_device_s = self.decode_device_s = 0.0
        self._prefill = jax.jit(
            lambda p, c, t, po, m: M.lm_prefill(p, {"tokens": t}, cfg,
                                                cache=c, pos0=po, mask=m))
        self._decode_fns: dict[int, callable] = {}

    # -- decode: n steps on-device between host syncs -----------------------

    def _decode_fn(self, n: int):
        fn = self._decode_fns.get(n)
        if fn is not None:
            return fn
        cfg, temp = self.cfg, self.temperature

        def run(params, cache, tok, pos, key):
            def body(carry, i):
                tok, pos, cache = carry
                logits, cache = M.lm_decode_step(params, cache, tok, pos, cfg)
                if temp > 0:
                    nxt = jax.random.categorical(jax.random.fold_in(key, i),
                                                 logits / temp, -1)
                else:
                    nxt = jnp.argmax(logits, -1)
                nxt = nxt.astype(jnp.int32)
                return (nxt[:, None], pos + 1, cache), nxt

            (_, _, cache), toks = jax.lax.scan(
                body, (tok, pos, cache), jnp.arange(n))
            return toks.T, cache                       # (slots, n)

        fn = self._decode_fns[n] = jax.jit(run)
        return fn

    # -- admission: chunked prefill -----------------------------------------

    def try_admit(self, prompt: list[int], gen_tokens: int) -> bool:
        # the cache holds max_len-1 prompt rows + the decode row; an
        # oversized prompt must be rejected loudly — silently truncating it
        # changes what the model conditions on
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the max_len "
                f"{self.max_len} cache (holds {self.max_len - 1} prompt "
                f"rows); rejecting instead of truncating")
        free = [s for s in range(self.slots) if not self.active[s]]
        if not free:
            return False
        s = free[0]
        self.cache = _slot_reset(self.cache, jnp.asarray(s, jnp.int32))
        mask = jnp.zeros((self.slots,), bool).at[s].set(True)
        logits = None
        td = time.perf_counter()
        for i in range(0, len(prompt), self.chunk):
            piece = prompt[i:i + self.chunk]
            tokens = np.zeros((self.slots, len(piece)), np.int32)
            tokens[s] = piece
            pos0 = jnp.asarray(self.pos, jnp.int32).at[s].set(i)
            logits, self.cache = self._prefill(
                self.params, self.cache, jnp.asarray(tokens), pos0, mask)
            self.prefill_steps += 1
        jax.block_until_ready(logits)
        self.prefill_device_s += time.perf_counter() - td
        self.prefill_tokens += len(prompt)

        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            first = int(jax.random.categorical(
                sub, logits[s] / self.temperature))
        else:
            first = int(jnp.argmax(logits[s]))
        self.active[s] = True
        self.pos[s] = len(prompt)
        self.outputs[s] = [first]
        self.budget[s] = gen_tokens
        self._maybe_finish(s)
        return True

    # -- decode step(s) ------------------------------------------------------

    def step(self) -> None:
        if not self.active.any():
            return
        act = np.flatnonzero(self.active)
        remaining = int(min(self.budget[s] - len(self.outputs[s])
                            for s in act))
        headroom = int(self.max_len - 1 - self.pos[act].max())
        n = max(1, min(self.decode_block, remaining, headroom))
        tokens = np.zeros((self.slots, 1), np.int32)
        for s in act:
            tokens[s, 0] = self.outputs[s][-1]
        self.key, sub = jax.random.split(self.key)
        td = time.perf_counter()
        toks, self.cache = self._decode_fn(n)(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.pos), sub)
        toks = np.asarray(toks)              # device sync
        self.decode_device_s += time.perf_counter() - td
        self.decode_steps += n
        for s in act:
            take = min(n, int(self.budget[s]) - len(self.outputs[s]))
            self.outputs[s].extend(int(v) for v in toks[s, :take])
            self.decoded_tokens += take
            self.pos[s] += n
            self._maybe_finish(s)

    def _maybe_finish(self, s: int) -> None:
        if len(self.outputs[s]) >= self.budget[s] \
                or self.pos[s] >= self.max_len - 1:
            self.active[s] = False
            self.completed.append(list(self.outputs[s]))

    @property
    def any_active(self) -> bool:
        return bool(self.active.any())


def _serve_paged(args, cfg, params, rng) -> None:
    """Streaming front-end over the paged engine: submit the request trace
    to the Scheduler and let it admit / preempt / retire against the pool."""
    from repro.obs import Registry, TraceRecorder
    from repro.serve import (FaultPlan, FaultyEngine, PagedEngine, Scheduler,
                             SpecPagedEngine, State, draft_of)

    reg = Registry()
    trace = TraceRecorder(enabled=bool(args.trace_out))
    num_pages = args.num_pages if args.num_pages is not None else \
        args.slots * -(-args.max_len // args.page_size) + 1
    kw = dict(slots=args.slots, num_pages=num_pages,
              page_size=args.page_size, max_len=args.max_len,
              chunk=args.chunk, tune=args.tune,
              decode_backend=args.decode_backend,
              moe_backend=args.moe_backend, quant=args.quant,
              kv_quant=args.kv_quant, metrics=reg, trace=trace)
    if args.spec_k:
        if args.draft_config == "self":
            draft_cfg, draft_params = cfg, params
        elif args.draft_config:
            draft_cfg = get_config(args.draft_config)
            if args.reduced:
                draft_cfg = draft_cfg.reduced()
            draft_cfg = dataclasses.replace(draft_cfg, vocab=cfg.vocab)
            draft_params = None        # fresh init at the draft geometry
        else:
            draft_cfg, draft_params = draft_of(cfg), None
        engine = SpecPagedEngine(cfg, params, spec_k=args.spec_k,
                                 draft_cfg=draft_cfg,
                                 draft_params=draft_params,
                                 rng=jax.random.PRNGKey(1), **kw)
    else:
        engine = PagedEngine(cfg, params, decode_block=args.decode_block,
                             **kw)
    plan = None
    front = engine
    if any((args.fault_admit, args.fault_decode, args.fault_transient,
            args.fault_nan)):
        plan = FaultPlan(args.fault_seed, p_admit=args.fault_admit,
                         p_growth=args.fault_decode,
                         p_transient=args.fault_transient,
                         p_nan=args.fault_nan, metrics=reg, trace=trace)
        front = FaultyEngine(engine, plan)
    swap_bytes = None if args.host_swap_mib is None \
        else int(args.host_swap_mib * 2**20)
    sched = Scheduler(front, host_swap_bytes=swap_bytes,
                      max_waiting=args.max_waiting, metrics=reg, trace=trace)
    for _ in range(args.requests):
        sched.submit(list(rng.integers(1, cfg.vocab, args.prompt_len)),
                     args.gen_tokens, deadline=args.deadline,
                     max_queue_wait=args.max_queue_wait)
    t0 = time.perf_counter()
    try:
        if args.metrics_every:
            # same convergence contract as run_until_done, with a compact
            # registry line printed every N scheduler quanta
            while sched.step():
                if sched.steps > 100_000:
                    raise RuntimeError("scheduler did not converge")
                if sched.steps % args.metrics_every == 0:
                    print(f"[q={sched.time}] {reg.line(prefix='sched')}")
            done = sorted(sched.finished, key=lambda r: r.rid)
        else:
            done = sched.run_until_done()
    except KeyboardInterrupt:
        # graceful drain: cancel everything in flight, free its pages,
        # then fall through to the same stats + leak check as a full run
        done = sched.drain(reason="interrupted")
        print(f"\ninterrupted — drained {len(done)} requests")
    dt = time.perf_counter() - t0
    # shutdown leak assertion: every page is either free or live-refcounted
    engine.pool.check()
    assert engine.pool.num_free + engine.pool.num_live \
        == engine.pool.capacity, "page leak at shutdown"
    npre = sum(r.preemptions for r in done)
    total = args.requests * (args.prompt_len + args.gen_tokens)
    print(f"served {len(done)} requests / {total} tokens (paged: "
          f"{engine.pool.capacity} pages x {engine.page_size} tok) in "
          f"{engine.prefill_steps} prefill + {engine.decode_steps} decode "
          f"model steps, {npre} preemptions, {dt:.2f}s")
    by_state = {s.value: n for s in State
                if (n := sum(r.state is s for r in done))}
    print(f"robustness: states {by_state} | swap-evictions "
          f"{engine.suspends} (resumed {engine.resumes}, "
          f"{sched.swap.used_bytes / 2**20:.2f} MiB held, "
          f"{sched.swap.refused} over-budget refusals) | "
          f"decode faults {sched.decode_faults}, NaN rescues "
          f"{engine.nan_rescues}")
    if plan is not None:
        print(f"fault injection: {plan.stats()}")
    # tok/s over DEVICE time (the jitted model calls + their sync), so the
    # number measures the engine, not the scheduler; host/scheduler time is
    # its own line instead of being smeared into throughput
    pdev, ddev = engine.prefill_device_s, engine.decode_device_s
    print(f"prefill: {engine.prefill_tokens} tok in {pdev:.2f}s device "
          f"({engine.prefill_tokens / max(pdev, 1e-9):.1f} tok/s)"
          f" | decode: {engine.decoded_tokens} tok in {ddev:.2f}s device "
          f"({engine.decoded_tokens / max(ddev, 1e-9):.1f} tok/s)"
          f" ({device_name()})")
    ovh = max(dt - pdev - ddev, 0.0)
    print(f"overhead: scheduler+host {ovh:.2f}s of {dt:.2f}s wall "
          f"({ovh / max(dt, 1e-9):.0%})")
    print(f"memory: weights {engine.weight_mib:.2f} MiB | paged kv pool "
          f"{engine.cache_mib:.2f} MiB "
          f"({engine.pool.tokens_capacity} pooled tokens)")
    if args.spec_k:
        print(f"speculative: K={args.spec_k} "
              f"draft={args.draft_config or 'draft_of'} | "
              f"acceptance {engine.acceptance_rate:.3f} "
              f"({engine.accepted}/{max(engine.drafted, 1)} drafts) | "
              f"{engine.spec_steps} verify steps "
              f"({engine.rescue_steps} tie-guard rescues) for "
              f"{engine.decoded_tokens} tokens "
              f"({engine.decoded_tokens / max(engine.spec_steps, 1):.2f} "
              f"tok/step)")
    print("sample output:", done[0].output[:8])
    if args.trace_out:
        trace.dump(args.trace_out)
        extra = f" ({trace.dropped} dropped)" if trace.dropped else ""
        print(f"trace: {len(trace)} events -> {args.trace_out}{extra}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            if args.metrics_out.endswith(".prom"):
                f.write(reg.to_prometheus())
            else:
                json.dump(reg.snapshot(), f, indent=1, sort_keys=True)
        print(f"metrics: {len(reg)} series -> {args.metrics_out}")
    if args.tune:
        from repro.tune import tune_report
        print(tune_report())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCHS)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny same-family config (the default, "
                         "sized for CPU); --no-reduced serves the published "
                         "widths")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk: prompt tokens per batched step")
    ap.add_argument("--decode-block", type=int, default=8,
                    help="decode steps run on-device between host syncs")
    ap.add_argument("--decode-backend", default=None,
                    choices=[None, "ref", "pallas"],
                    help="decode attention path (pallas = split-KV kernel)")
    ap.add_argument("--moe-backend", default=None,
                    choices=[None, "ref", "pallas"],
                    help="expert FFN path (pallas = fused grouped-expert "
                         "kernel, expert-axis coarsening)")
    ap.add_argument("--quant", default=None,
                    choices=[None, "none", "int8", "int4"],
                    help="weight-only quantization of FFN/MoE/attention "
                         "projections (repro.quant; dequant-fused kernels "
                         "where geometry allows, dense-dequant elsewhere)")
    ap.add_argument("--kv-quant", default=None, choices=[None, "none", "int8"],
                    help="int8 KV cache: quantize-on-append, dequant fused "
                         "into the split-KV decode kernel (~2x the "
                         "slots*max_len a host can hold)")
    from repro.tune import TUNE_CHOICES
    ap.add_argument("--tune", default=None, choices=[None, *TUNE_CHOICES],
                    help="warm the coarsening tuning cache before serving")
    ap.add_argument("--cache", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="KV cache layout: contiguous per-slot strides or "
                         "the paged pool + block tables (repro.serve)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged cache: tokens per page (= the decode "
                         "kernel's kv block)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged cache: pool pages incl. the null page "
                         "(default: slots*max_len/page_size + 1)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding (paged cache only): draft K "
                         "tokens per slot per step and verify them in one "
                         "batched short-q pass (0 = off)")
    ap.add_argument("--draft-config", default=None,
                    help="draft model for --spec-k: an arch name, 'self' "
                         "(draft = target, the acceptance upper bound), or "
                         "unset for the default draft_of() shrink")
    ap.add_argument("--host-swap-mib", type=float, default=None,
                    help="paged: host budget (MiB) for swap-out of preempted "
                         "slots; within budget, eviction suspends to host "
                         "and resumes without re-prefill (unset = unbounded, "
                         "0 = always recompute)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="paged: cancel any request still unfinished after "
                         "this many scheduler quanta (terminal CANCELLED, "
                         "pages freed)")
    ap.add_argument("--max-queue-wait", type=int, default=None,
                    help="paged: reject a request that waits more quanta "
                         "than this between admissions (terminal REJECTED "
                         "with a retry-after hint)")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="paged: backpressure bound on the wait queue; "
                         "submits past it are shed with REJECTED")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault-injection plan (see repro."
                         "serve.faults); faults fire only when a --fault-* "
                         "probability is set")
    ap.add_argument("--fault-admit", type=float, default=0.0,
                    help="P(injected PoolExhausted) per admit call")
    ap.add_argument("--fault-decode", type=float, default=0.0,
                    help="P(injected PoolExhausted page-growth failure) per "
                         "decode call")
    ap.add_argument("--fault-transient", type=float, default=0.0,
                    help="P(injected transient DecodeFault) per decode call")
    ap.add_argument("--fault-nan", type=float, default=0.0,
                    help="P(NaN-poisoned logits row) per emitted row "
                         "(exercises the NaN guard + decode-graph rescue)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="paged: record the run (request lifecycles, "
                         "engine/scheduler spans) and write a Chrome "
                         "trace-event JSON — load it in Perfetto or "
                         "chrome://tracing")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="paged: write the metrics registry at exit — a "
                         "JSON snapshot, or Prometheus text exposition when "
                         "the path ends in .prom")
    ap.add_argument("--metrics-every", type=int, default=None, metavar="N",
                    help="paged: print a compact metrics line every N "
                         "scheduler quanta")
    args = ap.parse_args()
    if args.spec_k and args.cache != "paged":
        ap.error("--spec-k needs --cache paged (the draft KV cache and "
                 "verify rollback are built on the page pool)")
    if args.cache != "paged" and (args.trace_out or args.metrics_out
                                  or args.metrics_every):
        ap.error("--trace-out/--metrics-out/--metrics-every need --cache "
                 "paged (the recorder hooks live in the scheduler/paged-"
                 "engine stack)")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    if args.cache == "paged":
        _serve_paged(args, cfg, params, rng)
        return
    server = BatchedServer(cfg, params, slots=args.slots,
                           max_len=args.max_len, chunk=args.chunk,
                           decode_block=args.decode_block, tune=args.tune,
                           decode_backend=args.decode_backend,
                           moe_backend=args.moe_backend, quant=args.quant,
                           kv_quant=args.kv_quant)

    pending = [list(rng.integers(1, cfg.vocab, args.prompt_len))
               for _ in range(args.requests)]
    t0 = time.perf_counter()
    try:
        while pending or server.any_active:
            while pending and server.try_admit(pending[0], args.gen_tokens):
                pending.pop(0)
            if not server.any_active:
                break
            server.step()
    except KeyboardInterrupt:
        # graceful drain: archive in-flight partial outputs, then fall
        # through to the normal stats so the run is still accounted for
        for s in np.flatnonzero(server.active):
            server.active[s] = False
            server.completed.append(list(server.outputs[s]))
        print(f"\ninterrupted — {len(pending)} requests unserved, "
              f"{len(server.completed)} archived (partial output kept)")
    dt = time.perf_counter() - t0
    total_tokens = args.requests * (args.prompt_len + args.gen_tokens)
    print(f"served {args.requests} requests / {total_tokens} tokens in "
          f"{server.prefill_steps} prefill + {server.decode_steps} decode "
          f"model steps, {dt:.2f}s")
    pdev, ddev = server.prefill_device_s, server.decode_device_s
    print(f"prefill: {server.prefill_tokens} tok in {pdev:.2f}s device "
          f"({server.prefill_tokens / max(pdev, 1e-9):.1f} tok/s)"
          f" | decode: {server.decoded_tokens} tok in {ddev:.2f}s device "
          f"({server.decoded_tokens / max(ddev, 1e-9):.1f} tok/s)"
          f" ({device_name()})")
    ovh = max(dt - pdev - ddev, 0.0)
    print(f"overhead: driver+host {ovh:.2f}s of {dt:.2f}s wall "
          f"({ovh / max(dt, 1e-9):.0%})")
    print(f"memory: weights {server.weight_mib:.2f} MiB "
          f"(dense {server.weight_mib_dense:.2f} MiB, "
          f"{server.weight_mib_dense / max(server.weight_mib, 1e-9):.2f}x) | "
          f"kv cache {server.cache_mib:.2f} MiB "
          f"(bf16 {server.cache_mib_dense:.2f} MiB, "
          f"{server.cache_mib_dense / max(server.cache_mib, 1e-9):.2f}x)")
    print("sample output:", server.completed[0][:8] if server.completed
          else server.outputs[0][:8])
    if args.tune:
        from repro.tune import tune_report
        print(tune_report())


if __name__ == "__main__":
    main()
