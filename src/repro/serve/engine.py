"""PagedEngine: the model-coupled paged-KV serving engine.

Owns the paged decode cache (repro.models.model.lm_init_cache_paged), the
page pool + block tables (repro.serve.paging), per-slot generation state,
and the jitted prefill/decode steps.  The Scheduler drives it through the
admit/decode/finish/preempt protocol (repro.serve.scheduler); it never
schedules on its own.

Key mechanics:

* **Admission** allocates exactly the pages the prompt needs, prefills the
  prompt in chunks through the block table (non-admitted slots' table rows
  are NULLed, so their garbage writes land on the null page — the paged
  replacement for the contiguous path's whole-cache mask select), and
  returns the first greedy token from the prefill logits.
* **Decode** grows each running slot's table on demand (pages covering the
  rows the next block will write) before launching a jitted on-device
  decode block; pool exhaustion surfaces as PoolExhausted for the scheduler
  to translate into a preemption.
* **Shared prefixes** are registered once (prefilled into their own pages +
  a snapshot of the non-paged per-slot state) and admitted by refcount:
  an admit whose prompt starts with the registered page-aligned token
  prefix increfs those pages instead of recomputing them.
* **Preempt/finish** release the slot's pages (decref — shared pages
  survive in the registry) and clear the slot.

Greedy sampling only: determinism (a request's outputs are identical to
running it alone, whatever the co-residents) is part of the contract the
scheduler simulation tests pin.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig, ATTN_GLOBAL, ATTN_LOCAL
from repro.obs import ENGINE_TRACK, NULL_TRACER, Registry
from repro.serve.paging import (BlockTables, DecodeFault, PagePool,
                                PoolExhausted, pages_needed)


@dataclasses.dataclass
class Suspension:
    """Host-resident snapshot of one suspended slot: the page rows its block
    table covered (gathered off-device), the non-paged per-slot state, and
    the generation cursor.  ``resume`` restores all of it into freshly
    allocated pages WITHOUT re-running prefill — the whole point of
    swap-preemption over recompute-preemption."""
    n_tokens: int           # cache rows live at suspend (= written)
    n_pages: int            # pages the snapshot covers
    last: int               # last sampled token
    remaining: int          # gen tokens left
    pages: Any              # {"blocks": [...], "tail": [...]} page gathers
    state: Any              # non-paged per-slot snapshot (recurrent/SSM)
    nbytes: int = 0

    def __post_init__(self):
        if not self.nbytes:
            self.nbytes = sum(
                int(a.nbytes) for a in jax.tree.leaves(
                    (self.pages, self.state)))


@dataclasses.dataclass
class PrefixRecord:
    """A registered shared prefix: its page-aligned token prefix, the pages
    holding those rows (registry keeps one refcount), and a snapshot of the
    non-paged per-slot state (recurrent/SSM/conv) after ingesting it."""
    tokens: tuple
    pages: list
    state: Any              # {"blocks": [leaf rows...], "tail": [...]}


def _tree_mib(tree) -> float:
    return sum(int(x.size) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "dtype")) / 2**20


class PagedEngine:
    """Paged-KV serving engine for one model instance.

    num_pages counts POOL pages including the reserved null page; the
    per-slot table holds ceil(max_len / page_size) entries and admission
    rejects any prompt_len + gen_tokens > max_len outright (the contiguous
    server's silent `max_len - 1` truncation has no paged analog)."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int,
                 num_pages: int, page_size: int, max_len: int,
                 chunk: int = 16, decode_block: int = 1,
                 tune: str | None = None, decode_backend: str | None = None,
                 moe_backend: str | None = None, quant: str | None = None,
                 kv_quant: str | None = None,
                 max_prefixes: int | None = None,
                 metrics: Registry | None = None, trace=None):
        if cfg.is_encdec:
            raise NotImplementedError("PagedEngine: enc-dec models are not "
                                      "supported")
        if decode_backend is not None:
            cfg = dataclasses.replace(cfg, decode_backend=decode_backend)
        if moe_backend is not None:
            cfg = dataclasses.replace(cfg, moe_backend=moe_backend)
        if quant is not None:
            cfg = dataclasses.replace(cfg, quant=quant)
        if kv_quant is not None:
            cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
        self.quant_report = None
        if cfg.quant in ("int8", "int4"):
            from repro.quant import quantize_params
            params, self.quant_report = quantize_params(
                params, cfg.quant, group=cfg.quant_group)
        self.obs = metrics if metrics is not None else Registry()
        self.trace = trace if trace is not None else NULL_TRACER
        if tune:
            from repro.tune import warm_from_flag
            warm_from_flag(cfg, tune, seq=max_len, batch=slots,
                           page_size=page_size, metrics=self.obs)
        self.cfg, self.params = cfg, params
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.chunk, self.decode_block = int(chunk), int(decode_block)
        self.pool = PagePool(num_pages, page_size)
        self.npp = pages_needed(max_len, page_size)
        self.bt = BlockTables(slots, self.npp)
        self.cache = M.lm_init_cache_paged(cfg, slots, num_pages, page_size)
        self.cache_mib = _tree_mib(self.cache)
        self.weight_mib = _tree_mib(params)

        self.active = np.zeros((slots,), bool)
        self.written = np.zeros((slots,), np.int32)   # cache rows filled
        self.last = np.zeros((slots,), np.int32)      # last sampled token
        self.remaining = np.zeros((slots,), np.int32)  # gen tokens left
        # LRU order: dict insertion order is recency (oldest first); a
        # shared-prefix admit hit moves its record to the end
        self.prefixes: dict[str, PrefixRecord] = {}
        self.max_prefixes = max_prefixes
        self.prefix_evictions = 0

        # engine counters live in the obs registry; the names below stay as
        # read-only properties so benchmarks/tests read the same ints
        o = self.obs
        self._c_prefill_steps = o.counter("engine_prefill_steps_total")
        self._c_decode_steps = o.counter("engine_decode_steps_total")
        self._c_prefill_tokens = o.counter("engine_prefill_tokens_total")
        self._c_decode_tokens = o.counter("engine_decode_tokens_total")
        self._c_suspends = o.counter("engine_suspends_total")
        self._c_resumes = o.counter("engine_resumes_total")
        self._c_swapped_tokens = o.counter(
            "engine_swapped_tokens_total",
            "cache rows carried across suspends")
        self._c_nan_rescues = o.counter(
            "engine_nan_rescues_total", "decode blocks re-run by the guard")
        # device-boundary timers: jitted call + block_until_ready ONLY (no
        # host bookkeeping) — what the tok/s lines should divide by
        self._c_prefill_dev = o.counter("engine_prefill_device_seconds_total")
        self._c_decode_dev = o.counter("engine_decode_device_seconds_total")
        self.fault_hook = None          # repro.serve.faults sets this
        self._attn_kinds = self._kind_flags(cfg)
        self._swap_page_bytes, self._swap_fixed_bytes = self._swap_layout()

        # named, so the compiled module reads jit_engine_prefill in a trace
        def engine_prefill(p, c, t, po, m, bt):
            return M.lm_prefill(p, {"tokens": t}, cfg, cache=c, pos0=po,
                                mask=m, block_table=bt)

        self._prefill = jax.jit(engine_prefill)
        self._decode_fns: dict[int, Any] = {}

    # -- static layout helpers ----------------------------------------------

    @staticmethod
    def _kind_flags(cfg):
        period, _, tail = M._period(cfg)
        attn = (ATTN_GLOBAL, ATTN_LOCAL)
        return ([k in attn for k in period], [k in attn for k in tail])

    # legacy counter names, now views over the obs registry ------------------

    @property
    def prefill_steps(self) -> int:
        return self._c_prefill_steps.value

    @property
    def decode_steps(self) -> int:
        return self._c_decode_steps.value

    @property
    def prefill_tokens(self) -> int:
        return self._c_prefill_tokens.value

    @property
    def decoded_tokens(self) -> int:
        return self._c_decode_tokens.value

    @property
    def suspends(self) -> int:
        return self._c_suspends.value

    @property
    def resumes(self) -> int:
        return self._c_resumes.value

    @property
    def swapped_out_tokens(self) -> int:
        return self._c_swapped_tokens.value

    @property
    def nan_rescues(self) -> int:
        return self._c_nan_rescues.value

    @property
    def prefill_device_s(self) -> float:
        return self._c_prefill_dev.value

    @property
    def decode_device_s(self) -> float:
        return self._c_decode_dev.value

    @property
    def page_size(self) -> int:
        return self.pool.page_size

    @property
    def pool_capacity(self) -> int:
        return self.pool.capacity

    @property
    def any_active(self) -> bool:
        return bool(self.active.any())

    def _device_table(self, active) -> jax.Array:
        return jnp.asarray(self.bt.device(active=active), jnp.int32)

    # -- per-slot non-paged state (recurrent/SSM/conv) ----------------------

    def _nonpaged(self, cache, fn_blocks, fn_tail):
        """Map over the NON-paged leaves only (paged pools pass through)."""
        blk_attn, tail_attn = self._attn_kinds
        blocks = [c if is_attn else jax.tree.map(fn_blocks, c)
                  for c, is_attn in zip(cache["blocks"], blk_attn)]
        tail = [c if is_attn else jax.tree.map(fn_tail, c)
                for c, is_attn in zip(cache["tail"], tail_attn)]
        return {"blocks": blocks, "tail": tail}

    def _slot_reset(self, slot: int):
        s = jnp.asarray(slot, jnp.int32)
        self.cache = self._nonpaged(
            self.cache,
            lambda a: a.at[:, s].set(jnp.zeros((), a.dtype)),
            lambda a: a.at[s].set(jnp.zeros((), a.dtype)))

    def _slot_snapshot(self, slot: int):
        return self._nonpaged(self.cache,
                              lambda a: a[:, slot], lambda a: a[slot])

    def _slot_load(self, slot: int, snap) -> None:
        blk_attn, tail_attn = self._attn_kinds
        s = jnp.asarray(slot, jnp.int32)
        blocks = [c if is_attn else jax.tree.map(
            lambda a, v: a.at[:, s].set(v), c, sc)
            for c, sc, is_attn in zip(self.cache["blocks"],
                                      snap["blocks"], blk_attn)]
        tail = [c if is_attn else jax.tree.map(
            lambda a, v: a.at[s].set(v), c, sc)
            for c, sc, is_attn in zip(self.cache["tail"],
                                      snap["tail"], tail_attn)]
        self.cache = {"blocks": blocks, "tail": tail}

    # -- resumable preemption: host swap of a slot's live pages -------------

    def _swap_layout(self) -> tuple[int, int]:
        """(bytes per swapped page, fixed per-slot bytes): paged leaves
        charge their page-axis row (axis 1 under the period stack, axis 0
        in the tail), non-paged leaves their slot row."""
        blk_attn, tail_attn = self._attn_kinds
        per_page = fixed = 0
        for c, attn in zip(self.cache["blocks"], blk_attn):
            for a in jax.tree.leaves(c):
                (per_page, fixed) = (per_page + a.nbytes // a.shape[1], fixed) \
                    if attn else (per_page, fixed + a.nbytes // a.shape[1])
        for c, attn in zip(self.cache["tail"], tail_attn):
            for a in jax.tree.leaves(c):
                (per_page, fixed) = (per_page + a.nbytes // a.shape[0], fixed) \
                    if attn else (per_page, fixed + a.nbytes // a.shape[0])
        return per_page, fixed

    def suspend_bytes(self, slot: int) -> int:
        """Host bytes suspend(slot) would take — the scheduler's swap-vs-
        recompute policy checks this against its SwapStore budget BEFORE
        deciding how to evict."""
        return self._swap_fixed_bytes + self._swap_page_bytes \
            * pages_needed(int(self.written[slot]), self.page_size)

    def _gather_pages(self, idx):
        """Copy the page-axis rows ``idx`` of every PAGED cache leaf to host
        memory; non-paged leaves map to None (the slot snapshot covers
        them).  SpecPagedEngine extends this with the draft pools."""
        i = jnp.asarray(idx, jnp.int32)
        blk_attn, tail_attn = self._attn_kinds
        return {
            "blocks": [jax.tree.map(lambda a: np.asarray(a[:, i]), c)
                       if attn else None
                       for c, attn in zip(self.cache["blocks"], blk_attn)],
            "tail": [jax.tree.map(lambda a: np.asarray(a[i]), c)
                     if attn else None
                     for c, attn in zip(self.cache["tail"], tail_attn)],
        }

    def _scatter_pages(self, idx, saved) -> None:
        """Write a _gather_pages snapshot back at (freshly allocated) page
        ids ``idx`` — the resume half of the swap."""
        i = jnp.asarray(idx, jnp.int32)
        self.cache = {
            "blocks": [c if sv is None else jax.tree.map(
                lambda a, v: a.at[:, i].set(v), c, sv)
                for c, sv in zip(self.cache["blocks"], saved["blocks"])],
            "tail": [c if sv is None else jax.tree.map(
                lambda a, v: a.at[i].set(v), c, sv)
                for c, sv in zip(self.cache["tail"], saved["tail"])],
        }

    def suspend(self, slot: int) -> Suspension:
        """Swap a running slot's state to host and free its device pages.
        Unlike ``preempt``, NO work is lost: ``resume`` restores the cache
        rows bitwise, so generation continues exactly where it stopped
        without re-running prefill.  Shared-prefix pages are copied too
        (they resume as private pages — sharing is not re-established)."""
        if not self.active[slot]:
            raise RuntimeError(f"suspend of inactive slot {slot}")
        n_tok = int(self.written[slot])
        # decode may have grown the table past the written rows before an
        # exhaustion elsewhere aborted the step; rows >= written are always
        # rewritten before any read, so only the covering pages swap out
        self.pool.release(self.bt.truncate(
            slot, pages_needed(n_tok, self.page_size)))
        pages = list(self.bt[slot])
        # the slot snapshot passes attention entries through by reference
        # (they live in the paged pools, gathered above) — null them so
        # only the non-paged per-slot rows copy to host
        snap, (blk_attn, tail_attn) = self._slot_snapshot(slot), \
            self._attn_kinds
        state = {
            "blocks": [None if attn else jax.tree.map(np.asarray, c)
                       for c, attn in zip(snap["blocks"], blk_attn)],
            "tail": [None if attn else jax.tree.map(np.asarray, c)
                     for c, attn in zip(snap["tail"], tail_attn)],
        }
        with self.trace.span("swap.gather", "swap", slot,
                             {"tokens": n_tok, "pages": len(pages)}):
            susp = Suspension(
                n_tokens=n_tok, n_pages=len(pages), last=int(self.last[slot]),
                remaining=int(self.remaining[slot]),
                pages=self._gather_pages(pages), state=state)
        self._drop(slot)
        self._c_suspends.inc()
        self._c_swapped_tokens.inc(n_tok)
        return susp

    def resume(self, slot: int, susp: Suspension) -> None:
        """Restore a suspension into freshly allocated pages.  Raises
        PoolExhausted with NO partial effects when the pool cannot serve
        the allocation right now (the caller keeps the suspension and
        retries later).  Runs zero prefill steps."""
        if self.active[slot]:
            raise RuntimeError(f"slot {slot} is already running")
        fresh = self.pool.alloc(susp.n_pages)   # raises, no side effects
        self.bt.append(slot, fresh)
        with self.trace.span("swap.scatter", "swap", slot,
                             {"tokens": susp.n_tokens,
                              "pages": susp.n_pages}):
            self._scatter_pages(fresh, susp.pages)
            self._slot_reset(slot)
            self._slot_load(slot, susp.state)
        self.active[slot] = True
        self.written[slot] = susp.n_tokens
        self.last[slot] = susp.last
        self.remaining[slot] = susp.remaining
        self._c_resumes.inc()

    # -- prefill ------------------------------------------------------------

    def _run_prefill(self, slot: int, tokens, pos_start: int, rid=None):
        """Chunked prefill of ``tokens`` into ``slot`` starting at row
        ``pos_start``; returns the final chunk's logits row."""
        mask = jnp.zeros((self.slots,), bool).at[slot].set(True)
        only = np.zeros((self.slots,), bool)
        only[slot] = True
        bt_dev = self._device_table(only)    # other slots' writes -> null
        logits = None
        tr = self.trace
        for i in range(0, len(tokens), self.chunk):
            piece = tokens[i:i + self.chunk]
            buf = np.zeros((self.slots, len(piece)), np.int32)
            buf[slot] = piece
            pos0 = jnp.asarray(self.written, jnp.int32).at[slot].set(
                pos_start + i)
            with tr.span("prefill.chunk", "engine", slot,
                         {"rid": rid, "pos": pos_start + i, "n": len(piece)}
                         if tr else None):
                td = time.perf_counter()
                logits, self.cache = self._prefill(
                    self.params, self.cache, jnp.asarray(buf), pos0, mask,
                    bt_dev)
                jax.block_until_ready(logits)
                self._c_prefill_dev.inc(time.perf_counter() - td)
            self._c_prefill_steps.inc()
        self._c_prefill_tokens.inc(len(tokens))
        return logits[slot]

    # -- engine protocol ----------------------------------------------------

    def admit(self, slot: int, req) -> int:
        """Allocate pages, ingest the prompt, return the first greedy token.
        Raises ValueError for prompts that can never fit, PoolExhausted when
        the pool can't serve the prompt right now (no partial effects).

        Traced as ``engine.admit`` on the slot's track: its ``prefill.chunk``
        and ``admit.first_token`` children are device calls and their host
        reads, so its self time is the host work and eager ops between
        them (allocation, slot reset, block-table upload)."""
        tr = self.trace
        with tr.span("engine.admit", "engine", slot,
                     {"rid": getattr(req, "rid", None)} if tr else None):
            return self._admit(slot, req)

    def _admit(self, slot: int, req) -> int:
        if self.active[slot]:
            raise RuntimeError(f"slot {slot} is already running")
        prompt, gen = list(req.prompt), int(req.gen)
        if not prompt or gen < 1:
            raise ValueError("admit needs a non-empty prompt and gen >= 1")
        if len(prompt) + gen > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + gen ({gen}) tokens exceed "
                f"max_len {self.max_len}; rejecting instead of truncating")

        pre = self.prefixes.get(req.prefix) if req.prefix else None
        start = 0
        shared: list[int] = []
        if pre is not None and len(pre.tokens) <= len(prompt) - 1 \
                and tuple(prompt[: len(pre.tokens)]) == pre.tokens:
            start, shared = len(pre.tokens), pre.pages
            # LRU touch: a hit is a use — move to the recency tail
            self.prefixes[req.prefix] = self.prefixes.pop(req.prefix)
        fresh = self.pool.alloc(pages_needed(len(prompt), self.page_size)
                                - len(shared))   # raises, no side effects
        self.pool.incref(shared)
        self.bt.append(slot, list(shared) + fresh)

        self._slot_reset(slot)
        if start:
            self._slot_load(slot, pre.state)
        logits = self._run_prefill(slot, prompt[start:], start,
                                   rid=getattr(req, "rid", None))
        with self.trace.span("admit.first_token", "engine", slot):
            first = int(jnp.argmax(logits))
        self.active[slot] = True
        self.written[slot] = len(prompt)
        self.last[slot] = first
        self.remaining[slot] = gen - 1
        return first

    def _decode_fn(self, n: int):
        fn = self._decode_fns.get(n)
        if fn is not None:
            return fn
        cfg = self.cfg

        # named, so the compiled module reads jit_engine_decode in a trace
        def engine_decode(params, cache, tok, pos, bt):
            def body(carry, _):
                tok, pos, cache = carry
                logits, cache = M.lm_decode_step(params, cache, tok, pos,
                                                 cfg, block_table=bt)
                with jax.named_scope("head"):
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (nxt[:, None], pos + 1, cache), (nxt, logits)

            (_, _, cache), (toks, lgs) = jax.lax.scan(
                body, (tok, pos, cache), jnp.arange(n))
            # (slots, n) tokens + (slots, n, V) per-step logits: the host-
            # visible logits feed the NaN guard below
            return toks.T, jnp.moveaxis(lgs, 0, 1), cache

        fn = self._decode_fns[n] = jax.jit(engine_decode)
        return fn

    def decode(self, slots) -> dict[int, list[int]]:
        """Run a decode block for the running ``slots``; returns the new
        greedy tokens per slot.  Page growth happens BEFORE the launch;
        PoolExhausted propagates to the scheduler (slots whose growth
        already succeeded keep their pages — consistent, not leaked).

        NaN guard: a step whose host-visible logits hold a NaN row (a
        transient fault — the injection harness poisons exactly here) is
        DISCARDED and re-run through the SAME jitted function: the rewrite
        of cache rows [written, written+n) is bitwise idempotent (same
        graph, same inputs; stale rows past ``written`` are pos-masked), so
        a rescued block's tokens are exactly the fault-free ones.  Retries
        are bounded; exhaustion raises DecodeFault with the per-slot
        cursors unadvanced (the scheduler retries the quantum).

        Traced as ``decode.block`` on the engine track, holding one
        ``decode.launch`` (exactly the device-seconds interval),
        ``decode.fetch`` (host copies of tokens and logits) and
        ``decode.guard`` (the NaN scan) per attempt; its self time is page
        growth and token bookkeeping."""
        slots = [s for s in slots if self.active[s]]
        if not slots:
            return {}
        n = max(1, min(self.decode_block,
                       *(int(self.remaining[s]) for s in slots)))
        tr = self.trace
        with tr.span("decode.block", "engine", ENGINE_TRACK,
                     {"slots": len(slots), "n": n} if tr else None):
            for s in slots:
                need = pages_needed(int(self.written[s]) + n,
                                    self.page_size) - self.bt.num_pages(s)
                if need > 0:
                    self.bt.append(s, self.pool.alloc(need))
            tokens = np.zeros((self.slots, 1), np.int32)
            tokens[slots, 0] = self.last[slots]
            retries = 0
            while True:
                toks, lg = self._launch_decode(n, tokens)
                with tr.span("decode.guard", "engine", ENGINE_TRACK):
                    poisoned = np.isnan(lg[slots]).any()
                if not poisoned:
                    break
                retries += 1
                if retries > 4:
                    raise DecodeFault(
                        f"non-finite logits persisted through {retries - 1} "
                        f"rescue re-runs")
                self._c_nan_rescues.inc()
                tr.event("nan.rescue", "engine", ENGINE_TRACK,
                         {"retry": retries})
            self._c_decode_steps.inc(n)
            self._c_decode_tokens.inc(n * len(slots))
            out = {}
            for s in slots:
                out[s] = [int(v) for v in toks[s]]
                self.last[s] = toks[s, -1]
                self.written[s] += n
                self.remaining[s] -= n
        return out

    def _launch_decode(self, n: int, tokens):
        """One decode block on the device for every slot; returns the
        host copies of its (slots, n) tokens and (slots, n, V) logits."""
        tr = self.trace
        with tr.span("decode.launch", "engine", ENGINE_TRACK):
            td = time.perf_counter()
            toks, lgs, self.cache = self._decode_fn(n)(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(self.written, jnp.int32),
                self._device_table(self.active))
            jax.block_until_ready(lgs)
            self._c_decode_dev.inc(time.perf_counter() - td)
        with tr.span("decode.fetch", "engine", ENGINE_TRACK):
            lg = np.asarray(lgs)
            if self.fault_hook is not None:
                lg = self.fault_hook.corrupt_logits(lg, site="decode")
            toks = np.asarray(toks)
        return toks, lg

    # -- admission accounting ------------------------------------------------

    @property
    def free_pages(self) -> int:
        return self.pool.num_free

    def _step_rows(self) -> int:
        """Worst-case cache rows one decode step appends per slot.  The
        speculative engine overrides this (K+1 rows per verify step) and
        exposes `step_growth_bound` to the scheduler's admission check."""
        return self.decode_block

    def _growth_bound(self, req=None) -> int:
        """Worst-case pages the NEXT decode step may allocate across the
        running slots — plus, when ``req`` is given, the pages admitting it
        would take (prompt, counted un-shared) and its own first step's
        growth."""
        n, ps = self._step_rows(), self.page_size
        total = 0
        for s in range(self.slots):
            if self.active[s]:
                total += max(0, pages_needed(int(self.written[s]) + n, ps)
                             - self.bt.num_pages(s))
        if req is not None:
            total += pages_needed(len(req.prompt) + n, ps)
        return total

    def _drop(self, slot: int) -> None:
        self.pool.release(self.bt.drop(slot))
        self.active[slot] = False
        self.written[slot] = self.last[slot] = self.remaining[slot] = 0

    def finish(self, slot: int) -> None:
        self._drop(slot)

    def preempt(self, slot: int) -> None:
        self._drop(slot)

    # -- shared prefixes ----------------------------------------------------

    def register_prefix(self, name: str, tokens) -> int:
        """Prefill the page-aligned head of ``tokens`` once and pin its
        pages under ``name`` (refcount held by the registry); returns the
        number of tokens the record covers (0 = too short to share).
        Needs a free slot to run the prefill in.

        With ``max_prefixes`` set, the registry is a bounded LRU: when full,
        the least-recently-used prefix whose pages nobody else holds
        (registry refcount only, i.e. every page at refcount 1) is evicted
        first; in-use prefixes are never evicted, and a full registry of
        in-use prefixes raises."""
        reg_len = (len(tokens) // self.page_size) * self.page_size
        if reg_len == 0:
            return 0
        if name in self.prefixes:       # re-register: replace, don't leak
            self.drop_prefix(name)
        if self.max_prefixes is not None:
            while len(self.prefixes) >= self.max_prefixes:
                victim = next(
                    (nm for nm, pre in self.prefixes.items()
                     if all(self.pool.refcount[p] == 1 for p in pre.pages)),
                    None)
                if victim is None:
                    raise RuntimeError(
                        f"prefix registry full ({self.max_prefixes}) and "
                        f"every prefix is referenced by a running slot")
                self.drop_prefix(victim)
                self.prefix_evictions += 1
        free = [s for s in range(self.slots) if not self.active[s]]
        if not free:
            raise RuntimeError("register_prefix needs a free slot")
        slot = free[0]
        pages = self.pool.alloc(pages_needed(reg_len, self.page_size))
        self.bt.append(slot, pages)
        self._slot_reset(slot)
        self._run_prefill(slot, list(tokens)[:reg_len], 0)
        snap = self._slot_snapshot(slot)
        self.bt.drop(slot)        # registry keeps the pages' refcount
        self.prefixes[name] = PrefixRecord(
            tokens=tuple(int(t) for t in tokens[:reg_len]), pages=pages,
            state=snap)
        return reg_len

    def drop_prefix(self, name: str) -> None:
        pre = self.prefixes.pop(name)
        self.pool.release(pre.pages)
