"""Speculative decoding on the paged engine: draft K, verify in one pass.

A small DRAFT model proposes K tokens per slot per step; the TARGET model
scores all K+1 positions in ONE batched pass through the short-q coarsened
flash kernel (models.model.lm_verify_step -> the `flash_attention_verify`
tuner family), and the longest prefix of draft tokens matching the target's
greedy argmaxes is accepted.  Greedy verify is EXACT in exact arithmetic:
every emitted token is the target's own argmax given the accepted prefix.

Bitwise parity with non-spec decode needs one more guard.  XLA lowers the
T-row verify graph and the 1-row decode graph with different reduction
orders (GEMM k-panels, attention/softmax reductions pick strategies by
shape), so verify logits match decode logits only to ~1% of the logit
spread (bf16 cache rows drift by an ulp and the error scales with
activation magnitude) — enough to flip an argmax on a near-tie.  The
engine therefore trusts a verify row only when its top-1/top-2 margin
clears ``tie_tau`` TIMES the row's logit std (default 0.1, an order of
magnitude above the observed relative divergence).  A row under the
guard ends the step's emission there; a slot that would emit nothing gets
its one token from a RESCUE pass through the base engine's own jitted
decode function — bitwise-identical to non-spec decode by construction, so
progress is guaranteed and every emitted token is one the base engine would
have produced.  tests/test_spec.py pins output parity, including under
forced rejection and preemption.

Expected speedup: with per-position acceptance rate a, one step emits
E = (1 - a^(K+1)) / (1 - a) tokens for one target verify (≈ one decode-step
cost amortized over E tokens) plus K cheap draft steps.

Paged mechanics:

* The draft KV cache is itself PAGED and shares the target's page-id space:
  page p means row p of the target pools AND row p of the draft pools, so
  one allocator/block-table/rollback covers both models.  Draft pool rows
  at reallocated pages are stale garbage by construction — always
  overwritten (prefill or draft scan) before any read.
* A verify step appends up to K+1 rows per slot, so pages are grown for
  the WORST case before any compute (PoolExhausted propagates with the
  same consistent-not-leaked contract as the base engine), and
  `step_growth_bound` lets the scheduler account that growth at admission
  so a step launched right after an admit can't abort mid-verify.
* Rejection rolls back: the slot's block table is truncated to the pages
  covering the accepted rows (BlockTables.truncate — shared-prefix pages
  sit at the front and are never touched) and the tail pages are released.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig, ATTN_GLOBAL, ATTN_LOCAL
from repro.obs import ENGINE_TRACK
from repro.serve.engine import PagedEngine
from repro.serve.paging import pages_needed


def draft_of(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Derive a draft config from a target config: the standard `reduced`
    shrink (few layers, d_model 128, d_ff 256) but sharing the target's
    FULL vocab — draft proposals must be target token ids."""
    small = cfg.reduced(**{k: v for k, v in overrides.items()
                           if k != "vocab"})
    return dataclasses.replace(small, vocab=cfg.vocab)


class SpecPagedEngine(PagedEngine):
    """PagedEngine whose decode step is draft-K / batched-verify.

    Same admit/decode/finish/preempt protocol as the base engine (the
    Scheduler drives both identically); `decode` returns the accepted
    tokens per slot — between 1 (immediate rejection: the target's
    correction) and K+1 (all drafts accepted + the bonus token) per step.

    draft_params=None initializes a fresh draft from ``rng`` (useful for
    benchmarks that want forced rejections); passing the target's own
    (cfg, params) as the draft gives acceptance rate 1.0 — the upper-bound
    sanity check.
    """

    def __init__(self, cfg: ModelConfig, params, *, spec_k: int,
                 draft_cfg: Optional[ModelConfig] = None, draft_params=None,
                 rng=None, tie_tau: float = 0.1, **kw):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        tune = kw.pop("tune", None)
        super().__init__(cfg, params, **kw)
        if tune:
            # warm with the verify family included (its spec carries K);
            # self.cfg carries the backend/quant replacements super applied
            from repro.tune import warm_from_flag
            warm_from_flag(self.cfg, tune, seq=self.max_len,
                           batch=self.slots, page_size=self.page_size,
                           spec_k=spec_k, metrics=self.obs)
        bad = [k for k in self.cfg.layer_kinds()
               if k not in (ATTN_GLOBAL, ATTN_LOCAL)]
        if bad:
            raise NotImplementedError(
                f"speculative decoding needs an attention-only stack "
                f"(recurrent/SSM state cannot be rewound past rejected "
                f"rows); target has {sorted(set(bad))}")
        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg if draft_cfg is not None \
            else draft_of(self.cfg)
        if self.draft_cfg.vocab != self.cfg.vocab:
            raise ValueError(
                f"draft vocab {self.draft_cfg.vocab} != target vocab "
                f"{self.cfg.vocab}; draft proposals must be target ids")
        bad = [k for k in self.draft_cfg.layer_kinds()
               if k not in (ATTN_GLOBAL, ATTN_LOCAL)]
        if bad:
            raise NotImplementedError(
                f"draft model must be attention-only; has {sorted(set(bad))}")
        if draft_params is None:
            draft_params = M.lm_init(rng if rng is not None
                                     else jax.random.PRNGKey(0),
                                     self.draft_cfg)
        self.draft_params = draft_params
        # the draft cache shares the TARGET's page-id space: one page pool
        # worth of ids, two sets of pools (target + draft) indexed by them
        self.draft_cache = M.lm_init_cache_paged(
            self.draft_cfg, self.slots, self.pool.num_pages, self.page_size)
        self.cache_mib += sum(
            int(x.size) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(self.draft_cache)) / 2**20

        # the draft pools swap out with the target's (shared page-id space)
        self._swap_page_bytes, self._swap_fixed_bytes = self._swap_layout()

        self.tie_tau = float(tie_tau)
        o = self.obs
        self._c_drafted = o.counter("spec_drafted_total",
                                    "draft tokens offered to verify")
        self._c_accepted = o.counter("spec_accepted_total",
                                     "draft tokens accepted")
        self._c_spec_steps = o.counter("spec_steps_total")
        self._c_rescues = o.counter(
            "spec_rescue_steps_total",
            "steps that needed a decode-graph rescue")
        self._c_nan_rows = o.counter(
            "spec_nan_rows_total", "verify rows voided by the NaN guard")
        dcfg = self.draft_cfg
        self._draft_prefill_fn = jax.jit(
            lambda p, c, t, po, m, bt: M.lm_prefill(
                p, {"tokens": t}, dcfg, cache=c, pos0=po, mask=m,
                block_table=bt))
        tcfg = self.cfg
        self._verify_fn = jax.jit(
            lambda p, c, t, po, vl, bt: M.lm_verify_step(
                p, c, t, po, tcfg, block_table=bt, valid_len=vl))
        self._draft_fns: dict[int, Any] = {}

    @property
    def drafted(self) -> int:
        return self._c_drafted.value

    @property
    def accepted(self) -> int:
        return self._c_accepted.value

    @property
    def spec_steps(self) -> int:
        return self._c_spec_steps.value

    @property
    def rescue_steps(self) -> int:
        return self._c_rescues.value

    @property
    def nan_rows(self) -> int:
        return self._c_nan_rows.value

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(1, self.drafted)

    # -- admission accounting (scheduler hook) ------------------------------

    def _step_rows(self) -> int:
        return self.spec_k + 1          # a verify appends up to K+1 rows

    def step_growth_bound(self, req=None) -> int:
        return self._growth_bound(req)

    # -- host swap (suspend/resume) covers BOTH models' pools ----------------

    def _swap_layout(self):
        per_page, fixed = super()._swap_layout()
        draft = getattr(self, "draft_cache", None)
        if draft is not None:           # absent during super().__init__
            for c in draft["blocks"]:   # attention-only: every leaf paged
                per_page += sum(a.nbytes // a.shape[1]
                                for a in jax.tree.leaves(c))
            for c in draft["tail"]:
                per_page += sum(a.nbytes // a.shape[0]
                                for a in jax.tree.leaves(c))
        return per_page, fixed

    def _gather_pages(self, idx):
        saved = super()._gather_pages(idx)
        i = jnp.asarray(idx, jnp.int32)
        saved["draft_blocks"] = [
            jax.tree.map(lambda a: np.asarray(a[:, i]), c)
            for c in self.draft_cache["blocks"]]
        saved["draft_tail"] = [jax.tree.map(lambda a: np.asarray(a[i]), c)
                               for c in self.draft_cache["tail"]]
        return saved

    def _scatter_pages(self, idx, saved) -> None:
        super()._scatter_pages(idx, saved)
        i = jnp.asarray(idx, jnp.int32)
        self.draft_cache = {
            "blocks": [jax.tree.map(lambda a, v: a.at[:, i].set(v), c, sv)
                       for c, sv in zip(self.draft_cache["blocks"],
                                        saved["draft_blocks"])],
            "tail": [jax.tree.map(lambda a, v: a.at[i].set(v), c, sv)
                     for c, sv in zip(self.draft_cache["tail"],
                                      saved["draft_tail"])],
        }

    # -- draft-side prefill --------------------------------------------------

    def _run_draft_prefill(self, slot: int, tokens) -> None:
        """Ingest the FULL prompt into the draft cache through the slot's
        (already-allocated) block table.  Shared-prefix pages are written
        too: sharers write identical draft K/V there (same tokens, same
        draft params, deterministic), so the frozen-page convention holds
        in effect if not in letter."""
        mask = jnp.zeros((self.slots,), bool).at[slot].set(True)
        only = np.zeros((self.slots,), bool)
        only[slot] = True
        bt_dev = self._device_table(only)
        for i in range(0, len(tokens), self.chunk):
            piece = tokens[i:i + self.chunk]
            buf = np.zeros((self.slots, len(piece)), np.int32)
            buf[slot] = piece
            pos0 = jnp.asarray(self.written, jnp.int32).at[slot].set(i)
            _, self.draft_cache = self._draft_prefill_fn(
                self.draft_params, self.draft_cache, jnp.asarray(buf), pos0,
                mask, bt_dev)

    def _admit(self, slot: int, req) -> int:
        first = super()._admit(slot, req)
        # no draft-side allocation: the target's pages cover the draft, so
        # this cannot raise PoolExhausted after super() succeeded
        self._run_draft_prefill(slot, list(req.prompt))
        return first

    # -- the spec step -------------------------------------------------------

    def _draft_fn(self, n: int):
        """Jitted draft scan: n chained greedy steps through the paged
        draft cache, step j writing cache row pos0+j and proposing the
        token for position pos0+j+1.  ``keff`` masks writes past a slot's
        own draft budget (batch padding)."""
        fn = self._draft_fns.get(n)
        if fn is not None:
            return fn
        dcfg = self.draft_cfg

        def run(params, cache, tok, pos0, keff, bt):
            def body(carry, j):
                tok, pos, cache = carry
                logits, cache = M.lm_decode_step(
                    params, cache, tok, pos, dcfg, block_table=bt,
                    write_mask=j <= keff)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (nxt[:, None], pos + 1, cache), nxt

            (_, _, cache), toks = jax.lax.scan(
                body, (tok, pos0, cache), jnp.arange(n))
            return toks.T, cache                     # (slots, n)

        fn = self._draft_fns[n] = jax.jit(run)
        return fn

    def decode(self, slots) -> dict[int, list[int]]:
        """One draft-K / verify / accept / rollback step for the running
        ``slots``.  Emits 1..K+1 tokens per slot.  Page growth for the
        WORST case (all K accepted) happens before any compute;
        PoolExhausted propagates to the scheduler with slots whose growth
        already succeeded keeping their pages — consistent, not leaked."""
        slots = [s for s in slots if self.active[s]]
        if not slots:
            return {}
        keff = np.zeros((self.slots,), np.int32)
        for s in slots:
            # never draft past the request's budget: a step emits at most
            # keff+1 tokens and remaining >= 1 here
            keff[s] = min(self.spec_k, int(self.remaining[s]) - 1)
        kpad = int(keff[slots].max())
        tr = self.trace
        with tr.span("verify.pass", "engine", ENGINE_TRACK,
                     {"slots": len(slots), "k": kpad} if tr else None):
            return self._spec_step(slots, keff, kpad)

    def _spec_step(self, slots, keff, kpad) -> dict[int, list[int]]:
        """The body of ``decode``, traced like the base engine's block:
        ``decode.launch`` (draft scan and verify, exactly the device-seconds
        interval), ``decode.fetch`` (the logits' host copy) and
        ``decode.guard`` (tie and NaN guards)."""
        ps, tr = self.page_size, self.trace
        for s in slots:
            need = pages_needed(int(self.written[s]) + int(keff[s]) + 1, ps) \
                - self.bt.num_pages(s)
            if need > 0:
                self.bt.append(s, self.pool.alloc(need))
        bt_dev = self._device_table(self.active)
        pos0 = jnp.asarray(self.written, jnp.int32)
        keff_dev = jnp.asarray(keff, jnp.int32)
        last = np.zeros((self.slots, 1), np.int32)
        last[slots, 0] = self.last[slots]
        last_dev = jnp.asarray(last)

        # draft keff+1 chained steps (kpad+1 padded): feeds last, d_1..d_k,
        # writing draft rows written..written+keff — the draft cache ends
        # one row AHEAD of the accepted prefix in the all-accept case and
        # exactly at it after a rollback, both equal to new_written
        with tr.span("decode.launch", "engine", ENGINE_TRACK):
            td = time.perf_counter()
            drafts, self.draft_cache = self._draft_fn(kpad + 1)(
                self.draft_params, self.draft_cache, last_dev, pos0,
                keff_dev, bt_dev)

            # verify all K+1 positions in ONE short-q pass: row t scores
            # position written+t+1 given [prompt..., last, d_1..d_t]
            vtok = jnp.concatenate([last_dev, drafts[:, :kpad]], axis=1)
            logits, self.cache = self._verify_fn(
                self.params, self.cache, vtok, pos0, keff_dev + 1, bt_dev)
            jax.block_until_ready(logits)
            self._c_decode_dev.inc(time.perf_counter() - td)
        with tr.span("decode.fetch", "engine", ENGINE_TRACK):
            lg = np.asarray(logits, np.float32)          # (slots, kpad+1, V)
            if self.fault_hook is not None:
                lg = self.fault_hook.corrupt_logits(lg, site="verify")
            drafts = np.asarray(drafts)
        with tr.span("decode.guard", "engine", ENGINE_TRACK):
            greedy = lg.argmax(-1)
            top2 = np.partition(lg, -2, axis=-1)[..., -2:]
            # tie guard threshold: margin relative to the row's logit
            # spread (inter-graph divergence scales with activation
            # magnitude)
            clear = (top2[..., 1] - top2[..., 0]) >= self.tie_tau * lg.std(-1)
            # NaN guard: a poisoned (non-finite) verify row compares False
            # into ``clear`` already, but make it explicit — the row is
            # voided, so emission stops before it and the decode-graph
            # rescue below takes over when nothing else would emit.  That
            # is the whole fault story: no token derived from a poisoned
            # row can ever be emitted.
            finite = np.isfinite(lg).all(-1)
            voided = int((~finite[np.asarray(slots)]).sum())
            clear &= finite
        if voided:
            self._c_nan_rows.inc(voided)
            tr.event("nan.voided", "engine", ENGINE_TRACK, {"rows": voided})
        self._c_decode_steps.inc()
        self._c_spec_steps.inc()

        out = {}
        rescue = []
        for s in slots:
            k, g, d, ok = int(keff[s]), greedy[s], drafts[s], clear[s]
            n_acc = 0
            while n_acc < k and ok[n_acc] and d[n_acc] == g[n_acc]:
                n_acc += 1
            # accepted drafts d_1..d_n_acc == g_0..g_{n_acc-1}, then the
            # target's own next token g_n_acc (correction or bonus) — but
            # only when row n_acc's margin clears the tie guard; a guarded
            # row's position is left to a decode-geometry step instead
            # (the rescue below, or simply the next step)
            emitted = [int(g[j]) for j in range(n_acc + (1 if ok[n_acc]
                                                         else 0))]
            self._c_drafted.inc(k)
            self._c_accepted.inc(n_acc)
            if not emitted:
                # keep the page holding row `written`: the rescue pass
                # scatters there and emits exactly one token
                rescue.append(s)
                self.pool.release(self.bt.truncate(
                    s, pages_needed(int(self.written[s]) + 1, ps)))
                continue
            new_written = int(self.written[s]) + len(emitted)
            # rollback: drop pages past the accepted rows (target AND
            # draft — shared id space); stale rows below the page boundary
            # are pos-masked and overwritten before any read
            self.pool.release(
                self.bt.truncate(s, pages_needed(new_written, ps)))
            self.written[s] = new_written
            self.last[s] = emitted[-1]
            self.remaining[s] -= len(emitted)
            self._c_decode_tokens.inc(len(emitted))
            out[s] = emitted

        if rescue:
            # one base-engine decode step, shared by every rescued slot:
            # the same jitted function the non-spec engine runs, so its
            # argmax (and the cache row it writes) is bitwise the base
            # engine's.  Non-rescued slots ride along harmlessly — their
            # scatter lands on their next row (correct token, overwritten
            # by the next verify) or the null page, and their logits are
            # discarded.
            self._c_rescues.inc()
            tokens = np.zeros((self.slots, 1), np.int32)
            tokens[slots, 0] = self.last[slots]
            with tr.span("decode.rescue", "engine", ENGINE_TRACK,
                         {"slots": len(rescue)} if tr else None):
                with tr.span("decode.launch", "engine", ENGINE_TRACK):
                    td = time.perf_counter()
                    toks, _, self.cache = self._decode_fn(1)(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(self.written, jnp.int32),
                        self._device_table(self.active))
                    jax.block_until_ready(toks)
                    self._c_decode_dev.inc(time.perf_counter() - td)
                with tr.span("decode.fetch", "engine", ENGINE_TRACK):
                    toks = np.asarray(toks)
            for s in rescue:
                tok = int(toks[s, 0])
                out[s] = [tok]
                self.written[s] += 1
                self.last[s] = tok
                self.remaining[s] -= 1
                self._c_decode_tokens.inc()
        return out
