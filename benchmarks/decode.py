"""Decode-attention table: dense full-length einsum baseline vs the
coarsened split-KV kernel at fixed degrees vs AUTO, across cache lengths.

For each cache length S in 128..4k (decode pos at the end of the cache —
the hardest case for the split kernel, since length-awareness saves
nothing) emit:

  dense          the unfused XLA einsum path: full-length scan + f32
                 logits/probability HBM round-trips (models/layers.py)
  con1/2/4/8     the split-KV kernel, kv-block coarsening at fixed degrees
  AUTO           the repro.tune pick over the full candidate space

`derived` is the modeled v5e time (core/analysis.decode_attention_cost);
`us_per_call` is CPU interpret wall time at a reduced geometry (transparency
only).  The acceptance bar: every coarsened row beats dense at S >= 512 and
AUTO matches or beats every fixed degree.

Drafted-K speculative rows (`decode,spec,...`) extend the trajectory:

  decode,spec,K<k>,a<alpha>    modeled decode tok/s speedup of a drafted-K
                               verify step over K+1 plain decode steps at
                               paper scale: E(alpha,K) tokens per verify
                               against the verify-kernel + draft-chain cost
                               (attention terms; target 28 layers, draft 4).
  decode,spec,serve,...        measured (CPU interpret) engine decode tok/s
                               + acceptance: the contiguous BatchedServer,
                               the non-spec PagedEngine, and SpecPagedEngine
                               at K in {2,4,8} with a self-draft (the
                               acceptance upper bound) on one trace.

The acceptance bar: some modeled K row clears 2x at the measured self-draft
acceptance's alpha bracket.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import CoarseningConfig
from repro.core.analysis import (decode_attention_cost,
                                 flash_attention_verify_cost)
from repro.kernels import ops
from repro.tune import KernelSpec, search
from benchmarks.common import wall_us, emit

# modeled (paper-scale) geometry
B, HKV, G, D, BKV = 8, 8, 4, 128, 128
H = HKV * G
# measured (CPU interpret) geometry
MB, MHKV, MG, MD, MBKV = 2, 2, 2, 32, 64
MH = MHKV * MG
LENGTHS = (128, 256, 512, 1024, 2048, 4096)
DEGREES = (1, 2, 4, 8)


def _measured_fn(s, cfg):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (MB, 1, MH, MD), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 1),
                           (MB, s, MHKV, MD), jnp.float32)
    vc = jax.random.normal(jax.random.fold_in(key, 2),
                           (MB, s, MHKV, MD), jnp.float32)
    pos = jnp.full((MB,), s - 1, jnp.int32)
    if cfg is None:
        from repro.kernels import ref
        return wall_us(lambda: ref.decode_attention(q, kc, vc, pos))
    if s % (MBKV * cfg.degree):
        return -1.0
    return wall_us(lambda: ops.decode_attention(q, kc, vc, pos, cfg,
                                                bkv=MBKV))


def main() -> None:
    for s in LENGTHS:
        pos = s - 1
        dense = decode_attention_cost(B, H, HKV, s, D, CoarseningConfig(),
                                      bkv=BKV, dense=True)
        emit(f"decode,S{s},dense",
             _measured_fn(s, None) if s <= 1024 else -1.0,
             dense.modeled_s * 1e6, speedup=1.0)
        for deg in DEGREES:
            if s % (BKV * deg):
                emit(f"decode,S{s},con{deg}", -1, -1, status="NA")
                continue
            cfg = CoarseningConfig.parse(f"con{deg}" if deg > 1 else "none")
            c = decode_attention_cost(B, H, HKV, s, D, cfg, bkv=BKV,
                                      kv_len=pos + 1)
            emit(f"decode,S{s},con{deg}",
                 _measured_fn(s, cfg) if s <= 1024 else -1.0,
                 c.modeled_s * 1e6,
                 speedup=round(dense.modeled_s / c.modeled_s, 2))
        spec = KernelSpec.make("decode_attention", (B, H, HKV, s, D),
                               dtype="bfloat16", bkv=BKV, window=0)
        best = search(spec).best
        c = decode_attention_cost(B, H, HKV, s, D, best, bkv=BKV,
                                  kv_len=pos + 1)
        emit(f"decode,S{s},AUTO[{best.label}]", -1.0, c.modeled_s * 1e6,
             speedup=round(dense.modeled_s / c.modeled_s, 2))
    spec_modeled_rows()
    spec_serve_rows()


# -- speculative decoding: drafted-K batched verify ---------------------------

SPEC_S, SPEC_PS = 2048, 128            # cache length / page size (modeled)
L_TARGET, L_DRAFT = 28, 4              # layer counts, paper-scale target
DH, DHKV, DD = 8, 2, 64                # draft attention geometry


def _auto_cost(family, shape, cost_fn, **params):
    from repro.tune import KernelSpec as KS
    best = search(KS.make(family, shape, dtype="bfloat16", **params)).best
    return best, cost_fn(best)


def spec_modeled_rows() -> None:
    """Modeled tok/s speedup of drafted-K decode at paper scale: one verify
    pass (T = K+1 short-q rows, tuned degree) plus a K+1-step draft chain
    replaces E(alpha, K) = (1-alpha^(K+1))/(1-alpha) decode steps of the
    target.  Attention terms only — the same convention as every other
    modeled row in this table."""
    npp = SPEC_S // SPEC_PS
    _, dec = _auto_cost(
        "decode_attention_paged", (B, H, HKV, npp, D),
        lambda cfg: decode_attention_cost(
            B, H, HKV, SPEC_S, D, cfg, bkv=SPEC_PS, kv_len=SPEC_S,
            page_size=SPEC_PS),
        page_size=SPEC_PS, window=0)
    _, ddec = _auto_cost(
        "decode_attention_paged", (B, DH, DHKV, npp, DD),
        lambda cfg: decode_attention_cost(
            B, DH, DHKV, SPEC_S, DD, cfg, bkv=SPEC_PS, kv_len=SPEC_S,
            page_size=SPEC_PS),
        page_size=SPEC_PS, window=0)
    step_base = L_TARGET * dec.modeled_s
    for k in (2, 4, 8):
        vbest, ver = _auto_cost(
            "flash_attention_verify", (B, H, HKV, k + 1, npp, D),
            lambda cfg: flash_attention_verify_cost(
                B, H, HKV, k + 1, SPEC_S, D, cfg, bkv=SPEC_PS,
                kv_len=SPEC_S, page_size=SPEC_PS),
            page_size=SPEC_PS, window=0)
        step_spec = L_TARGET * ver.modeled_s \
            + (k + 1) * L_DRAFT * ddec.modeled_s
        for alpha in (0.5, 0.8):
            e_tok = (1 - alpha ** (k + 1)) / (1 - alpha)
            emit(f"decode,spec,K{k},a{alpha},AUTO[{vbest.label}]", -1.0,
                 step_spec * 1e6 / e_tok,
                 tok_per_step=round(e_tok, 2),
                 speedup=round(e_tok * step_base / step_spec, 2))


def spec_serve_rows() -> None:
    """Measured (CPU interpret) engine decode tok/s on one trace: contiguous
    and paged non-spec baselines vs SpecPagedEngine at K in {2,4,8} with the
    target as its own draft — the acceptance-rate upper bound, bounded below
    1.0 only by the tie guard (see repro/serve/spec.py)."""
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.launch.serve import BatchedServer
    from repro.serve import PagedEngine, Scheduler, SpecPagedEngine

    cfg = get_config("qwen3-0.6b").reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    slots, max_len, gen, ps = 3, 64, 16, 8
    prompts = [list(map(int, rng.integers(1, cfg.vocab, int(n))))
               for n in rng.integers(5, 25, 5)]

    srv = BatchedServer(cfg, params, slots=slots, max_len=max_len, chunk=16,
                        decode_block=1)
    pending = list(prompts)
    while pending or srv.any_active:
        while pending and srv.try_admit(pending[0], gen):
            pending.pop(0)
        if not srv.any_active:
            break
        srv.step()
    emit("decode,spec,serve,contiguous", -1.0, -1.0,
         decode_tok_s=round(
             srv.decoded_tokens / max(srv.decode_device_s, 1e-9), 1))

    def paged(make):
        eng = make()
        sched = Scheduler(eng)
        for p in prompts:
            sched.submit(p, gen)
        sched.run_until_done()
        return eng

    kw = dict(slots=slots, num_pages=slots * (max_len // ps) + 1,
              page_size=ps, max_len=max_len, chunk=16)
    eng = paged(lambda: PagedEngine(cfg, params, decode_block=1, **kw))
    base_tok_s = eng.decoded_tokens / max(eng.decode_device_s, 1e-9)
    emit("decode,spec,serve,paged", -1.0, -1.0,
         decode_tok_s=round(base_tok_s, 1))
    for k in (2, 4, 8):
        eng = paged(lambda: SpecPagedEngine(
            cfg, params, spec_k=k, draft_cfg=cfg, draft_params=params, **kw))
        tok_s = eng.decoded_tokens / max(eng.decode_device_s, 1e-9)
        emit(f"decode,spec,serve,K{k}", -1.0, -1.0,
             decode_tok_s=round(tok_s, 1),
             acceptance=round(eng.acceptance_rate, 3),
             tok_per_step=round(
                 eng.decoded_tokens / max(eng.spec_steps, 1), 2),
             speedup=round(tok_s / max(base_tok_s, 1e-9), 2))


if __name__ == "__main__":
    main()
