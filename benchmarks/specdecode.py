"""Speculative-decode table: the short-q verify kernel family + the engine.

Rows (the CI spec-decode smoke job uploads this table as
experiments/BENCH_specdecode.json):

  specdecode,winner,<family>      the AUTO winning degree per attention
                                  family at ONE shared paper-scale geometry
                                  — decode (t=1), verify (t=K+1) and
                                  prefill pick different degrees, the
                                  tentpole's tuner story (pinned in
                                  tests/test_tune.py).
  specdecode,kernel,T<t>,...      modeled verify cost across draft depths
                                  and degrees, plus CPU interpret wall time
                                  at a reduced geometry for transparency.
  specdecode,engine,...           tiny end-to-end SpecPagedEngine runs:
                                  forced rejections (fresh random draft —
                                  acceptance ~0, pure overhead path) and a
                                  self-draft (acceptance upper bound), each
                                  checked bitwise against the non-spec
                                  PagedEngine on the same trace (`parity`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CoarseningConfig
from repro.core.analysis import (decode_attention_cost, flash_attention_cost,
                                 flash_attention_verify_cost)
from repro.kernels import ops
from repro.tune import KernelSpec, search
from benchmarks.common import wall_us, emit

# paper-scale geometry shared across the family-winner rows: a small-batch
# GQA serving shape where the three attention families split three ways
# (decode con4, verify con8, prefill con2 — pinned in tests/test_tune.py)
B, HKV, G, D = 2, 4, 8, 128
H = HKV * G
S, PS = 2048, 128
NPP = S // PS
SPEC_K = 4
SQ, PRE_BQ = 512, 256                  # prompt length / prefill q-tile

# reduced measured geometry (CPU interpret)
MB, MHKV, MG, MD, MPS = 2, 2, 2, 32, 64
MH = MHKV * MG
MS = 256


def winner_rows() -> None:
    fams = [
        ("decode_attention_paged", (B, H, HKV, NPP, D),
         dict(page_size=PS, window=0)),
        ("flash_attention_verify", (B, H, HKV, SPEC_K + 1, NPP, D),
         dict(page_size=PS, window=0)),
        ("flash_attention", (B, H, HKV, SQ, SQ, D),
         dict(causal=True, window=0, bq=PRE_BQ, bkv=128)),
    ]
    for fam, shape, params in fams:
        res = search(KernelSpec.make(fam, shape, dtype="bfloat16", **params))
        emit(f"specdecode,winner,{fam}", -1.0,
             res.candidates[0].score * 1e6, winner=res.best.label)


def kernel_rows() -> None:
    key = jax.random.PRNGKey(0)
    n_pages = MB * (MS // MPS) + 1
    kp = jax.random.normal(jax.random.fold_in(key, 1),
                           (n_pages, MHKV, MPS, MD), jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 2),
                           (n_pages, MHKV, MPS, MD), jnp.float32)
    perm = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    bt = jnp.asarray(perm.reshape(MB, MS // MPS), jnp.int32)
    for t in (3, 5, 9):                      # K in {2, 4, 8}
        q = jax.random.normal(key, (MB, t, MH, MD), jnp.float32)
        pos0 = jnp.full((MB,), MS - t, jnp.int32)
        for label in ("none", "con2", "gap2"):
            cfg = CoarseningConfig.parse(label) if label != "none" \
                else CoarseningConfig()
            c = flash_attention_verify_cost(B, H, HKV, t, S, D, cfg,
                                            bkv=PS, kv_len=S, page_size=PS)
            emit(f"specdecode,kernel,T{t},{label}",
                 wall_us(lambda: ops.flash_attention_verify(
                     q, kp, vp, bt, pos0, cfg)),
                 c.modeled_s * 1e6)


def engine_rows() -> None:
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import PagedEngine, Scheduler, SpecPagedEngine

    cfg = get_config("qwen3-0.6b").reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, cfg.vocab, int(n))))
               for n in (9, 17, 6)]
    gens = [12, 8, 10]
    kw = dict(slots=2, num_pages=17, page_size=8, max_len=64, chunk=8)

    def run(make):
        eng = make()
        sched = Scheduler(eng)
        for p, g in zip(prompts, gens):
            sched.submit(p, g)
        done = sched.run_until_done()
        eng.pool.check()
        return eng, [r.output for r in done]

    base, base_out = run(lambda: PagedEngine(cfg, params, decode_block=1,
                                             **kw))
    variants = [
        ("reject", dict(rng=jax.random.PRNGKey(7))),     # fresh random draft
        ("selfdraft", dict(draft_cfg=cfg, draft_params=params)),
    ]
    for name, dkw in variants:
        eng, out = run(lambda: SpecPagedEngine(cfg, params, spec_k=SPEC_K,
                                               **dkw, **kw))
        emit(f"specdecode,engine,{name}", -1.0, -1.0,
             parity=out == base_out,
             acceptance=round(eng.acceptance_rate, 3),
             tok_per_step=round(
                 eng.decoded_tokens / max(eng.spec_steps, 1), 2),
             rescues=eng.rescue_steps, leak_free=eng.pool.num_live == 0)


def main() -> None:
    winner_rows()
    kernel_rows()
    engine_rows()


if __name__ == "__main__":
    main()
