"""Cache warming for the launch drivers + a wall-clock measurer.

`warm_for_model` derives the kernel shapes a (train or serve) hot loop will
hit from the ModelConfig and autotunes each family once, so the first real
step already dispatches the winning coarsening config.  `wall_measurer`
builds the measured-timing closure for the exhaustive/greedy strategies
(CPU interpret wall time here; on a real TPU the same closure measures the
Mosaic-lowered kernel — see ROADMAP Open items).
"""
from __future__ import annotations

import math
import time
from typing import Optional

from repro.tune.cache import KernelSpec, TuningCache, default_cache
from repro.tune.search import autotune


def _round_down(n: int, q: int) -> int:
    return max(q, (n // q) * q)


# strategies the launch drivers accept for their --tune flag; "auto" is an
# alias for the modeled prior
TUNE_CHOICES = ("auto", "model", "greedy", "exhaustive")

# log(measured / modeled) buckets for the calibration histogram: 0 = the
# model is perfectly calibrated, +-0.7 ~ a 2x miss
RESIDUAL_BUCKETS = (-2.0, -1.0, -0.5, -0.25, -0.1, 0.0, 0.1, 0.25, 0.5,
                    1.0, 2.0)

# per-family calibration from the most recent measured warms in this
# process (accumulates across warm_for_model calls — the serve driver warms
# the target engine and then the spec engine); tune_report() formats it
LAST_CALIBRATION: dict[str, dict] = {}


def warm_from_flag(cfg, tune: str, *, seq: int, batch: int,
                   cache: Optional[TuningCache] = None,
                   page_size: Optional[int] = None,
                   spec_k: Optional[int] = None, metrics=None) -> dict:
    """The launch drivers' --tune entry point: map the flag value to a
    (strategy, measurer) pair and warm the cache."""
    if tune not in TUNE_CHOICES:
        raise ValueError(f"tune must be one of {TUNE_CHOICES}, got {tune!r}")
    measure = wall_measurer() if tune in ("greedy", "exhaustive") else None
    strategy = "model" if tune == "auto" else tune
    return warm_for_model(cfg, seq=seq, batch=batch, cache=cache,
                          measure=measure, strategy=strategy,
                          page_size=page_size, spec_k=spec_k,
                          metrics=metrics)


def _calibration(res) -> Optional[dict]:
    """Model-vs-measured agreement for one TuneResult: pairwise rank
    concordance (Kendall-style, ties skipped), top-1 pick match, and
    log(measured/modeled) residuals.  None when fewer than two candidates
    carry a measurement (nothing to rank)."""
    meas = [c for c in res.candidates if c.measured_s is not None
            and c.measured_s > 0 and c.modeled_s > 0]
    if len(meas) < 2:
        return None
    pairs = agree = 0
    for i in range(len(meas)):
        for j in range(i + 1, len(meas)):
            a, b = meas[i], meas[j]
            if a.modeled_s == b.modeled_s or a.measured_s == b.measured_s:
                continue
            pairs += 1
            agree += int((a.modeled_s < b.modeled_s)
                         == (a.measured_s < b.measured_s))
    model_pick = min(meas, key=lambda c: c.modeled_s)
    meas_pick = min(meas, key=lambda c: c.measured_s)
    resid = sorted(math.log(c.measured_s / c.modeled_s) for c in meas)
    return {
        "n_measured": len(meas),
        "rank_agreement": round(agree / pairs, 3) if pairs else 1.0,
        "top1_match": model_pick.cfg.label == meas_pick.cfg.label,
        "model_pick": model_pick.cfg.label,
        "measured_pick": meas_pick.cfg.label,
        "residuals": [round(r, 3) for r in resid],
        "residual_median": round(resid[len(resid) // 2], 3),
    }


def tune_report(cache: Optional[TuningCache] = None) -> str:
    """The --tune exit summary: cache hit/miss counts plus the per-family
    model-vs-measured calibration collected by this process's warms."""
    cache = cache or default_cache()
    st = cache.stats
    lines = [f"tune: cache {st['hits']} hits / {st['misses']} misses "
             f"({len(cache)} entries at {cache.path})"]
    if not LAST_CALIBRATION:
        lines.append("tune: calibration n/a — no family measured this run "
                     "(cache hits, or --tune auto/model which never "
                     "measures)")
        return "\n".join(lines)
    lines.append("tune: model-vs-measured calibration "
                 "(rank agreement over measured candidates; residual = "
                 "median log(measured/modeled), 0 is perfect):")
    for fam in sorted(LAST_CALIBRATION):
        c = LAST_CALIBRATION[fam]
        pick = "top-1 MATCH" if c["top1_match"] else (
            f"top-1 MISS (model {c['model_pick']} vs measured "
            f"{c['measured_pick']})")
        lines.append(f"tune:   {fam}: rank {c['rank_agreement']:.0%} over "
                     f"{c['n_measured']} measured, {pick}, residual "
                     f"{c['residual_median']:+.3f}")
    return "\n".join(lines)


def warm_for_model(cfg, *, seq: int, batch: int,
                   cache: Optional[TuningCache] = None,
                   measure=None, strategy: str = "model",
                   verbose: bool = True,
                   page_size: Optional[int] = None,
                   spec_k: Optional[int] = None, metrics=None) -> dict:
    """Autotune the kernel families a model step exercises; returns
    {family: winning-label}.  cfg is a repro.models.config.ModelConfig.

    With ``metrics`` (an obs Registry), each measured family's calibration
    lands in ``tune_rank_agreement{family=...}`` / ``tune_top1_match`` /
    the ``tune_residual_logratio`` histogram, and in LAST_CALIBRATION for
    tune_report()."""
    cache = cache or default_cache()
    toks = batch * seq
    d = cfg.d_model
    specs = {
        # elementwise residual/activation streams: toks*d elements
        "ew_stream": KernelSpec.make(
            "ew_stream", (_round_down(toks * d, 1024 * 16),),
            n_loads=2, ai=6, variant="base", block=1024),
        # embedding lookup: toks ids against the padded vocab table
        "embed_gather": KernelSpec.make(
            "embed_gather", (_round_down(toks, 256 * 8), cfg.vocab_padded, d),
            block=256),
        # the block matmul (toks, d) @ (d, d_ff)
        "matmul": KernelSpec.make(
            "matmul", (_round_down(toks, 128 * 8),
                       _round_down(cfg.d_ff, 128),
                       _round_down(d, 256)),
            dtype="bfloat16", bm=128, bn=128, bk=256),
        # split-KV decode attention at the full allocated cache length (the
        # serve hot loop); skipped via the ValueError path when seq doesn't
        # tile by the kv block
        "decode_attention": KernelSpec.make(
            "decode_attention",
            (batch, cfg.n_heads, cfg.n_kv_heads, seq, cfg.hd),
            dtype="bfloat16", bkv=min(128, seq), window=0),
        # training flash attention: the forward (q-row coarsening axis) and
        # the backward dK/dV pass (kv-block coarsening axis) tune
        # independent degrees — warm both so a cfg.attn_backend="pallas"
        # train step's first forward AND first grad dispatch from the cache
        "flash_attention": KernelSpec.make(
            "flash_attention",
            (batch, cfg.n_heads, cfg.n_kv_heads, seq, seq, cfg.hd),
            dtype="bfloat16", bq=min(128, seq), bkv=min(128, seq),
            causal=True),
        "flash_attention_bwd": KernelSpec.make(
            "flash_attention_bwd",
            (batch, cfg.n_heads, cfg.n_kv_heads, seq, seq, cfg.hd),
            dtype="bfloat16", bq=min(128, seq), bkv=min(128, seq),
            causal=True),
    }
    wbits = {"int8": 8, "int4": 4}.get(getattr(cfg, "quant", "none"))
    if wbits:
        # the dequant-fused quantized matmul: same geometry as the dense
        # spec but its own cache key (wbits/group) — the packed-pane byte
        # and dequant terms can move the winning degree
        specs["matmul_q"] = KernelSpec.make(
            "matmul", (_round_down(toks, 128 * 8),
                       _round_down(cfg.d_ff, 128),
                       _round_down(d, 256)),
            dtype="bfloat16", bm=128, bn=128, bk=256, wbits=wbits,
            group=cfg.quant_group if wbits == 4 else 0)
    if getattr(cfg, "kv_quant", "none") == "int8":
        specs["decode_attention_q"] = KernelSpec.make(
            "decode_attention",
            (batch, cfg.n_heads, cfg.n_kv_heads, seq, cfg.hd),
            dtype="int8", bkv=min(128, seq), window=0, kv_bits=8)
    if cfg.n_experts:
        # grouped-expert fused FFN over the padded dispatch buffer, at the
        # exact capacity the layer dispatches
        from repro.models.layers import moe_default_capacity
        cap = moe_default_capacity(toks, cfg.n_experts, cfg.top_k)
        specs["moe_ffn"] = KernelSpec.make(
            "moe_ffn", (cfg.n_experts_padded, cap, d, cfg.moe_d_ff),
            dtype="bfloat16")
        if wbits:
            specs["moe_ffn_q"] = KernelSpec.make(
                "moe_ffn", (cfg.n_experts_padded, cap, d, cfg.moe_d_ff),
                dtype="bfloat16", wbits=wbits,
                group=cfg.quant_group if wbits == 4 else 0)
        # the decode step dispatches at its own (much smaller) capacity:
        # blocks.attn_block_decode passes max(4, min(B, 4*top_k)) and
        # layers.moe clamps it to the step's B tokens — a distinct spec
        # key, warmed too so the serve hot loop's first token never
        # searches inline
        cap_dec = min(batch, max(4, min(batch, 4 * cfg.top_k)))
        if cap_dec != cap:
            specs["moe_ffn_decode"] = KernelSpec.make(
                "moe_ffn", (cfg.n_experts_padded, cap_dec, d, cfg.moe_d_ff),
                dtype="bfloat16")
    if cfg.window:
        # mixed global/local stacks dispatch two param sets — warm both
        specs["decode_attention_local"] = KernelSpec.make(
            "decode_attention",
            (batch, cfg.n_heads, cfg.n_kv_heads, seq, cfg.hd),
            dtype="bfloat16", bkv=min(128, seq), window=cfg.window)
        # local-layer prefill dispatches the block-sparse live-index
        # kernel (layers.flash_attention sparse path) — warm its family at
        # the exact pattern key the dispatch will resolve
        bq_s, bkv_s = min(cfg.attn_bq, seq), min(cfg.attn_bkv, seq)
        if seq % bq_s == 0 and seq % bkv_s == 0:
            from repro.kernels.sparse_attention import build_block_index
            gs = getattr(cfg, "attn_global_stride", None)
            sidx = build_block_index(seq, seq, bq_s, bkv_s, causal=True,
                                     window=cfg.window, global_stride=gs)
            specs["flash_attention_sparse"] = KernelSpec.make(
                "flash_attention_sparse",
                (batch, cfg.n_heads, cfg.n_kv_heads, seq, seq, cfg.hd),
                dtype="bfloat16", bq=bq_s, bkv=bkv_s, causal=True,
                window=cfg.window, gstride=gs or 0,
                max_live=int(sidx.shape[1]), n_live=int((sidx >= 0).sum()))
    if page_size:
        # paged serving: the block-table decode family at the per-slot page
        # budget (page size joins the spec key — different page sizes are
        # different kernels with different winning degrees)
        npp = max(1, seq // page_size)
        kv_q = getattr(cfg, "kv_quant", "none") == "int8"
        specs["decode_attention_paged"] = KernelSpec.make(
            "decode_attention_paged",
            (batch, cfg.n_heads, cfg.n_kv_heads, npp, cfg.hd),
            dtype="int8" if kv_q else "bfloat16", page_size=page_size,
            window=0, **({"kv_bits": 8} if kv_q else {}))
        if cfg.window:
            specs["decode_attention_paged_local"] = KernelSpec.make(
                "decode_attention_paged",
                (batch, cfg.n_heads, cfg.n_kv_heads, npp, cfg.hd),
                dtype="int8" if kv_q else "bfloat16", page_size=page_size,
                window=cfg.window, **({"kv_bits": 8} if kv_q else {}))
        if spec_k:
            # speculative decoding: the batched-verify short-q family at
            # T = K+1 rows (K drafted tokens plus the last accepted one).
            # Its own family key — scoring T*G rows per fetched page moves
            # the memory/compute crossover, so the winner differs from the
            # single-row decode family at the same page geometry
            specs["flash_attention_verify"] = KernelSpec.make(
                "flash_attention_verify",
                (batch, cfg.n_heads, cfg.n_kv_heads, spec_k + 1, npp,
                 cfg.hd),
                dtype="int8" if kv_q else "bfloat16", page_size=page_size,
                window=0, **({"kv_bits": 8} if kv_q else {}))
    out = {}
    for fam, spec in specs.items():
        results = []
        try:
            best = autotune(spec, cache=cache, measure=measure,
                            strategy=strategy, on_result=results.append)
        except ValueError as e:          # geometry too small to coarsen
            if verbose:
                print(f"tune: {fam}: skipped ({e})")
            continue
        out[fam] = best.label
        if verbose:
            print(f"tune: {fam} {spec.shape} -> {best.label}")
        cal = _calibration(results[0]) if results else None
        if cal is not None:
            LAST_CALIBRATION[fam] = cal
            if metrics is not None:
                metrics.gauge("tune_rank_agreement",
                              family=fam).set(cal["rank_agreement"])
                metrics.gauge("tune_top1_match",
                              family=fam).set(int(cal["top1_match"]))
                h = metrics.histogram("tune_residual_logratio",
                                      RESIDUAL_BUCKETS,
                                      "log(measured_s / modeled_s)")
                for r in cal["residuals"]:
                    h.observe(r)
    return out


def wall_measurer(reps: int = 3):
    """measure(spec, cfg) -> seconds by timing the jit'd op on this host.

    Supports the families the benchmark suite measures (including the
    quantized matmul/moe_ffn and int8-KV decode specs).  The ops layer
    builds kernels with ``interpret=(default backend == cpu)``, so on a TPU
    host this times the COMPILED (Mosaic-lowered) kernel and the cache
    entry's ``source='measured'`` provenance refers to real silicon;
    interpret-mode timing is the CPU fallback (ROADMAP "measured-timing
    tuning" item).
    """
    import jax
    import jax.numpy as jnp

    def measure(spec: KernelSpec, cfg) -> float:
        from repro.kernels import ops
        from repro.kernels import gather_stream as gs
        p = spec.p
        key = jax.random.PRNGKey(0)

        if spec.family == "ew_stream":
            n = spec.shape[0]
            xs = tuple(jax.random.normal(jax.random.fold_in(key, i), (n,))
                       for i in range(p.get("n_loads", 8)))
            fn = lambda: ops.ew_stream(xs, cfg, ai=p.get("ai", 6),
                                       variant=p.get("variant", "base"),
                                       block=p.get("block", 1024))
        elif spec.family == "gather_stream":
            n, table = spec.shape
            idx = jnp.asarray(gs.make_indices(
                n, table, int(p.get("window_elems", 8192)), seed=0))
            tabs = tuple(jax.random.normal(jax.random.fold_in(key, i),
                                           (table,))
                         for i in range(p.get("n_loads", 8)))
            fn = lambda: ops.gather_stream(idx, tabs, cfg,
                                           ai=p.get("ai", 6),
                                           block=p.get("block", 1024))
        elif spec.family == "matmul":
            m, n, k = spec.shape
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            a = jax.random.normal(key, (m, k), dt)
            b = jax.random.normal(jax.random.fold_in(key, 1), (k, n), dt)
            if p.get("wbits"):
                from repro.quant import quantize
                qw = quantize(b.astype(jnp.float32),
                              "int8" if p["wbits"] == 8 else "int4",
                              group=p.get("group") or 32)
                fn = lambda: ops.quant_matmul(a, qw, cfg, bm=p.get("bm", 128),
                                              bn=p.get("bn", 128),
                                              bk=p.get("bk", 256))
            else:
                fn = lambda: ops.matmul(a, b, cfg, bm=p.get("bm", 128),
                                        bn=p.get("bn", 128),
                                        bk=p.get("bk", 256))
        elif spec.family == "dp_scan":
            rows, cols = spec.shape
            c = jax.random.uniform(key, (rows, cols))
            fn = lambda: ops.dp_scan(c, cfg)
        elif spec.family == "stencil5":
            rows, cols = spec.shape
            x = jax.random.normal(key, (rows, cols))
            fn = lambda: ops.stencil5(x, cfg,
                                      block_rows=p.get("block_rows", 8))
        elif spec.family == "decode_attention":
            b, h, hkv, s, d = spec.shape
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            q = jax.random.normal(key, (b, 1, h, d), dt)
            kc = jax.random.normal(jax.random.fold_in(key, 1),
                                   (b, s, hkv, d), dt)
            vc = jax.random.normal(jax.random.fold_in(key, 2),
                                   (b, s, hkv, d), dt)
            pos = jnp.full((b,), s - 1, jnp.int32)
            w = p.get("window", 0) or None
            if p.get("kv_bits"):
                from repro.quant import quantize_kv
                kq, ks = quantize_kv(kc.astype(jnp.float32))
                vq, vs = quantize_kv(vc.astype(jnp.float32))
                fn = lambda: ops.decode_attention(q, kq, vq, pos, cfg,
                                                  bkv=p.get("bkv", 128),
                                                  window=w, k_scale=ks,
                                                  v_scale=vs)
            else:
                fn = lambda: ops.decode_attention(q, kc, vc, pos, cfg,
                                                  bkv=p.get("bkv", 128),
                                                  window=w)
        elif spec.family == "decode_attention_paged":
            b, h, hkv, npp, d = spec.shape
            ps = p.get("page_size", 64)
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            # a worst-case fragmented pool: every slot's pages permuted
            n_pages = b * npp + 1
            q = jax.random.normal(key, (b, 1, h, d), dt)
            kp = jax.random.normal(jax.random.fold_in(key, 1),
                                   (n_pages, hkv, ps, d), dt)
            vp = jax.random.normal(jax.random.fold_in(key, 2),
                                   (n_pages, hkv, ps, d), dt)
            bt = jnp.asarray(jax.random.permutation(
                jax.random.fold_in(key, 3),
                jnp.arange(1, n_pages)).reshape(b, npp), jnp.int32)
            pos = jnp.full((b,), npp * ps - 1, jnp.int32)
            w = p.get("window", 0) or None
            if p.get("kv_bits"):
                from repro.quant import quantize_kv
                kq, ks = quantize_kv(kp.astype(jnp.float32))
                vq, vs = quantize_kv(vp.astype(jnp.float32))
                fn = lambda: ops.paged_decode_attention(
                    q, kq, vq, bt, pos, cfg, window=w, k_scale=ks,
                    v_scale=vs)
            else:
                fn = lambda: ops.paged_decode_attention(
                    q, kp, vp, bt, pos, cfg, window=w)
        elif spec.family == "flash_attention_verify":
            b, h, hkv, t, npp, d = spec.shape
            ps = p.get("page_size", 64)
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            # same worst-case fragmented pool as the decode family, but T
            # drafted rows per slot ending at the last cache position
            n_pages = b * npp + 1
            q = jax.random.normal(key, (b, t, h, d), dt)
            kp = jax.random.normal(jax.random.fold_in(key, 1),
                                   (n_pages, hkv, ps, d), dt)
            vp = jax.random.normal(jax.random.fold_in(key, 2),
                                   (n_pages, hkv, ps, d), dt)
            bt = jnp.asarray(jax.random.permutation(
                jax.random.fold_in(key, 3),
                jnp.arange(1, n_pages)).reshape(b, npp), jnp.int32)
            pos0 = jnp.full((b,), npp * ps - t, jnp.int32)
            w = p.get("window", 0) or None
            if p.get("kv_bits"):
                from repro.quant import quantize_kv
                kq, ks = quantize_kv(kp.astype(jnp.float32))
                vq, vs = quantize_kv(vp.astype(jnp.float32))
                fn = lambda: ops.flash_attention_verify(
                    q, kq, vq, bt, pos0, cfg, window=w, k_scale=ks,
                    v_scale=vs)
            else:
                fn = lambda: ops.flash_attention_verify(
                    q, kp, vp, bt, pos0, cfg, window=w)
        elif spec.family == "ssd":
            b, h, g, s, pdim, n = spec.shape
            x = jax.random.normal(key, (b, h, s, pdim)) * 0.5
            dtv = jax.nn.softplus(
                jax.random.normal(jax.random.fold_in(key, 1), (b, h, s)))
            a = -jax.nn.softplus(
                jax.random.normal(jax.random.fold_in(key, 2), (h,)))
            bm = jax.random.normal(jax.random.fold_in(key, 3),
                                   (b, g, s, n)) * 0.5
            cm = jax.random.normal(jax.random.fold_in(key, 4),
                                   (b, g, s, n)) * 0.5
            fn = lambda: ops.ssd(x, dtv, a, bm, cm, cfg,
                                 chunk=p.get("chunk", 64))
        elif spec.family == "rglru":
            b, s, d = spec.shape
            x = jax.random.normal(key, (b, s, d)) * 0.5
            r = jax.random.normal(jax.random.fold_in(key, 1), (b, s, d))
            i = jax.random.normal(jax.random.fold_in(key, 2), (b, s, d))
            a_param = jax.random.normal(jax.random.fold_in(key, 3), (d,))
            fn = lambda: ops.rglru(x, r, i, a_param, cfg,
                                   block_d=p.get("block_d", 128),
                                   block_t=p.get("block_t", 64))
        elif spec.family in ("flash_attention", "flash_attention_bwd"):
            b, h, hkv, sq, sk, d = spec.shape
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            q = jax.random.normal(key, (b, h, sq, d), dt) * 0.5
            kk = jax.random.normal(jax.random.fold_in(key, 1),
                                   (b, hkv, sk, d), dt) * 0.5
            vv = jax.random.normal(jax.random.fold_in(key, 2),
                                   (b, hkv, sk, d), dt)
            causal = bool(p.get("causal", True))
            bq, bkv = p.get("bq", 128), p.get("bkv", 128)
            if spec.family == "flash_attention":
                fn = lambda: ops.flash_attention(
                    q, kk, vv, cfg, bwd_cfg="auto", bq=bq, bkv=bkv,
                    causal=causal)
            else:
                # time the backward the cfg controls: grad through the
                # custom-VJP op at a base forward with bwd_cfg pinned
                from repro.core.coarsening import CoarseningConfig
                grad = jax.jit(jax.grad(
                    lambda q_, k_, v_: jnp.sum(ops.flash_attention(
                        q_, k_, v_, CoarseningConfig(), bwd_cfg=cfg,
                        bq=bq, bkv=bkv, causal=causal)),
                    argnums=(1, 2)))
                fn = lambda: grad(q, kk, vv)
        elif spec.family == "flash_attention_sparse":
            b, h, hkv, sq, sk, d = spec.shape
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            q = jax.random.normal(key, (b, h, sq, d), dt) * 0.5
            kk = jax.random.normal(jax.random.fold_in(key, 1),
                                   (b, hkv, sk, d), dt) * 0.5
            vv = jax.random.normal(jax.random.fold_in(key, 2),
                                   (b, hkv, sk, d), dt)
            fn = lambda: ops.flash_attention_sparse(
                q, kk, vv, cfg, bq=p.get("bq", 128), bkv=p.get("bkv", 128),
                causal=bool(p.get("causal", True)),
                window=p.get("window") or None,
                global_stride=p.get("gstride") or None)
        elif spec.family == "moe_ffn":
            e, cap, d, f = spec.shape
            dt = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
            xe = jax.random.normal(key, (e, cap, d), dt)
            w1 = jax.random.normal(jax.random.fold_in(key, 1), (e, d, f), dt)
            w3 = jax.random.normal(jax.random.fold_in(key, 2), (e, d, f), dt)
            w2 = jax.random.normal(jax.random.fold_in(key, 3), (e, f, d), dt)
            wts = jax.random.uniform(jax.random.fold_in(key, 4), (e, cap))
            if p.get("wbits"):
                from repro.quant import quantize
                mode = "int8" if p["wbits"] == 8 else "int4"
                g = p.get("group") or 32
                q1, q3, q2 = (quantize(w.astype(jnp.float32), mode, group=g)
                              for w in (w1, w3, w2))
                fn = lambda: ops.quant_moe_ffn(xe, q1, q3, q2, wts, cfg)
            else:
                fn = lambda: ops.moe_ffn(xe, w1, w3, w2, wts, cfg)
        elif spec.family == "embed_gather":
            n_ids, vocab, d = spec.shape
            ids = jax.random.randint(key, (n_ids,), 0, vocab)
            table = jax.random.normal(jax.random.fold_in(key, 1), (vocab, d))
            fn = lambda: ops.embed_gather(ids, table, cfg,
                                          block=p.get("block", 256))
        else:
            raise ValueError(f"wall_measurer: unsupported family "
                             f"{spec.family!r}")

        jax.block_until_ready(fn())          # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    return measure
