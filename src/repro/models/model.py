"""LM / Enc-Dec model wrappers with period-scanned layer stacks.

Layers at the same position inside the repeating pattern period are stacked
(leading n_periods axis) and the forward pass `lax.scan`s over periods —
compile time is O(|period|) regardless of depth, which keeps the 80-cell
dry-run tractable.  Remainder layers (n_layers % |period|) run unrolled.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig, ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, SSM
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.layers import ShardCtx, NOSHARD

AUX_LOSS_WEIGHT = 0.01
LOSS_CHUNK = 512


def _period(cfg: ModelConfig):
    period = cfg.pattern_period or (ATTN_GLOBAL,)
    n_periods = cfg.n_layers // len(period)
    tail = cfg.layer_kinds()[n_periods * len(period):]
    return tuple(period), n_periods, tuple(tail)


def _block_init(kind: str, key, cfg: ModelConfig):
    if cfg.is_encdec:
        return B.dec_block_init(key, cfg)
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return B.attn_block_init(key, cfg)
    if kind == RECURRENT:
        return B.rglru_block_init(key, cfg)
    if kind == SSM:
        return B.mamba_block_init(key, cfg)
    raise ValueError(kind)


def _block_apply(kind: str, p, x, cfg, *, pos, mrope_pos3, shard, moe_capacity,
                 pos_trivial=False):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return B.attn_block(p, x, cfg, kind=kind, pos=pos,
                            mrope_pos3=mrope_pos3, shard=shard,
                            moe_capacity=moe_capacity,
                            pos_trivial=pos_trivial)
    if kind == RECURRENT:
        return B.rglru_block(p, x, cfg, shard=shard)
    if kind == SSM:
        return B.mamba_block(p, x, cfg, shard=shard)
    raise ValueError(kind)


def _block_decode(kind: str, p, x, cfg, cache, *, pos, shard,
                  block_table=None, write_mask=None, layer=None):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        if block_table is not None:
            return B.attn_block_decode_paged(p, x, cfg, cache, kind=kind,
                                             pos=pos,
                                             block_table=block_table,
                                             write_mask=write_mask,
                                             layer=layer, shard=shard)
        return B.attn_block_decode(p, x, cfg, cache, kind=kind, pos=pos,
                                   shard=shard)
    if kind == RECURRENT:
        return B.rglru_block_decode(p, x, cfg, cache, pos=pos)
    if kind == SSM:
        return B.mamba_block_decode(p, x, cfg, cache, pos=pos)
    raise ValueError(kind)


def _block_verify(kind: str, p, x, cfg, cache, *, pos0, block_table,
                  valid_len, shard, layer=None):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return B.attn_block_verify_paged(p, x, cfg, cache, kind=kind,
                                         pos0=pos0, block_table=block_table,
                                         valid_len=valid_len, layer=layer,
                                         shard=shard)
    # recurrent/SSM state is a running summary — rejected drafted tokens
    # cannot be rolled out of it, so speculative decoding is attention-only
    raise NotImplementedError(
        f"lm_verify_step: {kind!r} layers carry unrewindable state; "
        f"speculative decoding supports attention-only stacks")


def _block_cache(kind: str, cfg, b, s_max, dtype):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return B.attn_cache_init(cfg, b, s_max, dtype)
    if kind == RECURRENT:
        return B.rglru_cache_init(cfg, b, dtype)
    if kind == SSM:
        return B.mamba_cache_init(cfg, b, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig):
    period, n_periods, tail = _period(cfg)
    ks = jax.random.split(key, len(period) + len(tail) + 3)
    params: dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_padded, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": L.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.vocab_padded)
    params["blocks"] = [
        jax.vmap(lambda k: _block_init(kind, k, cfg))(
            jax.random.split(ks[2 + j], n_periods))
        for j, kind in enumerate(period)
    ]
    params["tail"] = [
        _block_init(kind, ks[2 + len(period) + j], cfg)
        for j, kind in enumerate(tail)
    ]
    if cfg.is_encdec:
        params["enc"] = _encoder_init(ks[-1], cfg)
    return params


def _encoder_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "blocks": jax.vmap(lambda k: B.enc_block_init(k, cfg))(
            jax.random.split(ks[0], cfg.n_enc_layers)),
        "norm": L.rmsnorm_init(cfg.d_model),
    }


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _compute_dtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _embed(params, tokens, cfg, batch):
    x = jnp.take(params["embed"], tokens, axis=0).astype(_compute_dtype(cfg))
    fe = batch.get("frontend_embeds")
    if fe is not None:
        x = x + fe.astype(x.dtype)        # modality stub: precomputed embeds
    return x


def _run_stack(params, x, cfg, *, pos, mrope_pos3, shard, moe_capacity,
               remat: str = "none", pos_trivial: bool = False):
    period, n_periods, tail = _period(cfg)

    def period_body(carry, xs):
        x, aux = carry
        xs = shard.constrain_params(xs)   # keep FSDP gather inside the loop
        for j, kind in enumerate(period):
            x, a = _block_apply(kind, xs[j], x, cfg, pos=pos,
                                mrope_pos3=mrope_pos3, shard=shard,
                                moe_capacity=moe_capacity,
                                pos_trivial=pos_trivial)
            aux = aux + a
        # Megatron-SP: residuals sequence-sharded on the TP axis between
        # blocks (shard.sp='model'); GSPMD then emits one RS+AG pair per
        # boundary instead of two ARs.  No-op when sp is None.
        x = shard.constrain(x, lambda P, c: P(c.dp, c.sp, None))
        return (x, aux), None

    body = period_body
    if remat == "full":
        body = jax.checkpoint(period_body, prevent_cse=False)
    elif remat == "dots":
        # save the Pallas attention output ("flash_attn_out") alongside the
        # dot products: the kernel is opaque to the dots policy, so without
        # the name the WHOLE pallas_call would re-run in the backward —
        # right before the backward kernels recompute from its residuals
        body = jax.checkpoint(
            period_body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_attn_out")))

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           tuple(params["blocks"]))
    for p_t, kind in zip(params["tail"], _period(cfg)[2]):
        x, a = _block_apply(kind, p_t, x, cfg, pos=pos, mrope_pos3=mrope_pos3,
                            shard=shard, moe_capacity=moe_capacity,
                            pos_trivial=pos_trivial)
        aux = aux + a
    return x, aux


def lm_apply(params, batch, cfg: ModelConfig, *, shard: ShardCtx = NOSHARD,
             moe_capacity=None, remat: str = "none",
             xkv_precompute: bool = False):
    """-> final hidden states (B,S,d), moe aux loss."""
    if cfg.is_encdec:
        return _encdec_apply(params, batch, cfg, shard=shard,
                             moe_capacity=moe_capacity, remat=remat,
                             xkv_precompute=xkv_precompute)
    tokens = batch["tokens"]
    b, s = tokens.shape
    pos = batch.get("positions")
    # statically-known trivial positions (row i IS global row i) are what
    # lets the flash kernel's causal mask stand in for the q_pos mask;
    # batches carrying explicit positions (packing, ragged starts) keep the
    # mea path
    pos_trivial = pos is None
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(params, tokens, cfg, batch)
    x = shard.constrain(x, lambda P, c: P(c.dp, c.sp, None))
    pos3 = batch.get("pos3")
    if pos3 is not None:
        pos3 = pos3.transpose(1, 0, 2)      # batch convention (B,3,S)->(3,B,S)
    x, aux = _run_stack(params, x, cfg, pos=pos,
                        mrope_pos3=pos3, shard=shard,
                        moe_capacity=moe_capacity, remat=remat,
                        pos_trivial=pos_trivial)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def _encdec_apply(params, batch, cfg, *, shard, moe_capacity, remat,
                  xkv_precompute: bool = False):
    frames = batch["src_frames"].astype(_compute_dtype(cfg))   # audio stub
    bsz, s_src, _ = frames.shape
    pos_src = jnp.broadcast_to(jnp.arange(s_src, dtype=jnp.int32)[None],
                               (bsz, s_src))

    def enc_body(x, p):
        return B.enc_block(p, x, cfg, pos=pos_src, shard=shard), None

    enc_fn = enc_body if remat == "none" else jax.checkpoint(enc_body,
                                                             prevent_cse=False)
    enc_x, _ = lax.scan(enc_fn, frames, params["enc"]["blocks"])
    enc_x = L.rmsnorm(params["enc"]["norm"], enc_x, cfg.norm_eps)

    tokens = batch["tokens"]
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(params, tokens, cfg, batch)

    xs = params["blocks"][0]
    if xkv_precompute:
        # §Perf lever: project encoder K/V for ALL decoder layers in one
        # batched einsum BEFORE the scan, so enc_x (the big activation) is
        # consumed once instead of being re-broadcast into every loop
        # iteration.
        # asdense: the stacked xattn projections are QTensors when the
        # params are weight-quantized (dense-dequant fallback path)
        wk = L.asdense(xs["xattn"]["wk"], enc_x.dtype)   # (L, d, kv*hd)
        wv = L.asdense(xs["xattn"]["wv"], enc_x.dtype)
        se = enc_x.shape[1]
        ek = jnp.einsum("bsd,ldh->lbsh", enc_x, wk)
        ev = jnp.einsum("bsd,ldh->lbsh", enc_x, wv)
        ek = ek.reshape(ek.shape[0], b, se, cfg.n_kv_heads, cfg.hd)
        ev = ev.reshape(ev.shape[0], b, se, cfg.n_kv_heads, cfg.hd)
        scan_xs = (xs, (ek, ev))

        def dec_body(carry, inp):
            p, kv = inp
            x, aux = carry
            x, a = B.dec_block(p, x, cfg, pos=pos, enc_out=enc_x,
                               shard=shard, enc_kv_pre=kv, pos_trivial=True)
            return (x, aux + a), None
    else:
        scan_xs = xs

        def dec_body(carry, p):
            x, aux = carry
            x, a = B.dec_block(p, x, cfg, pos=pos, enc_out=enc_x, shard=shard,
                               pos_trivial=True)
            return (x, aux + a), None

    dec_fn = dec_body if remat == "none" else jax.checkpoint(dec_body,
                                                             prevent_cse=False)
    (x, aux), _ = lax.scan(dec_fn, (x, jnp.zeros((), jnp.float32)),
                           scan_xs)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def _head(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def lm_loss(params, batch, cfg: ModelConfig, *, shard: ShardCtx = NOSHARD,
            moe_capacity=None, remat: str = "none",
            xkv_precompute: bool = False):
    """Chunked cross-entropy; returns (loss, metrics)."""
    hidden, aux = lm_apply(params, batch, cfg, shard=shard,
                           moe_capacity=moe_capacity, remat=remat,
                           xkv_precompute=xkv_precompute)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(labels, dtype=jnp.float32)
    b, s, d = hidden.shape
    head = _head(params, cfg)
    chunk = min(LOSS_CHUNK, s)
    n = s // chunk if s % chunk == 0 else 1
    chunk = s // n

    hs = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    vmask = jnp.arange(head.shape[-1]) < cfg.vocab    # mask pad-vocab ids

    # checkpointed: the (chunk, vocab) logits are recomputed in the backward
    # instead of being stashed per chunk (a 60+GiB saving at vocab 256k)
    @jax.checkpoint
    def ce_chunk(carry, xs):
        tot, cnt = carry
        h, lab, m = xs
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
        logits = jnp.where(vmask, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - gold) * m)
        cnt = cnt + jnp.sum(m)
        return (tot, cnt), None

    (tot, cnt), _ = lax.scan(ce_chunk, (0.0, 0.0), (hs, ls, ms))
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# decode / serve
# ---------------------------------------------------------------------------

def lm_init_cache(cfg: ModelConfig, b: int, s_max: int, dtype=jnp.bfloat16,
                  enc_len: int | None = None):
    period, n_periods, tail = _period(cfg)
    cache: dict[str, Any] = {
        "blocks": [
            jax.tree.map(lambda a: jnp.zeros((n_periods,) + a.shape, a.dtype),
                         _block_cache(kind, cfg, b, s_max, dtype))
            for kind in period
        ],
        "tail": [_block_cache(kind, cfg, b, s_max, dtype) for kind in tail],
    }
    if cfg.is_encdec:
        el = enc_len or s_max
        # per-layer cross K/V — stored stacked, consumed inside the scan.
        # kv_quant="int8" quantizes this cache too: the encoder K/V is
        # written once at prefill and read back every decode step, so it
        # gets the same payload+scale split as the self-attn caches.
        kvs = (n_periods, b, el, cfg.n_kv_heads)
        for c in cache["blocks"]:
            if cfg.kv_quant == "int8":
                c["enc_k"] = jnp.zeros(kvs + (cfg.hd,), jnp.int8)
                c["enc_v"] = jnp.zeros(kvs + (cfg.hd,), jnp.int8)
                c["enc_k_scale"] = jnp.zeros(kvs, jnp.float32)
                c["enc_v_scale"] = jnp.zeros(kvs, jnp.float32)
            else:
                c["enc_k"] = jnp.zeros(kvs + (cfg.hd,), dtype)
                c["enc_v"] = jnp.zeros(kvs + (cfg.hd,), dtype)
    return cache


def lm_init_cache_paged(cfg: ModelConfig, b: int, num_pages: int,
                        page_size: int, dtype=jnp.bfloat16):
    """Paged decode cache: attention K/V lives in GLOBAL page pools shared
    by every slot (stacked (n_periods, P, kv, page_size, hd) leaves — no
    batch axis; a slot's rows are reached through its block table), while
    recurrent/SSM state keeps the per-slot batch layout.  Every attention
    layer shares ONE page id space: page p means row p of each layer's
    pool, so the allocator hands out ids once and they apply stack-wide.

    Enc-dec models are out of scope (the cross K/V cache is inherently
    per-slot and the decoder self-attn path has no paged twin)."""
    if cfg.is_encdec:
        raise NotImplementedError("paged KV cache: enc-dec models are not "
                                  "supported (use lm_init_cache)")
    period, n_periods, tail = _period(cfg)

    def cache_for(kind, stacked_n=None):
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            c = B.attn_cache_init_paged(cfg, num_pages, page_size, dtype)
        else:
            c = _block_cache(kind, cfg, b, s_max=0, dtype=dtype)
        if stacked_n is None:
            return c
        return jax.tree.map(
            lambda a: jnp.zeros((stacked_n,) + a.shape, a.dtype), c)

    return {
        "blocks": [cache_for(kind, n_periods) for kind in period],
        "tail": [cache_for(kind) for kind in tail],
    }


def _cache_layer(cache, i):
    """Layer ``i``'s slice of a stacked cache (the scan's per-layer read
    of the carried stack)."""
    with jax.named_scope("cache_io"):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), cache)


def _cache_layer_set(cache, new, i):
    """The stacked cache with layer ``i`` replaced by ``new``."""
    with jax.named_scope("cache_io"):
        return jax.tree.map(
            lambda a, u: lax.dynamic_update_index_in_dim(a, u, i, 0),
            cache, new)


def _scan_cached(blocks, caches, x, kinds, paged, block_fn):
    """Scan the period-stacked ``blocks`` over ``x`` with the stacked
    ``caches`` in the scan CARRY (stacking them as scan ys instead makes
    XLA materialize a second full-cache buffer).

    ``block_fn(kind, p, x, cache, layer) -> (x, new cache)``.  With
    ``paged`` (the attention caches are page pools read through a block
    table), an attention entry gets the stacked pools and the layer index
    and reads and writes its pages in place: a layer's pool is never
    copied out of the stack.  Every other entry (per-slot recurrent/SSM/
    conv state, contiguous K/V) gets layer ``i``'s slice (``layer=None``),
    written back after the block."""
    def period_body(carry, pblk):
        x, caches, i = carry
        newc = []
        for j, kind in enumerate(kinds):
            if paged and kind in (ATTN_GLOBAL, ATTN_LOCAL):
                x, nc = block_fn(kind, pblk[j], x, caches[j], i)
            else:
                x, nc = block_fn(kind, pblk[j], x,
                                 _cache_layer(caches[j], i), None)
                nc = _cache_layer_set(caches[j], nc, i)
            newc.append(nc)
        return (x, newc, i + 1), None

    (x, new_blocks, _), _ = lax.scan(
        period_body, (x, list(caches), jnp.asarray(0, jnp.int32)),
        tuple(blocks))
    return x, list(new_blocks)


def lm_decode_step(params, cache, tokens, pos, cfg: ModelConfig, *,
                   shard: ShardCtx = NOSHARD, block_table=None,
                   write_mask=None):
    """tokens: (B,1) int32; pos: (B,) int32 -> (logits (B,V), new cache).

    ``block_table`` (B, npp) int32 switches the attention layers to the
    PAGED cache layout (pool leaves + table-routed scatters; see
    lm_init_cache_paged) — non-attention state is unaffected.
    ``write_mask`` (B,) bool (paged only) suppresses a slot's cache write
    — the speculative draft scan's padding guard."""
    period, n_periods, tail = _period(cfg)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, {"tokens": tokens})

    def block_fn(kind, p, x, c, layer):
        if cfg.is_encdec:
            return B.dec_block_decode(p, x, cfg, {**c}, pos=pos)
        return _block_decode(kind, p, x, cfg, c, pos=pos, shard=shard,
                             block_table=block_table, write_mask=write_mask,
                             layer=layer)

    x, new_blocks = _scan_cached(params["blocks"], cache["blocks"], x,
                                 period, block_table is not None, block_fn)
    new_tail = []
    for p_t, c_t, kind in zip(params["tail"], cache["tail"], tail):
        x, nc = _block_decode(kind, p_t, x, cfg, c_t, pos=pos, shard=shard,
                              block_table=block_table, write_mask=write_mask)
        new_tail.append(nc)

    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = (x[:, 0] @ _head(params, cfg).astype(x.dtype)
                  ).astype(jnp.float32)
        logits = logits[:, : cfg.vocab]               # drop pad-vocab ids
    return logits, {"blocks": new_blocks, "tail": new_tail}


def lm_verify_step(params, cache, tokens, pos0, cfg: ModelConfig, *,
                   block_table, valid_len=None, shard: ShardCtx = NOSHARD):
    """Speculative-decode batched verify: score T drafted tokens per slot
    in ONE pass.  tokens: (B,T) int32 — token t sits at cache position
    ``pos0[b] + t``; block_table: (B, npp) int32 (paged cache only);
    valid_len: optional (B,) int32 — rows ``t >= valid_len[b]`` are batch
    padding (their cache writes are suppressed and their logits garbage).
    Returns (logits (B,T,vocab) f32 — row t scores position pos0+t+1 — and
    the new cache, with rows [pos0, pos0+T) appended).

    Attention-only stacks: recurrent/SSM layers raise (their state cannot
    be rewound past rejected rows).  Row t's logits equal what
    `lm_decode_step` at pos0+t would produce given the same cache prefix —
    the exactness property the greedy accept rule builds on.
    """
    period, n_periods, tail = _period(cfg)
    if cfg.is_encdec:
        raise NotImplementedError("lm_verify_step: enc-dec models are not "
                                  "supported")
    x = _embed(params, tokens, cfg, {"tokens": tokens})

    def block_fn(kind, p, x, c, layer):
        return _block_verify(kind, p, x, cfg, c, pos0=pos0,
                             block_table=block_table, valid_len=valid_len,
                             shard=shard, layer=layer)

    x, new_blocks = _scan_cached(params["blocks"], cache["blocks"], x,
                                 period, True, block_fn)
    new_tail = []
    for p_t, c_t, kind in zip(params["tail"], cache["tail"], tail):
        x, nc = _block_verify(kind, p_t, x, cfg, c_t, pos0=pos0,
                              block_table=block_table, valid_len=valid_len,
                              shard=shard)
        new_tail.append(nc)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = (x @ _head(params, cfg).astype(x.dtype)).astype(jnp.float32)
    logits = logits[:, :, : cfg.vocab]            # drop pad-vocab ids
    return logits, {"blocks": new_blocks, "tail": new_tail}


def _block_prefill(kind: str, p, x, cfg, cache, *, pos0, block_table=None):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        if block_table is not None:
            return B.attn_block_prefill_paged(p, x, cfg, cache, kind=kind,
                                              pos0=pos0,
                                              block_table=block_table)
        return B.attn_block_prefill(p, x, cfg, cache, kind=kind, pos0=pos0)
    if kind == RECURRENT:
        return B.rglru_block_prefill(p, x, cfg, cache, pos0=pos0)
    if kind == SSM:
        return B.mamba_block_prefill(p, x, cfg, cache, pos0=pos0)
    raise ValueError(kind)


def _select_slots(mask, new, old, *, batch_axis: int):
    """Commit `new` cache leaves only for slots where mask is True."""
    def sel(n, o):
        shape = [1] * n.ndim
        shape[batch_axis] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n, o)
    return jax.tree.map(sel, new, old)


def _prefill_enc_cache(params, batch, cfg, cache):
    """Run the encoder once and persist every decoder layer's cross K/V into
    the stacked enc cache (the xkv_precompute trick, cached for decode)."""
    frames = batch["src_frames"].astype(_compute_dtype(cfg))
    bsz, s_src, _ = frames.shape
    pos_src = jnp.broadcast_to(jnp.arange(s_src, dtype=jnp.int32)[None],
                               (bsz, s_src))

    def enc_body(x, p):
        return B.enc_block(p, x, cfg, pos=pos_src), None

    enc_x, _ = lax.scan(enc_body, frames, params["enc"]["blocks"])
    enc_x = L.rmsnorm(params["enc"]["norm"], enc_x, cfg.norm_eps)

    blk = cache["blocks"][0]
    el = blk["enc_k"].shape[2]
    if s_src > el:
        raise ValueError(f"encoder length {s_src} exceeds enc cache {el}")
    xs = params["blocks"][0]
    wk = L.asdense(xs["xattn"]["wk"], enc_x.dtype)           # (L, d, kv*hd)
    wv = L.asdense(xs["xattn"]["wv"], enc_x.dtype)
    ek = jnp.einsum("bsd,ldh->lbsh", enc_x, wk)
    ev = jnp.einsum("bsd,ldh->lbsh", enc_x, wv)
    np_, kvh, hd = ek.shape[0], cfg.n_kv_heads, cfg.hd
    ek = ek.reshape(np_, bsz, s_src, kvh, hd)
    ev = ev.reshape(np_, bsz, s_src, kvh, hd)
    if "enc_k_scale" in blk:
        # quantized cross cache: same quantize-on-append as the self-attn
        # path, done once here since the encoder K/V never changes after
        # prefill; rows past s_src keep payload 0 / scale 0 (dequant -> 0)
        from repro.quant.qtypes import quantize_kv
        ek, eks = quantize_kv(ek.astype(jnp.float32))
        ev, evs = quantize_kv(ev.astype(jnp.float32))
        blk = {**blk,
               "enc_k": blk["enc_k"].at[:, :, :s_src].set(ek),
               "enc_v": blk["enc_v"].at[:, :, :s_src].set(ev),
               "enc_k_scale": blk["enc_k_scale"].at[:, :, :s_src].set(eks),
               "enc_v_scale": blk["enc_v_scale"].at[:, :, :s_src].set(evs)}
    else:
        blk = {**blk,
               "enc_k": blk["enc_k"].at[:, :, :s_src]
                   .set(ek.astype(blk["enc_k"].dtype)),
               "enc_v": blk["enc_v"].at[:, :, :s_src]
                   .set(ev.astype(blk["enc_v"].dtype))}
    return {**cache, "blocks": [blk] + list(cache["blocks"][1:])}


def lm_prefill(params, batch, cfg: ModelConfig, s_max: int | None = None, *,
               cache=None, pos0=None, mask=None, shard: ShardCtx = NOSHARD,
               dtype=jnp.bfloat16, block_table=None):
    """Chunked prefill: push a (B, T) token chunk through the stack, FILLING
    the decode caches (attention K/V rows [pos0, pos0+T), recurrent/SSM/conv
    states advanced T steps, enc-dec cross K/V from src_frames).

    Call repeatedly with increasing ``pos0`` to ingest a long prompt in
    chunks; composes exactly with per-token `lm_decode_step`, which is the
    parity invariant tests/test_prefill.py asserts.

    cache: existing decode cache to continue (created fresh from ``s_max``
    when None).  pos0: (B,) chunk start positions (default zeros).
    mask: optional (B,) bool — only masked slots commit cache/state updates
    (the continuous-batching admit path: other slots' caches are untouched).
    block_table: (B, npp) int32 — PAGED attention caches (see
    lm_init_cache_paged); attention writes route through the table (the
    caller nulls non-admitted slots' rows, which IS their write protection,
    so ``mask`` only guards the per-slot recurrent/SSM leaves).
    Returns (last-chunk-token logits (B, vocab) f32, new cache).
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    if cache is None:
        if s_max is None:
            raise ValueError("lm_prefill needs either a cache or s_max")
        if block_table is not None:
            raise ValueError("paged prefill needs an explicit cache from "
                             "lm_init_cache_paged")
        cache = lm_init_cache(cfg, b, s_max, dtype)
    if pos0 is None:
        pos0 = jnp.zeros((b,), jnp.int32)
    old_cache = cache

    period, n_periods, tail = _period(cfg)
    if cfg.is_encdec and batch.get("src_frames") is not None:
        cache = _prefill_enc_cache(params, batch, cfg, cache)

    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, batch)

    def block_fn(kind, p, x, c, layer):
        if cfg.is_encdec:
            return B.dec_block_prefill(p, x, cfg, {**c}, pos0=pos0)
        return _block_prefill(kind, p, x, cfg, c, pos0=pos0,
                              block_table=block_table)

    # prefill slices each layer's pool out of the stack: a chunk scatters
    # rows that span every kv head of a head-major page, and indexing the
    # stack in place let XLA copy the WHOLE stack to token-major and back
    # around every layer (measured: qwen1.5-4b-d10 docqa, 20 kv heads)
    x, new_blocks = _scan_cached(params["blocks"], cache["blocks"], x,
                                 period, False, block_fn)
    new_tail = []
    for p_t, c_t, kind in zip(params["tail"], cache["tail"], tail):
        x, nc = _block_prefill(kind, p_t, x, cfg, c_t, pos0=pos0,
                               block_table=block_table)
        new_tail.append(nc)

    new_cache = {"blocks": new_blocks, "tail": new_tail}
    if mask is not None:
        def committed(n, o, kind, batch_axis):
            # paged attention pools have NO batch axis — the null-routed
            # block table already confined the writes, so the new pool is
            # committed as-is; everything per-slot keeps the mask select
            if block_table is not None and kind in (ATTN_GLOBAL, ATTN_LOCAL):
                return n
            return _select_slots(mask, n, o, batch_axis=batch_axis)

        with jax.named_scope("cache_io"):
            new_cache = {
                "blocks": [committed(n, o, kind, 1)
                           for n, o, kind in zip(new_cache["blocks"],
                                                 old_cache["blocks"], period)],
                "tail": [committed(n, o, kind, 0)
                         for n, o, kind in zip(new_cache["tail"],
                                               old_cache["tail"], tail)],
            }

    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = (x[:, -1] @ _head(params, cfg).astype(x.dtype)
                  ).astype(jnp.float32)
        logits = logits[:, : cfg.vocab]
    return logits, new_cache
