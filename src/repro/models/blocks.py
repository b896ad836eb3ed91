"""Residual blocks for every assigned architecture family.

Layer stacking uses *period scanning* (models/model.py): parameters of layers
at the same position within the repeating pattern period are stacked and the
model scans over periods — compile-time stays O(period), not O(n_layers),
which keeps 80 dry-run compiles tractable.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig, ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, SSM
from repro.models import layers as L
from repro.models.layers import ShardCtx, NOSHARD


# ---------------------------------------------------------------------------
# attention + (ffn | moe) transformer block
# ---------------------------------------------------------------------------

def attn_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {"ln1": L.rmsnorm_init(cfg.d_model),
         "attn": L.attn_init(ks[0], cfg),
         "ln2": L.rmsnorm_init(cfg.d_model)}
    if cfg.n_experts:
        p["moe"] = L.moe_init(ks[1], cfg)
    else:
        p["ffn"] = L.ffn_init(ks[1], cfg.d_model, cfg.d_ff)
    return p


def _attn_kw(cfg: ModelConfig) -> dict:
    """The flash-attention dispatch knobs every attention site forwards."""
    return dict(backend=cfg.attn_backend, cfg=cfg.attn_cfg,
                bwd_cfg=cfg.attn_bwd_cfg, bq=cfg.attn_bq, bkv=cfg.attn_bkv)


def attn_block(p, x, cfg: ModelConfig, *, kind: str, pos, mrope_pos3=None,
               shard: ShardCtx = NOSHARD, moe_capacity=None,
               pos_trivial: bool = False):
    window = cfg.window if kind == ATTN_LOCAL else None
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos, mrope_pos3=mrope_pos3)
    q = shard.constrain_heads(q, cfg.n_heads)
    k = shard.constrain_heads(k, cfg.n_kv_heads)
    # sparse knobs ride only the LOCAL self-attention site (not _attn_kw:
    # cross/enc attention must never see a window-derived live index)
    o = L.flash_attention(q, k, v, causal=True, window=window, q_pos=pos,
                          pos_trivial=pos_trivial,
                          global_stride=(cfg.attn_global_stride
                                         if kind == ATTN_LOCAL else None),
                          sparse=cfg.attn_sparse,
                          sparse_cfg=cfg.attn_sparse_cfg, **_attn_kw(cfg))
    o = o.reshape(x.shape[0], x.shape[1], -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    x = x + o
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = 0.0
    if cfg.n_experts:
        y, aux = L.moe(p["moe"], h, cfg, shard=shard, capacity=moe_capacity)
    else:
        h2 = shard.constrain(h, lambda P, c: P(c.dp, None, None))
        y = L.ffn(p["ffn"], h2, backend=cfg.ffn_backend)
    return x + y, aux


def attn_block_decode(p, x, cfg: ModelConfig, cache, *, kind: str, pos,
                      shard: ShardCtx = NOSHARD):
    """x: (B,1,d); cache: {'k','v'[,'k_scale','v_scale']} (B,S,kv,hd);
    pos: (B,).  A quantized cache (cfg.kv_quant="int8", marked by the scale
    leaves) QUANTIZES ON APPEND: the new row is absmax-scaled per kv-head
    before the scatter, and the scales ride to the attention dispatch."""
    window = cfg.window if kind == ATTN_LOCAL else None
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos[:, None])
    bidx = jnp.arange(x.shape[0])
    quant = "k_scale" in cache
    # barrier: stops XLA from fusing the (f32 rope) -> bf16 convert into the
    # cache scatter, which would materialize the WHOLE cache in f32
    k_upd, v_upd = jax.lax.optimization_barrier((k[:, 0], v[:, 0]))
    kscale = vscale = None
    if quant:
        from repro.quant.qtypes import quantize_kv
        k_upd, ks_new = quantize_kv(k_upd.astype(jnp.float32))
        v_upd, vs_new = quantize_kv(v_upd.astype(jnp.float32))
        kscale = cache["k_scale"].at[bidx, pos].set(ks_new)
        vscale = cache["v_scale"].at[bidx, pos].set(vs_new)
    kc = cache["k"].at[bidx, pos].set(k_upd)
    vc = cache["v"].at[bidx, pos].set(v_upd)
    o = L.decode_attention(q, kc, vc, pos, window=window,
                           backend=cfg.decode_backend,
                           cfg=cfg.decode_attn_cfg, bkv=cfg.decode_bkv,
                           k_scale=kscale, v_scale=vscale)
    o = o.reshape(x.shape[0], 1, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    x = x + o
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        y, _ = L.moe(p["moe"], h, cfg, shard=shard,
                     capacity=max(4, min(x.shape[0], 4 * cfg.top_k)))
    else:
        y = L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
    newc = {"k": kc, "v": vc}
    if quant:
        newc.update(k_scale=kscale, v_scale=vscale)
    return x + y, newc


def attn_block_prefill(p, x, cfg: ModelConfig, cache, *, kind: str, pos0):
    """Chunked prefill: x (B,T,d); cache {'k','v'} (B,S,kv,hd); pos0 (B,)
    starting position of the chunk.  Writes the chunk's K/V into the cache
    rows [pos0, pos0+T) and attends against the FULL cache with global
    row positions — earlier chunks are visible, later rows are masked by
    causality — so successive chunks compose exactly with per-token decode.
    """
    b, t, _ = x.shape
    window = cfg.window if kind == ATTN_LOCAL else None
    pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None]     # (B,T)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
    bidx = jnp.arange(b)
    quant = "k_scale" in cache
    newc = {}
    if quant:
        # quantize-on-append, chunk rows at once: (B,T,kv,hd) -> int8 +
        # per-(token, kv-head) scales, matching the decode step exactly so
        # chunked ingestion composes with per-token decode
        from repro.quant.qtypes import quantize_kv
        kq, ks_new = quantize_kv(k.astype(jnp.float32))
        vq, vs_new = quantize_kv(v.astype(jnp.float32))
        k_upd, v_upd = jax.lax.optimization_barrier((kq, vq))
        newc["k_scale"] = cache["k_scale"].at[bidx[:, None], pos].set(ks_new)
        newc["v_scale"] = cache["v_scale"].at[bidx[:, None], pos].set(vs_new)
    else:
        # same barrier as the decode step: keep the f32 rope -> storage-dtype
        # convert out of the cache scatter so the whole cache never goes f32
        k_upd, v_upd = jax.lax.optimization_barrier(
            (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)))
    kc = cache["k"].at[bidx[:, None], pos].set(k_upd)
    vc = cache["v"].at[bidx[:, None], pos].set(v_upd)
    if quant:
        from repro.quant.qtypes import dequantize_kv
        ka = dequantize_kv(kc, newc["k_scale"]).astype(x.dtype)
        va = dequantize_kv(vc, newc["v_scale"]).astype(x.dtype)
    else:
        ka, va = kc, vc
    # chunk rows sit at ragged global positions inside a padded cache: the
    # dispatch always falls back to mea here (pos_trivial=False), by design
    o = L.flash_attention(q, ka, va, causal=True, window=window, q_pos=pos,
                          **_attn_kw(cfg))
    o = o.reshape(b, t, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    x = x + o
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        # capacity = all chunk tokens: prefill never drops, so chunked
        # ingestion can't diverge from per-token decode on routing overflow
        y, _ = L.moe(p["moe"], h, cfg, capacity=b * t)
    else:
        y = L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
    return x + y, {"k": kc, "v": vc, **newc}


def attn_cache_init(cfg: ModelConfig, b: int, s_max: int, dtype=jnp.bfloat16):
    """Decode K/V cache.  cfg.kv_quant="int8" allocates int8 payloads plus
    per-(token, kv-head) f32 scales — ~half the bytes of a bf16 cache, which
    is what roughly doubles the slots*max_len a host can hold.  (Enc-dec
    self-attn caches stay dense: the decoder blocks there don't carry the
    quantize-on-append path.  The enc-dec CROSS cache does quantize — it is
    written once at prefill, see lm_init_cache/_prefill_enc_cache.)"""
    if cfg.kv_quant == "int8" and not cfg.is_encdec:
        return {"k": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), jnp.int8),
                "v": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), jnp.int8),
                "k_scale": jnp.zeros((b, s_max, cfg.n_kv_heads), jnp.float32),
                "v_scale": jnp.zeros((b, s_max, cfg.n_kv_heads), jnp.float32)}
    return {"k": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), dtype)}


# -- paged attention cache (repro.serve: global page pool + block tables) ----

def attn_cache_init_paged(cfg: ModelConfig, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16):
    """Paged decode K/V: one global (P, Hkv, page_size, hd) pool per layer,
    shared by every slot through its block table.  Page 0 is the NULL page
    (repro.serve.paging): never allocated, and the write paths route
    inactive slots' scatters to it.  cfg.kv_quant="int8" composes — int8
    payload pools + per-(kv-head, row) f32 scale pools, double the pages
    per HBM byte.  Head-major pages make each (page, kv head) a contiguous
    (page_size, hd) tile: the unit the paged kernel DMAs."""
    shape = (num_pages, cfg.n_kv_heads, page_size, cfg.hd)
    if cfg.kv_quant == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _paged_rows(block_table, pos, page_size):
    """Physical (page, row) for logical cache rows ``pos``; pos may be (B,)
    or (B,T).  Rows past a slot's allocation resolve to the NULL page."""
    bidx = jnp.arange(block_table.shape[0])
    if pos.ndim == 2:
        bidx = bidx[:, None]
    return block_table[bidx, pos // page_size], pos % page_size


def _ffn_scope(cfg: ModelConfig) -> str:
    """Named scope of a block's feed-forward half."""
    return "moe" if cfg.n_experts else "ffn"


def _pool_set(pool, page, row, val, layer=None):
    """Write per-row values (pos.shape + (Hkv, ...)) into a head-major pool
    (P, Hkv, ps, ...) at physical (page, row); with ``layer``, into layer
    ``layer`` of the stack (L, P, Hkv, ps, ...) in place."""
    if layer is None:
        return pool.at[page, :, row].set(val)
    return pool.at[layer, page, :, row].set(val)


def attn_block_decode_paged(p, x, cfg: ModelConfig, cache, *, kind: str, pos,
                            block_table, write_mask=None, layer=None,
                            shard: ShardCtx = NOSHARD):
    """Paged twin of attn_block_decode: the new row scatters through the
    block table into the shared pool and attention reads the pool through
    the same table.  cache: {'k','v'[,'k_scale','v_scale']} pools
    (P,kv,ps,hd); block_table: (B, npp) int32; pos: (B,).  ``layer``
    (scalar int32) makes the cache leaves the layer stacks (L,P,kv,ps,hd):
    the block reads and writes layer ``layer``'s pages in place and
    returns the updated stacks.

    ``write_mask`` (B,) bool suppresses a slot's cache write (the
    speculative draft scan pads every slot to the batch-max draft length;
    padded steps run at positions past the slot's page coverage, where the
    table lookup CLAMPS and would alias a live page — so the page is routed
    to NULL before the scatter)."""
    window = cfg.window if kind == ATTN_LOCAL else None
    ps = cache["k"].shape[-2]
    quant = "k_scale" in cache
    kscale = vscale = None
    with jax.named_scope("attention"):
        with jax.named_scope("qkv"):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            q, k, v = L.qkv_project(p["attn"], h, cfg, pos[:, None])
        with jax.named_scope("kv_write"):
            k_upd, v_upd = jax.lax.optimization_barrier((k[:, 0], v[:, 0]))
            page, row = _paged_rows(block_table, pos, ps)
            if write_mask is not None:
                page = jnp.where(write_mask, page, 0)       # 0 == NULL_PAGE
            if quant:
                from repro.quant.qtypes import quantize_kv
                k_upd, ks_new = quantize_kv(k_upd.astype(jnp.float32))
                v_upd, vs_new = quantize_kv(v_upd.astype(jnp.float32))
                kscale = _pool_set(cache["k_scale"], page, row, ks_new, layer)
                vscale = _pool_set(cache["v_scale"], page, row, vs_new, layer)
            kc = _pool_set(cache["k"], page, row, k_upd, layer)
            vc = _pool_set(cache["v"], page, row, v_upd, layer)
        with jax.named_scope("core"):
            o = L.paged_decode_attention(q, kc, vc, block_table, pos,
                                         window=window,
                                         backend=cfg.decode_backend,
                                         cfg=cfg.decode_attn_cfg,
                                         k_scale=kscale, v_scale=vscale,
                                         layer=layer)
        with jax.named_scope("out"):
            o = o.reshape(x.shape[0], 1, -1) \
                @ L.asdense(p["attn"]["wo"], x.dtype)
            x = x + o
    with jax.named_scope(_ffn_scope(cfg)):
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if cfg.n_experts:
            y, _ = L.moe(p["moe"], h, cfg, shard=shard,
                         capacity=max(4, min(x.shape[0], 4 * cfg.top_k)))
        else:
            y = L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
        x = x + y
    newc = {"k": kc, "v": vc}
    if quant:
        newc.update(k_scale=kscale, v_scale=vscale)
    return x, newc


def attn_block_prefill_paged(p, x, cfg: ModelConfig, cache, *, kind: str,
                             pos0, block_table):
    """Paged twin of attn_block_prefill: the chunk's rows scatter through
    the block table; attention gathers the slot's logical view back out of
    the pool (mea fallback, as in the contiguous prefill).  Write
    protection for non-admitted slots comes from the table itself — the
    engine nulls their rows, so their scatters land on the null page."""
    b, t, _ = x.shape
    ps = cache["k"].shape[2]
    window = cfg.window if kind == ATTN_LOCAL else None
    pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None]     # (B,T)
    quant = "k_scale" in cache
    newc = {}
    with jax.named_scope("attention"):
        with jax.named_scope("qkv"):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
        with jax.named_scope("kv_write"):
            page, row = _paged_rows(block_table, pos, ps)
            if quant:
                from repro.quant.qtypes import quantize_kv
                kq, ks_new = quantize_kv(k.astype(jnp.float32))
                vq, vs_new = quantize_kv(v.astype(jnp.float32))
                k_upd, v_upd = jax.lax.optimization_barrier((kq, vq))
                newc["k_scale"] = _pool_set(cache["k_scale"], page, row,
                                            ks_new)
                newc["v_scale"] = _pool_set(cache["v_scale"], page, row,
                                            vs_new)
            else:
                k_upd, v_upd = jax.lax.optimization_barrier(
                    (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)))
            kc = _pool_set(cache["k"], page, row, k_upd)
            vc = _pool_set(cache["v"], page, row, v_upd)
        with jax.named_scope("core"):
            # the slot's whole logical view gathered out of the pool
            from repro.kernels.ref import paged_view
            ka, va = paged_view(kc, block_table), paged_view(vc, block_table)
            if quant:
                from repro.quant.qtypes import dequantize_kv
                ka = dequantize_kv(ka, paged_view(newc["k_scale"],
                                                  block_table)).astype(x.dtype)
                va = dequantize_kv(va, paged_view(newc["v_scale"],
                                                  block_table)).astype(x.dtype)
            o = L.flash_attention(q, ka, va, causal=True, window=window,
                                  q_pos=pos, **_attn_kw(cfg))
        with jax.named_scope("out"):
            o = o.reshape(b, t, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
            x = x + o
    with jax.named_scope(_ffn_scope(cfg)):
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if cfg.n_experts:
            y, _ = L.moe(p["moe"], h, cfg, capacity=b * t)
        else:
            y = L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
        x = x + y
    return x, {"k": kc, "v": vc, **newc}


def attn_block_verify_paged(p, x, cfg: ModelConfig, cache, *, kind: str,
                            pos0, block_table, valid_len=None, layer=None,
                            shard: ShardCtx = NOSHARD):
    """Batched-verify twin of attn_block_decode_paged (speculative decode):
    T drafted rows per slot scatter through the block table at positions
    ``pos0[b] + t`` and attention scores all of them in one short-q pass
    (L.verify_attention — the flash_attention_verify tuner family).

    ``valid_len`` (B,) int32 marks rows ``t >= valid_len[b]`` as batch
    padding.  Their writes MUST be suppressed: JAX clamps out-of-bounds
    gathers, so a padded row past the slot's page coverage would resolve
    the table lookup to a LIVE page and the scatter would corrupt it —
    route the page to NULL before the scatter instead (scatters to row 0
    of the null page are harmless by construction).  ``layer`` as in
    attn_block_decode_paged."""
    b, t, _ = x.shape
    ps = cache["k"].shape[-2]
    window = cfg.window if kind == ATTN_LOCAL else None
    pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None]     # (B,T)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
    page, row = _paged_rows(block_table, pos, ps)
    if valid_len is not None:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid_len[:, None]
        page = jnp.where(live, page, 0)             # 0 == NULL_PAGE
    quant = "k_scale" in cache
    kscale = vscale = None
    if quant:
        from repro.quant.qtypes import quantize_kv
        kq, ks_new = quantize_kv(k.astype(jnp.float32))
        vq, vs_new = quantize_kv(v.astype(jnp.float32))
        k_upd, v_upd = jax.lax.optimization_barrier((kq, vq))
        kscale = _pool_set(cache["k_scale"], page, row, ks_new, layer)
        vscale = _pool_set(cache["v_scale"], page, row, vs_new, layer)
    else:
        k_upd, v_upd = jax.lax.optimization_barrier(
            (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)))
    kc = _pool_set(cache["k"], page, row, k_upd, layer)
    vc = _pool_set(cache["v"], page, row, v_upd, layer)
    o = L.verify_attention(q, kc, vc, block_table, pos0, window=window,
                           backend=cfg.decode_backend,
                           cfg=cfg.decode_attn_cfg,
                           k_scale=kscale, v_scale=vscale, layer=layer)
    o = o.reshape(b, t, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    x = x + o
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        # full capacity, as in chunked prefill: verify never drops on
        # routing overflow
        y, _ = L.moe(p["moe"], h, cfg, shard=shard, capacity=b * t)
    else:
        y = L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
    newc = {"k": kc, "v": vc}
    if quant:
        newc.update(k_scale=kscale, v_scale=vscale)
    return x + y, newc


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ---------------------------------------------------------------------------

def rglru_block_init(key, cfg: ModelConfig):
    d, dr = cfg.d_model, cfg.d_rnn
    ks = jax.random.split(key, 8)
    return {
        "ln1": L.rmsnorm_init(d),
        "wx": L.dense_init(ks[0], d, dr),
        "wgate": L.dense_init(ks[1], d, dr),
        "conv": L.conv1d_init(ks[2], dr, cfg.conv_width),
        "wr": L.dense_init(ks[3], dr, dr),
        "wi": L.dense_init(ks[4], dr, dr),
        "br": jnp.zeros((dr,), jnp.float32),
        "bi": jnp.zeros((dr,), jnp.float32),
        # softplus(a_param) ~ 0.08 -> decay a in the stable range
        "a_param": jnp.log(jnp.expm1(jnp.full((dr,), 0.08))),
        "wo": L.dense_init(ks[5], dr, d, scale=1.0 / math.sqrt(dr)),
        "ln2": L.rmsnorm_init(d),
        "ffn": L.ffn_init(ks[6], d, cfg.d_ff),
    }


def rglru_block(p, x, cfg: ModelConfig, *, shard: ShardCtx = NOSHARD):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    branch = h @ p["wx"].astype(h.dtype)
    gate = h @ p["wgate"].astype(h.dtype)
    bx = L.causal_conv1d(p["conv"], branch)
    r = (bx @ p["wr"].astype(bx.dtype)) + p["br"].astype(bx.dtype)
    i = (bx @ p["wi"].astype(bx.dtype)) + p["bi"].astype(bx.dtype)
    from repro.kernels import ref as KREF
    hseq = KREF.rglru(bx.astype(jnp.float32), r.astype(jnp.float32),
                      i.astype(jnp.float32), p["a_param"]).astype(x.dtype)
    y = (hseq * jax.nn.gelu(gate)) @ _rglru_out(p, x.dtype)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend), 0.0


def _rglru_out(p, dtype):
    # out proj: reuse wgate^T shape (dr, d) — stored lazily as its own param
    return L.asdense(p["wo"], dtype)


def rglru_block_decode(p, x, cfg: ModelConfig, cache, *, pos):
    with jax.named_scope("recurrent"):
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        branch = h @ p["wx"].astype(h.dtype)           # (B,1,dr)
        gate = h @ p["wgate"].astype(h.dtype)
        yt, conv_state = L.causal_conv1d_step(p["conv"], cache["conv"],
                                              branch[:, 0])
        bx = yt[:, None]
        r = (bx @ p["wr"].astype(bx.dtype)) + p["br"].astype(bx.dtype)
        i = (bx @ p["wi"].astype(bx.dtype)) + p["bi"].astype(bx.dtype)
        rg = jax.nn.sigmoid(r.astype(jnp.float32))
        ig = jax.nn.sigmoid(i.astype(jnp.float32))
        from repro.kernels.ref import RGLRU_C
        log_a = -RGLRU_C * jax.nn.softplus(p["a_param"])[None, None] * rg
        a_t = jnp.exp(log_a)
        mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
        hnew = a_t[:, 0] * cache["h"] + mult[:, 0] * (
            ig[:, 0] * bx[:, 0].astype(jnp.float32))
        y = (hnew[:, None].astype(x.dtype) * jax.nn.gelu(gate)) \
            @ _rglru_out(p, x.dtype)
        x = x + y
    with jax.named_scope("ffn"):
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
    return x, {"conv": conv_state, "h": hnew}


def rglru_block_prefill(p, x, cfg: ModelConfig, cache, *, pos0):
    """Chunked prefill: run T tokens through the recurrence starting from
    the cached (conv, h) state and return the advanced state."""
    with jax.named_scope("recurrent"):
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        branch = h @ p["wx"].astype(h.dtype)
        gate = h @ p["wgate"].astype(h.dtype)
        bx, conv_state = L.causal_conv1d_prefill(p["conv"], cache["conv"],
                                                 branch)
        r = (bx @ p["wr"].astype(bx.dtype)) + p["br"].astype(bx.dtype)
        i = (bx @ p["wi"].astype(bx.dtype)) + p["bi"].astype(bx.dtype)
        from repro.kernels import ref as KREF
        hseq, h_last = KREF.rglru_with_state(
            bx.astype(jnp.float32), r.astype(jnp.float32),
            i.astype(jnp.float32), p["a_param"], cache["h"])
        y = (hseq.astype(x.dtype) * jax.nn.gelu(gate)) \
            @ _rglru_out(p, x.dtype)
        x = x + y
    with jax.named_scope("ffn"):
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend)
    return x, {"conv": conv_state, "h": h_last}


def rglru_cache_init(cfg: ModelConfig, b: int, dtype=jnp.bfloat16):
    return {"conv": jnp.zeros((b, cfg.conv_width - 1, cfg.d_rnn), dtype),
            "h": jnp.zeros((b, cfg.d_rnn), jnp.float32)}


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) block
# ---------------------------------------------------------------------------

def mamba_block_init(key, cfg: ModelConfig):
    d, din = cfg.d_model, cfg.d_inner
    g, n, hh = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_ch = din + 2 * g * n
    ks = jax.random.split(key, 6)
    return {
        "ln": L.rmsnorm_init(d),
        "in_proj": L.dense_init(ks[0], d, 2 * din + 2 * g * n + hh),
        "conv": L.conv1d_init(ks[1], conv_ch, cfg.conv_width),
        "dt_bias": jnp.zeros((hh,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, hh)),
        "d_skip": jnp.ones((hh,), jnp.float32),
        "gnorm": L.rmsnorm_init(din),
        "out_proj": L.dense_init(ks[2], din, d),
    }


def _mamba_split(cfg, zxbcdt):
    din, g, n, hh = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z, xbc, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * g * n], axis=-1)
    return z, xbc, dt


def mamba_block(p, x, cfg: ModelConfig, *, shard: ShardCtx = NOSHARD):
    b, s, d = x.shape
    din, g, n, hh = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    ph = cfg.ssm_headdim
    h0 = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    zxbcdt = h0 @ p["in_proj"].astype(h0.dtype)
    z, xbc, dt_raw = _mamba_split(cfg, zxbcdt)
    xbc = jax.nn.silu(L.causal_conv1d(p["conv"], xbc))
    xs, bc = jnp.split(xbc, [din], axis=-1)
    bmat, cmat = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    a = -jnp.exp(p["a_log"])
    from repro.kernels import ref as KREF
    xh = xs.reshape(b, s, hh, ph).astype(jnp.float32)         # (B,S,H,P)
    chunk = 64 if s % 64 == 0 else (16 if s % 16 == 0 else 1)
    y = KREF.ssd_chunked(xh, dt, a,
                         bmat.reshape(b, s, g, n).astype(jnp.float32),
                         cmat.reshape(b, s, g, n).astype(jnp.float32),
                         chunk=chunk)
    y = y + xh * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, din).astype(x.dtype)
    y = L.rmsnorm(p["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
    return x + y @ p["out_proj"].astype(x.dtype), 0.0


def mamba_block_decode(p, x, cfg: ModelConfig, cache, *, pos):
    with jax.named_scope("ssm"):
        b = x.shape[0]
        din, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
        hh = cfg.ssm_heads
        ph = cfg.ssm_headdim
        h0 = L.rmsnorm(p["ln"], x, cfg.norm_eps)
        zxbcdt = (h0 @ p["in_proj"].astype(h0.dtype))[:, 0]
        z, xbc, dt_raw = _mamba_split(cfg, zxbcdt)
        yt, conv_state = L.causal_conv1d_step(p["conv"], cache["conv"], xbc)
        xbc = jax.nn.silu(yt)
        xs, bc = jnp.split(xbc, [din], axis=-1)
        bmat, cmat = jnp.split(bc, 2, axis=-1)                    # (B, g*n)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"])                       # (B,H)
        a = -jnp.exp(p["a_log"])                                  # (H,)
        xh = xs.reshape(b, hh, ph).astype(jnp.float32)
        rep = hh // g
        bm = jnp.repeat(bmat.reshape(b, g, n), rep, axis=1).astype(jnp.float32)
        cm = jnp.repeat(cmat.reshape(b, g, n), rep, axis=1).astype(jnp.float32)
        dA = jnp.exp(dt * a[None])                                # (B,H)
        state = cache["ssm"] * dA[..., None, None] + jnp.einsum(
            "bhn,bhp->bhpn", bm * dt[..., None], xh)
        y = jnp.einsum("bhn,bhpn->bhp", cm, state)
        y = y + xh * p["d_skip"][None, :, None]
        y = y.reshape(b, 1, din).astype(x.dtype)
        y = L.rmsnorm(p["gnorm"], y * jax.nn.silu(z[:, None]), cfg.norm_eps)
        return x + y @ p["out_proj"].astype(x.dtype), \
            {"conv": conv_state, "ssm": state}


def mamba_block_prefill(p, x, cfg: ModelConfig, cache, *, pos0):
    """Chunked prefill: advance (conv, ssm) state over T tokens at once via
    the chunked SSD scan seeded with the cached state."""
    with jax.named_scope("ssm"):
        b, t, _ = x.shape
        din, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
        hh = cfg.ssm_heads
        ph = cfg.ssm_headdim
        h0 = L.rmsnorm(p["ln"], x, cfg.norm_eps)
        zxbcdt = h0 @ p["in_proj"].astype(h0.dtype)
        z, xbc, dt_raw = _mamba_split(cfg, zxbcdt)
        yconv, conv_state = L.causal_conv1d_prefill(p["conv"], cache["conv"],
                                                    xbc)
        xbc = jax.nn.silu(yconv)
        xs, bc = jnp.split(xbc, [din], axis=-1)
        bmat, cmat = jnp.split(bc, 2, axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"])                     # (B,T,H)
        a = -jnp.exp(p["a_log"])
        from repro.kernels import ref as KREF
        xh = xs.reshape(b, t, hh, ph).astype(jnp.float32)
        chunk = 64 if t % 64 == 0 else (16 if t % 16 == 0 else 1)
        y, state = KREF.ssd_chunked(
            xh, dt, a,
            bmat.reshape(b, t, g, n).astype(jnp.float32),
            cmat.reshape(b, t, g, n).astype(jnp.float32),
            chunk=chunk, state0=cache["ssm"].transpose(0, 1, 3, 2),
            return_state=True)
        y = y + xh * p["d_skip"][None, None, :, None]
        y = y.reshape(b, t, din).astype(x.dtype)
        y = L.rmsnorm(p["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
        return x + y @ p["out_proj"].astype(x.dtype), \
            {"conv": conv_state, "ssm": state.transpose(0, 1, 3, 2)}


def mamba_cache_init(cfg: ModelConfig, b: int, dtype=jnp.bfloat16):
    conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {"conv": jnp.zeros((b, cfg.conv_width - 1, conv_ch), dtype),
            "ssm": jnp.zeros((b, cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.ssm_state), jnp.float32)}


# ---------------------------------------------------------------------------
# encoder / decoder blocks (seamless-m4t)
# ---------------------------------------------------------------------------

def enc_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"ln1": L.rmsnorm_init(cfg.d_model),
            "attn": L.attn_init(ks[0], cfg),
            "ln2": L.rmsnorm_init(cfg.d_model),
            "ffn": L.ffn_init(ks[1], cfg.d_model, cfg.d_ff)}


def enc_block(p, x, cfg: ModelConfig, *, pos, shard: ShardCtx = NOSHARD):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
    # non-causal: mask-free, so kernel eligibility needs no trivial-pos proof
    o = L.flash_attention(q, k, v, causal=False, q_pos=pos, **_attn_kw(cfg))
    x = x + o.reshape(x.shape[0], x.shape[1], -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend)


def dec_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    return {"ln1": L.rmsnorm_init(cfg.d_model),
            "attn": L.attn_init(ks[0], cfg),
            "lnx": L.rmsnorm_init(cfg.d_model),
            "xattn": L.attn_init(ks[1], cfg),
            "ln2": L.rmsnorm_init(cfg.d_model),
            "ffn": L.ffn_init(ks[2], cfg.d_model, cfg.d_ff)}


def _cross_attention(p, x, enc_kv, cfg: ModelConfig, enc_scales=None):
    b, s, _ = x.shape
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = (x @ L.asdense(p["wq"], x.dtype)).reshape(b, s, nq, hd)
    k, v = enc_kv
    if enc_scales is not None:
        # quantized cross cache (cfg.kv_quant="int8"): int8 payloads with
        # per-(token, kv-head) scales, dequantized to the activation dtype
        from repro.quant.qtypes import dequantize_kv
        ks, vs = enc_scales
        k = dequantize_kv(k, ks).astype(x.dtype)
        v = dequantize_kv(v, vs).astype(x.dtype)
    # non-causal cross attention: the kernel serves Sq != Sk geometries
    return L.flash_attention(q, k, v, causal=False,
                             **_attn_kw(cfg)).reshape(b, s, -1) \
        @ L.asdense(p["wo"], x.dtype)


def _enc_scales(cache):
    """(k_scale, v_scale) from a cross cache when quantized, else None."""
    if "enc_k_scale" in cache:
        return cache["enc_k_scale"], cache["enc_v_scale"]
    return None


def enc_kv(p, enc_out, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    k = (enc_out @ L.asdense(p["wk"], enc_out.dtype)).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = (enc_out @ L.asdense(p["wv"], enc_out.dtype)).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    return k, v


def dec_block(p, x, cfg: ModelConfig, *, pos, enc_out,
              shard: ShardCtx = NOSHARD, enc_kv_pre=None,
              pos_trivial: bool = False):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
    o = L.flash_attention(q, k, v, causal=True, q_pos=pos,
                          pos_trivial=pos_trivial, **_attn_kw(cfg))
    x = x + o.reshape(x.shape[0], x.shape[1], -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
    kv = enc_kv_pre if enc_kv_pre is not None \
        else enc_kv(p["xattn"], enc_out, cfg)
    x = x + _cross_attention(p["xattn"], h, kv, cfg)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend), 0.0


def dec_block_prefill(p, x, cfg: ModelConfig, cache, *, pos0):
    """Chunked prefill for the enc-dec decoder: fill the self-attn cache
    rows for the chunk and cross-attend the cached encoder K/V (which
    lm_prefill populates from src_frames when present)."""
    b, t, _ = x.shape
    pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos)
    bidx = jnp.arange(b)
    kc = cache["k"].at[bidx[:, None], pos].set(k.astype(cache["k"].dtype))
    vc = cache["v"].at[bidx[:, None], pos].set(v.astype(cache["v"].dtype))
    # ragged chunk positions against the padded cache: mea fallback, as in
    # attn_block_prefill
    o = L.flash_attention(q, kc, vc, causal=True, q_pos=pos,
                          **_attn_kw(cfg))
    x = x + o.reshape(b, t, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
    x = x + _cross_attention(p["xattn"], h,
                             (cache["enc_k"], cache["enc_v"]), cfg,
                             enc_scales=_enc_scales(cache))
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    enc = {kk: cache[kk] for kk in cache if kk.startswith("enc_")}
    return x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend), {"k": kc, "v": vc,
                                                             **enc}


def dec_block_decode(p, x, cfg: ModelConfig, cache, *, pos):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, pos[:, None])
    bidx = jnp.arange(x.shape[0])
    kc = cache["k"].at[bidx, pos].set(k[:, 0])
    vc = cache["v"].at[bidx, pos].set(v[:, 0])
    o = L.decode_attention(q, kc, vc, pos, backend=cfg.decode_backend,
                           cfg=cfg.decode_attn_cfg, bkv=cfg.decode_bkv)
    x = x + o.reshape(x.shape[0], 1, -1) @ L.asdense(p["attn"]["wo"], x.dtype)
    h = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
    x = x + _cross_attention(p["xattn"], h,
                             (cache["enc_k"], cache["enc_v"]), cfg,
                             enc_scales=_enc_scales(cache))
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    enc = {kk: cache[kk] for kk in cache if kk.startswith("enc_")}
    return x + L.ffn(p["ffn"], h, backend=cfg.ffn_backend), {"k": kc, "v": vc,
                                                             **enc}
