"""Model building blocks (pure functional JAX): norms, RoPE/M-RoPE, causal
depthwise conv, memory-efficient GQA attention, SwiGLU FFN, MoE.

All matmul-bearing blocks route through coarsenable kernels when
``backend='pallas'`` (small shapes / TPU); the default XLA path ('ref') is
used for CPU training, tests and the dry-run lowering.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref as KREF
from repro.models.config import ModelConfig
from repro.quant.qtypes import QTensor, asdense, dequantize, dequantize_kv

Params = dict


# --------------------------------------------------------------------------
# sharding context: axis names used for with_sharding_constraint hooks
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    dp: Any = None            # data-parallel axis name(s), e.g. ('pod','data')
    tp: Any = None            # tensor-parallel axis name, e.g. 'model'
    sp: Any = None            # sequence axis for long-context cells
    tp_size: int = 1
    dp_size: int = 1
    enabled: bool = False
    mesh: Any = None          # jax Mesh (needed by shard_map code paths)
    # optional (path, leaf) -> PartitionSpec used to re-constrain per-layer
    # parameter slices INSIDE the period scan, keeping the FSDP all-gather
    # in the loop body instead of hoisted over the whole stacked tensor
    param_spec_fn: Any = None

    def constrain_params(self, tree):
        if not self.enabled or self.param_spec_fn is None:
            return tree
        import jax
        return jax.tree_util.tree_map_with_path(
            lambda p, l: lax.with_sharding_constraint(
                l, self.param_spec_fn(p, l)), tree)

    def constrain(self, x, spec_fn):
        if not self.enabled:
            return x
        from jax.sharding import PartitionSpec as P
        return lax.with_sharding_constraint(x, spec_fn(P, self))

    def constrain_heads(self, x, n_heads: int):
        """Shard a (B,S,H,D) tensor's head axis on tp — only when it divides
        evenly (a non-divisible constraint fights GSPMD's propagation and
        triggers involuntary remat/replication)."""
        if not self.enabled or n_heads % max(1, self.tp_size):
            return x
        from jax.sharding import PartitionSpec as P
        return lax.with_sharding_constraint(x, P(self.dp, None, self.tp, None))


NOSHARD = ShardCtx()


def act(x, spec):
    return x


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, d_in, d_out, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale)


def rmsnorm_init(d):
    return {"scale": jnp.zeros((d,), dtype=jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"])).astype(dt)


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (B,S,H,D); pos: (B,S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (D/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs    # (B,S,D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    dt = x.dtype
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dt)


def apply_mrope(x: jax.Array, pos3: jax.Array, theta: float,
                sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.  pos3: (3,B,S) (temporal, height, width);
    sections give the number of frequency *pairs* drawn from each component."""
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                       # (D/2,)
    # choose the position component per frequency-pair index
    comp = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)
    ])                                                  # (D/2,)
    pos_sel = pos3.transpose(1, 2, 0)[..., comp].astype(jnp.float32)  # (B,S,D/2)
    ang = pos_sel * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    dt = x.dtype
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dt)


# --------------------------------------------------------------------------
# causal depthwise conv1d (mamba2 / griffin), with decode cache
# --------------------------------------------------------------------------

def conv1d_init(key, channels, width):
    return {"w": jax.random.normal(key, (width, channels), jnp.float32)
            / math.sqrt(width),
            "b": jnp.zeros((channels,), jnp.float32)}


def causal_conv1d(p, x):
    """x: (B,S,C) -> (B,S,C); causal depthwise window sum."""
    w = p["w"]
    width = w.shape[0]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        shift = width - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + xi.astype(jnp.float32) * w[i]
    return (out + p["b"]).astype(x.dtype)


def causal_conv1d_step(p, state, xt):
    """state: (B,width-1,C) trailing inputs; xt: (B,C) -> (yt, new_state)."""
    w, b = p["w"], p["b"]
    width = w.shape[0]
    buf = jnp.concatenate([state, xt[:, None, :]], axis=1)   # (B,width,C)
    yt = jnp.einsum("bwc,wc->bc", buf.astype(jnp.float32), w) + b
    return yt.astype(xt.dtype), buf[:, 1:]


def causal_conv1d_prefill(p, state, x):
    """Chunked form: state (B,width-1,C) left context; x (B,T,C) ->
    (y (B,T,C), new_state) — matches T applications of causal_conv1d_step."""
    w = p["w"]
    width = w.shape[0]
    t = x.shape[1]
    buf = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B,w-1+T,C)
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(width):
        out = out + buf[:, i:i + t].astype(jnp.float32) * w[i]
    return (out + p["b"]).astype(x.dtype), buf[:, t:].astype(state.dtype)


# --------------------------------------------------------------------------
# memory-efficient GQA attention (pure-jnp flash; the XLA model path)
# --------------------------------------------------------------------------

def mea_attention(q, k, v, *, causal=True, window=None, q_pos=None,
                  k_len=None, q_chunk=512, kv_chunk=512, scale=None):
    """Chunked (flash-style) attention in pure jnp.

    q: (B,Sq,H,D); k,v: (B,Sk,Hkv,D).  q_pos: (B,Sq) global row positions
    (defaults to arange).  k_len: optional valid kv length (decode).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32)[None], (b, sq))

    # keep K/V in their storage dtype (bf16): a full f32 upconversion of the
    # cache doubles+ the live set; the MXU accumulates in f32 via
    # preferred_element_type instead.
    qg = (q.reshape(b, sq, hkv, g, d) * jnp.asarray(scale, q.dtype))
    kf, vf = k, v

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq = (sq + q_chunk - 1) // q_chunk
    nk = (sk + kv_chunk - 1) // kv_chunk
    # pad to multiples
    def padto(x, n, axis):
        pad = n - x.shape[axis]
        if pad == 0:
            return x
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        return jnp.pad(x, cfg)

    qg = padto(qg, nq * q_chunk, 1)
    qp = padto(q_pos, nq * q_chunk, 1)
    kf = padto(kf, nk * kv_chunk, 1)
    vf = padto(vf, nk * kv_chunk, 1)

    kpos = jnp.arange(nk * kv_chunk, dtype=jnp.int32)
    if k_len is not None and jnp.ndim(k_len) > 0:
        valid_k = kpos[None, :] < k_len[:, None]          # (B, Sk)
    else:                                                 # scalar or None
        valid_k = kpos < (sk if k_len is None else k_len)
        valid_k = jnp.broadcast_to(valid_k[None], (b, nk * kv_chunk))

    qg = qg.reshape(b, nq, q_chunk, hkv, g, d)
    qp = qp.reshape(b, nq, q_chunk)
    kc = kf.reshape(b, nk, kv_chunk, hkv, d)
    vc = vf.reshape(b, nk, kv_chunk, hkv, d)
    kpc = kpos.reshape(nk, kv_chunk)
    vkc = valid_k.reshape(b, nk, kv_chunk)

    def q_step(_, qi):
        qblk, qpos_blk = qi                               # (B,qc,hkv,g,d),(B,qc)

        # checkpointed: without this the backward saves every (q,kv) chunk's
        # probability block — i.e. the full S^2 attention matrix.  With it
        # only the per-chunk (m,l,acc) carry survives and s/p are recomputed
        # in the backward, flash-attention style.
        @jax.checkpoint
        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kp, vk = ki
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qblk, kblk,
                           preferred_element_type=jnp.float32)
            mask = vk[:, None, :]                         # (B,1,Sk)
            if causal:
                mask = mask & (kp[None, None, :] <= qpos_blk[:, :, None])
            if window is not None:
                mask = mask & (kp[None, None, :] > qpos_blk[:, :, None] - window)
            mask = mask[:, :, None, None, :]              # (B,q,1,1,k)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mask
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, q_chunk, hkv, g), -1e30),
                jnp.zeros((b, q_chunk, hkv, g)),
                jnp.zeros((b, q_chunk, hkv, g, d)))
        (m, l, acc), _ = lax.scan(
            kv_step, init,
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
             kpc, vkc.transpose(1, 0, 2)))
        l = jnp.where(l == 0.0, 1.0, l)
        return None, acc / l[..., None]

    _, out = lax.scan(jax.checkpoint(q_step), None,
                      (qg.transpose(1, 0, 2, 3, 4, 5), qp.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq].astype(q.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, q_pos=None,
                    k_len=None, pos_trivial=False, scale=None,
                    backend: str = "ref", cfg="auto", bwd_cfg="auto",
                    bq: int = 128, bkv: int = 128, global_stride=None,
                    sparse: str = "auto", sparse_cfg="auto"):
    """Training/prefill attention dispatch.  q: (B,Sq,H,D);
    k, v: (B,Sk,Hkv,D) -> (B,Sq,H,D).

    backend="pallas" dispatches the coarsened custom-VJP flash kernel
    (kernels/flash_attention.py; cfg/bwd_cfg resolved through repro.tune
    for "auto" — forward q-row axis and backward kv-block axis tune
    independent degrees).  Everything the kernel cannot serve falls back to
    ``mea_attention`` — which is also the parity oracle it is tested
    against:

      * causal/window masking needs Sq == Sk and statically trivial row
        positions (``pos_trivial=True``: q row i IS global row i) — ragged
        ``q_pos`` (chunked prefill, packed batches) falls back
      * ``k_len`` (valid-prefix masking against a padded cache) falls back
      * Sq/Sk must tile by the bq/bkv blocks (and the resolved degrees)

    When a ``window`` is set (local-attention layers) and the geometry is
    kernel-eligible, ``sparse="auto"`` routes to the BLOCK-SPARSE kernel
    (`ops.flash_attention_sparse`): each q-block program walks only the kv
    blocks its precomputed live index lists, so a long-context prefill
    pays live traffic instead of the dense causal grid.  ``sparse="off"``
    pins the dense-mask kernel.  ``global_stride=g`` adds LongFormer-style
    global columns (every g-th kv position visible past the window) to the
    pattern — only meaningful together with ``window``.  Backward through
    the sparse path reuses the dense-mask backward kernels (identical
    (m, l) residuals); a global-stride pattern differentiates the jnp
    oracle instead — and when the sparse path is ineligible, a
    global-stride pattern falls back to that oracle too, since neither the
    dense kernel nor mea can express the strided columns.

    The kernel output is checkpoint-named "flash_attn_out" so the
    remat="dots" policy saves it instead of re-running the whole Pallas
    kernel in the backward.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if (backend == "pallas" and sparse != "off" and k_len is None
            and causal and window is not None and sq == sk and pos_trivial
            and h % hkv == 0):
        blk_q, blk_k = min(bq, sq), min(bkv, sk)
        if sq % blk_q == 0 and sk % blk_k == 0:
            from repro.core.coarsening import CoarseningConfig
            from repro.kernels import ops
            from repro.kernels.sparse_attention import max_live_blocks
            ml = max_live_blocks(sq, sk, blk_q, blk_k, causal=True,
                                 window=window, global_stride=global_stride)
            rsp = sparse_cfg if isinstance(sparse_cfg, str) \
                and sparse_cfg == "auto" \
                else (sparse_cfg if isinstance(sparse_cfg, CoarseningConfig)
                      else CoarseningConfig.parse(sparse_cfg))
            # an explicit slot degree the padded index can't tile falls
            # through to the dense path ("auto" legality guarantees a fit)
            if rsp == "auto" or ml % rsp.degree == 0:
                o = ops.flash_attention_sparse(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), rsp, bwd_cfg=bwd_cfg,
                    bq=blk_q, bkv=blk_k, causal=True, window=window,
                    global_stride=global_stride, scale=scale)
                from jax.ad_checkpoint import checkpoint_name
                o = checkpoint_name(o, "flash_attn_out")
                return o.transpose(0, 2, 1, 3).astype(q.dtype)
    if (global_stride and window is not None and k_len is None
            and sq == sk and (pos_trivial or q_pos is None)):
        # the strided global columns exist in no other backend's mask:
        # dense flash and mea would silently drop them — take the jnp
        # oracle (dense cost, exact semantics).  Ragged positions (chunked
        # prefill) keep the plain-window mea path below: the stride only
        # defines extra VISIBLE columns, and chunked prefill already
        # re-attends the full prefix per chunk.
        from repro.kernels import ops
        o = ops.flash_attention_sparse(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), backend="ref", causal=causal,
            window=window, global_stride=global_stride, scale=scale)
        return o.transpose(0, 2, 1, 3).astype(q.dtype)
    if backend == "pallas" and k_len is None:
        blk_q, blk_k = min(bq, sq), min(bkv, sk)
        ok = h % hkv == 0 and sq % blk_q == 0 and sk % blk_k == 0
        if causal or window is not None:
            ok = ok and sq == sk and pos_trivial
        if ok:
            from repro.core.coarsening import CoarseningConfig
            from repro.kernels import ops
            rcfg = ops.resolve_cfg(cfg, "flash_attention",
                                   (b, h, hkv, sq, sk, d),
                                   dtype=q.dtype.name, backend="pallas",
                                   bq=blk_q, bkv=blk_k, causal=bool(causal))
            # the bwd cfg stays "auto" (unresolved) on the default path:
            # the family's legality guarantees a tileable pick and the
            # flash_attention_bwd search only runs when a backward trace
            # does — forward-only model calls (eval, enc, cross) pay
            # nothing.  Only an EXPLICIT bwd label needs the degree guard.
            rbwd = bwd_cfg if isinstance(bwd_cfg, str) and bwd_cfg == "auto" \
                else (bwd_cfg if isinstance(bwd_cfg, CoarseningConfig)
                      else CoarseningConfig.parse(bwd_cfg))
            bwd_ok = rbwd == "auto" or sk % (blk_k * rbwd.degree) == 0
            # an explicit degree the geometry can't tile falls back too
            if sq % (blk_q * rcfg.degree) == 0 and bwd_ok:
                o = ops.flash_attention(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), rcfg, bwd_cfg=rbwd,
                    bq=blk_q, bkv=blk_k, causal=causal, window=window,
                    scale=scale)
                from jax.ad_checkpoint import checkpoint_name
                o = checkpoint_name(o, "flash_attn_out")
                return o.transpose(0, 2, 1, 3).astype(q.dtype)
    return mea_attention(q, k, v, causal=causal, window=window, q_pos=q_pos,
                         k_len=k_len, scale=scale)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, scale=None,
                     backend: str = "ref", cfg="auto", bkv: int = 128,
                     k_scale=None, v_scale=None):
    """Single-token attention against a cache.  q: (B,1,H,D);
    caches: (B,S,Hkv,D); pos: (B,) current position (0-based).

    backend="pallas" dispatches to the coarsened split-KV kernel
    (kernels/decode_attention.py, cfg resolved through repro.tune for
    "auto") when the cache geometry tiles; anything the kernel cannot
    serve falls back to the dense full-length einsum below — which is also
    the parity oracle the kernel is tested against.

    ``k_scale``/``v_scale`` (B,S,Hkv) mark an int8-quantized cache
    (cfg.kv_quant="int8"): the kernel fuses the dequant into its VMEM pass
    (kv_bits=8 — a separate tuner cache key from the bf16 geometry); the
    dense fallback dequantizes the whole cache first.
    """
    b, _, h, d = q.shape
    if backend == "pallas":
        s_all, hkv_all = k_cache.shape[1], k_cache.shape[2]
        blk = min(bkv, s_all)
        if h % hkv_all == 0 and s_all % blk == 0:
            from repro.kernels import ops
            params = dict(bkv=blk, window=window or 0)
            if k_scale is not None:
                params["kv_bits"] = 8
            rcfg = ops.resolve_cfg(cfg, "decode_attention",
                                   (b, h, hkv_all, s_all, d),
                                   dtype=k_cache.dtype.name,
                                   backend="pallas", **params)
            # an explicit degree the cache length can't tile falls back too
            if s_all % (blk * rcfg.degree) == 0:
                return ops.decode_attention(q, k_cache, v_cache, pos, rcfg,
                                            bkv=blk, window=window,
                                            scale=scale, k_scale=k_scale,
                                            v_scale=v_scale)
    if k_scale is not None:
        # dense-dequant fallback (and the parity oracle for the fused path)
        k_cache = dequantize_kv(k_cache, k_scale)
        v_cache = dequantize_kv(v_cache, v_scale)
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # never upconvert the cache (it is the dominant buffer at decode);
    # accumulate in f32 via preferred_element_type instead
    qg = (q.reshape(b, hkv, g, d) * jnp.asarray(scale, q.dtype)
          ).astype(k_cache.dtype)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    kpos = jnp.arange(s, dtype=jnp.int32)
    mask = kpos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] > pos[:, None] - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _layer_pool(pool, layer):
    """Layer ``layer``'s pool out of a stack (None passes through): what
    the paged kernels, which take one layer's pool, are handed."""
    return pool if pool is None or layer is None else pool[layer]


def paged_decode_attention(q, k_pool, v_pool, block_table, pos, *,
                           window=None, scale=None, backend: str = "ref",
                           cfg="auto", k_scale=None, v_scale=None,
                           layer=None):
    """Single-token attention against a PAGED cache.  q: (B,1,H,D);
    pools: (P, Hkv, page_size, D) shared by every slot; block_table:
    (B, npp) int32 per-slot logical->physical page map; pos: (B,).

    backend="pallas" dispatches the block-table split-KV kernel
    (kernels/decode_attention.make_paged_kernel; the kv block IS the page,
    cfg resolved through the "decode_attention_paged" tuner family — page
    size joins the spec key).  The fallback gathers the table into a
    contiguous per-slot view and runs the dense einsum path — which is also
    the parity oracle the paged kernel is tested against.

    ``k_scale``/``v_scale`` (P, Hkv, page_size) mark int8 pools
    (cfg.kv_quant="int8"): dequant is fused into the kernel pass; the
    fallback dequantizes the gathered view first.

    ``layer`` (scalar int32) makes the pools and scales the layer stacks
    (L, P, ...): the fallback gathers layer ``layer``'s view straight out
    of the stack; the kernel takes that layer's pool.
    """
    b, _, h, d = q.shape
    hkv, ps = k_pool.shape[-3:-1]
    npp = block_table.shape[1]
    if backend == "pallas" and h % hkv == 0:
        from repro.kernels import ops
        params = dict(page_size=ps, window=window or 0)
        if k_scale is not None:
            params["kv_bits"] = 8
        rcfg = ops.resolve_cfg(cfg, "decode_attention_paged",
                               (b, h, hkv, npp, d),
                               dtype=k_pool.dtype.name,
                               backend="pallas", **params)
        # an explicit degree the per-slot page count can't tile falls back
        if npp % rcfg.degree == 0:
            return ops.paged_decode_attention(
                q, _layer_pool(k_pool, layer), _layer_pool(v_pool, layer),
                block_table, pos, rcfg, window=window, scale=scale,
                k_scale=_layer_pool(k_scale, layer),
                v_scale=_layer_pool(v_scale, layer))
    # gather-to-contiguous fallback (and the paged kernel's parity oracle)
    k_view = KREF.paged_view(k_pool, block_table, layer)
    v_view = KREF.paged_view(v_pool, block_table, layer)
    ks = vs = None
    if k_scale is not None:
        ks = KREF.paged_view(k_scale, block_table, layer)
        vs = KREF.paged_view(v_scale, block_table, layer)
    return decode_attention(q, k_view, v_view, pos, window=window,
                            scale=scale, backend="ref",
                            k_scale=ks, v_scale=vs)


def verify_attention(q, k_pool, v_pool, block_table, pos0, *, window=None,
                     scale=None, backend: str = "ref", cfg="auto",
                     k_scale=None, v_scale=None, layer=None):
    """Batched-verify attention against a PAGED cache (speculative decode).
    q: (B,T,H,D) — row t attends at cache position ``pos0[b] + t``; pools /
    block_table / scales / layer as in `paged_decode_attention`; pos0: (B,).

    backend="pallas" dispatches the short-q block-table kernel
    (kernels/decode_attention.make_verify_kernel, tuned under the
    "flash_attention_verify" family — its own cache key: scoring T*G rows
    per fetched page moves the winning degree away from the decode
    family's).  The fallback gathers the table into a contiguous view and
    runs the decode dense contraction with one extra row axis — each row is
    the exact computation `decode_attention`'s fallback would do at that
    position, which is what makes greedy verify bitwise-exact against
    sequential decode on the ref backend.
    """
    b, t, h, d = q.shape
    hkv, ps = k_pool.shape[-3:-1]
    npp = block_table.shape[1]
    if backend == "pallas" and h % hkv == 0:
        from repro.kernels import ops
        params = dict(page_size=ps, window=window or 0)
        if k_scale is not None:
            params["kv_bits"] = 8
        rcfg = ops.resolve_cfg(cfg, "flash_attention_verify",
                               (b, h, hkv, t, npp, d),
                               dtype=k_pool.dtype.name,
                               backend="pallas", **params)
        # an explicit degree the per-slot page count can't tile falls back
        if npp % rcfg.degree == 0:
            return ops.flash_attention_verify(
                q, _layer_pool(k_pool, layer), _layer_pool(v_pool, layer),
                block_table, pos0, rcfg, window=window, scale=scale,
                k_scale=_layer_pool(k_scale, layer),
                v_scale=_layer_pool(v_scale, layer))
    # gather-to-contiguous fallback (and the verify kernel's parity oracle)
    k_view = KREF.paged_view(k_pool, block_table, layer)
    v_view = KREF.paged_view(v_pool, block_table, layer)
    if k_scale is not None:
        k_view = dequantize_kv(
            k_view, KREF.paged_view(k_scale, block_table, layer))
        v_view = dequantize_kv(
            v_view, KREF.paged_view(v_scale, block_table, layer))
    s = npp * ps
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = (q.reshape(b, t, hkv, g, d) * jnp.asarray(scale, q.dtype)
          ).astype(k_view.dtype)
    logits = jnp.einsum("bthgd,bshd->bthgs", qg, k_view,
                        preferred_element_type=jnp.float32)
    kpos = jnp.arange(s, dtype=jnp.int32)
    rows = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B,T)
    mask = kpos[None, None, :] <= rows[:, :, None]                  # (B,T,S)
    if window is not None:
        mask = mask & (kpos[None, None, :] > rows[:, :, None] - window)
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bthgs,bshd->bthgd", p.astype(v_view.dtype), v_view,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(q.dtype)


# --------------------------------------------------------------------------
# attention block params
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, d_model=None):
    d = d_model or cfg.d_model
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, nq * hd),
        "wk": dense_init(ks[1], d, nkv * hd),
        "wv": dense_init(ks[2], d, nkv * hd),
        "wo": dense_init(ks[3], nq * hd, d, scale=1.0 / math.sqrt(nq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((nkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((nkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["qnorm"] = rmsnorm_init(hd)
        p["knorm"] = rmsnorm_init(hd)
    return p


def qkv_project(p, x, cfg: ModelConfig, pos, *, mrope_pos3=None):
    """x: (B,S,d) -> q (B,S,H,hd), k,v (B,S,Hkv,hd) with rope applied."""
    b, s, _ = x.shape
    hd, nq, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    # asdense: quantized (QTensor) projections take the dense-dequant path —
    # the qkv matmuls are a small slice of a step next to FFN/cache traffic
    q = x @ asdense(p["wq"], x.dtype)
    k = x @ asdense(p["wk"], x.dtype)
    v = x @ asdense(p["wv"], x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, nq, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q, cfg.norm_eps)
        k = rmsnorm(p["knorm"], k, cfg.norm_eps)
    if cfg.mrope_sections is not None:
        pos3 = mrope_pos3
        if pos3 is None:
            pos3 = jnp.broadcast_to(pos[None], (3,) + pos.shape)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def ffn_init(key, d, d_ff):
    ks = jax.random.split(key, 3)
    return {"w1": dense_init(ks[0], d, d_ff),
            "w3": dense_init(ks[1], d, d_ff),
            "w2": dense_init(ks[2], d_ff, d, scale=1.0 / math.sqrt(d_ff))}


def ffn(p, x, *, backend: str = "ref", cfg="auto"):
    """SwiGLU FFN.  The gate/up/down matmuls route through ops.matmul so
    dense-FFN models hit the coarsening tuner too: backend="ref" is a
    dtype-preserving passthrough (the CPU-training path — numerics
    unchanged); backend="pallas" dispatches the blocked coarsenable kernel
    with cfg="auto" resolved through repro.tune.  Geometries the kernel's
    default (bm=128, bn=128, bk=256) blocks can't tile fall back to the
    passthrough.

    Quantized weights (QTensor leaves, written by repro.quant
    ``quantize_params``) dispatch the dequant-fused kernel through
    ops.quant_matmul when backend="pallas" and the geometry tiles —
    packed weight panes, dequant in VMEM, its own tuner cache key — and
    otherwise take the dense-dequant fallback, which is also the parity
    oracle tests/test_quant.py checks the kernel against."""
    from repro.kernels import ops
    shp = x.shape
    xt = x.reshape(-1, shp[-1])
    t, d = xt.shape
    if isinstance(p["w1"], QTensor):
        d_ff = p["w1"].shape[-1]
        g = p["w1"].group or 256
        if backend == "pallas" and not (t % 128 or d % 256 or d_ff % 256
                                        or 256 % g):
            qmm = lambda a, qw: ops.quant_matmul(a, qw, cfg).astype(x.dtype)
            h = jax.nn.silu(qmm(xt, p["w1"])) * qmm(xt, p["w3"])
            return qmm(h, p["w2"]).reshape(shp)
    w1 = asdense(p["w1"], x.dtype)
    w3 = asdense(p["w3"], x.dtype)
    w2 = asdense(p["w2"], x.dtype)
    d_ff = w1.shape[1]
    be = backend
    if be == "pallas" and (t % 128 or d % 256 or d_ff % 256):
        be = "ref"
    mm = lambda a, b: ops.matmul(a, b, cfg, backend=be).astype(x.dtype)
    h = jax.nn.silu(mm(xt, w1)) * mm(xt, w3)
    return mm(h, w2).reshape(shp)


# --------------------------------------------------------------------------
# MoE (top-k, optional shared experts) — capacity-based EP-shardable dispatch
# --------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig):
    d, e, ff = cfg.d_model, cfg.n_experts_padded, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e),
        "w1": jax.random.normal(ks[1], (e, d, ff)) / math.sqrt(d),
        "w3": jax.random.normal(ks[2], (e, d, ff)) / math.sqrt(d),
        "w2": jax.random.normal(ks[3], (e, ff, d)) / math.sqrt(ff),
    }
    if cfg.n_shared_experts:
        sf = cfg.n_shared_experts * ff
        ks2 = jax.random.split(ks[4], 4)
        p["shared"] = ffn_init(ks2[0], d, sf)
        p["shared_gate"] = dense_init(ks2[1], d, 1)
    return p


def moe_default_capacity(t: int, e: int, k: int) -> int:
    """The moe() default per-expert capacity (factor 1.5, floor 8, clamped
    to the token count).  Shared by tune.warm and benchmarks/moe.py so
    warmed/modeled kernel specs match the geometry the layer dispatches."""
    return min(t, max(8, int(1.5 * k * t / e)))


def moe_expert_ffn(xe, w1, w3, w2, comb, cfg: ModelConfig):
    """Per-expert gate/up/down over the padded dispatch buffer, scaled by
    the combine weights: xe (E,C,d), w1/w3 (E,d,F), w2 (E,F,d), comb (E,C)
    -> (E,C,d) float32.

    cfg.moe_backend="pallas" dispatches the fused grouped-expert kernel
    (kernels/moe_ffn.py) with the EXPERT axis as the coarsening axis
    (cfg.moe_ffn_cfg resolved through repro.tune for "auto"); the einsum
    chain below is the oracle the kernel is tested against and the
    automatic fallback for degrees the expert count can't tile.

    Quantized expert weights (QTensor) dispatch the dequant-fused variant
    (ops.quant_moe_ffn: packed expert panes + per-program VMEM dequant)
    when the backend and int4 group geometry allow, else they dequantize
    densely and run the einsum oracle.
    """
    e, c, d = xe.shape
    f = w1.shape[-1]
    quant = isinstance(w1, QTensor)
    if cfg.moe_backend == "pallas":
        from repro.kernels import ops
        if quant:
            if w1.bits == 8 or (d % w1.group == 0 and f % w1.group == 0):
                rcfg = ops.resolve_cfg(cfg.moe_ffn_cfg, "moe_ffn",
                                       (e, c, d, f), dtype=xe.dtype.name,
                                       backend="pallas", wbits=w1.bits,
                                       group=w1.group)
                if e % rcfg.degree == 0:
                    return ops.quant_moe_ffn(xe, w1, w3, w2, comb, rcfg)
        else:
            rcfg = ops.resolve_cfg(cfg.moe_ffn_cfg, "moe_ffn", (e, c, d, f),
                                   dtype=xe.dtype.name, backend="pallas")
            # an explicit degree the expert axis can't tile falls back too
            if e % rcfg.degree == 0:
                return ops.moe_ffn(xe, w1.astype(xe.dtype),
                                   w3.astype(xe.dtype),
                                   w2.astype(xe.dtype), comb, rcfg)
    w1, w3, w2 = (asdense(w) for w in (w1, w3, w2))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1.astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w3.astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, w2.astype(xe.dtype))
    return (ye * comb[..., None].astype(ye.dtype)).astype(jnp.float32)


def moe(p, x, cfg: ModelConfig, *, capacity: int | None = None,
        renorm: bool = True, shard: ShardCtx = NOSHARD):
    """x: (B,S,d) -> (B,S,d), aux load-balance loss.

    Dispatch: per-expert top-capacity gather (EP-shardable on the expert
    axis; no (T,E,C) one-hot).  Overflow tokens are dropped (capacity
    factor 1.5 by default), standard for large-scale EP.

    When a mesh is attached (shard.mesh) the computation runs under
    shard_map: each (data, model) shard routes its LOCAL tokens to its LOCAL
    experts and the contributions are psum'd over the expert ('model') axis —
    gathers and the combine-scatter stay device-local, which is what keeps
    the dispatch buffers from being replicated by GSPMD.
    """
    if shard.enabled and shard.mesh is not None and shard.tp_size > 1:
        return _moe_shardmap(p, x, cfg, capacity=capacity, renorm=renorm,
                             shard=shard)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    e_pad = cfg.n_experts_padded
    xt = x.reshape(t, d)
    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    if e_pad != e:
        logits = jnp.where(jnp.arange(e_pad) < e, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, k)                          # (T,k)
    if renorm:
        w = w / (w.sum(-1, keepdims=True) + 1e-9)

    # aux loss (Switch): e * sum_e f_e * P_e  (pad experts contribute ~0)
    onehot = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)   # (T,k,E_pad)
    f = onehot.sum(axis=(0, 1)) / t                          # fraction routed
    pmean = probs.mean(axis=0)
    aux = e * jnp.sum(f * pmean)

    cap = capacity if capacity is not None else moe_default_capacity(t, e, k)
    cap = min(cap, t)
    # per-expert token weights (E_pad, T) — shardable on E (model axis)
    tokw = jnp.einsum("tke,tk->et", onehot, w)
    tokw = shard.constrain(tokw, lambda P, c: P(c.tp, None))
    topw, topi = lax.top_k(tokw, cap)                     # (E_pad,C)
    live = topw > 0.0
    xe = jnp.take(xt, topi.reshape(-1), axis=0).reshape(e_pad, cap, d)
    xe = xe * live[..., None]
    xe = shard.constrain(xe, lambda P, c: P(c.tp, None, None))
    ye = moe_expert_ffn(xe, p["w1"], p["w3"], p["w2"], topw * live, cfg)
    # combine-scatter in cfg.moe_combine_dtype (bf16 halves the accumulator
    # traffic, mirroring the EP psum wire saving on the shardmap path)
    cdt = jnp.dtype(cfg.moe_combine_dtype)
    y = jnp.zeros((t, d), dtype=cdt).at[topi.reshape(-1)].add(
        ye.reshape(-1, d).astype(cdt))
    y = y.astype(x.dtype)
    y = shard.constrain(y, lambda P, c: P(c.dp, None))

    if cfg.n_shared_experts:
        gate = jax.nn.sigmoid((xt @ p["shared_gate"].astype(xt.dtype))
                              .astype(jnp.float32)).astype(x.dtype)
        y = y + ffn(p["shared"], xt, backend=cfg.ffn_backend) * gate
    return y.reshape(b, s, d), aux


def _moe_shardmap(p, x, cfg: ModelConfig, *, capacity, renorm,
                  shard: ShardCtx):
    """Expert-parallel MoE via shard_map (see `moe` docstring)."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    e_pad = cfg.n_experts_padded
    tp, tp_axis, dp = shard.tp_size, shard.tp, shard.dp
    if e_pad % tp:
        raise ValueError(f"padded experts {e_pad} not divisible by tp={tp}")
    e_l = e_pad // tp

    xt = x.reshape(t, d)
    # quantized expert weights dequantize up front on the shard_map path:
    # QTensor leaves can't ride through the per-axis PartitionSpecs below
    # (payload and scales shard differently), so EP keeps the dense-dequant
    # fallback; the single-shard path gets the fused quantized kernel
    w1, w3, w2 = (asdense(p[k]) for k in ("w1", "w3", "w2"))
    router = p["router"]

    def body(xt_l, router_, w1_l, w3_l, w2_l):
        t_l = xt_l.shape[0]
        logits = (xt_l @ router_.astype(xt_l.dtype)).astype(jnp.float32)
        if e_pad != e:
            logits = jnp.where(jnp.arange(e_pad) < e, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, k)
        if renorm:
            w = w / (w.sum(-1, keepdims=True) + 1e-9)
        onehot = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32)
        f = onehot.sum(axis=(0, 1)) / t_l
        aux = e * jnp.sum(f * probs.mean(axis=0))
        for ax in (dp if isinstance(dp, tuple) else (dp,)):
            aux = lax.pmean(aux, ax)

        cap = capacity if capacity is not None \
            else moe_default_capacity(t_l, e, k)
        cap = min(cap, t_l)
        j = lax.axis_index(tp_axis)
        ids_local = j * e_l + jnp.arange(e_l)              # global expert ids
        sel = idx[None] == ids_local[:, None, None]        # (E_l, T_l, k)
        tokw = jnp.einsum("etk,tk->et", sel.astype(jnp.float32), w)
        topw, topi = lax.top_k(tokw, cap)                  # (E_l, C)
        live = (topw > 0.0)
        xe = jnp.take(xt_l, topi.reshape(-1), axis=0).reshape(e_l, cap, d)
        xe = xe * live[..., None]
        ye = moe_expert_ffn(xe, w1_l, w3_l, w2_l, topw * live, cfg)
        y_l = jnp.zeros((t_l, d), jnp.float32).at[topi.reshape(-1)].add(
            ye.reshape(-1, d))
        # combine experts across the EP axis; bf16 halves the wire (§Perf)
        y_l = lax.psum(y_l.astype(jnp.dtype(cfg.moe_combine_dtype)), tp_axis)
        return y_l.astype(xt_l.dtype), aux

    y, aux = jax.shard_map(
        body, mesh=shard.mesh,
        in_specs=(P(dp, None), P(), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=(P(dp, None), P()),
        check_vma=False,
    )(xt, router, w1, w3, w2)

    if cfg.n_shared_experts:
        gate = jax.nn.sigmoid((xt @ p["shared_gate"].astype(xt.dtype))
                              .astype(jnp.float32)).astype(x.dtype)
        y = y + ffn(p["shared"], xt, backend=cfg.ffn_backend) * gate
    return y.reshape(b, s, d), aux
