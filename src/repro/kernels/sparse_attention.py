"""Block-sparse flash attention: per-q-block live-KV indices, coarsened over
the LIVE block list.

The dense flash kernel (kernels/flash_attention.py) walks every kv block and
masks the dead ones — at long context with a local window almost the whole
sweep is dead work: the DMA and grid-step latency are paid before the mask
throws the tile away.  This kernel moves the sparsity from the predicate
level to the kernel-structure level: a host-side builder enumerates, per q
block, the kv blocks that contain at least one live (q, k) pair under the
pattern (causal / sliding window / LongFormer-style global stride), pads
every row to the same ``max_live`` length with a NULL sentinel — the same
static-shape trick serve/paging.py plays with its NULL page, except the
sentinel here is -1 because block 0 is a legitimately live block — and the
kernel resolves logical block ids through that index in-body, exactly like
``make_paged_kernel`` resolves pages through a block table.

Coarsening applies over the live-SLOT axis instead of the dense kv range:

  consecutive : one program owns C adjacent index slots (slot si*C+j) —
                for the window band these are usually adjacent kv blocks.
  gapped      : one program owns C slots strided max_live/C apart
                (slot j*seg+si) — the strided-LSU analog; physically both
                kinds issue C index-resolved block loads per step, the
                paged-decode story.

NULL (-1) slots are skipped under ``pl.when`` — no DMA, no compute — which
is what makes poisoned dead blocks (garbage K/V outside the live set)
invisible by construction, not by masking.  Per-element masks still apply
inside listed blocks (diagonal partials, window edges, stride columns).

The jnp ``ref_sparse_attention`` below is the dense-mask parity oracle; it
is also the training fallback for patterns the dense backward kernels can't
express (global stride — see ops.flash_attention_sparse).
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coarsening import CoarseningConfig, KIND_GAPPED
from repro.kernels.flash_attention import _dot_nt

NEG = -1e30

# the NULL slot sentinel: index rows are padded to max_live with it, and the
# kernel skips sentinel slots entirely (serve/paging.py reserves a null PAGE
# instead — its page 0 is never allocated; kv block 0 is live under every
# causal pattern, so the index uses an out-of-range id rather than a
# reserved block)
NULL_BLOCK = -1


# ---------------------------------------------------------------------------
# pattern semantics (shared by the builder, the kernel and the oracle)
# ---------------------------------------------------------------------------

def _element_mask(rows, cols, *, causal: bool, window, global_stride):
    """Live (q, k) pairs under the pattern, elementwise over broadcastable
    row/col position arrays (works for both jnp and np inputs).

    causal         : col <= row
    window         : col > row - window ... OR the col is a global column
    global_stride  : cols ≡ 0 (mod stride) are globally attended (LongFormer
                     global tokens), still subject to causality
    """
    xp = jnp if isinstance(rows, jnp.ndarray) else np
    mask = xp.ones(xp.broadcast_shapes(rows.shape, cols.shape), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        keep = cols > rows - window
        if global_stride:
            keep |= cols % global_stride == 0
        mask &= keep
    return mask


def _block_live(sq: int, sk: int, bq: int, bkv: int, *, causal: bool,
                window, global_stride) -> np.ndarray:
    """(nq, nk) bool: block (i, j) contains >= 1 live (q, k) pair.

    Computed in closed form per tile (the band boundaries have slope 1, so a
    rectangle intersects the band iff its (row - col) range does), which
    keeps the builder O(nq * nk) instead of O(sq * sk) — exactness is pinned
    against the elementwise mask by the hypothesis property tests.
    """
    nq, nk = sq // bq, sk // bkv
    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    r0, r1 = i * bq, i * bq + bq - 1          # tile row range
    c0, c1 = j * bkv, j * bkv + bkv - 1       # tile col range
    live = np.ones((nq, nk), dtype=bool)
    if causal:
        live &= c0 <= r1
    if window is not None:
        band = c1 > r0 - window
        if global_stride:
            # smallest multiple of the stride inside the tile's col range
            cg = -(-c0 // global_stride) * global_stride
            stride_live = cg <= c1
            if causal:
                # some fused row can see it (broadcasts (1,nk) -> (nq,nk))
                stride_live = stride_live & (cg <= r1)
            band |= stride_live
        live &= band
    return live


def max_live_blocks(sq: int, sk: int, bq: int, bkv: int, *,
                    causal: bool = True, window=None, global_stride=None,
                    pad_multiple: int = 8) -> int:
    """The padded per-q-block index width build_block_index will produce —
    exposed so tuner specs can carry max_live without building the index."""
    live = _block_live(sq, sk, bq, bkv, causal=causal, window=window,
                       global_stride=global_stride)
    ml = int(live.sum(axis=1).max(initial=1))
    return -(-ml // pad_multiple) * pad_multiple


@functools.lru_cache(maxsize=256)
def build_block_index(sq: int, sk: int, bq: int, bkv: int, *,
                      causal: bool = True, window: int | None = None,
                      global_stride: int | None = None,
                      pad_multiple: int = 8) -> np.ndarray:
    """Per-q-block live kv block ids, NULL-padded to a static shape.

    Returns (nq, max_live) int32: row i lists the kv block ids with at least
    one live (q, k) pair for q rows [i*bq, (i+1)*bq), ascending, padded to
    ``max_live`` with NULL_BLOCK.  max_live is rounded up to ``pad_multiple``
    so every tuner degree in {1, 2, 4, 8} divides the slot count (the
    degree-divisibility legality the flash_attention_sparse family checks).

    Cached (the index is a pure function of the geometry); treat the result
    as read-only.
    """
    if sq % bq or sk % bkv:
        raise ValueError(f"sequence not tileable: {sq}x{sk} by {bq}x{bkv}")
    live = _block_live(sq, sk, bq, bkv, causal=causal, window=window,
                       global_stride=global_stride)
    nq = live.shape[0]
    counts = live.sum(axis=1)
    max_live = -(-int(counts.max(initial=1)) // pad_multiple) * pad_multiple
    idx = np.full((nq, max_live), NULL_BLOCK, dtype=np.int32)
    for i in range(nq):
        row = np.nonzero(live[i])[0]
        idx[i, :len(row)] = row
    return idx


def ref_sparse_attention(q, k, v, *, causal: bool = True, window=None,
                         global_stride=None, scale=None):
    """Dense-mask oracle over (B,H,Sq,D) x (B,Hkv,Sk,D) — kernels/ref.py's
    ``attention`` extended with the global-stride columns.  The parity
    target for the sparse kernel and the jnp fallback for ineligible
    geometries / strided training."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = _element_mask(jnp.arange(sq)[:, None], jnp.arange(sk)[None, :],
                         causal=causal, window=window,
                         global_stride=global_stride)
    logits = jnp.where(mask, logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# the block-sparse kernel
# ---------------------------------------------------------------------------

def make_kernel(b: int, h: int, hkv: int, s: int, d: int,
                cfg: CoarseningConfig, *, bq: int = 128, bkv: int = 128,
                max_live: int, causal: bool = True,
                window: int | None = None, global_stride: int | None = None,
                scale: float | None = None, interpret: bool = True,
                sk: int | None = None,
                return_residuals: bool = False) -> Callable:
    """Block-sparse forward.  run(q (B,H,Sq,D), k, v (B,Hkv,Sk,D),
    idx (nq, max_live) int32) -> o (B,H,Sq,D) f32, or (o, m, l) with
    m, l (B,H,Sq) f32 when ``return_residuals``.

    The grid is (B, H, Sq/bq, max_live/C): each program owns one q block and
    C index SLOTS per step (consecutive slot si*C+j, gapped slot j*seg+si),
    resolves each slot to a logical kv block id through ``idx`` in-body and
    loads only those blocks — NULL slots are skipped under ``pl.when``
    (no DMA), so dead kv blocks are never read at all.
    """
    sq = s
    sk = sq if sk is None else sk
    c = cfg.degree
    if sq % bq or sk % bkv:
        raise ValueError("seq not tileable")
    if max_live % c:
        raise ValueError("live-slot list not tileable by degree")
    gapped = cfg.kind == KIND_GAPPED
    group = h // hkv
    nq, nkb = sq // bq, sk // bkv
    n_steps = max_live // c
    seg = max_live // c                # gapped slot stride
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def body(idx_ref, q_ref, k_ref, v_ref, *refs):
        if return_residuals:
            o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
        else:
            o_ref, m_ref, l_ref, acc_ref = refs
        qi, si = pl.program_id(2), pl.program_id(3)

        @pl.when(si == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols0 = jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        for j in range(c):             # unrolled: C index-resolved slots
            slot = (j * seg + si) if gapped else (si * c + j)
            lb = idx_ref[0, slot]      # logical kv block id, or NULL_BLOCK

            @pl.when(lb >= 0)          # NULL slot: no DMA, no compute
            def _slot(lb=lb):
                q = q_ref[...].reshape(bq, d)
                kk = k_ref[0, 0, pl.ds(lb, 1)].reshape(bkv, d)
                vv = v_ref[0, 0, pl.ds(lb, 1)].reshape(bkv, d)
                cols = cols0 + lb * bkv                        # (1, bkv)
                mask = _element_mask(rows, cols, causal=causal,
                                     window=window,
                                     global_stride=global_stride)
                sij = _dot_nt(q, kk) * scale
                sij = jnp.where(mask, sij, NEG)
                m_prev = m_ref[...]
                m_new = jnp.maximum(m_prev, sij.max(axis=1, keepdims=True))
                p = jnp.where(mask, jnp.exp(sij - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
                acc_ref[...] = (acc_ref[...] * alpha
                                + jnp.dot(p, vv.astype(jnp.float32),
                                          preferred_element_type=jnp.float32))
                m_ref[...] = m_new

        @pl.when(si == n_steps - 1)
        def _fin():
            l = l_ref[...]
            lg = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = (acc_ref[...] / lg).reshape(o_ref.shape)
            if return_residuals:
                mo_ref[...] = m_ref[...].reshape(mo_ref.shape)
                lo_ref[...] = l.reshape(lo_ref.shape)

    # the index row rides whole per q block; K/V ride WHOLE viewed as
    # (B, Hkv, nkb, bkv, D) so the body can resolve any listed block —
    # the make_paged_kernel idiom (its pools ride whole the same way)
    idx_spec = pl.BlockSpec((1, max_live),
                            lambda bb, hh, qi, si: (qi, 0))
    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda bb, hh, qi, si: (bb, hh, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, nkb, bkv, d),
                           lambda bb, hh, qi, si: (bb, hh // group, 0, 0, 0))
    r_spec = pl.BlockSpec((1, 1, bq, 1),
                          lambda bb, hh, qi, si: (bb, hh, qi, 0))

    out_specs = (q_spec, r_spec, r_spec) if return_residuals else q_spec
    out_shape = (
        (jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
         jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
         jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32))
        if return_residuals
        else jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32))

    call = pl.pallas_call(
        body,
        grid=(b, h, nq, n_steps),
        in_specs=[
            idx_spec,
            q_spec,
            kv_spec,
            kv_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )

    def run(q, k, v, idx):
        kv = lambda x: x.reshape(b, hkv, nkb, bkv, d)
        out = call(idx, q, kv(k), kv(v))
        if not return_residuals:
            return out
        o, m, l = out                  # already in global row order
        return o, m.reshape(b, h, sq), l.reshape(b, h, sq)

    return run
