"""Split-KV decode attention with kv-block coarsening (flash-decode style).

Decode attention — one query token per sequence against a (S, Hkv, D) cache —
is the serving hot path: every generated token must stream the live cache
prefix.  The coarsenable work-item axis here is the KV-BLOCK axis: each
program owns C kv blocks of ``bkv`` rows,

  consecutive : C adjacent blocks -> one (C*bkv, D) cache DMA per operand
                per program (the wide burst-coalesced LSU, paper Fig. 4 top)
  gapped      : C blocks strided S/C apart -> C strided DMAs per operand
                (the C narrow cached LSUs, paper Fig. 4 bottom)

and reduces them into a partial online-softmax state ``(m, l, acc)``.  A
cheap exact combine outside the kernel merges the per-split partials
(split-KV / flash-decode).  The grid is LENGTH-AWARE: a program whose fused
kv rows all lie beyond the slot's ``pos`` (or entirely left of its sliding
window) skips its compute, so per-token cost tracks the live prefix
``pos+1`` rather than the allocated ``max_len`` — coarsening then divides
the remaining per-block DMA issue overhead by C (paper §III.B: fewer total
memory accesses at bounded resource cost).
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coarsening import CoarseningConfig, KIND_GAPPED
from repro.kernels.flash_attention import _dot_nt

NEG = -1e30


def _combine(m, l, acc):
    """Merge per-split partial softmax states exactly.

    m, l: (B, Hkv, G, n_splits); acc: (B, Hkv, G, n_splits, D).
    """
    m_max = m.max(axis=-1)
    w = jnp.exp(m - m_max[..., None])
    w = jnp.where(m <= NEG * 0.5, 0.0, w)           # dead splits contribute 0
    l_tot = (l * w).sum(axis=-1)
    out = (acc * w[..., None]).sum(axis=-2)
    l_tot = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return out / l_tot[..., None]


def make_kernel(b: int, h: int, hkv: int, s: int, d: int,
                cfg: CoarseningConfig, *, bkv: int = 128,
                window: int | None = None, scale: float | None = None,
                kv_bits: int | None = None,
                interpret: bool = True) -> Callable:
    """Build the split-KV decode kernel.

    Returned callable: run(q (B,1,H,D), k_cache, v_cache (B,S,Hkv,D),
    pos (B,) int32) -> (B,1,H,D).

    ``kv_bits=8`` enables the int8 KV-cache mode: the caches arrive int8
    with per-(token, kv-head) f32 scales (B,S,Hkv) and the callable becomes
    run(q, k_cache, v_cache, k_scale, v_scale, pos).  The dequant
    (scale-multiply) is fused into the same VMEM pass the online softmax
    already makes, so the cache DMA — the decode hot path's dominant
    traffic — halves against bf16 while the kernel math stays f32.
    """
    c = cfg.degree
    if s % (c * bkv):
        raise ValueError(f"cache len {s} not tileable by degree*bkv={c * bkv}")
    gapped = cfg.kind == KIND_GAPPED
    g = h // hkv
    if g * hkv != h:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads {hkv}")
    n_splits = s // (c * bkv)
    sg = s // c                          # gapped segment length (rows)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if kv_bits not in (None, 8):
        raise ValueError(f"kv_bits must be None or 8, got {kv_bits}")
    quant = kv_bits == 8

    def body(pos_ref, q_ref, k_ref, v_ref, *refs):
        if quant:
            ks_ref, vs_ref, m_ref, l_ref, acc_ref = refs
        else:
            m_ref, l_ref, acc_ref = refs
        si = pl.program_id(2)
        pos = pos_ref[0, 0]

        # fused kv row extent for the length-aware skip
        if gapped:
            first_row = si * bkv
            last_row = (c - 1) * sg + si * bkv + bkv - 1
        else:
            first_row = si * c * bkv
            last_row = si * c * bkv + c * bkv - 1
        live = first_row <= pos
        if window is not None:
            live &= last_row > pos - window

        @pl.when(live)
        def _compute():
            q = q_ref[...].reshape(g, d).astype(jnp.float32)
            kk = k_ref[...].reshape(c * bkv, d)
            vv = v_ref[...].reshape(c * bkv, d)
            if quant:
                # fused dequant: one scale-multiply over the pane already in
                # VMEM (per-token x kv-head scales)
                kk = kk.astype(jnp.float32) * ks_ref[...].reshape(c * bkv, 1)
                vv = vv.astype(jnp.float32) * vs_ref[...].reshape(c * bkv, 1)
            m = jnp.full((g,), NEG, jnp.float32)
            l = jnp.zeros((g,), jnp.float32)
            acc = jnp.zeros((g, d), jnp.float32)
            cols0 = jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
            for j in range(c):          # unrolled: C fused kv blocks
                start = (j * sg + si * bkv) if gapped else (si * c * bkv
                                                            + j * bkv)
                cols = cols0 + start
                mask = cols <= pos
                if window is not None:
                    mask &= cols > pos - window
                kj = kk[j * bkv:(j + 1) * bkv].astype(jnp.float32)
                vj = vv[j * bkv:(j + 1) * bkv].astype(jnp.float32)
                sij = jnp.dot(q, kj.T,
                              preferred_element_type=jnp.float32) * scale
                sij = jnp.where(mask, sij, NEG)
                m_new = jnp.maximum(m, sij.max(axis=1))
                p = jnp.exp(sij - m_new[:, None]) * mask
                alpha = jnp.exp(m - m_new)
                l = l * alpha + p.sum(axis=1)
                acc = acc * alpha[:, None] + jnp.dot(
                    p, vj, preferred_element_type=jnp.float32)
                m = m_new
            m_ref[...] = m.reshape(m_ref.shape)
            l_ref[...] = l.reshape(l_ref.shape)
            acc_ref[...] = acc.reshape(acc_ref.shape)

        @pl.when(jnp.logical_not(live))
        def _dead():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # K/V cache views: consecutive fetches one contiguous (c*bkv, d) pane;
    # gapped views the row axis as (C, S/C) and fetches C strided panes.
    # The scale panes follow the same distribution, minus the D axis.
    if gapped:
        kv_spec = pl.BlockSpec((1, c, bkv, 1, d),
                               lambda bb, hh, si: (bb, 0, si, hh, 0))
        kv_view = lambda x: x.reshape(b, c, sg, hkv, d)
        sc_spec = pl.BlockSpec((1, c, bkv, 1),
                               lambda bb, hh, si: (bb, 0, si, hh))
        sc_view = lambda x: x.reshape(b, c, sg, hkv)
    else:
        kv_spec = pl.BlockSpec((1, c * bkv, 1, d),
                               lambda bb, hh, si: (bb, si, hh, 0))
        kv_view = lambda x: x
        sc_spec = pl.BlockSpec((1, c * bkv, 1),
                               lambda bb, hh, si: (bb, si, hh))
        sc_view = lambda x: x

    in_specs = [
        pl.BlockSpec((1, 1), lambda bb, hh, si: (bb, 0)),          # pos
        pl.BlockSpec((1, 1, g, d), lambda bb, hh, si: (bb, hh, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    if quant:
        in_specs += [sc_spec, sc_spec]

    call = pl.pallas_call(
        body,
        grid=(b, hkv, n_splits),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, g, 1), lambda bb, hh, si: (bb, hh, 0, si)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, hh, si: (bb, hh, 0, si)),
            pl.BlockSpec((1, 1, g, 1, d),
                         lambda bb, hh, si: (bb, hh, 0, si, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, n_splits), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, n_splits), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, n_splits, d), jnp.float32),
        ),
        interpret=interpret,
    )

    if quant:
        def run(q, k_cache, v_cache, k_scale, v_scale, pos):
            qv = q.reshape(b, hkv, g, d)
            pos2 = pos.reshape(b, 1).astype(jnp.int32)
            m, l, acc = call(pos2, qv, kv_view(k_cache), kv_view(v_cache),
                             sc_view(k_scale), sc_view(v_scale))
            out = _combine(m, l, acc)                 # (B, Hkv, G, D)
            return out.reshape(b, 1, h, d).astype(q.dtype)
    else:
        def run(q, k_cache, v_cache, pos):
            qv = q.reshape(b, hkv, g, d)
            pos2 = pos.reshape(b, 1).astype(jnp.int32)
            m, l, acc = call(pos2, qv, kv_view(k_cache), kv_view(v_cache))
            out = _combine(m, l, acc)                 # (B, Hkv, G, D)
            return out.reshape(b, 1, h, d).astype(q.dtype)

    return run


def _combine_splits(m, l, acc):
    """`_combine` for the paged kernels' split-major layout: m, l
    (B, Hkv, n_splits, R, 1), acc (B, Hkv, n_splits, R, D) -> (B, Hkv, R, D)."""
    m_max = m.max(axis=2, keepdims=True)
    w = jnp.exp(m - m_max)
    w = jnp.where(m <= NEG * 0.5, 0.0, w)           # dead splits contribute 0
    l_tot = (l * w).sum(axis=2)
    out = (acc * w).sum(axis=2)
    l_tot = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return out / l_tot


def _paged_call(b: int, h: int, hkv: int, t: int, n_pages: int, npp: int,
                d: int, cfg: CoarseningConfig, *, page_size: int,
                window: int | None, scale: float | None,
                kv_bits: int | None, interpret: bool) -> Callable:
    """The block-table split-KV kernel shared by paged decode (t=1) and
    batched verify (t drafted rows per slot).

    Pools arrive as (P, Hkv, page_size, D) and stay in HBM
    (``memory_space=ANY``): each program resolves its C logical pages
    through the block table (scalar-prefetched into SMEM together with the
    slot positions) and DMAs exactly those C per-head page tiles into a
    VMEM scratch — the paper's coarsened LSU, C table-resolved page
    fetches per program.  VMEM use is therefore C pages per operand,
    independent of the pool size.

    call(pos0 (B,), table (B*npp,), q (B,Hkv,T*G,D), k_pool, v_pool
    [, k_scale, v_scale (P,Hkv,ps)]) -> split partials (m, l, acc) in the
    layout `_combine_splits` reads; q row ``ti*G + gi`` sits at cache
    position ``pos0 + ti``.
    """
    c = cfg.degree
    ps = page_size
    if npp % c:
        raise ValueError(f"slot pages {npp} not tileable by degree {c}")
    gapped = cfg.kind == KIND_GAPPED
    g = h // hkv
    if g * hkv != h:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads {hkv}")
    if kv_bits not in (None, 8):
        raise ValueError(f"kv_bits must be None or 8, got {kv_bits}")
    quant = kv_bits == 8
    n_splits = npp // c
    seg = npp // c                       # gapped logical-page stride
    rows = t * g                         # fused q rows per program
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def logical_page(si, j):
        return (j * seg + si) if gapped else (si * c + j)

    def body(pos_ref, bt_ref, q_ref, k_hbm, v_hbm, *refs):
        if quant:
            ks_hbm, vs_hbm, m_ref, l_ref, acc_ref, k_buf, v_buf, ks_buf, \
                vs_buf, sem = refs
        else:
            m_ref, l_ref, acc_ref, k_buf, v_buf, sem = refs
        bb, hh, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        pos0 = pos_ref[bb]

        # fused logical-row extent for the length-aware skip (page indices
        # are logical, so the extent math matches the contiguous kernel's);
        # the deepest row (pos0 + t - 1) reaches furthest right, the
        # shallowest (pos0) bounds the window skip on the left
        if gapped:
            first_row = si * ps
            last_row = ((c - 1) * seg + si) * ps + ps - 1
        else:
            first_row = si * c * ps
            last_row = (si * c + c - 1) * ps + ps - 1
        live = first_row <= pos0 + (t - 1)
        if window is not None:
            live &= last_row > pos0 - window

        @pl.when(live)
        def _compute():
            # C table-resolved page fetches per operand, all in flight at once
            pairs = [(k_hbm, k_buf), (v_hbm, v_buf)]
            if quant:
                pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
            copies = []
            for j in range(c):
                pp = bt_ref[bb * npp + logical_page(si, j)]
                for n, (src, dst) in enumerate(pairs):
                    # a K/V page tile is (ps, D); a scale page is (1, ps)
                    at = (pp, hh) if n < 2 else (pp, pl.ds(hh, 1))
                    cp = pltpu.make_async_copy(src.at[at], dst.at[j],
                                               sem.at[n, j])
                    cp.start()
                    copies.append(cp)
            for cp in copies:
                cp.wait()

            q = q_ref[...].reshape(rows, d).astype(jnp.float32)
            m = jnp.full((rows, 1), NEG, jnp.float32)
            l = jnp.zeros((rows, 1), jnp.float32)
            acc = jnp.zeros((rows, d), jnp.float32)
            ri = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0)
            tpos = pos0 + (jax.lax.div(ri, g) if t > 1 else 0)
            cols0 = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
            for j in range(c):       # unrolled: the C fetched pages
                kj = k_buf[j].astype(jnp.float32)
                vj = v_buf[j].astype(jnp.float32)
                cols = cols0 + logical_page(si, j) * ps
                mask = cols <= tpos
                if window is not None:
                    mask &= cols > tpos - window
                sij = _dot_nt(q, kj) * scale
                if quant:
                    # fused dequant: per-(row, kv-head) scales ride the lane
                    # axis of the scores (K) and of the probabilities (V)
                    sij = sij * ks_buf[j]
                sij = jnp.where(mask, sij, NEG)
                m_new = jnp.maximum(m, sij.max(axis=1, keepdims=True))
                p = jnp.where(mask, jnp.exp(sij - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + p.sum(axis=1, keepdims=True)
                pv = p * vs_buf[j] if quant else p
                acc = acc * alpha + jnp.dot(
                    pv, vj, preferred_element_type=jnp.float32)
                m = m_new
            m_ref[...] = m.reshape(m_ref.shape)
            l_ref[...] = l.reshape(l_ref.shape)
            acc_ref[...] = acc.reshape(acc_ref.shape)

        @pl.when(jnp.logical_not(live))
        def _dead():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    n_ops = 4 if quant else 2
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    split = lambda bb, hh, si, *_: (bb, hh, si, 0, 0)
    out_shape = (
        jax.ShapeDtypeStruct((b, hkv, n_splits, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, n_splits, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, n_splits, rows, d), jnp.float32),
    )

    def call(pos0, block_table, q, *pools):
        scratch = [pltpu.VMEM((c, ps, d), pools[0].dtype)] * 2
        if quant:
            scratch += [pltpu.VMEM((c, 1, ps), jnp.float32)] * 2
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                   # pos0, block table
            grid=(b, hkv, n_splits),
            in_specs=[pl.BlockSpec((1, 1, rows, d),
                                   lambda bb, hh, si, *_: (bb, hh, 0, 0))]
                     + [hbm] * n_ops,
            out_specs=(pl.BlockSpec((1, 1, 1, rows, 1), split),
                       pl.BlockSpec((1, 1, 1, rows, 1), split),
                       pl.BlockSpec((1, 1, 1, rows, d), split)),
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((n_ops, c))],
        )
        return pl.pallas_call(body, grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret)(
            pos0.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1),
            q, *pools)

    return call


def make_paged_kernel(b: int, h: int, hkv: int, n_pages: int, npp: int,
                      d: int, cfg: CoarseningConfig, *, page_size: int = 64,
                      window: int | None = None, scale: float | None = None,
                      kv_bits: int | None = None,
                      interpret: bool = True) -> Callable:
    """Split-KV decode attention through a per-slot BLOCK TABLE.

    The caches arrive as a global page pool shared by every slot —
    k/v: (P, Hkv, page_size, D) — and each slot's logical cache row ``r``
    of kv head ``hh`` lives at ``pool[block_table[slot, r // page_size],
    hh, r % page_size]``.  The kv block IS the page (bkv == page_size), so
    the coarsening axis is the LOGICAL-PAGE axis of the slot: each program
    owns C logical pages,

      consecutive : C adjacent logical pages
      gapped      : C logical pages strided npp/C apart

    and in BOTH cases the physical fetch is C table-resolved page DMAs —
    paging is the paper's *gapped* access pattern with the fixed stride
    replaced by the block-table indirection (C narrow cached LSUs,
    Fig. 4 bottom); coarsening amortizes the per-page issue + table-lookup
    overhead exactly as it amortizes the strided DMA issue overhead.

    Logical pages past a slot's allocation sit at NULL_PAGE in the table;
    their rows are beyond ``pos`` and the causal mask (which also covers
    partially-filled tail pages) zeroes them out of the softmax.

    Returned callable:
      run(q (B,1,H,D), k_pool, v_pool (P,Hkv,ps,D), block_table (B,npp)
          int32, pos (B,) int32) -> (B,1,H,D)
    ``kv_bits=8``: pools are int8 with (P,Hkv,ps) f32 scale pools and the
    callable takes (q, k_pool, v_pool, k_scale, v_scale, block_table, pos);
    the dequant is fused into the same VMEM pass.
    """
    call = _paged_call(b, h, hkv, 1, n_pages, npp, d, cfg,
                       page_size=page_size, window=window, scale=scale,
                       kv_bits=kv_bits, interpret=interpret)
    g = h // hkv

    def run(q, *args):
        *pools, block_table, pos = args
        out = _combine_splits(*call(pos, block_table,
                                    q.reshape(b, hkv, g, d), *pools))
        return out.reshape(b, 1, h, d).astype(q.dtype)

    return run


def make_verify_kernel(b: int, h: int, hkv: int, t: int, n_pages: int,
                       npp: int, d: int, cfg: CoarseningConfig, *,
                       page_size: int = 64, window: int | None = None,
                       scale: float | None = None,
                       kv_bits: int | None = None,
                       interpret: bool = True) -> Callable:
    """Batched-verify attention through a per-slot block table (short-q
    flash: the speculative-decode geometry).

    This is `make_paged_kernel` generalized from one query row to T drafted
    rows per slot: the coarsening axis is still the slot's LOGICAL-PAGE
    axis (each program owns C table-resolved page fetches), but every
    fused page is now scored against a (T*G, D) q pane — row t of slot b
    sits at cache position ``pos0[b] + t`` and carries its own
    causal/window mask.  That changes the economics the tuner sees: decode
    (t=1) amortizes the per-page issue + table-lookup latency over G query
    rows, verify amortizes it over T*G rows, so the memory/compute
    crossover — and the winning degree — moves (the
    ``flash_attention_verify`` tuner family).

    Returned callable:
      run(q (B,T,H,D), k_pool, v_pool (P,Hkv,ps,D), block_table (B,npp)
          int32, pos0 (B,) int32) -> (B,T,H,D)
    ``kv_bits=8``: int8 pools + (P,Hkv,ps) f32 scale pools, callable takes
    (q, k_pool, v_pool, k_scale, v_scale, block_table, pos0).
    """
    call = _paged_call(b, h, hkv, t, n_pages, npp, d, cfg,
                       page_size=page_size, window=window, scale=scale,
                       kv_bits=kv_bits, interpret=interpret)
    g = h // hkv

    def run(q, *args):
        *pools, block_table, pos0 = args
        # (B,T,H,D) -> (B,Hkv,T*G,D), rows (ti, gi) flattened t-major
        qv = q.reshape(b, t, hkv, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(b, hkv, t * g, d)
        out = _combine_splits(*call(pos0, block_table, qv, *pools))
        return out.reshape(b, hkv, t, g, d).transpose(0, 2, 1, 3, 4) \
                  .reshape(b, t, h, d).astype(q.dtype)

    return run
