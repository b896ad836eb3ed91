"""Flash attention with q-row-block coarsening (GQA / causal / local window)
plus custom-VJP backward kernels.

Forward — the q-row axis is the coarsenable "work-item" axis:

  consecutive : one program owns C adjacent q blocks -> one (C*bq, D) DMA and
                — because the fused rows are adjacent — the causal triangle
                skip still prunes ~half the kv blocks.
  gapped      : one program owns C q blocks strided S/C apart.  The fused rows
                span the whole sequence, so the causal skip degenerates to the
                worst row — the TPU analog of the paper's divergence penalty
                (work-items with different control paths fused together).

KV tiles are fetched once per fused program (paper §III.B: fewer total memory
accesses) — consecutive coarsening divides kv traffic by C up to the causal
skew.  GQA is expressed in the kv index_map (heads share kv tiles).

Backward — two passes, each coarsened on the axis it streams:

  dK/dV (``make_bwd_dkv_kernel``): the KV-BLOCK axis is the work-item axis,
      exactly as in the split-KV decode kernel.  Each program owns C kv
      blocks (consecutive = one wide (C*bkv, D) K/V/dK/dV pane per operand,
      gapped = C strided panes) and sweeps the q blocks, recomputing the
      probabilities flash-style from the saved (m, l) residuals.  The causal
      skip prunes q blocks strictly before the fused kv rows — consecutive
      keeps the pruning, gapped fuses an early kv block into every program
      and degenerates to the worst row (same divergence framing as decode).

  dQ (``make_bwd_dq_kernel``): coarsened on the q-row axis *matching the
      forward* — one program owns the same C q blocks the forward fused and
      sweeps kv blocks accumulating dQ.

Both backward passes recompute p = exp(s - m) / l from the forward residuals
instead of materializing the (S, S) probability matrix — the fused-kernel
saving the mea/XLA baseline cannot express (its per-chunk carry round-trips
HBM between scan steps).
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

NEG = -1e30


def _check_geometry(sq: int, sk: int, causal: bool, window) -> None:
    if (causal or window is not None) and sq != sk:
        raise ValueError(f"causal/window attention needs sq == sk "
                         f"(got {sq} vs {sk})")


def _q_axis_layout(b: int, h: int, sq: int, d: int, c: int, bq: int,
                   gapped: bool):
    """BlockSpecs + array views for the q-row-coarsened kernels (forward
    and dQ): the (C*bq, D) q/do/dq tiles and the (C*bq, 1) residual rows.
    Residuals travel as (..., Sq, 1) columns so every block's last two dims
    are (8,128)-aligned or full, which the Mosaic lowering requires.  The
    gapped view (C, Sq/C) is a pure reshape of row order, so residual
    arrays flatten back to (B, H, Sq) with rows in global order."""
    sg = sq // c
    if gapped:
        q_spec = pl.BlockSpec((1, 1, c, bq, d),
                              lambda bb, hh, qi, ki: (bb, hh, 0, qi, 0))
        q_view = lambda q: q.reshape(b, h, c, sg, d)
        r_spec = pl.BlockSpec((1, 1, c, bq, 1),
                              lambda bb, hh, qi, ki: (bb, hh, 0, qi, 0))
        r_view = lambda r: r.reshape(b, h, c, sg, 1)
        o_shape, r_shape = (b, h, c, sg, d), (b, h, c, sg, 1)
    else:
        q_spec = pl.BlockSpec((1, 1, c * bq, d),
                              lambda bb, hh, qi, ki: (bb, hh, qi, 0))
        q_view = lambda q: q
        r_spec = pl.BlockSpec((1, 1, c * bq, 1),
                              lambda bb, hh, qi, ki: (bb, hh, qi, 0))
        r_view = lambda r: r.reshape(b, h, sq, 1)
        o_shape, r_shape = (b, h, sq, d), (b, h, sq, 1)
    return q_spec, q_view, r_spec, r_view, o_shape, r_shape


def _fused_ids(axis_ids, p, *, c: int, blk: int, seg: int, gapped: bool):
    """Global row (or column) ids of the C fused blocks of program ``p``:
    ``axis_ids`` is a 2-D iota over the fused axis (0..C*blk-1).  A
    consecutive program owns C adjacent blocks; a gapped one owns blocks
    ``seg`` rows apart (2-D iotas only: Mosaic has no 1-D vector layout)."""
    if not gapped:
        return p * (c * blk) + axis_ids
    return (jax.lax.div(axis_ids, blk) * seg + p * blk
            + jax.lax.rem(axis_ids, blk))


def _dot_nt(a, b):
    """a (M,K) . b (N,K)^T -> (M,N) f32 without materialising b.T."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a (K,M)^T . b (K,N) -> (M,N) f32 without materialising a.T."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _q_axis_mask_live(qi, ki, *, c: int, bq: int, bkv: int, sg: int,
                      gapped: bool, causal: bool, window):
    """(mask, live) for one (q program, kv block) step of a q-row-coarsened
    kernel.  mask is the per-element causal/window mask over the fused
    (C*bq, bkv) tile; live is the whole-block skip: a consecutive program's
    fused rows are adjacent so the causal triangle prunes ~half the kv
    blocks, a gapped program's rows span the sequence so the skip
    degenerates to the worst row (the divergence penalty)."""
    rows_per_prog = c * bq
    shape = (rows_per_prog, bkv)
    rows = _fused_ids(jax.lax.broadcasted_iota(jnp.int32, shape, 0), qi,
                      c=c, blk=bq, seg=sg, gapped=gapped)
    cols = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = jnp.ones(shape, dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window

    min_row = qi * bq if gapped else qi * rows_per_prog  # smallest fused row
    live = jnp.bool_(True)
    if causal:
        live = ki * bkv <= (min_row + rows_per_prog - 1 if not gapped
                            else (c - 1) * sg + qi * bq + bq - 1)
    if window is not None:
        # skip kv blocks entirely left of every fused row's window
        live &= (ki + 1) * bkv > (min_row - (window or 0) + 1)
    return mask, live


def make_kernel(b: int, h: int, hkv: int, s: int, d: int,
                cfg: CoarseningConfig, *, bq: int = 128, bkv: int = 128,
                causal: bool = True, window: int | None = None,
                scale: float | None = None,
                interpret: bool = True, sk: int | None = None,
                return_residuals: bool = False) -> Callable:
    """Forward kernel.  run(q (B,H,Sq,D), k, v (B,Hkv,Sk,D)) -> o (B,H,Sq,D)
    f32, or (o, m, l) with m, l (B,H,Sq) f32 when ``return_residuals`` —
    the online-softmax row max and normalizer the backward kernels consume.
    ``sk`` (default Sq) supports cross-attention; causal/window need Sq==Sk.
    """
    sq = s
    sk = sq if sk is None else sk
    c = cfg.degree
    if sq % (c * bq) or sk % bkv:
        raise ValueError("seq not tileable")
    _check_geometry(sq, sk, causal, window)
    gapped = cfg.kind == KIND_GAPPED
    group = h // hkv
    nq, nk = sq // (c * bq), sk // bkv
    sg = sq // c                      # gapped slice length
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rows_per_prog = c * bq

    def body(q_ref, k_ref, v_ref, *refs):
        if return_residuals:
            o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
        else:
            o_ref, m_ref, l_ref, acc_ref = refs
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mask, live = _q_axis_mask_live(qi, ki, c=c, bq=bq, bkv=bkv, sg=sg,
                                       gapped=gapped, causal=causal,
                                       window=window)

        @pl.when(live)
        def _compute():
            q = q_ref[...].reshape(rows_per_prog, d)
            kk = k_ref[...].reshape(bkv, d)
            vv = v_ref[...].reshape(bkv, d)
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

        @pl.when(ki == nk - 1)
        def _fin():
            l = l_ref[...]
            lg = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = (acc_ref[...] / lg).reshape(o_ref.shape)
            if return_residuals:
                mo_ref[...] = m_ref[...].reshape(mo_ref.shape)
                lo_ref[...] = l.reshape(lo_ref.shape)

    kv_index = lambda bb, hh, qi, ki: (bb, hh // group, ki, 0)
    q_spec, q_view, r_spec, _, o_shape, r_shape = _q_axis_layout(
        b, h, sq, d, c, bq, gapped)

    out_specs = (q_spec, r_spec, r_spec) if return_residuals else q_spec
    out_shape = (
        (jax.ShapeDtypeStruct(o_shape, jnp.float32),
         jax.ShapeDtypeStruct(r_shape, jnp.float32),
         jax.ShapeDtypeStruct(r_shape, jnp.float32))
        if return_residuals else jax.ShapeDtypeStruct(o_shape, jnp.float32))

    call = pl.pallas_call(
        body,
        grid=(b, h, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bkv, d), kv_index),
            pl.BlockSpec((1, 1, bkv, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((rows_per_prog, 1), jnp.float32),
            pltpu.VMEM((rows_per_prog, 1), jnp.float32),
            pltpu.VMEM((rows_per_prog, d), jnp.float32),
        ],
        interpret=interpret,
    )

    def run(q, k, v):
        out = call(q_view(q), k, v)
        if not return_residuals:
            return out.reshape(b, h, sq, d)
        o, m, l = out
        # the gapped residual view (C, Sq/C) is a pure reshape of row order
        return (o.reshape(b, h, sq, d), m.reshape(b, h, sq),
                l.reshape(b, h, sq))

    return run


def make_bwd_dq_kernel(b: int, h: int, hkv: int, s: int, d: int,
                       cfg: CoarseningConfig, *, bq: int = 128,
                       bkv: int = 128, causal: bool = True,
                       window: int | None = None,
                       scale: float | None = None,
                       interpret: bool = True,
                       sk: int | None = None) -> Callable:
    """dQ pass, coarsened on the q-row axis exactly like the forward.

    run(q, k, v, do (B,H,Sq,D), m, l, delta (B,H,Sq)) -> dq (B,H,Sq,D) f32,
    where delta = rowsum(do * o) and (m, l) are the forward residuals.
    """
    sq = s
    sk = sq if sk is None else sk
    c = cfg.degree
    if sq % (c * bq) or sk % bkv:
        raise ValueError("seq not tileable")
    _check_geometry(sq, sk, causal, window)
    gapped = cfg.kind == KIND_GAPPED
    group = h // hkv
    nq, nk = sq // (c * bq), sk // bkv
    sg = sq // c
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    rows_per_prog = c * bq

    def body(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref,
             dq_ref, acc_ref):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mask, live = _q_axis_mask_live(qi, ki, c=c, bq=bq, bkv=bkv, sg=sg,
                                       gapped=gapped, causal=causal,
                                       window=window)

        @pl.when(live)
        def _compute():
            q = q_ref[...].reshape(rows_per_prog, d).astype(jnp.float32)
            kk = k_ref[...].reshape(bkv, d).astype(jnp.float32)
            vv = v_ref[...].reshape(bkv, d).astype(jnp.float32)
            do = do_ref[...].reshape(rows_per_prog, d).astype(jnp.float32)
            m = m_ref[...].reshape(rows_per_prog, 1)
            l = l_ref[...].reshape(rows_per_prog, 1)
            l = jnp.where(l == 0.0, 1.0, l)
            dl = dl_ref[...].reshape(rows_per_prog, 1)
            sij = _dot_nt(q, kk) * scale
            # flash-style recompute: p from the saved (m, l) residuals; the
            # double-where keeps masked entries at exp(NEG)~0 even when a
            # row's m is the NEG sentinel (fully-masked rows)
            p = jnp.exp(jnp.where(mask, sij - m, NEG)) / l
            dp = _dot_nt(do, vv)
            ds = p * (dp - dl)
            acc_ref[...] += jnp.dot(ds, kk,
                                    preferred_element_type=jnp.float32) * scale

        @pl.when(ki == nk - 1)
        def _fin():
            dq_ref[...] = acc_ref[...].reshape(dq_ref.shape)

    kv_index = lambda bb, hh, qi, ki: (bb, hh // group, ki, 0)
    q_spec, q_view, r_spec, r_view, o_shape, _ = _q_axis_layout(
        b, h, sq, d, c, bq, gapped)

    call = pl.pallas_call(
        body,
        grid=(b, h, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bkv, d), kv_index),
            pl.BlockSpec((1, 1, bkv, d), kv_index),
            q_spec,                                    # do
            r_spec, r_spec, r_spec,                    # m, l, delta
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(o_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows_per_prog, d), jnp.float32)],
        interpret=interpret,
    )

    def run(q, k, v, do, m, l, delta):
        dq = call(q_view(q), k, v, q_view(do), r_view(m), r_view(l),
                  r_view(delta))
        return dq.reshape(b, h, sq, d)

    return run


def make_bwd_dkv_kernel(b: int, h: int, hkv: int, s: int, d: int,
                        cfg: CoarseningConfig, *, bq: int = 128,
                        bkv: int = 128, causal: bool = True,
                        window: int | None = None,
                        scale: float | None = None,
                        interpret: bool = True,
                        sk: int | None = None) -> Callable:
    """dK/dV pass with the KV-BLOCK axis as the coarsening axis.

    Each program owns C kv blocks (consecutive = one wide (C*bkv, D) pane
    per K/V/dK/dV operand, gapped = C strided panes) and sweeps q blocks
    recomputing one wide dQ·K tile per step.  run(q, k, v, do, m, l, delta)
    -> (dk, dv) (B,Hkv,Sk,D) f32 — per-q-head partials are reduced over the
    GQA group outside the kernel.
    """
    sq = s
    sk = sq if sk is None else sk
    c = cfg.degree
    if sk % (c * bkv) or sq % bq:
        raise ValueError("seq not tileable")
    _check_geometry(sq, sk, causal, window)
    gapped = cfg.kind == KIND_GAPPED
    group = h // hkv
    nkv, nq = sk // (c * bkv), sq // bq
    skg = sk // c                      # gapped segment length (kv rows)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    cols_per_prog = c * bkv

    def body(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref,
             dk_ref, dv_ref, dk_s, dv_s):
        ki, qi = pl.program_id(2), pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            dk_s[...] = jnp.zeros_like(dk_s)
            dv_s[...] = jnp.zeros_like(dv_s)

        shape = (bq, cols_per_prog)
        cols = _fused_ids(jax.lax.broadcasted_iota(jnp.int32, shape, 1), ki,
                          c=c, blk=bkv, seg=skg, gapped=gapped)
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = jnp.ones(shape, dtype=bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window

        # causal skip: prune q blocks strictly before every fused kv row.
        # consecutive: min fused col = ki*C*bkv keeps ~half the sweep pruned;
        # gapped fuses segment-0 rows into every program -> worst-row sweep
        # (the decode kernel's divergence framing).
        live = jnp.bool_(True)
        if causal:
            min_col = ki * bkv if gapped else ki * cols_per_prog
            live = min_col <= qi * bq + bq - 1
        if window is not None:
            max_col = ((c - 1) * skg + ki * bkv + bkv - 1) if gapped \
                else ki * cols_per_prog + cols_per_prog - 1
            live &= max_col > qi * bq - window

        @pl.when(live)
        def _compute():
            q = q_ref[...].reshape(bq, d).astype(jnp.float32)
            kk = k_ref[...].reshape(cols_per_prog, d).astype(jnp.float32)
            vv = v_ref[...].reshape(cols_per_prog, d).astype(jnp.float32)
            do = do_ref[...].reshape(bq, d).astype(jnp.float32)
            m = m_ref[...].reshape(bq, 1)
            l = l_ref[...].reshape(bq, 1)
            l = jnp.where(l == 0.0, 1.0, l)
            dl = dl_ref[...].reshape(bq, 1)
            sij = _dot_nt(q, kk) * scale
            p = jnp.exp(jnp.where(mask, sij - m, NEG)) / l
            dv_s[...] += _dot_tn(p, do)
            dp = _dot_nt(do, vv)
            ds = p * (dp - dl)
            dk_s[...] += _dot_tn(ds, q) * scale

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[...] = dk_s[...].reshape(dk_ref.shape)
            dv_ref[...] = dv_s[...].reshape(dv_ref.shape)

    if gapped:
        kv_spec = pl.BlockSpec((1, 1, c, bkv, d),
                               lambda bb, hh, ki, qi: (bb, hh // group, 0, ki, 0))
        kv_view = lambda x: x.reshape(b, hkv, c, skg, d)
        dkv_spec = pl.BlockSpec((1, 1, c, bkv, d),
                                lambda bb, hh, ki, qi: (bb, hh, 0, ki, 0))
        dkv_shape = (b, h, c, skg, d)
    else:
        kv_spec = pl.BlockSpec((1, 1, c * bkv, d),
                               lambda bb, hh, ki, qi: (bb, hh // group, ki, 0))
        kv_view = lambda x: x
        dkv_spec = pl.BlockSpec((1, 1, c * bkv, d),
                                lambda bb, hh, ki, qi: (bb, hh, ki, 0))
        dkv_shape = (b, h, sk, d)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bb, hh, ki, qi: (bb, hh, qi, 0))
    r_spec = pl.BlockSpec((1, 1, bq, 1),
                          lambda bb, hh, ki, qi: (bb, hh, qi, 0))
    r_view = lambda r: r.reshape(b, h, sq, 1)

    call = pl.pallas_call(
        body,
        grid=(b, h, nkv, nq),
        in_specs=[
            q_spec,
            kv_spec,
            kv_spec,
            q_spec,                                    # do
            r_spec, r_spec, r_spec,                    # m, l, delta
        ],
        out_specs=(dkv_spec, dkv_spec),
        out_shape=(jax.ShapeDtypeStruct(dkv_shape, jnp.float32),
                   jax.ShapeDtypeStruct(dkv_shape, jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((cols_per_prog, d), jnp.float32),
            pltpu.VMEM((cols_per_prog, d), jnp.float32),
        ],
        interpret=interpret,
    )

    def run(q, k, v, do, m, l, delta):
        dkh, dvh = call(q, kv_view(k), kv_view(v), do, r_view(m), r_view(l),
                        r_view(delta))
        dkh = dkh.reshape(b, h, sk, d)
        dvh = dvh.reshape(b, h, sk, d)
        # GQA: reduce per-q-head partials onto the shared kv heads
        dk = dkh.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv = dvh.reshape(b, hkv, group, sk, d).sum(axis=2)
        return dk, dv

    return run
