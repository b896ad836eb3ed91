"""Pure-jnp oracles for every Pallas kernel family (no pallas, no tiling).

Each oracle computes the kernel semantics in one untiled shot; tests assert
that every (kind, degree, replication, vector_width) Pallas variant matches
its oracle, which is exactly the paper's correctness invariant: coarsening
redistributes work but must not change results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# --- ew_stream --------------------------------------------------------------

def ew_stream(inputs, *, ai: int, variant: str = "base") -> jax.Array:
    """Oracle for kernels.ew_stream: same math, whole array, no tiling."""
    from repro.kernels.ew_stream import _variant_compute

    n = inputs[0].shape[0]
    n_arith = ai * (len(inputs) + 1)
    regs = [x.reshape(1, n) for x in inputs]
    gids = jnp.arange(n, dtype=jnp.int32).reshape(1, n)
    return _variant_compute(variant, regs, gids, n_arith).reshape(n)


# --- gather_stream ----------------------------------------------------------

def gather_stream(tables, idx, *, ai: int) -> jax.Array:
    """Oracle for the indirect-indexed kernel: out[i] = chain(t[idx[i]]...)."""
    from repro.kernels.ew_stream import _arith_chain

    n = idx.shape[0]
    regs = [t[idx] for t in tables]
    n_arith = ai * (len(tables) + 1)
    return _arith_chain(regs, n_arith)


# --- matmul -----------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


# --- stencil (5-point, Hotspot analog) --------------------------------------

def stencil5(x: jax.Array, coef: tuple = (0.5, 0.125, 0.125, 0.125, 0.125)) -> jax.Array:
    c0, cn, cs, cw, ce = coef
    xp = jnp.pad(x, 1, mode="edge")
    return (c0 * x + cn * xp[:-2, 1:-1] + cs * xp[2:, 1:-1]
            + cw * xp[1:-1, :-2] + ce * xp[1:-1, 2:])


# --- chunked row scan (Pathfinder DP analog) --------------------------------

def dp_scan(cost: jax.Array) -> jax.Array:
    """Pathfinder dynamic programming: row t distance =
    cost[t] + min(shift-left, center, shift-right) of row t-1."""
    def step(prev, row):
        left = jnp.concatenate([prev[:1], prev[:-1]])
        right = jnp.concatenate([prev[1:], prev[-1:]])
        cur = row + jnp.minimum(prev, jnp.minimum(left, right))
        return cur, cur
    init = cost[0]
    _, rows = jax.lax.scan(step, init, cost[1:])
    return jnp.concatenate([init[None], rows], axis=0)


# --- flash attention ---------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              window: int | None = None, scale: float | None = None) -> jax.Array:
    """(B,H,Sq,D) x (B,Hkv,Sk,D) GQA attention oracle (Sk may differ from Sq
    for the non-causal cross-attention case)."""
    b, h, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


# --- decode attention ---------------------------------------------------------

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int | None = None,
                     scale: float | None = None) -> jax.Array:
    """Dense full-length decode-attention oracle (same math as the XLA model
    path in models/layers.py).  q: (B,1,H,D); caches: (B,S,Hkv,D);
    pos: (B,) int32."""
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg,
                        k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s, dtype=jnp.int32)
    mask = kpos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] > pos[:, None] - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


# --- paged pools -------------------------------------------------------------

def paged_view(pool: jax.Array, block_table: jax.Array,
               layer: jax.Array | None = None) -> jax.Array:
    """Gather a page pool through a block table into the contiguous per-slot
    view: pool (P, Hkv, page_size, ...) with block_table (B, npp) ->
    (B, npp * page_size, Hkv, ...).  Serves K/V pools and their (P, Hkv,
    page_size) scale pools alike.  With ``layer`` the pool is the layer
    stack (L, P, Hkv, page_size, ...) and the view is gathered straight
    out of layer ``layer``'s pages."""
    bt = block_table.astype(jnp.int32)
    g = jnp.swapaxes(pool[bt] if layer is None else pool[layer, bt], 2, 3)
    b, npp, ps = g.shape[:3]
    return g.reshape((b, npp * ps) + g.shape[3:])


# --- verify attention (speculative decode: short q vs a long cache) ----------

def verify_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos0: jax.Array, *, window: int | None = None,
                     scale: float | None = None) -> jax.Array:
    """Batched-verify oracle: T drafted rows against the full cache.

    q: (B,T,H,D); caches: (B,S,Hkv,D); pos0: (B,) int32 — row t of slot b
    sits at cache position ``pos0[b] + t`` and attends to every cache row at
    or before it.  T==1 is exactly `decode_attention`; the math mirrors it
    row for row (same einsum contraction, f32 accumulation, dense softmax)
    so a verify pass over rows the decode path would have produced one at a
    time is bitwise-identical to it on the ref backend."""
    b, t, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, t, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bthgd,bshd->bthgs", qg,
                        k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s, dtype=jnp.int32)
    rows = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]   # (B,T)
    mask = kpos[None, None, :] <= rows[:, :, None]                   # (B,T,S)
    if window is not None:
        mask = mask & (kpos[None, None, :] > rows[:, :, None] - window)
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bthgs,bshd->bthgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, t, h, d).astype(q.dtype)


# --- grouped-expert MoE FFN ---------------------------------------------------

def moe_ffn(xe: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array,
            wts: jax.Array) -> jax.Array:
    """Oracle for the grouped-expert fused FFN: per-expert
    ``(silu(xe@w1) * (xe@w3)) @ w2`` over the padded dispatch buffer,
    scaled by the per-token combine weights.

    xe: (E,C,d); w1,w3: (E,d,F); w2: (E,F,d); wts: (E,C) -> (E,C,d) f32.
    """
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1,
                               preferred_element_type=jnp.float32))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w3,
                       preferred_element_type=jnp.float32)
    ye = jnp.einsum("ecf,efd->ecd", h.astype(xe.dtype), w2,
                    preferred_element_type=jnp.float32)
    return ye * wts[..., None].astype(jnp.float32)


# --- Mamba-2 SSD --------------------------------------------------------------

def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
        chunk: int = 64) -> jax.Array:
    """Naive (quadratic-in-S, exact) SSD oracle.

    x:(b,s,h,p) dt:(b,s,h) A:(h,) B:(b,s,g,n) C:(b,s,g,n); g divides h.
    y[t] = sum_{u<=t} C[t]·B[u] * exp(sum_{u<v<=t} dA[v]) * dt[u] * x[u]
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)          # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2)
    dA = dt * A[None, None, :]               # (b,s,h) log-decay per step
    cum = jnp.cumsum(dA, axis=1)             # (b,s,h)
    # L[t,u] = exp(cum[t]-cum[u]) for u<=t else 0
    diff = cum[:, :, None, :] - cum[:, None, :, :]      # (b,t,u,h)
    tids = jnp.arange(s)
    causal = (tids[None, :, None, None] >= tids[None, None, :, None])
    # double-where: clamp the non-causal exponent BEFORE exp so its (masked)
    # gradient can't produce inf * 0 = nan
    diff = jnp.where(causal, diff, 0.0)
    L = jnp.where(causal, jnp.exp(diff), 0.0)
    CB = jnp.einsum("bthn,buhn->btuh", Ch, Bh)          # (b,t,u,h)
    W = CB * L * dt[:, None, :, :]                      # weight for x[u]
    return jnp.einsum("btuh,buhp->bthp", W, x)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int = 64,
                state0: jax.Array | None = None,
                return_state: bool = False):
    """Linear-time chunked SSD (the model/XLA path; same math as `ssd`).

    Layouts as `ssd`: x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n).
    lax.scan over chunks carrying the (h,n,p) state — O(S*c) not O(S^2).
    state0: optional (b,h,n,p) initial state (chunked prefill continuation);
    return_state=True additionally returns the final state.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)

    def resh(t):
        return t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    xs, dts = resh(x), resh(dt)
    Bh, Ch = resh(B), resh(C)
    tri = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))

    def step(state, inp):
        xc, dtc, bc, cc = inp                      # (b,c,h,p) (b,c,h) (b,c,g,n)
        dA = dtc * A[None, None, :]                # (b,c,h)
        cum = jnp.cumsum(dA, axis=1)
        diff = cum[:, :, None, :] - cum[:, None, :, :]      # (b,t,u,h)
        diff = jnp.where(tri[None, :, :, None], diff, 0.0)
        L = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
        bh = jnp.repeat(bc, rep, axis=2)           # (b,c,h,n)
        ch = jnp.repeat(cc, rep, axis=2)
        cb = jnp.einsum("bthn,buhn->btuh", ch, bh)
        w = cb * L * dtc[:, None, :, :]
        y = jnp.einsum("btuh,buhp->bthp", w, xc)
        y = y + jnp.einsum("bthn,bhnp->bthp", ch * jnp.exp(cum)[..., None],
                           state)
        total = cum[:, -1]                         # (b,h)
        w_in = dtc * jnp.exp(total[:, None] - cum) # (b,c,h)
        upd = jnp.einsum("bthn,bthp->bhnp", bh * w_in[..., None], xc)
        state = jnp.exp(total)[..., None, None] * state + upd
        return state, y

    if state0 is None:
        state0 = jnp.zeros((b, h, n, p), x.dtype)
    state, ys = jax.lax.scan(step, state0.astype(x.dtype), (xs, dts, Bh, Ch))
    y = ys.swapaxes(0, 1).reshape(b, s, h, p)
    return (y, state) if return_state else y


# --- RG-LRU (RecurrentGemma) --------------------------------------------------

RGLRU_C = 8.0


def rglru(x: jax.Array, r: jax.Array, i: jax.Array,
          a_param: jax.Array) -> jax.Array:
    """RG-LRU oracle: h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * (i_t * x_t).

    x,r,i: (b,s,d) (r,i are pre-sigmoid gates), a_param: (d,) pre-softplus.
    a_t = exp(-c * softplus(a_param) * sigmoid(r_t)).
    """
    hs, _ = rglru_with_state(x, r, i, a_param, None)
    return hs


def rglru_with_state(x: jax.Array, r: jax.Array, i: jax.Array,
                     a_param: jax.Array, h0: jax.Array | None):
    """`rglru` with an explicit initial state — the chunked-prefill form.

    h0: (b,d) f32 hidden state (None -> zeros).  Returns (hs, h_final) so a
    later chunk (or the per-token decode step) can continue the recurrence.
    """
    rg = jax.nn.sigmoid(r)
    ig = jax.nn.sigmoid(i)
    log_a = -RGLRU_C * jax.nn.softplus(a_param)[None, None, :] * rg  # (b,s,d)
    a = jnp.exp(log_a)
    gated = ig * x
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))

    def step(h, inp):
        a_t, gx_t, m_t = inp
        h = a_t * h + m_t * gx_t
        return h, h
    b, s, d = x.shape
    init = jnp.zeros((b, d), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    xs = (a.swapaxes(0, 1), gated.swapaxes(0, 1), mult.swapaxes(0, 1))
    h_final, hs = jax.lax.scan(step, init, xs)
    return hs.swapaxes(0, 1), h_final
