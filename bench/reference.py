"""Plain float32 reference of the dense decoder-only transformers the
benchmark serves and trains (Qwen2/Qwen1.5 and Qwen3 blocks).

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching, nothing imported from the program.  Per layer: RMSNorm, Q/K/V
projections (+ bias when the configuration has it), per-head RMSNorm of
queries and keys (Qwen3), rotary embedding on rotated halves, causal
grouped-query attention, output projection, residual; RMSNorm, SwiGLU,
residual.  Then a final RMSNorm and the head (the embedding's transpose
when tied).  Weights come from ``bench.weights`` in its layout.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor (weights) or per-row (activations) absmax
scale before the product, the precision step below the configurations'
bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis=None):
    """Round to float8 e4m3 under an absmax scale (whole tensor, or per
    slice along ``axis``) and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, axis=-1), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotary embedding on rotated halves.  x: (T, H, D); pos: (T,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, q_block: int = 512):
    """Causal GQA attention over one sequence, queries in blocks so the
    score matrix stays small.  q: (T, H, D); k, v: (T, Hkv, D)."""
    t, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qb = min(q_block, t)
    pad = -t % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, h, d)
    kpos = jnp.arange(t)

    def block(i):
        qi = qp[i]
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = lax.map(block, jnp.arange(qp.shape[0]))
    return out.reshape(-1, h, d)[:t]


def layer(w, x, pos, s, quant=None):
    """One decoder layer on one sequence x: (T, d)."""
    t = x.shape[0]
    h = rmsnorm(x, w["ln1"], s.eps)
    q = _mm(h, w["wq"], quant)
    k = _mm(h, w["wk"], quant)
    v = _mm(h, w["wv"], quant)
    if s.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(t, s.heads, s.head_dim)
    k = k.reshape(t, s.kv_heads, s.head_dim)
    v = v.reshape(t, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = rmsnorm(q, w["q_norm"], s.eps)
        k = rmsnorm(k, w["k_norm"], s.eps)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    o = attention(q, k, v).reshape(t, s.heads * s.head_dim)
    x = x + _mm(o, w["wo"], quant)
    h = rmsnorm(x, w["ln2"], s.eps)
    y = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(y, w["w_down"], quant)


def hidden(weights, tokens, s, quant=None):
    """Final hidden states (T, d) of one token sequence."""
    x = weights["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    x, _ = lax.scan(lambda x, w: (layer(w, x, pos, s, quant), None), x,
                    weights["layers"])
    return rmsnorm(x, weights["final_norm"], s.eps)


def head(weights, s):
    return weights["embed"].T if s.tied else weights["lm_head"]


def logits(weights, h, s, quant=None):
    return _mm(h, head(weights, s), quant)


@functools.partial(jax.jit, static_argnames=("s", "rows", "control"))
def served_gaps(weights, tokens, start, served, *, s, rows: int,
                control: bool = False):
    """How far below the reference's best logit each served token lies.

    tokens: (T,) prompt + served tokens, zero-padded; row ``start + i``
    predicts ``served[i]``, for ``rows`` rows (padded rows give garbage,
    which the caller drops).  Returns the gaps (rows,); with ``control``,
    also the gaps of the tokens the fp8 control puts first."""
    idx = start + jnp.arange(rows)

    def at_rows(quant):
        return logits(weights, hidden(weights, tokens, s, quant)[idx], s,
                      quant)

    ref = at_rows(None)
    best = ref.max(-1)
    got = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return got
    first = at_rows("fp8").argmax(-1)
    return got, best - jnp.take_along_axis(ref, first[:, None], -1)[:, 0]


def loss(weights, tokens, labels, s, quant=None):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(weights, hidden(weights, tokens, s, quant), s, quant)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - gold)
