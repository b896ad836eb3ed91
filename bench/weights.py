"""Random weights of a dense decoder-only transformer, drawn from a seed.

The benchmark owns its weights: the program under test and the plain
reference both receive them from here, so the reference never reads
anything the program made.  Every leaf is drawn on its own key
(``fold_in(key, leaf id)``, then the layer index), so a layer can be drawn
alone and equals its slice of the stacked draw.

Layout (the "bench layout", Hugging Face conventions): per layer ``ln1``,
``wq``/``wk``/``wv`` (d_in, d_out), biases ``bq``/``bk``/``bv`` when the
configuration has them, ``q_norm``/``k_norm`` (head_dim) when it normalises
queries and keys, ``wo``, ``ln2``, ``w_gate``/``w_up``/``w_down``; globally
``embed`` (vocab, d), ``final_norm`` and, untied, ``lm_head`` (d, vocab).
RMSNorm weights multiply (``x * w``); rotary embedding rotates halves.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# leaf -> (id, kind); ids are part of the draw and never change
LAYER_LEAVES = {
    "ln1": (1, "norm"), "wq": (2, "proj"), "wk": (3, "proj"),
    "wv": (4, "proj"), "wo": (5, "proj"), "bq": (6, "bias"),
    "bk": (7, "bias"), "bv": (8, "bias"), "q_norm": (9, "norm"),
    "k_norm": (10, "norm"), "ln2": (11, "norm"), "w_gate": (12, "proj"),
    "w_up": (13, "proj"), "w_down": (14, "proj"),
}
GLOBAL_LEAVES = {"embed": 100, "final_norm": 101, "lm_head": 102}


def seed_key(seed_words):
    """PRNG key from a seed split into two uint32 words (lo, hi), so seeds
    past 32 bits work and one compiled program serves every seed."""
    lo, hi = seed_words[0], seed_words[1]
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def seed_words(seed: int):
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def layer_shapes(s) -> dict:
    """Shapes of one layer's leaves for sizes ``s`` (see work.Sizes)."""
    d, hd, nq, nkv, ff = s.hidden, s.head_dim, s.heads, s.kv_heads, s.inter
    shapes = {"ln1": (d,), "wq": (d, nq * hd), "wk": (d, nkv * hd),
              "wv": (d, nkv * hd), "wo": (nq * hd, d), "ln2": (d,),
              "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    if s.qkv_bias:
        shapes.update(bq=(nq * hd,), bk=(nkv * hd,), bv=(nkv * hd,))
    if s.qk_norm:
        shapes.update(q_norm=(hd,), k_norm=(hd,))
    return shapes


def _draw(key, kind: str, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.02 * z
    return z / math.sqrt(shape[0])            # proj: fan-in scaling


def layer_leaf(key, s, name: str, layer):
    """One layer's leaf ``name``; ``layer`` may be traced."""
    lid, kind = LAYER_LEAVES[name]
    k = jax.random.fold_in(jax.random.fold_in(key, lid), layer)
    return _draw(k, kind, layer_shapes(s)[name])


def stacked_leaf(key, s, name: str):
    """Leaf ``name`` of every layer, stacked on a leading layer axis."""
    return jax.vmap(lambda l: layer_leaf(key, s, name, l))(
        jnp.arange(s.layers))


def global_leaf(key, s, name: str):
    k = jax.random.fold_in(key, GLOBAL_LEAVES[name])
    if name == "embed":
        return 0.02 * jax.random.normal(k, (s.vocab, s.hidden), jnp.float32)
    if name == "final_norm":
        return _draw(k, "norm", (s.hidden,))
    if name == "lm_head":
        return _draw(k, "proj", (s.hidden, s.vocab))
    raise KeyError(name)


def global_weights(key, s) -> dict:
    names = ["embed", "final_norm"] + ([] if s.tied else ["lm_head"])
    return {n: global_leaf(key, s, n) for n in names}


def rope_pair_perm(head_dim: int):
    """Column order that turns a rotate-halves head (pairs i, i + D/2) into
    an adjacent-pairs head (pairs 2j, 2j+1): new column 2j is old column j,
    new column 2j+1 is old column j + D/2."""
    half = head_dim // 2
    return jnp.stack([jnp.arange(half), jnp.arange(half) + half],
                     axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("s",))
def _bench_tree(words, s):
    key = seed_key(words)
    return {**global_weights(key, s),
            "layers": {n: stacked_leaf(key, s, n) for n in layer_shapes(s)}}


def bench_weights(seed: int, s):
    """Every weight in the bench layout (float32, on the device)."""
    return jax.block_until_ready(_bench_tree(seed_words(seed), s))
