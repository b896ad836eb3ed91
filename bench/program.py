"""The benchmark's side of the program's interface: its model configuration
and its parameter tree, both built from a configuration file and a seed.

The weights are ``bench.weights``' draw, moved into the program's layout:
layers stacked under ``blocks[0]``, vocabulary rows padded with zeros,
RMSNorm weights stored as ``scale = w - 1`` (the program multiplies by
``1 + scale``), and each query/key head's columns reordered from rotated
halves to the adjacent pairs the program's rotary embedding rotates.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.work import Sizes


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry ``conf["arch"]`` with every size taken from the file."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(conf["arch"]), n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        head_dim=conf["head_dim"], qkv_bias=conf["qkv_bias"],
        qk_norm=conf["qk_norm"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"])


def _program_tree(key, s: Sizes, vocab_padded: int):
    perm = W.rope_pair_perm(s.head_dim)

    def heads_perm(x, n):             # (..., n*hd) -> columns reordered
        shp = x.shape
        return x.reshape(shp[:-1] + (n, s.head_dim))[..., perm].reshape(shp)

    leaf = functools.partial(W.stacked_leaf, key, s)
    attn = {"wq": heads_perm(leaf("wq"), s.heads),
            "wk": heads_perm(leaf("wk"), s.kv_heads),
            "wv": leaf("wv"), "wo": leaf("wo")}
    if s.qkv_bias:
        attn.update(bq=heads_perm(leaf("bq"), s.heads),
                    bk=heads_perm(leaf("bk"), s.kv_heads), bv=leaf("bv"))
    if s.qk_norm:
        attn.update(qnorm={"scale": leaf("q_norm")[..., perm] - 1.0},
                    knorm={"scale": leaf("k_norm")[..., perm] - 1.0})
    block = {"ln1": {"scale": leaf("ln1") - 1.0}, "attn": attn,
             "ln2": {"scale": leaf("ln2") - 1.0},
             "ffn": {"w1": leaf("w_gate"), "w3": leaf("w_up"),
                     "w2": leaf("w_down")}}
    pad = vocab_padded - s.vocab
    tree = {"embed": jnp.pad(W.global_leaf(key, s, "embed"),
                             ((0, pad), (0, 0))),
            "final_norm": {"scale": W.global_leaf(key, s, "final_norm")
                           - 1.0},
            "blocks": [block], "tail": []}
    if not s.tied:
        tree["lm_head"] = jnp.pad(W.global_leaf(key, s, "lm_head"),
                                  ((0, 0), (0, pad)))
    return tree


@functools.partial(jax.jit, static_argnames=("s", "vocab_padded"))
def _make(words, *, s: Sizes, vocab_padded: int):
    return _program_tree(W.seed_key(words), s, vocab_padded)


def program_params(seed: int, s: Sizes, cfg):
    """The program's parameters for ``seed``, made on the device in one
    jitted call (float32, as the program stores them)."""
    from repro.models import model as M
    want = M.abstract_params(cfg)
    make = functools.partial(_make, s=s, vocab_padded=cfg.vocab_padded)
    got = jax.eval_shape(make, W.seed_words(seed))
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.map(jnp.shape, want)}")
    return jax.block_until_ready(make(W.seed_words(seed)))
