"""Operations and bytes a step needs, computed from the model's sizes alone.

These are the yardstick's counts: what the arithmetic of the architecture
requires, never what an implementation happens to do, so a change that
removes wasted work moves the measured time and not the count.  Matmul
FLOPs count 2 per multiply-add; attention counts QK^T and PV over the keys
each query may see (causal); norms, rotary and softmax are left out, so
every share computed from these counts is a lower bound on the truth.
"""
from __future__ import annotations

import dataclasses

BF16 = 2          # bytes: the configurations' compute dtype


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    inter: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Sizes":
        """From a configuration file (Hugging Face key names)."""
        return cls(hidden=c["hidden_size"], inter=c["intermediate_size"],
                   layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   vocab=c["vocab_size"], tied=c["tie_word_embeddings"],
                   qkv_bias=c["qkv_bias"], qk_norm=c["qk_norm"],
                   rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"])


def layer_matmul_params(s: Sizes) -> int:
    """Weights one layer multiplies by, per token."""
    d, hd = s.hidden, s.head_dim
    attn = d * s.heads * hd + 2 * d * s.kv_heads * hd + s.heads * hd * d
    return attn + 3 * d * s.inter


def head_params(s: Sizes) -> int:
    return s.hidden * s.vocab


def matmul_params(s: Sizes) -> int:
    """Every weight a token's forward pass multiplies by (layers + head)."""
    return s.layers * layer_matmul_params(s) + head_params(s)


def kv_bytes_per_token(s: Sizes, dtype_bytes: int = BF16) -> int:
    """K and V of one position, every layer."""
    return s.layers * 2 * s.kv_heads * s.head_dim * dtype_bytes


def attn_flops(s: Sizes, keys_seen: int) -> int:
    """QK^T and PV for queries that together see ``keys_seen`` keys."""
    return 4 * s.layers * s.heads * s.head_dim * keys_seen


def prefill_flops(s: Sizes, prompt_len: int) -> int:
    """Forward FLOPs a prompt needs: every prompt token through the layers,
    causal attention, and the head once for the first output token."""
    n = int(prompt_len)
    return (2 * s.layers * layer_matmul_params(s) * n
            + attn_flops(s, n * (n + 1) // 2) + 2 * head_params(s))


def decode_step_work(s: Sizes, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) one decode step needs for the running sequences,
    where ``contexts[i]`` is the number of keys sequence i attends to
    (its new token included).  Bytes: every weight read once at bf16 plus
    the live K/V rows at bf16."""
    ctx = [int(c) for c in contexts]
    flops = 2 * matmul_params(s) * len(ctx) + attn_flops(s, sum(ctx))
    nbytes = BF16 * matmul_params(s) + kv_bytes_per_token(s) * sum(ctx)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of its two roofs."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])

