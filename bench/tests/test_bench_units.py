"""CPU tests of the benchmark's yardstick: trace reduction, work counts,
traffic generation, tail arithmetic and the peaks table."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import gen, peaks, tails, trace_reduce, work

# ---------------------------------------------------------------- trace


def test_busy_union_idle_and_gaps_by_span():
    # device ops overlap (0-2, 1-3), then 5-6 and 8-12 (clipped at 10)
    ops = {"/device:TPU:0": [("a", 0.0, 2.0), ("b", 1.0, 3.0),
                             ("a", 5.0, 6.0), ("c", 8.0, 12.0)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.step", 2.5, 7.0),
             ("bench.decode", 3.0, 4.5), ("bench.idle", 7.0, 8.0)]
    out = trace_reduce.reduce(ops, spans, (0.0, 10.0))
    assert out["busy_s"] == pytest.approx(3.0 + 1.0 + 2.0)
    assert out["window_s"] == 10.0
    idle = dict(out["idle_gaps"])
    # gaps 3-5 and 6-8: 3-4.5 in decode, 4.5-5 and 6-7 in step, 7-8 idle
    assert idle == pytest.approx({"bench.decode": 1.5, "bench.step": 1.5,
                                  "bench.idle": 1.0})
    assert dict(out["device_ops"]) == pytest.approx(
        {"a": 3.0, "b": 2.0, "c": 2.0})


def test_busy_is_averaged_over_devices_and_gaps_outside_spans():
    ops = {"/device:TPU:0": [("x", 0.0, 4.0)],
           "/device:TPU:1": [("x", 0.0, 2.0)]}
    out = trace_reduce.reduce(ops, [], (0.0, 4.0))
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["idle_gaps"] == []          # device 0 never idles
    out = trace_reduce.reduce({"d": [("x", 1.0, 2.0)]}, [], (0.0, 4.0))
    assert dict(out["idle_gaps"]) == pytest.approx(
        {trace_reduce.NO_SPAN: 3.0})


def test_no_device_ops_gives_nothing_to_read():
    assert trace_reduce.reduce({}, [("bench.window", 0, 1)], (0, 1)) == {}


# ---------------------------------------------------------------- work

QWEN3 = work.Sizes(hidden=1024, inter=3072, layers=28, heads=16, kv_heads=8,
                   head_dim=128, vocab=151936, tied=True, qkv_bias=False,
                   qk_norm=True, rope_theta=1e6, eps=1e-6)
QWEN15 = work.Sizes(hidden=2560, inter=6912, layers=10, heads=20,
                    kv_heads=20, head_dim=128, vocab=151936, tied=False,
                    qkv_bias=True, qk_norm=False, rope_theta=5e6, eps=1e-6)


def test_sizes_from_the_checkout_config_files():
    repo = tinycell.REPO
    bm = json.loads((repo / "BENCHMARK.json").read_text())
    files = {c["name"]: json.loads((repo / c["file"]).read_text())
             for c in bm["configs"]}
    assert work.Sizes.from_config(files["qwen3-0.6b"]) == QWEN3
    assert work.Sizes.from_config(files["qwen1.5-4b-d10"]) == QWEN15


@pytest.mark.parametrize("s, per_layer, head, kv_token", [
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, ffn 3x1024x3072
    (QWEN3, 2097152 + 2097152 + 2097152 + 9437184, 1024 * 151936,
     28 * 2 * 8 * 128 * 2),
    # q, k, v, o 2560x2560 each, ffn 3x2560x6912
    (QWEN15, 4 * 6553600 + 53084160, 2560 * 151936, 10 * 2 * 20 * 128 * 2),
])
def test_work_counts_by_hand(s, per_layer, head, kv_token):
    assert work.layer_matmul_params(s) == per_layer
    assert work.matmul_params(s) == s.layers * per_layer + head
    assert work.kv_bytes_per_token(s) == kv_token
    p = s.layers * per_layer
    # prefill of 3 tokens: 2 FLOPs per weight per token, causal attention
    # over 1 + 2 + 3 keys (4 FLOPs per head-dim per key), the head once
    attn = 4 * s.layers * s.heads * s.head_dim * 6
    assert work.prefill_flops(s, 3) == 2 * p * 3 + attn + 2 * head
    # decode step, two sequences seeing 10 and 20 keys
    f, b = work.decode_step_work(s, [10, 20])
    assert f == 2 * (p + head) * 2 + 4 * s.layers * s.heads * s.head_dim * 30
    assert b == 2 * (p + head) + kv_token * 30


def test_least_time_takes_the_longer_roof():
    pk = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert work.least_time(1000.0, 50.0, pk) == 10.0
    assert work.least_time(100.0, 50.0, pk) == 5.0


# ---------------------------------------------------------------- peaks


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------- traffic

OPEN = {"kind": "open_loop", "rate": 4.0, "block": 8,
        "prompt": {"median": 256, "sigma": 0.8, "min": 16, "max": 2048,
                   "multiple": 16},
        "output": {"median": 192, "sigma": 0.8, "min": 16, "max": 1024}}


def test_traffic_is_determined_by_the_seed():
    big = 2**31 + 12345
    a = gen.schedule(OPEN, seconds=10, seed=big, vocab=1000)
    b = gen.schedule(OPEN, seconds=10, seed=big, vocab=1000)
    c = gen.schedule(OPEN, seconds=10, seed=big + 1, vocab=1000)
    assert [(d.at, d.prompt, d.gen) for d in a] == \
        [(d.at, d.prompt, d.gen) for d in b]
    # every seed: the same lengths and gaps in the same order, other tokens
    assert [(d.at, len(d.prompt), d.gen) for d in a] == \
        [(d.at, len(d.prompt), d.gen) for d in c]
    assert [d.prompt for d in a] != [d.prompt for d in c]
    assert len(a) == len(c) == 40                   # rate x seconds
    assert a[0].at == 0.0 and all(x.at < y.at for x, y in zip(a, a[1:]))
    assert a[-1].at < 10 and c[-1].at < 10


def test_prompt_lengths_round_up_to_16_inside_the_clip():
    for d in gen.schedule(OPEN, seconds=30, seed=3, vocab=1000):
        assert len(d.prompt) % 16 == 0 and 16 <= len(d.prompt) <= 2048
        assert 16 <= d.gen <= 1024
        assert all(1 <= t < 1000 for t in d.prompt)


def test_each_block_holds_one_value_per_stratum():
    u = gen._quantiles(32, 8)
    for k in range(4):
        blk = u[8 * k: 8 * k + 8]
        assert sorted(np.floor(blk * 8).astype(int)) == list(range(8))


def test_backlog_is_due_at_once():
    bl = {**OPEN, "kind": "backlog", "count": 12}
    ds = gen.schedule(bl, seconds=5, seed=1, vocab=100)
    assert len(ds) == 12 and all(d.at == 0.0 for d in ds)


# ---------------------------------------------------------------- tails


def rec(due, deliveries, prompt_len=32):
    return SimpleNamespace(due=due, deliveries=deliveries,
                           prompt_len=prompt_len)


def test_ttft_counts_unanswered_requests_with_their_wait():
    rs = [rec(1.0, [(1.5, 1), (2.0, 4)]),     # answered: 0.5
          rec(2.0, []),                         # never: waits 10 - 2
          rec(9.0, [(11.0, 1)]),                # answered after t1: 10 - 9
          rec(12.0, [])]                        # due after the window
    assert tails.ttft(rs, 0.0, 10.0) == [0.5, 8.0, 1.0]
    assert tails.percentile([0.5, 8.0, 1.0], 50) == 1.0
    assert tails.percentile([], 90) is None


def test_itl_shares_a_block_gap_evenly():
    rs = [rec(0.0, [(1.0, 1), (1.4, 4), (2.0, 2)])]
    assert tails.itl(rs, 0.0, 10.0) == pytest.approx(
        [0.1] * 4 + [0.3] * 2)
    assert tails.itl(rs, 1.5, 10.0) == pytest.approx([0.3] * 2)


def test_tokens_served_in_the_window():
    rs = [rec(0.0, [(1.0, 1), (2.0, 4)], prompt_len=100),
          rec(0.0, [(11.0, 1)], prompt_len=50)]
    assert tails.tokens_served(rs, 0.0, 10.0) == 100 + 1 + 4
    assert tails.percentile(list(range(11)), 90) == pytest.approx(9.0)
