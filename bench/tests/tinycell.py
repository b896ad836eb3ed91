"""Fixtures for the benchmark's CPU tests: a tiny cell, added as files only
in a temporary tree, which the harness runs on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CONFIGS = {
    # GQA, per-head query/key norm, tied head (the Qwen3 block)
    "tiny-gqa": {"arch": "qwen3-0.6b", "hidden_size": 64,
                 "intermediate_size": 128, "num_hidden_layers": 2,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 16, "vocab_size": 512, "rope_theta": 1e6,
                 "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
                 "qkv_bias": False, "qk_norm": True},
    # MHA, Q/K/V bias, untied head (the Qwen1.5 block)
    "tiny-mha": {"arch": "qwen1.5-4b", "hidden_size": 64,
                 "intermediate_size": 160, "num_hidden_layers": 2,
                 "num_attention_heads": 4, "num_key_value_heads": 4,
                 "head_dim": 16, "vocab_size": 512, "rope_theta": 5e6,
                 "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
                 "qkv_bias": True, "qk_norm": False},
}

TINY_TRAFFIC = {
    "kind": "open_loop", "rate": 6.0, "block": 4,
    "prompt": {"median": 24, "sigma": 0.5, "min": 16, "max": 64,
               "multiple": 16},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
}

TINY_WORKLOAD = {
    "engine": {"slots": 4, "num_pages": 24, "page_size": 16, "max_len": 80,
               "chunk": 16, "decode_block": 4},
    "check": {"served_gap_max": 0.1, "sample_tokens": 16},
}


def write_cell(root: Path, cell: str, config: str, *, traffic=None,
               workload=None, metrics=("setup_s", "ttft_p75_s"),
               family=None) -> Path:
    """Add one cell to a copy of the checkout's BENCHMARK.json under
    ``root``, as new files only.  ``metrics`` names the end-to-end
    metrics the cell reports; ``family``, a pair (name, source), adds the
    family module ``bench/families/<name>.py`` and names it in the
    configuration file."""
    root = Path(root)
    for sub in ("configs", "traffic", "workloads", "metrics", "families"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = dict(TINY_CONFIGS[config])
    if family is not None:
        name, source = family
        (root / "bench" / "families" / f"{name}.py").write_text(source)
        conf["family"] = name
    (root / "bench" / "configs" / f"{config}.json").write_text(
        json.dumps(conf))
    (root / "bench" / "traffic" / f"{cell}.json").write_text(
        json.dumps(traffic or TINY_TRAFFIC))
    (root / "bench" / "workloads" / f"{cell}.json").write_text(
        json.dumps(workload or TINY_WORKLOAD))
    bm["configs"].append({"name": config, "source": "test",
                          "file": f"bench/configs/{config}.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": cell, "config": config, "traffic": cell,
                            "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] in metrics and "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def run_cell(root: Path, cell: str, *, seed: int = 2**31 + 7,
             seconds: float = 2.0, trace: int = 0) -> dict:
    """One harness run on the CPU (no chip look, no compile cache)."""
    import time
    from bench import run
    return run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(trace)], root=root,
                   require_tpu=False, compile_cache=False,
                   t_start=time.perf_counter())


@pytest.fixture
def tiny_root(tmp_path):
    return write_cell(tmp_path, "tiny.chat", "tiny-mha")
