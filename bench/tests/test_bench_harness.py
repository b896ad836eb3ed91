"""The harness end to end on the CPU, at a tiny size: it refuses to run
without a TPU; it finds a cell and a metric added as files only; a sound
run is correct; and a run whose timed path is broken underneath, or the
fp8 control in the program's place, comes out not correct."""
from __future__ import annotations

import json
import time

import jax.numpy as jnp
import pytest

import tinycell
from bench import registry, run, serve

CELL = "tiny.chat"
PROBE = '''"""Test metric: the window's length in seconds."""
UNIT = "s"


def read(rec):
    t0, t1 = rec["window"]
    return t1 - t0
'''


def test_run_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "qwen3-0.6b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "not a TPU" in out.err


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A backlog of requests, so every slot of the engine serves some."""
    backlog = {**tinycell.TINY_TRAFFIC, "kind": "backlog", "count": 12}
    workload = {**tinycell.TINY_WORKLOAD, "engine": {
        **tinycell.TINY_WORKLOAD["engine"], "host_swap_bytes": 0}}
    return tinycell.write_cell(tmp_path_factory.mktemp("cell"), CELL,
                               "tiny-mha", traffic=backlog,
                               workload=workload)


@pytest.mark.parametrize("trace", [0, 1])
def test_files_added_alone_are_found_and_run(tmp_path, trace):
    root = tinycell.write_cell(tmp_path, CELL, "tiny-gqa")
    (root / "bench" / "metrics" / "tiny_window_s.py").write_text(PROBE)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "tiny_window_s", "unit": "s",
                            "better": "lower", "source": "host_clock",
                            "layer": "harness", "moves": "setup_s",
                            "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = registry.load_cell(CELL, root)
    assert cell.config["hidden_size"] == 64
    assert [m["name"] for m in cell.per_layer] == ["tiny_window_s"]
    out = tinycell.run_cell(root, CELL, trace=trace)
    want = {"tiny_window_s"} if trace else {"setup_s", "ttft_p75_s"}
    assert set(out["metrics"]) == want
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2] == "checks" and out["device"]["platform"] == "cpu"


def _roll(orig):
    def f(*a, **k):
        lg, c = orig(*a, **k)
        return jnp.roll(lg, 1, axis=-1), c
    return f


def _keep_cache(orig):
    def f(params, cache, *a, **k):
        lg, _ = orig(params, cache, *a, **k)
        return lg, cache
    return f


def _keep_prefill_cache(orig):
    def f(*a, **k):
        lg, _ = orig(*a, **k)
        return lg, k["cache"]
    return f


def _half_batch(orig):
    def f(*a, **k):
        lg, c = orig(*a, **k)
        half = lg.shape[0] // 2
        return lg.at[half:].set(0.0), c
    return f


FAULTS = {
    "token altered where produced": ("lm_decode_step", _roll),
    "decode step returns its state unchanged": ("lm_decode_step",
                                                _keep_cache),
    "prefill returns its state unchanged": ("lm_prefill",
                                            _keep_prefill_cache),
    "half of the batch left out": ("lm_decode_step", _half_batch),
}


def test_sound_run_is_correct(root):
    out = tinycell.run_cell(root, CELL)
    assert out["correct"], out["checks"]
    assert out["checks"]["served_gap_max"]["value"] <= 0.1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro.models import model as M
    name, wrap = FAULTS[fault]
    monkeypatch.setattr(M, name, wrap(getattr(M, name)))
    out = tinycell.run_cell(root, CELL)
    assert not out["correct"], out["checks"]


def test_fp8_control_is_not_correct(root):
    cell = registry.load_cell(CELL, root)
    seed = 2**31 + 11
    rec = serve.window(cell, seed=seed, seconds=2.0, trace=False,
                       t_start=time.perf_counter(), profile_dir=None)
    checks = serve.check(rec, seed=seed, limits=cell.workload["check"],
                         control=True)
    gap, limit = checks["served_gap_max"]
    ctl, _ = checks["control_gap_max"]
    assert gap <= limit < ctl, checks
