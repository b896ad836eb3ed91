"""Roofline extraction + analytic perf model validation.

Key documented fact: XLA cost_analysis counts while-loop bodies ONCE
(test_cost_analysis_counts_while_once proves it).  The §Roofline terms are
therefore derived from core/perfmodel.py closed forms, validated here against
cost_analysis on a fully-unrolled reduced config.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import rooflines
from repro.core.perfmodel import MeshInfo, train_step_terms, decode_step_terms
from repro.configs import get_config


# ---------------------------------------------------------------------------
# collective-bytes HLO parser
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
  %ag = f32[256,4096]{1,0} all-gather(f32[16,4096]{1,0} %x), dimensions={0}
  %ar = bf16[1024]{0} all-reduce(bf16[1024]{0} %y), to_apply=%add
  %rs = f32[16,128]{1,0} reduce-scatter(f32[256,128]{1,0} %z), dimensions={0}
  %cp = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %w)
  %noise = f32[2,2]{1,0} add(f32[2,2]{1,0} %a, f32[2,2]{1,0} %b)
"""


def test_collective_bytes_parser():
    out = rooflines.collective_bytes(SAMPLE_HLO)
    assert out["all-gather"] == 256 * 4096 * 4
    assert out["all-reduce"] == 2 * 1024 * 2          # AR counted 2x (RS+AG)
    assert out["reduce-scatter"] == 16 * 128 * 4
    assert out["collective-permute"] == 8 * 8 * 4
    assert out["count"] == 4
    assert out["total"] == sum(out[k] for k in (
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute"))


def test_collective_bytes_real_hlo():
    """Parse a real compiled psum HLO."""
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda x: lax.psum(x, "d"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    hlo = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    out = rooflines.collective_bytes(hlo)
    assert out["count"] >= 1
    assert out["total"] >= 64 * 64 * 4


# ---------------------------------------------------------------------------
# the while-loop undercount fact
# ---------------------------------------------------------------------------

def test_cost_analysis_counts_while_once():
    def f_scan(w, x):
        y, _ = lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)
        return y

    def f_unroll(w, x):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    fs = jax.jit(f_scan).lower(w, x).compile().cost_analysis()["flops"]
    fu = jax.jit(f_unroll).lower(w, x).compile().cost_analysis()["flops"]
    assert fu == pytest.approx(8 * fs, rel=0.01)


# ---------------------------------------------------------------------------
# perfmodel vs cost_analysis on an unrolled reduced config
# ---------------------------------------------------------------------------

def test_perfmodel_matmul_flops_match_hlo():
    """Dense matmul flops of a reduced qwen3 forward match XLA's count when
    the program is fully unrolled (period scan replaced by python loop)."""
    from repro.models import model as M
    from repro.models.config import ATTN_GLOBAL

    cfg = get_config("qwen3-0.6b").reduced(n_layers=2, vocab=256)
    params = M.lm_init(jax.random.PRNGKey(0), cfg)

    # unrolled forward: python loop over layers (no scan anywhere except
    # attention chunking, disabled by tiny seq < chunk)
    from repro.models import blocks as B
    import repro.models.layers as L

    def fwd(params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                               tokens.shape)
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["blocks"][0])
            x, _ = B.attn_block(p, x, cfg, kind=ATTN_GLOBAL, pos=pos)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params["embed"].T
        return (x @ head.astype(x.dtype)).astype(jnp.float32)

    b, s = 2, 64
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    p_abs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         params)
    ca = jax.jit(fwd).lower(p_abs, tok).compile().cost_analysis()
    hlo_flops = ca["flops"]

    # analytic forward matmul+attention flops (train terms / bwd_mult, tp=1)
    t = train_step_terms(cfg, seq=s, batch=b, mesh=MeshInfo(dp=1, tp=1),
                         remat="none", n_micro=1)
    fwd_flops = t.flops / 3.0            # remat none -> bwd_mult 3, fwd = 1/3
    # HLO includes softmax/norms we don't count: demand agreement within 30%
    assert hlo_flops == pytest.approx(fwd_flops, rel=0.3), \
        (hlo_flops, fwd_flops)


# ---------------------------------------------------------------------------
# perfmodel sanity across archs/cells
# ---------------------------------------------------------------------------

MESH = MeshInfo(dp=16, tp=16)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "yi-34b", "olmoe-1b-7b",
                                  "mamba2-370m", "recurrentgemma-2b"])
def test_terms_positive_and_scale(arch):
    cfg = get_config(arch)
    t = train_step_terms(cfg, seq=4096, batch=256, mesh=MESH)
    assert t.flops > 0 and t.hbm_bytes > 0 and t.coll_bytes > 0
    t2 = train_step_terms(cfg, seq=4096, batch=512, mesh=MESH)
    assert t2.flops == pytest.approx(2 * t.flops, rel=0.01)


def test_decode_terms_kv_dominated():
    cfg = get_config("yi-34b")
    t = decode_step_terms(cfg, seq=32768, batch=128, mesh=MESH)
    # decode at 32k must be memory-dominated: bytes/819GBs >> flops/197T
    assert t.hbm_bytes / 819e9 > t.flops / 197e12


def test_moe_flops_use_active_params():
    moe = get_config("olmoe-1b-7b")
    t = train_step_terms(moe, seq=4096, batch=256, mesh=MESH)
    # full-expert compute would be ~8x the top-8 active compute
    dense_equiv = train_step_terms(
        moe, seq=4096, batch=256, mesh=MESH, moe_capacity_factor=1.0)
    assert t.flops < 1.5 * dense_equiv.flops


def test_multipod_adds_pod_collectives():
    cfg = get_config("qwen3-0.6b")
    t1 = train_step_terms(cfg, seq=4096, batch=256, mesh=MeshInfo(16, 16))
    t2 = train_step_terms(cfg, seq=4096, batch=256,
                          mesh=MeshInfo(32, 16, pods=2))
    assert "pod_allreduce" in t2.notes and "pod_allreduce" not in t1.notes
