"""Compile the smoke path's kernels for a described TPU v5e, at qwen3-0.6b
widths (H=16, Hkv=8, D=128), without a chip: the TPU compiler refuses here
what interpret mode accepts (unaligned blocks, 1-D vectors, too much VMEM).
Each test asserts the compiled program holds the Mosaic kernel.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every xdist worker imports
this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CoarseningConfig
from repro.kernels import decode_attention, flash_attention, matmul

H, HKV, D = 16, 8, 128
SLOTS, PAGE, NPP = 8, 16, 128           # 8 slots x max_len 2048, page 16
N_PAGES = SLOTS * NPP + 1
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _cfg(label):
    return CoarseningConfig.parse(label) if label != "con1" \
        else CoarseningConfig()


@pytest.mark.parametrize("spec", ["con1", "con4"])
def test_paged_decode_compiles(one_chip, spec):
    run = decode_attention.make_paged_kernel(
        SLOTS, H, HKV, N_PAGES, NPP, D, _cfg(spec), page_size=PAGE,
        interpret=False)
    pool = ((N_PAGES, HKV, PAGE, D), jnp.bfloat16)
    _compile(run, one_chip, ((SLOTS, 1, H, D), jnp.bfloat16), pool, pool,
             ((SLOTS, NPP), jnp.int32), ((SLOTS,), jnp.int32))


def test_paged_verify_compiles(one_chip):
    t = 5
    run = decode_attention.make_verify_kernel(
        SLOTS, H, HKV, t, N_PAGES, NPP, D, _cfg("con4"), page_size=PAGE,
        interpret=False)
    pool = ((N_PAGES, HKV, PAGE, D), jnp.bfloat16)
    _compile(run, one_chip, ((SLOTS, t, H, D), jnp.bfloat16), pool, pool,
             ((SLOTS, NPP), jnp.int32), ((SLOTS,), jnp.int32))


Q = ((1, H, SEQ, D), jnp.bfloat16)
KV = ((1, HKV, SEQ, D), jnp.bfloat16)
DO = ((1, H, SEQ, D), jnp.float32)
RES = ((1, H, SEQ), jnp.float32)


@pytest.mark.parametrize("spec", ["con4", "gap4"])
def test_flash_forward_compiles(one_chip, spec):
    run = flash_attention.make_kernel(1, H, HKV, SEQ, D, _cfg(spec),
                                      return_residuals=True,
                                      interpret=False)
    _compile(run, one_chip, Q, KV, KV)


def test_flash_backward_dq_compiles(one_chip):
    run = flash_attention.make_bwd_dq_kernel(1, H, HKV, SEQ, D, _cfg("con4"),
                                             interpret=False)
    _compile(run, one_chip, Q, KV, KV, DO, RES, RES, RES)


def test_flash_backward_dkv_compiles(one_chip):
    run = flash_attention.make_bwd_dkv_kernel(1, H, HKV, SEQ, D,
                                              _cfg("con4"), interpret=False)
    _compile(run, one_chip, Q, KV, KV, DO, RES, RES, RES)


def test_matmul_compiles(one_chip):
    run = matmul.make_kernel(1024, 3072, 1024, _cfg("con4"), interpret=False)
    _compile(run, one_chip, ((1024, 1024), jnp.float32),
             ((1024, 3072), jnp.float32))


# the model's named scopes, and the leaf ops a compiled decode program may
# hold outside them: copies, which the compiler makes itself (whole-stack
# copies, loop-carry and layout copies); ops it makes with no op_name (the
# weights' bf16 casts hoisted out of the loops, buffer allocations); and
# the scan machinery engine_decode and the layer scan trace themselves
# (loop counters, the per-step outputs stacked into the block's, and a
# period's slice of the stacked weights)
MODEL_SCOPES = {"embed", "attention", "recurrent", "ssm", "ffn", "moe",
                "head", "cache_io"}
COPIES = {"copy", "copy-start", "copy-done"}
COMPILER_MADE = {"convert", "custom-call"}
SCAN_MACHINERY = {"add", "lt", "dynamic_update_slice", "broadcast_in_dim",
                  "squeeze"}
NO_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
          "while", "conditional"}


def _control_lines(hlo: str):
    """The instructions of the entry computation and of the while bodies
    and conditions: what a device trace shows."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%(\S+) ", line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(2)
            comps[cur] = (bool(m.group(1)), [])
        elif line.startswith("}"):
            cur = None
        elif cur and " = " in line:
            comps[cur][1].append(line)
    control = {n for n, (entry, _) in comps.items() if entry}
    for _, body in comps.values():
        for line in body:
            control.update(m.group(1) for m in re.finditer(
                r"(?:body|condition)=%([\w.\-]+)", line))
    for n in control:
        yield from comps[n][1]


def _leaf_ops(hlo: str):
    """(opcode, op_name) of every control instruction that does work."""
    for line in _control_lines(hlo):
        m = re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([a-z][\w\-]*)\(", line)
        if m and m.group(1) not in NO_OPS:
            on = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), on.group(1) if on else ""


def _out_dims(line: str):
    """The dims of an instruction's array output, or None for a tuple."""
    m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]", line)
    return None if m is None else tuple(
        int(d) for d in m.group(1).split(",") if d)


def _engine_program(sharding, arch: str, program: str):
    """(engine, optimized HLO text) of a tiny PagedEngine's
    ``engine_decode`` (a block of 4), or a self-drafting SpecPagedEngine's
    verify pass (4 rows), compiled for the described chip."""
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import PagedEngine, SpecPagedEngine

    cfg = get_config(arch).reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    kw = dict(slots=2, num_pages=11, page_size=8, max_len=32, chunk=8,
              decode_block=4)
    vec = jnp.zeros((2,), jnp.int32)
    if program == "engine_decode":
        eng = PagedEngine(cfg, params, **kw)
        bt = eng._device_table(np.ones(2, bool))
        fn, rest = eng._decode_fn(4), (jnp.zeros((2, 1), jnp.int32), vec, bt)
    else:
        eng = SpecPagedEngine(cfg, params, spec_k=3, draft_cfg=cfg,
                              draft_params=params, **kw)
        bt = eng._device_table(np.ones(2, bool))
        fn, rest = eng._verify_fn, (jnp.zeros((2, 4), jnp.int32), vec,
                                    vec + 4, bt)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (eng.params, eng.cache) + rest)
    return eng, fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b"])
def test_decode_program_ops_carry_model_scopes(one_chip, arch):
    """Every leaf op of a tiny engine_decode program compiled for the chip
    carries a model scope in its op_name, or is one of the listed unscoped
    kinds; the scopes the device-time reduction reads all appear.
    ``cache_io`` (the per-layer slice and write-back of per-slot state)
    appears exactly when the stack has per-slot state: paged attention
    pools are read and written in place in the stack."""
    eng, hlo = _engine_program(one_chip, arch, "engine_decode")
    seen, stray = set(), []
    for op, name in _leaf_ops(hlo):
        parts = name.split("/")
        scopes = [p for p in parts if p in MODEL_SCOPES]
        if scopes:
            seen.add("/".join(p for p in parts if p in MODEL_SCOPES
                              or p in {"qkv", "kv_write", "core", "out"}))
        elif op in COPIES:
            continue
        elif not name:
            if op not in COMPILER_MADE:
                stray.append((op, name))
        elif not (parts[0] == "jit(engine_decode)"
                  and set(parts[1:-1]) <= {"while", "body", "cond",
                                           "closed_call"}
                  and parts[-1] in SCAN_MACHINERY):
            stray.append((op, name))
    assert not stray, stray
    assert {"embed", "attention/qkv", "attention/kv_write", "attention/core",
            "ffn", "head"} <= seen, seen
    per_slot_state = not all(eng._attn_kinds[0] + eng._attn_kinds[1])
    assert ("cache_io" in seen) == per_slot_state, seen


@pytest.mark.parametrize("program", ["engine_decode", "spec_verify"])
def test_paged_pools_are_indexed_in_place(one_chip, program):
    """The compiled decode and speculative verify programs never copy one
    layer's K/V pool out of the stacked pool and back: no control
    instruction outputs an array of a single layer pool's shape, and no
    dynamic-slice or dynamic-update-slice takes the stack.  (Prefill
    still slices: see models.model.lm_prefill.)"""
    eng, hlo = _engine_program(one_chip, "qwen3-0.6b", program)
    stack = eng.cache["blocks"][0]["k"].shape
    assert stack[0] >= 2, stack
    layer_pools = [line.strip() for line in _control_lines(hlo)
                   if _out_dims(line) == stack[1:]]
    assert not layer_pools, layer_pools
    dims = {m.group(1): _out_dims(line) for line in hlo.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%(\S+) = ", line))}
    sliced = [line.strip() for line in hlo.splitlines()
              if (m := re.search(r" dynamic-(?:update-)?slice\(%([\w.\-]+)",
                                 line)) and dims.get(m.group(1)) == stack]
    assert not sliced, sliced
