"""Paged (block-table) decode attention: parity vs the contiguous oracle
through randomly permuted, fragmented block tables — across the coarsening
matrix, GQA, sliding window, int8-KV pools, and pages whose tail rows lie
past ``pos`` (must be masked, not read) — plus the decode_attention_paged
tuner family (candidate legality, page_size/kv_bits in the spec key, paged
cost direction, cfg='auto' dispatch)."""
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoarseningConfig
from repro.core.analysis import decode_attention_cost
from repro.kernels import ops
from repro.models import layers as L
from repro.quant import quantize_kv
from repro.tune import KernelSpec, enumerate_candidates, model_cost, search

tune_cache = importlib.import_module("repro.tune.cache")
tune_search = importlib.import_module("repro.tune.search")

KEY = jax.random.PRNGKey(3)
B, HKV, G, D = 2, 2, 2, 16
H = HKV * G
PS, NPP = 8, 8                      # page size, per-slot table entries
S = PS * NPP
N_PAGES = B * NPP + 3               # a few never-referenced pages
SPECS = ("none", "con2", "con4", "gap2", "gap4")


def _fragmented():
    """Pools + a randomly permuted block table; every row past each slot's
    ``pos`` (page tails AND whole never-referenced pages) is poisoned with
    huge values so any unmasked read shows up as a parity failure."""
    rng = np.random.default_rng(11)
    kp = rng.normal(size=(N_PAGES, HKV, PS, D)).astype(np.float32)
    vp = rng.normal(size=(N_PAGES, HKV, PS, D)).astype(np.float32)
    perm = rng.permutation(np.arange(1, N_PAGES))[: B * NPP].reshape(B, NPP)
    pos = np.asarray([PS * 3 + 2, S - 1], np.int32)   # mid-page + full
    for bb in range(B):
        for lp in range(NPP):
            row0 = lp * PS
            dead = max(0, min(PS, pos[bb] + 1 - row0))
            kp[perm[bb, lp], :, dead:] = 1e4
            vp[perm[bb, lp], :, dead:] = 1e4
    unref = sorted(set(range(1, N_PAGES)) - set(perm.ravel()))
    kp[unref] = 1e4
    vp[unref] = 1e4
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(perm, jnp.int32), jnp.asarray(pos))


@pytest.fixture(scope="module")
def data():
    return _fragmented()


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("window", [None, 11], ids=["causal", "window"])
def test_paged_matches_contiguous_oracle(data, spec, window):
    """Both coarsening kinds, GQA heads, fragmented table, poisoned tails:
    the paged kernel must equal the gather-to-contiguous dense oracle."""
    q, kp, vp, bt, pos = data
    cfg = CoarseningConfig.parse(spec) if spec != "none" \
        else CoarseningConfig()
    want = ops.paged_decode_attention(q, kp, vp, bt, pos, backend="ref",
                                      window=window)
    got = ops.paged_decode_attention(q, kp, vp, bt, pos, cfg,
                                     backend="pallas", window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(got)).max() < 100, "poisoned tail row leaked in"


def test_paged_oracle_equals_contiguous_reference(data):
    """The gather oracle itself must agree with the plain contiguous path
    when the table is the identity layout."""
    q, kp, vp, bt, pos = data
    k = jnp.swapaxes(kp[bt], 2, 3).reshape(B, S, HKV, D)
    v = jnp.swapaxes(vp[bt], 2, 3).reshape(B, S, HKV, D)
    want = L.decode_attention(q, k, v, pos)
    got = ops.paged_decode_attention(q, kp, vp, bt, pos, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("spec", ("con2", "gap2"))
def test_int8_kv_pool_parity(data, spec):
    q, kp, vp, bt, pos = data
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    want = ops.paged_decode_attention(q, kq, vq, bt, pos, backend="ref",
                                      k_scale=ks, v_scale=vs)
    got = ops.paged_decode_attention(q, kq, vq, bt, pos,
                                     CoarseningConfig.parse(spec),
                                     backend="pallas", k_scale=ks,
                                     v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_layers_paged_dispatch_and_fallback(data):
    """models.layers.paged_decode_attention: the pallas path matches the
    gather fallback, and a degree that can't tile npp falls back silently."""
    q, kp, vp, bt, pos = data
    want = L.paged_decode_attention(q, kp, vp, bt, pos, backend="ref")
    got = L.paged_decode_attention(q, kp, vp, bt, pos, backend="pallas",
                                   cfg="con2")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # npp=8 is not divisible by degree 16 -> dense fallback, not an error
    got = L.paged_decode_attention(q, kp, vp, bt, pos, backend="pallas",
                                   cfg="con16")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("site", ["decode", "verify"])
def test_layers_paged_attention_reads_one_layer_of_a_stack(data, site,
                                                           backend):
    """Given the layer stack and a layer index, the layer-level paged
    attention reads only that layer's pages: the result equals the same
    call on that layer's own pool, while every other layer is poisoned."""
    q, kp, vp, bt, pos = data
    poison = jnp.full_like(kp, 1e4)
    ks, vs = jnp.stack([poison, kp, poison]), jnp.stack([poison, vp, poison])
    layer = jnp.asarray(1, jnp.int32)
    if site == "decode":
        fn, args = L.paged_decode_attention, (q, bt, pos)
    else:
        t = 3
        fn = L.verify_attention
        args = (jax.random.normal(KEY, (B, t, H, D), jnp.float32), bt,
                pos - (t - 1))
    kw = dict(backend=backend, cfg="con2")
    want = fn(args[0], kp, vp, *args[1:], **kw)
    got = fn(args[0], ks, vs, *args[1:], layer=layer, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# tuner family
# ---------------------------------------------------------------------------

PAGED_SPEC = KernelSpec.make("decode_attention_paged", (8, 32, 8, 32, 128),
                             dtype="bfloat16", page_size=128, window=0)


def test_candidates_divide_the_page_table():
    cands = enumerate_candidates(PAGED_SPEC)
    assert cands
    for c in cands:
        assert 32 % c.degree == 0
        assert c.replication == 1 and c.vector_width == 1
    small = KernelSpec.make("decode_attention_paged", (2, 4, 2, 4, 32),
                            dtype="float32", page_size=64, window=0)
    assert all(c.degree <= 4 for c in enumerate_candidates(small))


def test_page_size_and_kv_bits_join_the_spec_key():
    a = KernelSpec.make("decode_attention_paged", (8, 32, 8, 32, 128),
                        dtype="bfloat16", page_size=128, window=0)
    b = KernelSpec.make("decode_attention_paged", (8, 32, 8, 32, 128),
                        dtype="bfloat16", page_size=64, window=0)
    c = KernelSpec.make("decode_attention_paged", (8, 32, 8, 32, 128),
                        dtype="int8", page_size=128, window=0, kv_bits=8)
    assert len({a.key, b.key, c.key}) == 3


def test_paged_cost_pays_the_table_lookup():
    """Paging turns every kv pane into a table-indexed fetch: the modeled
    cost must exceed the same geometry's contiguous cost (extra descriptors
    + per-page lookup latency), for both kinds."""
    b, h, hkv, d = 8, 32, 8, 128
    ps, npp = 128, 32
    for spec in ("none", "con4", "gap4"):
        cfg = CoarseningConfig.parse(spec) if spec != "none" \
            else CoarseningConfig()
        contig = decode_attention_cost(b, h, hkv, npp * ps, d, cfg,
                                       bkv=ps).modeled_s
        paged = decode_attention_cost(b, h, hkv, npp * ps, d, cfg, bkv=ps,
                                      page_size=ps).modeled_s
        assert paged > contig, spec


def test_paged_auto_dispatch(scratch_default_cache, data):
    """cfg='auto' searches the decode_attention_paged family once, persists
    the winner, and matches the explicitly-tuned kernel."""
    q, kp, vp, bt, pos = data
    before = tune_search.SEARCH_COUNT
    got = ops.paged_decode_attention(q, kp, vp, bt, pos, "auto")
    assert tune_search.SEARCH_COUNT == before + 1
    spec = KernelSpec.make("decode_attention_paged", (B, H, HKV, NPP, D),
                           dtype="float32", page_size=PS, window=0)
    best = search(spec).best
    want = ops.paged_decode_attention(q, kp, vp, bt, pos, best)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    blob = json.load(open(scratch_default_cache))
    assert blob["entries"][spec.key]["cfg"] == best.label
    assert model_cost(spec, best) <= min(
        model_cost(spec, c) for c in enumerate_candidates(spec)) * (1 + 1e-9)


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("window", [None, 11], ids=["causal", "window"])
def test_verify_matches_contiguous_oracle(data, spec, window):
    """The short-q verify kernel (T drafted rows per slot, row t at
    pos0 + t) through the same fragmented, poisoned table must equal the
    gather-to-contiguous verify oracle."""
    q, kp, vp, bt, pos = data
    t = 3
    qt = jax.random.normal(KEY, (B, t, H, D), jnp.float32)
    pos0 = pos - (t - 1)
    cfg = CoarseningConfig.parse(spec) if spec != "none" \
        else CoarseningConfig()
    want = ops.flash_attention_verify(qt, kp, vp, bt, pos0, backend="ref",
                                      window=window)
    got = ops.flash_attention_verify(qt, kp, vp, bt, pos0, cfg,
                                     backend="pallas", window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(got)).max() < 100, "poisoned tail row leaked in"
