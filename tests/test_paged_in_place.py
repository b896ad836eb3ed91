"""Paged attention layers of the decode and verify steps read and write
their K/V pages in place in the stacked pool (models/model._scan_cached;
prefill still slices each layer's pool): the paged engine's greedy
tokens after a prompt and two decode blocks equal each request's solo
run on the contiguous cache (no block table), and no page the requests
never held changes, in any layer.

Cases: an attention-only stack, a hybrid stack (recurrent state sliced
per layer beside pools indexed in place), int8 K/V pools with their
scale pools, and the speculative draft scan, whose padded steps carry a
``write_mask``.  The pools start filled with random values, so an
untouched page reads back its own bits, not zeros."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import Request

KW = dict(slots=2, num_pages=14, page_size=8, max_len=40, chunk=8,
          decode_block=4)

CASES = {
    "attention": ("qwen3-0.6b", {}, (12, 12)),
    "hybrid": ("recurrentgemma-2b", {}, (12, 12)),
    "int8_kv": ("qwen3-0.6b", {"kv_quant": "int8"}, (12, 12)),
    # the short request drafts fewer tokens than the batch's longest, so
    # its padded draft steps run under write_mask=False
    "spec_draft": ("qwen3-0.6b", {}, (16, 3)),
}


@pytest.fixture(scope="module")
def models():
    from repro.configs import get_config
    from repro.models import model as M
    out = {}
    for arch in {a for a, _, _ in CASES.values()}:
        cfg = get_config(arch).reduced()
        out[arch] = (cfg, M.lm_init(jax.random.PRNGKey(0), cfg))
    return out


def _solo(cfg, params, prompt, gen):
    from repro.launch.serve import BatchedServer
    srv = BatchedServer(cfg, params, slots=1, max_len=KW["max_len"],
                        chunk=KW["chunk"], decode_block=KW["decode_block"])
    assert srv.try_admit(list(prompt), gen)
    while srv.any_active:
        srv.step()
    return srv.completed[0][:gen]


def _fill_pools(cache, attn_kinds, key):
    """``cache`` with every attention pool leaf replaced by random bits."""
    def rand(a, k):
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -127, 128, jnp.int32
                                      ).astype(jnp.int8)
        return jax.random.uniform(k, a.shape, jnp.float32, 0.5, 1.5
                                  ).astype(a.dtype)

    def fill(c, is_attn, i):
        if not is_attn:
            return c
        leaves, tree = jax.tree.flatten(c)
        keys = jax.random.split(jax.random.fold_in(key, i), len(leaves))
        return jax.tree.unflatten(tree, [rand(a, k)
                                         for a, k in zip(leaves, keys)])

    nb = len(cache["blocks"])
    return {"blocks": [fill(c, a, j) for j, (c, a) in
                       enumerate(zip(cache["blocks"], attn_kinds[0]))],
            "tail": [fill(c, a, nb + j) for j, (c, a) in
                     enumerate(zip(cache["tail"], attn_kinds[1]))]}


def _pools(cache, attn_kinds):
    """(name, array) of every attention pool leaf, page axis first after
    the layer axis of stacked leaves."""
    out = []
    for part, flags in (("blocks", attn_kinds[0]), ("tail", attn_kinds[1])):
        for j, (c, is_attn) in enumerate(zip(cache[part], flags)):
            if is_attn:
                out += [(f"{part}{j}.{k}", np.asarray(v), part == "blocks")
                        for k, v in c.items()]
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_engine_matches_contiguous_and_spares_other_pages(models,
                                                                case):
    from repro.serve import PagedEngine, SpecPagedEngine
    arch, over, gens = CASES[case]
    cfg, params = models[arch]
    cfg = dataclasses.replace(cfg, **over)
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(1, cfg.vocab, n))) for n in (11, 6)]

    if case == "spec_draft":
        eng = SpecPagedEngine(cfg, params, spec_k=3, draft_cfg=cfg,
                              draft_params=params, **KW)
    else:
        eng = PagedEngine(cfg, params, **KW)
    kinds = eng._attn_kinds
    assert kinds[0] and any(kinds[0]), "no stacked attention pool"
    key = jax.random.PRNGKey(1)
    eng.cache = _fill_pools(eng.cache, kinds, key)
    caches = {"cache": eng.cache}
    if case == "spec_draft":
        eng.draft_cache = _fill_pools(eng.draft_cache, kinds,
                                      jax.random.fold_in(key, 1))
        caches["draft_cache"] = eng.draft_cache
    before = {n: _pools(c, kinds) for n, c in caches.items()}

    # every page the pool hands out, including the speculative step's
    # worst-case growth that it truncates again within the step
    held, alloc = set(), eng.pool.alloc
    eng.pool.alloc = lambda n: held.update(pages := alloc(n)) or pages
    out = [[eng.admit(s, Request(rid=s, prompt=p, gen=g))]
           for s, (p, g) in enumerate(zip(prompts, gens))]
    for _ in range(2):
        for s, toks in eng.decode([s for s in range(2)
                                   if eng.remaining[s] > 0]).items():
            out[s] += toks

    for s, (p, g) in enumerate(zip(prompts, gens)):
        want = _solo(cfg, params, p, g)
        assert len(out[s]) >= min(g, 3), (s, out[s])
        assert out[s] == want[:len(out[s])], (case, s)

    spared = np.array(sorted(set(range(1, KW["num_pages"])) - held))
    assert spared.size, "every page was held: grow num_pages"
    for name, c in caches.items():
        after = _pools(getattr(eng, name), kinds)
        for (leaf, a0, stacked), (_, a1, _) in zip(before[name], after):
            ax = 1 if stacked else 0
            np.testing.assert_array_equal(
                np.take(a1, spared, axis=ax), np.take(a0, spared, axis=ax),
                err_msg=f"{case}: {name} {leaf} changed a page no request "
                        f"held")
            assert not np.array_equal(a1, a0), f"{case}: {leaf} unwritten"
