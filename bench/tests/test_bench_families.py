"""The harness reaches the model only through a configuration's family
module: the ``dense`` family gives exactly what the harness's own modules
give; a configuration without ``"family"`` is dense and an unknown family
fails by its path; and a family added as one file in a temporary tree runs
end to end on the CPU, its own reference deciding ``correct``."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

import tinycell
from bench import program, reference, registry, weights, work
from bench.work import Sizes

CELL = "tiny.chat"
SEED = 2**33 + 21
DENSE = tinycell.REPO / "bench" / "families" / "dense.py"

# appended to a copy of the dense family: its reference leaves out the
# final RMSNorm, so the served tokens lie far below that reference's best
NO_FINAL_NORM = '''

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _hidden(w, tokens, s):
    x = w["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    x, _ = lax.scan(lambda x, lw: (reference.layer(lw, x, pos, s), None), x,
                    w["layers"])
    return x


@functools.partial(jax.jit, static_argnames=("s", "rows", "control"))
def served_gaps(w, tokens, start, served, *, s, rows, control=False):
    idx = start + jnp.arange(rows)
    ref = reference.logits(w, _hidden(w, tokens, s)[idx], s)
    got = ref.max(-1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    return (got, got) if control else got
'''


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module", params=sorted(tinycell.TINY_CONFIGS))
def cell(request, tmp_path_factory):
    root = tinycell.write_cell(tmp_path_factory.mktemp("family"), CELL,
                               request.param)
    return registry.load_cell(CELL, root)


def test_dense_family_sizes_and_weights_equal_the_direct_draw(cell):
    s = Sizes.from_config(cell.config)
    assert cell.family.sizes(cell.config) == s
    _assert_trees_equal(cell.family.bench_weights(SEED, s),
                        weights.bench_weights(SEED, s))


def test_dense_family_program_params_equal_the_direct_build(cell):
    s = Sizes.from_config(cell.config)
    cfg = program.model_config(cell.config)
    assert cell.family.model_config(cell.config) == cfg
    _assert_trees_equal(cell.family.program_params(SEED, s, cfg),
                        program.program_params(SEED, s, cfg))


@pytest.mark.parametrize("control", [False, True])
def test_dense_family_served_gaps_equal_the_direct_reference(cell, control):
    s = Sizes.from_config(cell.config)
    w = weights.bench_weights(SEED, s)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, s.vocab, 40).astype(np.int32)
    served = rng.integers(0, s.vocab, 8).astype(np.int32)
    kw = dict(s=s, rows=8, control=control)
    _assert_trees_equal(cell.family.served_gaps(w, tokens, 15, served, **kw),
                        reference.served_gaps(w, tokens, 15, served, **kw))


def test_dense_family_work_counts_equal_the_direct_counts(cell):
    s = Sizes.from_config(cell.config)
    for n in (1, 16, 333):
        assert cell.family.prefill_flops(s, n) == work.prefill_flops(s, n)
    for ctx in ([1], [17, 40, 3], list(range(1, 65))):
        assert cell.family.decode_step_work(s, ctx) == \
            work.decode_step_work(s, ctx)


@pytest.mark.parametrize("name", ["qwen3-0.6b.chat", "qwen1.5-4b-d10.docqa"])
def test_a_config_without_family_is_dense(name):
    cell = registry.load_cell(name)
    assert "family" not in cell.config
    assert cell.family.__file__ == str(DENSE)


def test_an_unknown_family_fails_and_names_its_path(tmp_path):
    root = tinycell.write_cell(tmp_path, CELL, "tiny-gqa")
    conf = root / "bench" / "configs" / "tiny-gqa.json"
    conf.write_text(json.dumps({**json.loads(conf.read_text()),
                                "family": "no_such_family"}))
    with pytest.raises(FileNotFoundError,
                       match="bench/families/no_such_family.py"):
        registry.load_cell(CELL, root)


@pytest.mark.parametrize("variant", ["copy", "no_final_norm"])
def test_a_family_added_as_one_file_runs_end_to_end(tmp_path, variant):
    source = DENSE.read_text() + ("" if variant == "copy" else NO_FINAL_NORM)
    backlog = {**tinycell.TINY_TRAFFIC, "kind": "backlog", "count": 12}
    root = tinycell.write_cell(tmp_path, CELL, "tiny-mha", traffic=backlog,
                               family=(f"tiny_{variant}", source))
    cell = registry.load_cell(CELL, root)
    assert cell.family.__file__ == str(
        root / "bench" / "families" / f"tiny_{variant}.py")
    out = tinycell.run_cell(root, CELL)
    assert out["attempted"] > 0 and out["failed"] == 0
    gap = out["checks"]["served_gap_max"]
    assert out["correct"] == (variant == "copy"), out["checks"]
    assert (gap["value"] <= gap["limit"]) == (variant == "copy")
