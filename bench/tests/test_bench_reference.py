"""The plain float32 reference against the program's own forward pass and
loss, at small widths on the CPU, for both block variants the benchmark
serves: GQA with query/key norm and a tied head, MHA with Q/K/V bias and
an untied head.  Both get the same draw of weights from ``bench.weights``,
moved into the program's layout by ``bench.program``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinycell
from bench import program, reference, weights
from bench.work import Sizes

SEED = 2**33 + 5


def _setup(name):
    conf = tinycell.TINY_CONFIGS[name]
    s = Sizes.from_config(conf)
    # the program in float32, so the two differ by accumulation order only
    cfg = dataclasses.replace(program.model_config(conf),
                              compute_dtype="float32")
    return s, cfg, program.program_params(SEED, s, cfg), \
        weights.bench_weights(SEED, s)


@pytest.mark.parametrize("name", sorted(tinycell.TINY_CONFIGS))
def test_reference_matches_program_forward(name):
    from repro.models import model as M
    s, cfg, params, w = _setup(name)
    tokens = np.random.default_rng(0).integers(0, s.vocab, (2, 40))
    h, _ = M.lm_apply(params, {"tokens": jnp.asarray(tokens)}, cfg)
    got = (h @ M._head(params, cfg))[..., : s.vocab]
    for row in range(2):
        want = reference.logits(w, reference.hidden(w, tokens[row], s), s)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got[row] - want).max()) < 1e-4 * scale


@pytest.mark.parametrize("name", sorted(tinycell.TINY_CONFIGS))
def test_reference_matches_program_loss(name):
    from repro.models import model as M
    s, cfg, params, w = _setup(name)
    tok = np.random.default_rng(1).integers(0, s.vocab, (2, 33))
    batch = {"tokens": jnp.asarray(tok[:, :-1]),
             "labels": jnp.asarray(tok[:, 1:])}
    got, _ = M.lm_loss(params, batch, cfg)
    want = np.mean([reference.loss(w, tok[r, :-1], tok[r, 1:], s)
                    for r in range(2)])
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))


def test_a_layer_drawn_alone_equals_its_slice_of_the_stacked_draw():
    s = Sizes.from_config(tinycell.TINY_CONFIGS["tiny-mha"])
    key = weights.seed_key(weights.seed_words(SEED))
    for name in weights.layer_shapes(s):
        np.testing.assert_array_equal(
            weights.stacked_leaf(key, s, name)[1],
            weights.layer_leaf(key, s, name, 1))


def test_served_gaps_are_zero_for_the_reference_own_tokens():
    s = Sizes.from_config(tinycell.TINY_CONFIGS["tiny-gqa"])
    w = weights.bench_weights(SEED, s)
    prompt = list(np.random.default_rng(2).integers(1, s.vocab, 16))
    seq = list(prompt)
    for _ in range(6):                        # greedy by the reference
        seq.append(int(reference.logits(
            w, reference.hidden(w, jnp.asarray(seq), s), s)[-1].argmax()))
    toks = np.zeros(40, np.int32)
    toks[:len(seq)] = seq
    served = np.zeros(8, np.int32)
    served[:6] = seq[16:]
    gaps, ctl = reference.served_gaps(w, toks, 15, served, s=s, rows=8,
                                      control=True)
    assert np.all(np.asarray(gaps)[:6] < 1e-5)
    assert np.all(np.asarray(ctl)[:6] >= 0)
    wrong = served.copy()
    wrong[:6] = (wrong[:6] + 1) % s.vocab
    assert float(np.max(reference.served_gaps(
        w, toks, 15, wrong, s=s, rows=8)[:6])) > 1e-3
