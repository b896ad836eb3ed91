"""The one traffic generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<name>.json``) holds parameters only.  Its
``kind`` says how requests arrive:

* ``open_loop``: independent users at ``rate`` requests/s; a run of
  ``seconds`` gets ``round(rate * seconds)`` requests, all due inside it.
* ``backlog``: ``count`` requests all due when the window opens, enough to
  keep every slot busy to its end (saturated serving).

``prompt`` and ``output`` give each length's distribution: lognormal with
``median`` and ``sigma``, clipped to ``[min, max]``; prompts are rounded up
to a multiple of ``multiple``.

Every seed gets the same multiset of lengths and gaps, so the seed does
not change how much work a run holds.  The values are quantiles of the
distribution, stratified in blocks of ``block``: each run of ``block``
consecutive requests holds one value from each stratum, so any stretch of
the run carries about the same work.  The order inside each block is
shuffled once, from a constant, and is the same for every run: under an
open loop near the knee the order alone moved the chat cell's latency
tails by half across seeds.  The run's seed draws the token ids.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Due:
    """One request of the schedule: when it is due (seconds after the
    window opens), its prompt token ids and how many tokens it asks for."""
    at: float
    prompt: list
    gen: int


def _quantiles(n: int, block: int) -> np.ndarray:
    """n probabilities in (0, 1): block k holds one point of each of the
    ``block`` strata, shifted by k inside the stratum; returned block by
    block, strata in order."""
    blocks = -(-n // block)
    j = np.arange(block)[None, :]
    k = np.arange(blocks)[:, None]
    u = (j * blocks + k + 0.5) / (block * blocks)
    return u.reshape(-1)[:n]


def _shuffle_blocks(values: np.ndarray, block: int, rng) -> np.ndarray:
    out = values.copy()
    for s in range(0, len(out), block):
        out[s:s + block] = rng.permutation(out[s:s + block])
    return out


def _lognormal(u: np.ndarray, p: dict) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    v = np.exp(math.log(p["median"]) + p["sigma"] * z)
    v = np.clip(np.ceil(v), p["min"], p["max"]).astype(np.int64)
    m = int(p.get("multiple", 1))
    return -(-v // m) * m


def request_count(traffic: dict, seconds: float) -> int:
    if traffic["kind"] == "open_loop":
        return max(1, int(round(traffic["rate"] * seconds)))
    if traffic["kind"] == "backlog":
        return int(traffic["count"])
    raise ValueError(f"unknown serving traffic kind {traffic['kind']!r}")


def schedule(traffic: dict, *, seconds: float, seed: int,
             vocab: int) -> list[Due]:
    """The requests of one run, in order of due time."""
    n = request_count(traffic, seconds)
    block = int(traffic.get("block", 16))
    rng, order = np.random.default_rng(int(seed)), np.random.default_rng(0)
    u = _quantiles(n, block)
    prompts = _shuffle_blocks(_lognormal(u, traffic["prompt"]), block, order)
    outputs = _shuffle_blocks(_lognormal(u, traffic["output"]), block, order)
    if traffic["kind"] == "open_loop":
        gaps = _shuffle_blocks(-np.log1p(-u), block, order)  # Exp(1) quantiles
        gaps = gaps * (seconds / gaps.sum())                # n gaps fill it
        at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        at = np.zeros(n)
    return [Due(at=float(at[i]),
                prompt=rng.integers(1, vocab, int(prompts[i])).tolist(),
                gen=int(outputs[i]))
            for i in range(n)]
