"""The one traffic generator: a traffic file's parameters and a seed in,
waves of requests out.

A traffic file (``bench/traffic/<name>.json``) gives a log-normal length
distribution for prompts and for outputs (``median``, ``sigma``, ``min``,
``max``) and the ``wave`` size.  Every wave holds the same multiset of
lengths: the ``wave`` quantiles of each distribution at (i + 0.5) / wave,
clipped.  The seed draws everything else: the order of the prompt lengths
and, apart, of the output lengths, anew for every wave, and the token ids,
uniform over the vocabulary.  So every seed carries the same work, in
another order in each wave.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """The n quantiles of a clipped log-normal, rounded to whole tokens."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(dist["median"] * math.exp(dist["sigma"] * z))
        out.append(int(min(max(v, dist["min"]), dist["max"])))
    return out


def waves(spec: dict, seed: int, vocab: int, first_rid: int = 0):
    """Endless waves of ``(rid, prompt_ids, max_new_tokens)``; rids count up
    from ``first_rid`` across waves."""
    rng = np.random.default_rng(int(seed) & (2**64 - 1))
    n = spec["wave"]
    plens = quantile_lengths(spec["prompt"], n)
    olens = quantile_lengths(spec["output"], n)
    rid = first_rid
    while True:
        wave = []
        for p, o in zip(rng.permutation(plens), rng.permutation(olens)):
            wave.append((rid, rng.integers(0, vocab, int(p)).tolist(), int(o)))
            rid += 1
        yield wave
