"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is drawn
from the seed: the longest one first, then others in a seeded order until
the sample holds ``check_tokens`` served tokens (the traffic file's number).
The configuration's plain float32 reference runs once over each prompt with
its served tokens, and at each served position reads how far the served
token's logit lies below the reference's best: the widest such gap is
``logit_gap``, their mean ``mean_logit_gap``.  The run is correct when every
request was served whole and each number that the cell's limits file
(``bench/limits/<cell>.json``, ``limits``) names is within its limit.

``control_gaps`` are the same readings for a lower precision: at each
position of the same prompts and tokens, the gap of the token that the
reference computed with ``quant``-bit operands puts first.
"""
from __future__ import annotations

import numpy as np


def sample(done: list, seed: int, check_tokens: int) -> list:
    """done: (rid, prompt, generated) of each finished request."""
    if not done:
        return []
    order = sorted(done, key=lambda r: (-(len(r[1]) + len(r[2])), r[0]))
    longest, rest = order[0], sorted(order[1:], key=lambda r: r[0])
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 3])
    picked, n = [longest], len(longest[2])
    for i in rng.permutation(len(rest)):
        if n >= check_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i][2])
    return picked


def _scored(reqs):
    seqs = [list(p) + list(g[:-1]) for _, p, g in reqs]
    score = [range(len(p) - 1, len(p) - 1 + len(g)) for _, p, g in reqs]
    return seqs, score


def _numbers(gaps) -> dict:
    gaps = np.concatenate(gaps)
    return {"logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def logit_gaps(ref_module, conf: dict, seed: int, reqs: list) -> dict:
    """How far below the reference's best logit each served token lies: the
    widest gap (``logit_gap``) and the mean over all served positions
    (``mean_logit_gap``)."""
    seqs, score = _scored(reqs)
    logits = ref_module.logits(conf, seed, seqs, score)
    return _numbers([lg.max(-1) - lg[np.arange(len(gen)), np.asarray(gen)]
                     for lg, (_, _, gen) in zip(logits, reqs)])


def control_gaps(ref_module, conf: dict, seed: int, reqs: list,
                 quant: int) -> dict:
    """The same numbers for the token that the reference at ``quant`` bits
    puts first, at the same positions."""
    seqs, score = _scored(reqs)
    ref = ref_module.logits(conf, seed, seqs, score)
    low = ref_module.logits(conf, seed, seqs, score, quant=quant)
    return _numbers([r.max(-1) - r[np.arange(len(r)), lo.argmax(-1)]
                     for r, lo in zip(ref, low)])


def judge(numbers: dict, limits: dict) -> dict:
    """Each number that has a limit, beside it."""
    return {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
