"""The one general generator of request traffic. A traffic mix is a data
file of parameters (``perf/traffic/<name>.json``); this file turns its
``requests`` section and a seed into a stream of (prompt length, output
length) pairs and, with the vocabulary, into token ids.

Lengths are STRATIFIED: a mix fixes a multiset of ``pool`` pairs at the
quantiles of its two length distributions, paired by a permutation that
the file's own ``pairing_seed`` fixes. The run's ``--seed`` only orders
them (and draws the token ids), so every seed does the same work per
``pool`` requests and the tails of the distributions are always there.
The order is shuffled in ``block``-sized strata: block k holds every
``pool/block``-th quantile, so any ``block`` consecutive requests are a
fair sample of the whole mix and a window shorter than the pool still
sees it all.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Iterator, List, Tuple

import numpy as np


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles of ``spec``'s distribution,
    ascending. ``dist`` is ``lognormal`` (``median``, ``sigma``; heavy
    right tail) or ``uniform``; both are clipped to [``min``, ``max``]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            v = spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(u))
        elif spec["dist"] == "uniform":
            v = lo + u * (hi - lo)
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def multiset(req: dict) -> List[Tuple[int, int]]:
    """The mix's fixed pairs, in stratum order: entries
    [k*block, (k+1)*block) are stratum k. Independent of ``--seed``."""
    pool, block = int(req["pool"]), int(req["block"])
    if pool % block:
        raise ValueError(f"pool {pool} is not a multiple of block {block}")
    stride = pool // block
    prompts = quantile_lengths(req["prompt_len"], pool)
    outputs = quantile_lengths(req["output_len"], pool)
    fixed = np.random.default_rng(int(req["pairing_seed"]))
    pairs: List[Tuple[int, int]] = []
    for k in range(stride):
        # stratum k: every stride-th quantile of each marginal, paired by
        # a permutation fixed in the file
        p = prompts[k::stride]
        o = [outputs[k::stride][j] for j in fixed.permutation(block)]
        pairs.extend(zip(p, o))
    limit = req.get("max_total")
    if limit is not None:
        bad = [pr for pr in pairs if pr[0] + pr[1] > limit]
        if bad:
            raise ValueError(f"{len(bad)} pairs exceed prompt + output "
                             f"<= {limit}, e.g. {bad[0]}")
    return pairs


def ordered(req: dict, seed: int) -> Iterator[Tuple[int, int]]:
    """The endless request stream of one run: passes over the multiset,
    each pass with its strata in a seeded order and each stratum
    shuffled."""
    pairs = multiset(req)
    block = int(req["block"])
    rng = np.random.default_rng([int(seed), 0x7261])
    while True:
        for k in rng.permutation(len(pairs) // block):
            stratum = pairs[k * block:(k + 1) * block]
            for j in rng.permutation(block):
                yield stratum[j]


def stationary_cut(req: dict, seed: int, n: int) -> List[float]:
    """Shares in (0, 1] by which the first ``n`` requests' outputs are cut,
    so that a closed loop starts in its steady mix of ages instead of
    with a cold batch: stratified uniform, seeded order."""
    rng = np.random.default_rng([int(seed), 0x6375])
    return [float(s) for s in rng.permutation(
        (np.arange(n) + 0.5) / n)]


def token_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Prompt ``index`` of the run: seeded uniform ids."""
    rng = np.random.default_rng([int(seed), 0x746f, int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int32)
