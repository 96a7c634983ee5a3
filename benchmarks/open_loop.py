"""Open-loop request traffic from a data file, and the arithmetic on the
stamps a run of it leaves: the one generator every serving mix goes
through (a new mix is a new ``traffic/<name>.json`` and nothing else).

**The multiset is fixed, the seed orders it, block by block.**  A run
sends ``N = ceil(rate x (warmup_s + seconds))`` requests, rounded up to
whole blocks of the file's ``block`` requests.  Every block is the SAME
multiset: its prompt lengths are the ``block`` quantiles
``(i + 0.5) / block`` of the file's ``prompt_len`` distribution, its
answer lengths those of ``output_len``, its arrival gaps those of an
exponential at the stated rate.  ``--seed`` permutes, block by block,
which prompt length meets which answer length and gap and in what order
they arrive, and draws the token ids.  So every seed offers the same
work at the same rate, and any prefix of the arrivals (all that a window
above the knee serves) holds whole blocks of identical work and a part
of one more; a prefix of ONE permutation of all N would be a random
subset, and the work inside the window would follow the seed after all.

Nothing here imports JAX or the program under test.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


# ----------------------------------------------------------- schedule
def _normal_quantile(p: float) -> float:
    return statistics.NormalDist().inv_cdf(p)


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles ``(i + 0.5) / n`` of a log-normal length
    (``median`` and ``sigma`` of the logarithm), rounded and clipped to
    ``[min, max]``."""
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution "
                         f"{spec['distribution']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    q = [math.exp(mu + sigma * _normal_quantile((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(rate: float, n: int):
    """The ``n`` quantiles of an exponential arrival gap at ``rate`` a
    second."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def n_requests(traffic: dict, seconds: float) -> int:
    n = int(math.ceil(float(traffic["rate_per_s"])
                      * (float(traffic["warmup_s"]) + seconds)))
    block = int(traffic["block"])
    return int(math.ceil(n / block)) * block


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """``due_s`` (seconds after the schedule's start, the first at 0),
    ``prompt_len``, ``output_len`` and ``prompts`` (a list of int32
    arrays) of the N requests of one run, in arrival order."""
    n = n_requests(traffic, seconds)
    block = int(traffic["block"])
    rng = np.random.default_rng(seed)

    def ordered(quantiles):
        return np.concatenate([quantiles[rng.permutation(block)]
                               for _ in range(n // block)])

    prompt_len = ordered(length_quantiles(traffic["prompt_len"], block))
    output_len = ordered(length_quantiles(traffic["output_len"], block))
    gaps = ordered(gap_quantiles(float(traffic["rate_per_s"]), block))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    prompts = [rng.integers(0, vocab, int(m)).astype(np.int32)
               for m in prompt_len]
    return {"n": n, "due_s": due, "prompt_len": prompt_len,
            "output_len": output_len, "prompts": prompts}


# ------------------------------------------------------------- stamps
def nearest_rank(values, percent: float) -> float:
    """The ``percent``-th percentile by nearest rank: the smallest value
    with at least that share of the values at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(percent / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_account(token_stamps, t_open: float, t_close: float) -> dict:
    """``token_stamps``: one list of host-clock stamps a request, one
    stamp a token, in order.  A token counts if its stamp falls inside
    ``[t_open, t_close)``; a gap between two consecutive tokens of one
    answer counts if BOTH its stamps do."""
    tokens, gaps = 0, []
    for stamps in token_stamps:
        inside = [t_open <= t < t_close for t in stamps]
        tokens += sum(inside)
        for i in range(1, len(stamps)):
            if inside[i - 1] and inside[i]:
                gaps.append(stamps[i] - stamps[i - 1])
    return {"tokens": tokens, "gaps_s": gaps}
