"""The one general traffic generator. A mix is a data file of parameters
(``benchmarks/traffic/<mix>.json``); its ``kind`` picks the reading:

* ``epochs``: a training job. Nothing to generate: the engine's own sampler
  draws the rows from ``--seed``; the file states the data set's size and
  the steps of an epoch.
* ``open_loop``: requests from independent users, sent on a schedule whether
  or not earlier ones have finished. Every seed gets the SAME set of
  inter-arrival gaps, prompt lengths and answer lengths (the quantiles of
  the stated distributions at ``n`` evenly spaced points), in another
  order: the seed changes which request meets which, never how much work
  a run holds, so runs with different seeds differ no more than two runs
  of one seed.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Schedule:
    due: np.ndarray           # seconds from the window's opening (may be < 0: pre-roll)
    prompt_len: np.ndarray
    answer_len: np.ndarray
    prompts: List[np.ndarray]  # int32 tokens
    in_window: np.ndarray     # bool: due inside [0, seconds)


def _lognormal_set(n: int, median: float, sigma: float, lo: int, hi: int
                   ) -> np.ndarray:
    """The n evenly spaced quantiles of a lognormal, clipped to [lo, hi]."""
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * q)), lo, hi).astype(np.int64)


def _exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The n evenly spaced quantiles of the exponential inter-arrival gap
    of a Poisson process of ``rate``, scaled to the process's mean gap."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g * (n / rate) / g.sum()


def open_loop_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                       max_total: int) -> Schedule:
    """The requests of one run: ``rate * seconds`` due inside the window
    and ``rate * preroll_s`` before it, which fill the engine to its steady
    state and are served but not counted."""
    rate = float(mix["rate_per_s"])
    pre = float(mix.get("preroll_s", 0.0))
    n_win = max(1, int(math.floor(rate * seconds)))
    n_pre = int(math.floor(rate * pre))
    rng = np.random.default_rng([int(seed), 0x0be9])

    def part(n, t0, span):
        if n == 0:
            return (np.zeros(0),) + (np.zeros(0, np.int64),) * 2
        gaps = rng.permutation(_exponential_gaps(n, rate))
        due = t0 + (np.cumsum(gaps) - gaps[0] * 0.5) * (span * rate / n)
        p, a = mix["prompt"], mix["answer"]
        pl = rng.permutation(_lognormal_set(n, p["median"], p["sigma"],
                                            p["min"], p["max"]))
        al = rng.permutation(_lognormal_set(n, a["median"], a["sigma"],
                                            a["min"], a["max"]))
        return due, pl, np.minimum(al, max_total - pl)

    d0, p0, a0 = part(n_pre, -pre, pre)
    d1, p1, a1 = part(n_win, 0.0, seconds)
    due = np.concatenate([d0, d1])
    plen = np.concatenate([p0, p1]).astype(np.int64)
    alen = np.concatenate([a0, a1]).astype(np.int64)
    if (alen < 1).any():
        raise ValueError("a prompt leaves no room for an answer: "
                         "prompt.max must be below max_total")
    prompts = [rng.integers(0, vocab, int(n)).astype(np.int32) for n in plen]
    return Schedule(due=due, prompt_len=plen, answer_len=alen,
                    prompts=prompts, in_window=due >= 0.0)
