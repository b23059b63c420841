"""Open-loop arrivals in waves on top of a steady trickle: a second reading
of a traffic mix beside ``harness/traffic.py``'s Poisson one (whose length
sets and gap quantiles it reuses, unedited).

An ``open_loop`` mix that states, beside its mean rate ``R``, a ``burst``
group: ``share`` of the requests arrive in bursts, one every ``period_s``
at a fixed phase (the first ``phase_s`` after the window opens), each
``share * R * period_s`` requests spread evenly over ``width_s``; the rest
is a Poisson base at ``(1 - share) * R``. The pre-roll runs the same
pattern backwards from the window's opening.

As in ``traffic.py``, every seed gets the SAME work in another order. The
bursts' times are the mix's; the base's gaps are the evenly spaced
quantiles of the exponential, and the prompt and answer lengths of a part
(pre-roll, window) the evenly spaced quantiles of the mix's distributions
at as many points as the part has requests, base and bursts together. The
seed permutes the gaps, and the prompt lengths and the answer lengths over
every arrival of the part: it decides which request arrives inside a burst
and which beside it, and which short answer decodes through which burst.
"""

from __future__ import annotations

import math

import numpy as np

from . import traffic


def _burst_starts(burst: dict, lo: float, hi: float) -> list:
    """Start times of the bursts that begin in [lo, hi)."""
    period, phase = float(burst["period_s"]), float(burst["phase_s"])
    k = math.ceil((lo - phase) / period)
    out = []
    while phase + k * period < hi:
        out.append(phase + k * period)
        k += 1
    return out


def burst_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                   max_total: int) -> traffic.Schedule:
    """The requests of one run: the base and the bursts due inside
    [0, seconds), and those of the pre-roll before it (served, not
    counted)."""
    rate, burst = float(mix["rate_per_s"]), mix["burst"]
    pre = float(mix.get("preroll_s", 0.0))
    share, width = float(burst["share"]), float(burst["width_s"])
    base_rate = (1.0 - share) * rate
    n_each = max(1, int(round(share * rate * float(burst["period_s"]))))
    rng = np.random.default_rng([int(seed), 0x0b57])
    p, a = mix["prompt"], mix["answer"]

    def part(t0, span):
        n = int(math.floor(base_rate * span))
        due = []
        if n:
            gaps = rng.permutation(traffic._exponential_gaps(n, base_rate))
            due.append(t0 + (np.cumsum(gaps) - gaps[0] * 0.5) * (
                span * base_rate / n))
        for start in _burst_starts(burst, t0, t0 + span):
            due.append(start + width * np.arange(n_each) / n_each)
        due = np.concatenate(due) if due else np.zeros(0)
        if not len(due):
            return (due,) + (np.zeros(0, np.int64),) * 2
        pl = rng.permutation(traffic._lognormal_set(
            len(due), p["median"], p["sigma"], p["min"], p["max"]))
        al = rng.permutation(traffic._lognormal_set(
            len(due), a["median"], a["sigma"], a["min"], a["max"]))
        return due, pl, np.minimum(al, max_total - pl)

    d0, p0, a0 = part(-pre, pre)
    d1, p1, a1 = part(0.0, seconds)
    due = np.concatenate([d0, d1])
    plen = np.concatenate([p0, p1]).astype(np.int64)
    alen = np.concatenate([a0, a1]).astype(np.int64)
    if (alen < 1).any():
        raise ValueError("a prompt leaves no room for an answer: "
                         "prompt.max must be below max_total")
    prompts = [rng.integers(0, vocab, int(n)).astype(np.int32) for n in plen]
    return traffic.Schedule(due=due, prompt_len=plen, answer_len=alen,
                            prompts=prompts, in_window=due >= 0.0)
