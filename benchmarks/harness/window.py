"""The measured window's two loops. Both run in the one process that holds
the chip, on ``time.monotonic`` (the clock ``ServeEngine`` stamps with), and
put a ``jax.profiler.TraceAnnotation`` around every call into the program so
that a traced run can say what the host was doing in each idle gap.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np


def annotate(name: str):
    """A host span ``bench:<name>`` in the profiler's own trace (free when
    no trace is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


# ------------------------------------------------------------------ training

@dataclasses.dataclass
class EpochCall:
    epoch: int
    start: float
    end: float
    out: dict


def drive_epochs(train_epoch: Callable[[int], dict], first_epoch: int,
                 seconds: float, now=time.monotonic) -> List[EpochCall]:
    """Call ``train_epoch`` back to back for ``seconds``. Only whole calls
    count, so a call is started only while the last call's length still
    fits before the window closes (the first two are always made)."""
    calls: List[EpochCall] = []
    t_open = now()
    epoch = first_epoch
    while True:
        t0 = now()
        last = calls[-1].end - calls[-1].start if calls else 0.0
        if len(calls) >= 2 and t0 + last > t_open + seconds:
            break
        with annotate("train_epoch"):
            out = train_epoch(epoch)
        calls.append(EpochCall(epoch, t0, now(), out))
        epoch += 1
    return calls


# ------------------------------------------------------------------- serving

@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    ticks: int        # decode ticks this call ran
    prefills: int     # prefills this call ran


@dataclasses.dataclass
class OpenLoopResult:
    t_open: float
    submit_ts: np.ndarray        # when each request was really submitted
    accepted: np.ndarray         # bool
    completions: dict            # request index -> the engine's Completion
    steps: List[StepRecord]
    t_close: float               # the last completion's evict, or window end


class EngineAdapter:
    """What the loop needs of a server; the family implements it."""

    def submit(self, index: int) -> bool: ...
    def step(self) -> list: ...          # completions: objects with .rid
    def busy(self) -> bool: ...
    def counters(self) -> tuple: ...     # (ticks, prefills) so far


def drive_open_loop(eng: EngineAdapter, due: np.ndarray, t_open: float,
                    now=time.monotonic, sleep=time.sleep,
                    deadline: Optional[float] = None) -> OpenLoopResult:
    """Submit request ``i`` once ``t_open + due[i]`` has passed, step the
    engine while it has work, sleep until the next due time while it has
    none, and go on until every request was submitted and every accepted
    one came back (the drain after the window). ``deadline`` (seconds after
    ``t_open``) bounds a run whose engine never finishes."""
    n = len(due)
    order = np.argsort(due, kind="stable")
    submit_ts = np.full(n, np.nan)
    accepted = np.zeros(n, bool)
    completions = {}
    steps: List[StepRecord] = []
    k = 0
    outstanding = 0
    while True:
        t = now()
        while k < n and t_open + due[order[k]] <= t:
            i = int(order[k])
            with annotate("submit"):
                ok = eng.submit(i)
            submit_ts[i] = now()
            accepted[i] = ok
            outstanding += ok
            k += 1
        if eng.busy():
            c0 = eng.counters()
            t0 = now()
            with annotate("step"):
                done = eng.step()
            t1 = now()
            c1 = eng.counters()
            steps.append(StepRecord(t0, t1, c1[0] - c0[0], c1[1] - c0[1]))
            for c in done:
                completions[int(c.rid)] = c
                outstanding -= 1
        elif k < n:
            with annotate("sleep"):
                sleep(max(0.0, t_open + due[order[k]] - now()))
        else:
            break
        if deadline is not None and now() > t_open + deadline:
            break
    return OpenLoopResult(t_open=t_open, submit_ts=submit_ts,
                          accepted=accepted, completions=completions,
                          steps=steps, t_close=now())
