"""Median wall time of the ``ServeEngine.step()`` calls inside the window
that ran one decode tick and no prefill (by the deltas of the engine's
``ticks`` and ``prefills`` counters around the call)."""

import statistics


def read(obs):
    steps = obs.get("engine_steps")
    if steps is None:
        return None
    ticks = [1e3 * (s.end - s.start) for s in steps
             if s.ticks == 1 and s.prefills == 0]
    return statistics.median(ticks) if ticks else None
