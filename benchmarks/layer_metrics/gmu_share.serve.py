"""Share of a serving window's device own time in the gated memory units,
found by the name the program gave them: instructions of the tick whose
``op_name`` holds the ``gmu`` scope (``tpu_dist/models/phi4flash.py``: the
two projections and the gate of seven layers; they read no cache at all).
Absent where the program names no such scope."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "gmu")
