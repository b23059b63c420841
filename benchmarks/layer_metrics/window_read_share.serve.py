"""Share of a serving window's device own time in the decode reads of the
window rings, found by the name the program gave them: instructions of the
tick whose ``op_name`` holds the ``window_read`` scope
(``tpu_dist/models/phi4flash.py``, inside ``paged_read``: eight layers, at
most ``window`` tokens a slot each). Absent where the program names no such
scope."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "window_read")
