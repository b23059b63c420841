"""Share of a serving window's device own time in the paged read, found
by the name the program gave it: instructions of the tick whose
``op_name`` holds the ``paged_read`` scope (``ops/paged_attention.py``:
the gather of every slot's pages and what consumes the gathered rows)."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "paged_read")
