"""Share of a serving window's device own time in the decode reads of the
ONE KV layer that other layers read too, found by the name the program gave
them: instructions of the tick whose ``op_name`` holds the ``shared_kv_read``
scope (``tpu_dist/models/phi4flash.py``, inside ``paged_read``: the full
layer's own read and the seven cross layers' of the same pages). Absent
where the program names no such scope."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "shared_kv_read")
