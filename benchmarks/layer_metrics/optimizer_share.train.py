"""Share of a training window's device own time in the optimizer's
update: instructions of the step whose ``op_name`` holds the ``optimizer``
scope (``engine/steps.py:_apply_update``)."""

from benchmarks.trace import scopes


def read(obs):
    if "step_records" not in obs:
        return None
    return scopes.share(obs, "optimizer")
