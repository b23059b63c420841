"""Share of a training window's device own time in the cross-entropy,
forward and backward: instructions of the step whose ``op_name`` holds the
``loss`` scope (``engine/steps.py``, ``engine/lm_steps.py``)."""

from benchmarks.trace import scopes


def read(obs):
    if "step_records" not in obs:
        return None
    return scopes.share(obs, "loss")
