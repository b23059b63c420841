"""Share of a serving window's device own time in the tick's one-step
state update, by the ``ssm_step`` scope (``tpu_dist/ops/selective_scan.py``):
what reading and writing every slot's recurrent state costs a tick. It
lies inside ``mamba_share.serve``."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "ssm_step")
