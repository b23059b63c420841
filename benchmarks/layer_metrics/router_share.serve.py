"""Share of a serving window's device own time in the router of the timed
program (the decode tick), by the ``moe_router`` scope
(``tpu_dist/models/nemotron_h.py``, ``tpu_dist/ops/routed_experts.py``: the
float32 scores over every expert of the router's width, the top-k, the
weights and each row's place among the held experts). Absent where the
program names no such scope."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "moe_router")
