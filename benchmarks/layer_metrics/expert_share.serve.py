"""Share of a serving window's device own time in the routed experts'
products of the timed program (the decode tick), found by the name the
program gave them: instructions that are in, or fuse an operation of, the
``routed_experts`` scope (``tpu_dist/ops/routed_experts.py``: the held
experts' two products over the tick's rows, the squared ReLU and the fold by
the rows' weights). The join is on the tick's own instructions
(``trace/scopes.py``), so a prefill's grouped products count under the
window's total, not under the share. Absent where the program names no such
scope."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "routed_experts")
