"""Rows a held expert sees a decode tick, from inside the program: the mean
over the window's ``serve.tick`` spans of ``expert_rows / (expert_layers x
experts_held)`` (``ServeEngine._count_experts``: the assignments of live
rows that landed on held experts, summed over the expert layers by the
tick's own program). With dropless routing it is ``active slots x top_k /
router width``: the load an expert is under, which says how far its products
are from being bound by anything but its weights' read. Absent in a program
without the attributes."""

import statistics

from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    load = [sp.attrs["expert_rows"]
            / (sp.attrs["expert_layers"] * sp.attrs["experts_held"])
            for sp in spans or ()
            if sp.name == "serve.tick" and sp.attrs.get("expert_layers")]
    return statistics.mean(load) if load else None
