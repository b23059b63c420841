"""Mean number of active slots holding recurrent state a decode tick, from
inside the program: the ``state_slots`` attribute of the window's
``serve.tick`` spans (0 for a model that keeps pages only; absent in a
program without the attribute). Lower is better at a cell's fixed arrival
rate: by Little's law the mean is the rate times the time a request holds
its slot, so it falls only when requests are done sooner."""

import statistics

from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    held = [sp.attrs["state_slots"] for sp in spans or ()
            if sp.name == "serve.tick" and "state_slots" in sp.attrs]
    return statistics.mean(held) if held else None
