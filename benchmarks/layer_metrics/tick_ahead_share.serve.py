"""Share (%) of the window's decode ticks whose tokens were computed one
tick ahead of the host, from inside the program: of the window's
``serve.tick`` spans, those whose ``ahead`` attribute is 1 (the tick that
produced the span's tokens was dispatched while the tick before it was
still unread). A span without the attribute counts as 0, so a program that
reads every tick before it dispatches the next reads 0.0, not nothing."""

from benchmarks.trace import program_spans as ps


def read(obs):
    ticks = [sp for sp in ps.serving_spans(obs) or ()
             if sp.name == "serve.tick"]
    if not ticks:
        return None
    return 100.0 * sum(sp.attrs.get("ahead", 0) == 1
                       for sp in ticks) / len(ticks)
