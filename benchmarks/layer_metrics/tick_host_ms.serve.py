"""The host's own milliseconds a decode tick, from inside the program:
the median, over the window's ``serve.step`` spans that hold one
``serve.tick`` and no ``serve.prefill``, of the step's length less its
``tick.wait`` (the ``device_get``). That is the time a tick during which
the device has nothing queued: evict, admit, the numpy block tables, the
uploads, the dispatch, the per-token bookkeeping."""

import statistics

from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    if not spans:
        return None
    by_sid = {sp.sid: sp for sp in spans}
    kids = ps.children(spans)
    with_prefill = {st.sid for sp in spans if sp.name == "serve.prefill"
                    for st in [ps.ancestor(sp, by_sid, "serve.step")] if st}
    host = []
    for step in spans:
        if step.name != "serve.step" or step.sid in with_prefill:
            continue
        ticks = [k for k in kids[step.sid] if k.name == "serve.tick"]
        if len(ticks) != 1:
            continue
        wait = sum(w.end - w.start for w in kids[ticks[0].sid]
                   if w.name == "tick.wait")
        host.append(1e3 * (step.end - step.start - wait))
    return statistics.median(host) if host else None
