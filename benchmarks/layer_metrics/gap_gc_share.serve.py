"""Share (%) of the window's decoding time spent inside Python's full
garbage collections, from inside the program: the ``host.gc`` spans
(``tpu_dist/obs/trace.py:gc_seconds``; the oldest generation only), under
a ``serve.step`` span or between two, weighed like
``gap_prefill_share.serve``: a moment counts once for every request
waiting for its next token then. 0.0, not nothing, where the program times
its collections and no full one fell in the window; None for a program
that does not."""

from benchmarks.trace import admissions
from benchmarks.trace import program_spans as ps


def read(obs):
    try:
        from tpu_dist.obs.trace import gc_seconds  # noqa: F401
    except ImportError:
        return None
    spans = ps.serving_spans(obs)
    if not spans:
        return None
    pauses = [sp for sp in spans if sp.name == "host.gc"]
    if pauses:
        print("full garbage collections in the window: " + ", ".join(
            f"{1e3 * (sp.end - sp.start):.1f} ms" for sp in pauses),
            flush=True)
    return admissions.share_of_decoding(
        spans, [(sp.start, sp.end, None) for sp in pauses])
