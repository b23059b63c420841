"""Share (%) of the window's decoding time spent behind admissions, from
inside the program. Decoding time counts a moment once for every request
waiting for its next token then (the ``rids`` of the ``serve.tick`` spans
say who receives one at each pass's end); inside it, the own time of every
OTHER request's prefill, ``(end - issued) - behind_s``
(``benchmarks/trace/admissions.py``). What of the distance from
``tick_ms`` to ``gap_p95_ms`` a faster prefill could take away at most.
None for a program whose spans lack ``behind_s``."""

from benchmarks.trace import admissions
from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    prefills = admissions.waited_prefills(spans)
    if not prefills:
        return None
    return admissions.share_of_decoding(
        spans, admissions.own_intervals(prefills))
