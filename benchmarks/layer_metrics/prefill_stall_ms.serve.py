"""How long every decoding request stands still for one admission: the
median length of the window's ``serve.prefill`` spans (block table, padded
prompt, dispatch, and the ``device_get`` of the first token)."""

import statistics

from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    stalls = [1e3 * (sp.end - sp.start) for sp in spans or ()
              if sp.name == "serve.prefill"]
    return statistics.median(stalls) if stalls else None
