"""Roofline share of the grouped in-place read over the ONE KV layer that
other layers read too, in a traced serving window: the least time one chip
could take to read the live tokens' K and V once a reading layer
(``benchmarks/kernels/paged_grouped_read.py``: shapes -> bytes; the HBM
floor) over the time the trace shows in the Mosaic custom-calls named after
the ``shared_kv_read`` scope.

The live tokens come from the program's own spans: a ``serve.tick`` reads
its ``live_tokens`` (the decoding slots' positions + 1) once for each of
the model's ``shared_readers`` (the layer itself and the layers that read
it; the attribute is on the window's ``serve.prefill`` spans, and the
configuration's layer pattern gives the same count), a ``serve.prefill``
its prompt's length once for each reader BUT the layer itself, whose
prefill attends inside the prompt. Spans that lie wholly inside the window
are counted, the trace's kernel time is everything the window holds, so an
edge can only lower the share. Absent where the program has no such
attribute or kernel."""

from benchmarks.kernels import paged_grouped_read as kernel
from benchmarks.trace import program_spans as ps


def read(obs):
    trace, spans = obs.get("trace"), ps.serving_spans(obs)
    if trace is None or not spans:
        return None
    readers = max((sp.attrs.get("shared_readers") or 0 for sp in spans
                   if sp.name == "serve.prefill"), default=0)
    ticks = [sp.attrs["live_tokens"] for sp in spans
             if sp.name == "serve.tick" and "live_tokens" in sp.attrs]
    if not readers or not ticks:
        return None
    prompts = [sp.attrs["prompt_len"] for sp in spans
               if sp.name == "serve.prefill"]
    return kernel.roofline_share(
        obs, kernel.SHARED_CALL,
        readers * sum(ticks) + (readers - 1) * sum(prompts),
        readers * len(ticks) + (readers - 1) * len(prompts),
        f"shared KV read ({len(ticks)} ticks, {len(prompts)} prefills)")
