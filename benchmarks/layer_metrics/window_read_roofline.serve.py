"""Roofline share of the grouped in-place read over the window rings, in a
traced serving window: the least time one chip could take to read the
window's live tokens' K and V once a window layer
(``benchmarks/kernels/paged_grouped_read.py``: the HBM floor) over the time
the trace shows in the Mosaic custom-calls named after the ``window_read``
scope.

A ``serve.tick`` span's ``window_tokens`` is the sum over its decoding
slots of ``min(position + 1, window)``: what one window layer's read sees;
``window_layers`` (on the window's ``serve.prefill`` spans) layers read
that much a tick. A prefill's window layers attend inside the prompt and
call no such kernel. Absent where the program has no such attribute or
kernel."""

from benchmarks.kernels import paged_grouped_read as kernel
from benchmarks.trace import program_spans as ps


def read(obs):
    trace, spans = obs.get("trace"), ps.serving_spans(obs)
    if trace is None or not spans:
        return None
    layers = max((sp.attrs.get("window_layers") or 0 for sp in spans
                  if sp.name == "serve.prefill"), default=0)
    ticks = [sp.attrs["window_tokens"] for sp in spans
             if sp.name == "serve.tick" and "window_tokens" in sp.attrs]
    if not layers or not ticks:
        return None
    return kernel.roofline_share(
        obs, kernel.WINDOW_CALL, layers * sum(ticks), layers * len(ticks),
        f"window read ({len(ticks)} ticks)")
