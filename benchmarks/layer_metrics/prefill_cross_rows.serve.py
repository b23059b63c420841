"""Rows of a prompt that a prefill ran its model's LAST layers and head on,
from inside the program: the mean ``cross_rows`` of the window's
``serve.prefill`` spans (``ServeEngine._prefill``: the rows of logits the
bucket's traced program returned). 1.0 where the layers that cache nothing
run on each prompt's last live row only; the bucket's length otherwise.
Absent in a program without the attribute."""

import statistics

from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    rows = [sp.attrs["cross_rows"] for sp in spans or ()
            if sp.name == "serve.prefill"
            and sp.attrs.get("cross_rows") is not None]
    return statistics.mean(rows) if rows else None
