"""Roofline share of the selective-scan kernel in a traced serving window:
the least time one chip could take for every ``selective_scan`` call of the
window's prefills (``benchmarks/kernels/selective_scan.py``: shapes ->
bytes; the HBM floor) over the time the trace shows in the Mosaic
custom-calls named after the ``selective_scan`` scope.

The calls come from the program's own ``serve.prefill`` spans: one a
slot-state layer (``state_layers``) at the span's ``bucket``, one prompt a
call. Spans that lie wholly inside the window are counted, the trace's
kernel time is everything the window holds, so an edge can only lower the
share."""

from benchmarks.harness import device
from benchmarks.kernels import selective_scan as ss
from benchmarks.trace import program_spans as ps

def read(obs):
    trace, spans = obs.get("trace"), ps.serving_spans(obs)
    if trace is None or not spans:
        return None
    spent = sum(v for k, v in trace.op_seconds.items() if ss.CALL.search(k))
    calls = [(sp.attrs["bucket"], sp.attrs["state_layers"]) for sp in spans
             if sp.name == "serve.prefill" and sp.attrs.get("state_layers")]
    if spent <= 0 or not calls:
        return None
    s = obs["cell"].config
    channels = s["mamba_expand"] * s["hidden_size"]
    itemsize = {"bf16": 2, "fp32": 4}[
        obs["cell"].workload["engine"]["precision"]]
    peaks = device.peaks(obs["device_kind"])
    least = sum(layers * ss.least_seconds(
        ss.scan(1, bucket, channels, s["mamba_d_state"], itemsize),
        peaks)["seconds"] for bucket, layers in calls)
    n = sum(layers for _, layers in calls)
    print(f"selective scan: {n} calls in {len(calls)} prefills, "
          f"{1e6 * spent / n:.1f} us a call in the trace, HBM floor "
          f"{1e6 * least / n:.1f} us a call", flush=True)
    return 100.0 * least / spent
