"""Roofline share of the prefills' grouped expert products in a traced
serving window: the least time one chip could take for the products of every
prefill that ran the routed layer's SORTED form
(``benchmarks/kernels/routed_experts.py``: counts -> bytes and operations;
the larger of the HBM and the MXU floor) over the time the trace shows in
the Mosaic custom-calls of the megablox ``gmm`` kernel, two a layer, named
``%gmm.N``.

The counts come from the program's own ``serve.prefill`` spans: of those
whose program says it ran grouped products (``grouped_calls`` above 0: the
form it was traced in, from the program itself), the ``experts_hit`` and the
``expert_rows`` (assignments of live rows that landed on held experts) that
the prefill's program counted, both summed over its expert layers. Spans
that lie wholly inside the window are counted, the trace's kernel time is
everything the window holds, so an edge can only lower the share. Absent
where the program has no such attribute or kernel, or where no prefill of
the window was long enough for the sorted form."""

from benchmarks.harness import device
from benchmarks.kernels import routed_experts as kernel
from benchmarks.trace import program_spans as ps


def read(obs):
    trace, spans = obs.get("trace"), ps.serving_spans(obs)
    if trace is None or not spans:
        return None
    grouped = [sp for sp in spans if sp.name == "serve.prefill"
               and sp.attrs.get("grouped_calls")]
    spent = sum(v for k, v in trace.op_seconds.items()
                if kernel.GMM_CALL.search(k))
    if not grouped or spent <= 0:
        return None
    s = obs["cell"].config
    itemsize = {"bf16": 2, "fp32": 4}[
        obs["cell"].workload["engine"]["precision"]]
    least = kernel.least_seconds(
        kernel.prefill(sum(sp.attrs["experts_hit"] for sp in grouped),
                       sum(sp.attrs["expert_rows"] for sp in grouped),
                       s["moe_latent_size"], s["moe_intermediate_size"],
                       itemsize),
        device.peaks(obs["device_kind"]))
    calls = sum(sp.attrs["grouped_calls"] for sp in grouped)
    print(f"grouped expert products: {calls} calls in {len(grouped)} "
          f"prefills, {1e3 * spent / calls:.3f} ms a call in the trace, "
          f"floor ({least['bound']}) {1e3 * least['seconds'] / calls:.3f} "
          "ms a call", flush=True)
    return 100.0 * least["seconds"] / spent
