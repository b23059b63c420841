"""Roofline share of the decode ticks' routed products in a traced serving
window: the least time one chip could take to read the weights of the held
experts that some row chose and to move the live rows in and out
(``benchmarks/kernels/routed_experts.py``: counts -> bytes; the HBM floor)
over the time the trace shows in the tick's instructions of the
``routed_experts`` scope (``trace/scopes.py``'s join).

The counts come from the program's own ``serve.tick`` spans: ``experts_hit``
(held experts with at least one row, summed over the expert layers by the
tick's program) and the rows that decoded (``rids``) times
``expert_layers``. Spans that lie wholly inside the window are counted, the
trace's time is everything the window holds, so an edge can only lower the
share; an expert no row chose and a slot that sat out are not counted, so
the dense form, which reads every held expert, stays under 100%. Absent
where the program has no such attribute or scope."""

from benchmarks.harness import device
from benchmarks.kernels import routed_experts as kernel
from benchmarks.trace import program_spans as ps
from benchmarks.trace import scopes


def read(obs):
    trace, text = obs.get("trace"), obs.get("hlo_text")
    spans = ps.serving_spans(obs)
    if trace is None or not text or not spans:
        return None
    ticks = [sp for sp in spans
             if sp.name == "serve.tick" and sp.attrs.get("expert_layers")]
    spent = scopes.scope_seconds(trace.op_seconds, text, "routed_experts")
    if not ticks or spent <= 0:
        return None
    s = obs["cell"].config
    itemsize = {"bf16": 2, "fp32": 4}[
        obs["cell"].workload["engine"]["precision"]]
    hit = sum(sp.attrs["experts_hit"] for sp in ticks)
    rows = sum(len(sp.attrs["rids"]) * sp.attrs["expert_layers"]
               for sp in ticks)
    least = kernel.least_seconds(
        kernel.tick(hit, rows, s["moe_latent_size"],
                    s["moe_intermediate_size"], itemsize),
        device.peaks(obs["device_kind"]))["seconds"]
    n = len(ticks)
    print(f"routed experts: {n} ticks, {hit / n:.1f} experts hit and "
          f"{rows / n:.1f} rows a tick over the expert layers, "
          f"{1e3 * spent / n:.3f} ms a tick in the trace, HBM floor "
          f"{1e3 * least / n:.3f} ms a tick", flush=True)
    return 100.0 * least / spent
