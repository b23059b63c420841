"""Roofline share of the decode ticks' one-step Mamba-2 state update in a
traced serving window: the least time one chip could take to read and write
the state of the rows that decoded and to move their small operands
(``benchmarks/kernels/ssd_step.py``: counts -> bytes; the HBM floor) over
the time the trace shows in the tick's instructions of the ``ssm_step``
scope (``trace/scopes.py``'s join).

The rows come from the program's own ``serve.tick`` spans: ``state_slots``
(the slots that decoded the tick) times the Mamba-2 layers, the ``M`` of the
configuration's ``hybrid_override_pattern``; the widths from the
configuration. Spans that lie wholly inside the window are counted, the
trace's time is everything the window holds, and the device's list of live
rows holds every row the host counts and, with ticks issued ahead, now and
then one that has just finished: an edge or such a row can only lower the
share. A slot that sat out is not counted, so the plain form, which passes
over every slot's state, stays under 100% by the share of slots that
decode. Absent where the program has no such attribute or scope, or the
configuration no Mamba-2 layer."""

from benchmarks.harness import device
from benchmarks.kernels import ssd_step as kernel
from benchmarks.trace import program_spans as ps
from benchmarks.trace import scopes


def read(obs):
    trace, text = obs.get("trace"), obs.get("hlo_text")
    spans = ps.serving_spans(obs)
    s = obs["cell"].config
    layers = s.get("hybrid_override_pattern", "").count("M")
    if trace is None or not text or not spans or not layers:
        return None
    ticks = [sp for sp in spans
             if sp.name == "serve.tick" and sp.attrs.get("state_slots")]
    spent = scopes.scope_seconds(trace.op_seconds, text, "ssm_step")
    if not ticks or spent <= 0:
        return None
    itemsize = {"bf16": 2, "fp32": 4}[
        obs["cell"].workload["engine"]["precision"]]
    rows = sum(sp.attrs["state_slots"] for sp in ticks) * layers
    least = kernel.least_seconds(
        kernel.step(rows, s["mamba_num_heads"], s["mamba_head_dim"],
                    s["ssm_state_size"], s["n_groups"], itemsize),
        device.peaks(obs["device_kind"]))["seconds"]
    n = len(ticks)
    print(f"ssm step: {n} ticks, {rows / n:.1f} live rows a tick over "
          f"{layers} Mamba-2 layers, {1e3 * spent / n:.3f} ms a tick in the "
          f"trace, HBM floor {1e3 * least / n:.3f} ms a tick", flush=True)
    return 100.0 * least / spent
