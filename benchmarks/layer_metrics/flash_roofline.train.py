"""Roofline share of the flash-attention kernels of a traced LM training
window: the least time one v5e chip could take for every forward and
backward call (``benchmarks/kernels/flash_attention.py``: shapes ->
operations and bytes; max of FLOPs / peak and bytes / bandwidth) over the
time the trace shows in them. The kernels are the step's Mosaic
``custom-call``s (three a layer: forward, dq, dk+dv). Prints which bound
sets the floor."""

import re

from benchmarks.harness import device
from benchmarks.kernels import flash_attention as fa

KERNEL = re.compile(r"\bcustom-call\(")


def read(obs):
    trace, recs = obs.get("trace"), obs.get("step_records")
    if trace is None or not recs:
        return None
    spent = sum(v for k, v in trace.op_seconds.items() if KERNEL.search(k))
    if spent <= 0:
        return None
    cell = obs["cell"]
    s, e = cell.config, cell.workload["engine"]
    shape = (e["batch_size"] // cell.chips, e["seq_len"], s["num_heads"],
             s["head_dim"])
    peaks = device.peaks(obs["device_kind"])
    fwd = fa.least_seconds(fa.forward(*shape), peaks)
    bwd = fa.least_seconds(fa.backward(*shape), peaks)
    steps = sum(r["steps_in_dispatch"] for r in recs)
    least = steps * s["num_layers"] * (fwd["seconds"] + bwd["seconds"])
    print(f"flash attention: {1e3 * spent / steps:.3f} ms a step in "
          f"{3 * s['num_layers']} kernels, floor "
          f"{1e3 * least / steps:.3f} ms (forward {fwd['bound']}-bound, "
          f"backward {bwd['bound']}-bound)", flush=True)
    return 100.0 * least / spent
