"""Share of a serving window's device own time in the selective-scan
kernel: the prefill side of the Mamba mixers, which ``mamba_share.serve``
(joined on the tick's instructions) cannot see. The Mosaic custom-calls
named after the ``selective_scan`` scope (``tpu_dist/ops/selective_scan.py``),
26 a prefill, over every device operation of the window; absent where the
program has no such kernel."""

from benchmarks.kernels import selective_scan as ss


def read(obs):
    trace = obs.get("trace")
    if trace is None:
        return None
    spent = sum(v for k, v in trace.op_seconds.items() if ss.CALL.search(k))
    total = sum(trace.op_seconds.values())
    return 100.0 * spent / total if spent > 0 else None
