"""Share of the device's busy time spent in convolutions, by own time. On
the TPU a convolution sits inside a fusion whose name does not say so: the
events are joined with the timed program's optimized HLO
(``benchmarks/trace/hlo.py``) and counted when their instruction is a
convolution or a fusion over a computation that holds one."""

from benchmarks.trace import hlo


def read(obs):
    trace, text = obs.get("trace"), obs.get("hlo_text")
    if trace is None or not text:
        return None
    names = hlo.instructions_holding(text, "convolution")
    conv = sum(v for k, v in trace.op_seconds.items()
               if hlo.event_instruction(k) in names)
    total = sum(trace.op_seconds.values())
    return 100.0 * conv / total if conv > 0 else None
