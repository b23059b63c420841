"""Share (%) of a serving window's device own time in instructions that
are NOT the decode tick's: the prefill programs and the engine's two small
row helpers. The device's side of ``gap_prefill_share.serve``. An event is
the tick's when its name, opcode and result shape are those of an
instruction of the timed program (``benchmarks/trace/scopes.py``, as the
scope shares join); another program's instruction that collides on all
three counts as the tick's, so this reads low, never high. Beside it the
same seconds from the spans' side: the sum of the window's prefills' own
time (``benchmarks/trace/admissions.py``). None without a trace, or for a
program whose spans lack ``behind_s``."""

from benchmarks.trace import admissions, scopes
from benchmarks.trace import program_spans as ps


def read(obs):
    trace, text = obs.get("trace"), obs.get("hlo_text")
    prefills = admissions.waited_prefills(ps.serving_spans(obs))
    if trace is None or not text or not prefills:
        return None
    ticks = set(scopes.Program(text).key.values())
    total = sum(trace.op_seconds.values())
    other = sum(v for k, v in trace.op_seconds.items()
                if scopes._key(k) not in ticks)
    if total <= 0:
        return None
    print(f"device time outside the tick program: {other:.6f} s of "
          f"{total:.6f} s busy; the window's {len(prefills)} prefills' own "
          f"time by the spans: "
          f"{sum(admissions.own_s(sp) for sp in prefills):.6f} s",
          flush=True)
    return 100.0 * other / total
