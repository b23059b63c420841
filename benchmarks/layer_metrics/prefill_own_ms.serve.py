"""What ONE admission adds to every decoding request's next gap, from inside
the program: the MEAN over the window's ``serve.prefill`` spans of a
prefill's own time, ``(end - issued) - behind_s``
(``benchmarks/trace/admissions.py``): the tick that was in flight when the
prefill was dispatched, which ``prefill_stall_ms.serve`` has held since
the tick runs one ahead of the host, is out. A mean, not a median: a long
prompt's admission costs the slots more and is as much theirs. Prints one
line a prefill bucket. None for a program whose spans lack ``behind_s``."""

import statistics
from collections import defaultdict

from benchmarks.trace import admissions
from benchmarks.trace import program_spans as ps


def read(obs):
    prefills = admissions.waited_prefills(ps.serving_spans(obs))
    if not prefills:
        return None
    ticks = [s.end - s.start for s in obs["engine_steps"]
             if s.ticks == 1 and s.prefills == 0]
    tick = statistics.median(ticks) if ticks else None
    by_bucket = defaultdict(list)
    for sp in prefills:
        by_bucket[sp.attrs["bucket"]].append(sp)
    for bucket, group in sorted(by_bucket.items()):
        own = statistics.mean(admissions.own_s(sp) for sp in group)
        behind = statistics.mean(sp.attrs["behind_s"] for sp in group)
        print(f"prefill bucket {bucket}: {len(group)} prefills, own "
              f"{1e3 * own:.3f} ms, behind the tick in flight "
              f"{1e3 * behind:.3f} ms"
              + (f", own = {own / tick:.2f} pure-tick passes of "
                 f"{1e3 * tick:.3f} ms" if tick else ""), flush=True)
    return 1e3 * statistics.mean(admissions.own_s(sp) for sp in prefills)
