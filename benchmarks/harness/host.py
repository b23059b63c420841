"""What the host did to the process during the window, from the kernel's own
counters: read once as the window opens and once as it closes, so a run
that reads far off says whether the host held the process back (a stall
shows as run-queue wait, pressure or steal) or the time went on the device.
A counter this kernel does not have is left out.
"""

from __future__ import annotations

import os
import time
from typing import Dict


def _first_line(path: str) -> str:
    with open(path) as f:
        return f.readline()


def read() -> Dict[str, float]:
    """Cumulative seconds, by counter."""
    out = {"process_cpu_s": sum(os.times()[:2])}
    try:        # the thread that drives the window: on-CPU and runnable-but-waiting
        run_ns, wait_ns = _first_line("/proc/thread-self/schedstat").split()[:2]
        out["thread_cpu_s"] = int(run_ns) * 1e-9
        out["thread_runqueue_wait_s"] = int(wait_ns) * 1e-9
    except (OSError, ValueError):
        pass
    for what in ("cpu", "memory", "io"):
        try:    # "some avg10=0.00 ... total=<microseconds>"
            total = _first_line(f"/proc/pressure/{what}").rsplit("total=", 1)[1]
            out[f"pressure_{what}_s"] = int(total) * 1e-6
        except (OSError, ValueError, IndexError):
            pass
    try:        # "cpu user nice system idle iowait irq softirq steal ..."
        out["steal_s"] = int(_first_line("/proc/stat").split()[8]) \
            / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    try:        # grows only while the machine is suspended
        out["suspended_s"] = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - time.monotonic()
    except (AttributeError, OSError):
        pass
    return out


def delta(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    return {k: round(after[k] - before[k], 6) for k in before if k in after}
