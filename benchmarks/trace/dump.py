#!/usr/bin/env python3
"""Look at one trace by hand: ``python3 benchmarks/trace/dump.py <file.xplane.pb>``
prints the planes and their lines, the benchmark's host spans, and the
device operations that took most own time."""

from __future__ import annotations

import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.trace import reduce as tr  # noqa: E402


def main(path: str, top: int = 40) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name}: {lines[:12]}")
    ops, spans = tr.read_xplane(path)
    print("host spans:", Counter(n for n, _, _ in spans).most_common(12))
    summary = tr.summarize(ops, spans)
    print(f"window {summary.window_s:.4f} s, busy {summary.busy_s:.4f} s, "
          f"idle share {summary.idle_share:.4f}")
    for name, secs in summary.top(summary.op_seconds, top):
        print(f"  {secs * 1e3:10.3f} ms  {name[:150]}")
    print("idle by host span:", summary.top(summary.idle_seconds))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 40)
