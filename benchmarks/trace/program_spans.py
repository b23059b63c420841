#!/usr/bin/env python3
"""The program's own spans (``tpu_dist.obs.trace``), read two ways.

From the ring, in the process that ran the window: ``ring_spans()`` and the
helpers the span readers under ``layer_metrics/`` share. A program without
the ring (an older checkout) gives ``None``, and so do they.

From a kept trace, on the profiler's clock: every span also entered a
``jax.profiler.TraceAnnotation("tpu_dist:<name>", sid=<n>)``, so an
``.xplane.pb`` holds them beside the device's operations.

    python3 benchmarks/trace/program_spans.py <dir> [--run <cell> --seed N]

``--run`` first runs the cell traced into ``<dir>`` in this process
(``benchmarks/run.py --trace 1 --keep-trace <dir>``) and writes the ring
beside the trace (``<dir>/spans.jsonl``) with the window's bounds
(``<dir>/program_spans.json``). Then, from what ``<dir>`` holds: each
device idle gap of the window goes to the innermost program span covering
it; the idle time under each of the benchmark's own spans is split the same
way; and, given the ring's dump, the offset between the engine's clock and
the profiler's is measured from the spans present in both (joined on
``sid``), with the residual that offset leaves.

The device's operations and the host's spans share one trace, not one
clock: on the v5e machine the device's events lie 1-2 ms before the host
spans that caused them (a matrix product shown before its dispatch began).
``device_clock_shift`` bounds that skew from causality (the device cannot
be busy between a ``*.wait`` that drained it and the next ``*.dispatch``),
and the idle time is split with the device's events moved by the middle of
those bounds; the split as recorded is kept beside it.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.trace import reduce  # noqa: E402

PREFIX = "tpu_dist:"
NO_SPAN = "(no program span)"

ProgramEvent = Tuple[str, float, float, Optional[int]]   # name, ns, ns, sid


# ---------------------------------------------------------------- the ring

def ring_spans() -> Optional[list]:
    """The closed spans of this process's ring, oldest first; None where
    the program has no ring."""
    try:
        from tpu_dist.obs import trace

        return trace.ring().snapshot()
    except (ImportError, AttributeError):
        return None


def serving_spans(obs: dict) -> Optional[list]:
    """The ring's spans that lie inside the serving window, by the bounds
    of the benchmark's own ``engine_steps`` (both on ``time.monotonic``)."""
    steps, spans = obs.get("engine_steps"), ring_spans()
    if not steps or not spans:
        return None
    lo, hi = min(s.start for s in steps), max(s.end for s in steps)
    return [sp for sp in spans if sp.start >= lo and sp.end <= hi]


def children(spans: Sequence) -> Dict[int, list]:
    out = defaultdict(list)
    for sp in spans:
        out[sp.parent].append(sp)
    return out


def ancestor(span, by_sid: dict, name: str):
    """The nearest enclosing span called ``name`` (or None)."""
    while span is not None and span.name != name:
        span = by_sid.get(span.parent)
    return span


def token_times(spans: Sequence) -> Dict[object, List[float]]:
    """rid -> the engine-clock time of each of its tokens, from inside the
    program: the end of its ``serve.prefill`` (the first token; a chunked
    prompt's last chunk) and of every ``serve.tick`` that lists it."""
    first, later = {}, defaultdict(list)
    for sp in spans:
        if sp.name == "serve.prefill":
            first[sp.attrs["rid"]] = sp.end
        elif sp.name == "serve.tick":
            for rid in sp.attrs["rids"]:
                later[rid].append(sp.end)
    rids = set(first) | set(later)
    return {rid: sorted(([first[rid]] if rid in first else [])
                        + later.get(rid, [])) for rid in rids}


# ----------------------------------------------------------- a kept trace

def trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_program_events(path: str) -> List[ProgramEvent]:
    """The ``tpu_dist:`` events of the host planes, with their ``sid``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    sid = dict(ev.stats).get("sid")
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                None if sid is None else int(sid)))
    return out


def window_bounds(spans: Sequence[reduce.Interval], offsets_s=None
                  ) -> Tuple[float, float]:
    """[lo, hi] ns of the measured window: the ``bench:window`` span, or
    the part of it between the two offsets (seconds from its start)."""
    lo, hi = next((s, e) for n, s, e in spans if n == reduce.WINDOW_SPAN)
    if offsets_s is not None:
        lo, hi = lo + offsets_s[0] * 1e9, lo + offsets_s[1] * 1e9
    return lo, hi


def idle_by_program_span(device_ops: Sequence[reduce.Interval],
                         program: Sequence[reduce.Interval], lo: float,
                         hi: float, within: Sequence[reduce.Interval] = None
                         ) -> Dict[str, float]:
    """Idle nanoseconds of one device in [lo, hi] by the innermost program
    span covering each piece; ``within`` keeps only the idle time that the
    given intervals (say, the ``bench:step`` spans) cover."""
    flat = [(s, e) for _, s, e in reduce.clip(device_ops, lo, hi)]
    idle = reduce.gaps(flat, lo, hi)
    if within is not None:
        cover = [(s, e) for _, s, e in reduce.clip(within, lo, hi)]
        idle = [piece for a, b in idle
                for piece in _intersect(a, b, cover)]
    out = reduce.label_gaps(idle, reduce.clip(program, lo, hi))
    return {(NO_SPAN if k == "(no span)" else k): v for k, v in out.items()}


def _intersect(a: float, b: float, cover) -> List[Tuple[float, float]]:
    return [(max(a, s), min(b, e)) for s, e in cover if e > a and s < b]


def busy_blocks(intervals: Sequence[Tuple[float, float]]
                ) -> List[Tuple[float, float]]:
    """The union of the intervals as disjoint blocks, in order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def drained_intervals(events: Sequence[ProgramEvent]
                      ) -> List[Tuple[float, float]]:
    """When the host holds nothing in flight: from the end of a ``*.wait``
    span (its ``device_get`` returned) to the start of the next
    ``*.dispatch`` span."""
    marks = sorted((s, e, n.rsplit(".", 1)[-1]) for n, s, e, _ in events
                   if n.endswith((".wait", ".dispatch")))
    out, drained_at = [], None
    for s, e, kind in marks:
        if kind == "wait":
            drained_at = e
        elif drained_at is not None:
            if s > drained_at:
                out.append((drained_at, s))
            drained_at = None
    return out


def device_clock_shift(device_ops: Sequence[reduce.Interval],
                       events: Sequence[ProgramEvent],
                       radius_ns: float = 5e6
                       ) -> Optional[Tuple[float, float]]:
    """Bounds (ns) on what to ADD to the device's event times so that no
    operation runs while the host holds nothing in flight; None where no
    shift within ``radius_ns`` does that (or nothing constrains it). The
    lower bound is reached when some dispatch's first operation starts the
    moment its span opens, the upper when some wait returns the moment the
    last operation ends: the truth lies between."""
    import numpy as np

    blocks = busy_blocks([(s, e) for _, s, e in device_ops])
    drained = drained_intervals(events)
    if not blocks or not drained:
        return None
    starts = np.array([s for s, _ in blocks])
    ends = np.array([e for _, e in blocks])
    excluded = []
    for a, b in drained:
        # a block [x, y] moved by s overlaps [a, b] for s in (a - y, b - x)
        i = np.searchsorted(ends, a - radius_ns, "right")
        j = np.searchsorted(starts, b + radius_ns, "left")
        excluded += [(a - y, b - x) for x, y in zip(starts[i:j], ends[i:j])]
    free = reduce.gaps(excluded, -radius_ns, radius_ns)
    if not free or not excluded:
        return None
    # the least correction that causality allows: the run nearest to zero
    return min(free, key=lambda g: 0.0 if g[0] <= 0.0 <= g[1]
               else min(abs(g[0]), abs(g[1])))


def clock_offset(events: Sequence[ProgramEvent], ring_rows: Sequence[dict]
                 ) -> Optional[dict]:
    """The engine's clock against the profiler's, from the spans in both:
    offset = median(profiler start - ring start), and what it leaves: the
    95th percentile and the largest |deviation| over the joined spans."""
    ring = {int(r["sid"]): float(r["start"]) for r in ring_rows}
    diffs = [s * 1e-9 - ring[sid] for _, s, _, sid in events
             if sid is not None and sid in ring]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    dev = sorted(abs(d - offset) for d in diffs)
    return {"offset_s": offset, "joined": len(diffs),
            "residual_p95_s": dev[min(len(dev) - 1, int(0.95 * len(dev)))],
            "residual_max_s": dev[-1]}


def analyse(path: str, offsets_s=None, ring_path: str = None) -> dict:
    """Everything the command prints, from one ``.xplane.pb`` (and the
    ring's dump of the same run, where there is one)."""
    device_ops, bench = reduce.read_xplane(path)
    events = read_program_events(path)
    program = [(n, s, e) for n, s, e, _ in events]
    lo, hi = window_bounds(bench, offsets_s)
    out = {"window_s": (hi - lo) * 1e-9, "program_events": len(events),
           "device_clock_shift_s": None, "idle_s": {}, "idle_under": {},
           "idle_under_as_recorded": {}}
    covers = {name: [sp for sp in bench if sp[0] == name]
              for name in sorted({b for b, _, _ in bench}
                                 - {reduce.WINDOW_SPAN})}
    n = len(device_ops)

    def add(into: dict, split: Dict[str, float]) -> None:
        for k, v in split.items():
            into[k] = into.get(k, 0.0) + v * 1e-9 / n

    for ops in device_ops:
        bounds = device_clock_shift(reduce.clip(ops, lo - 1e9, hi + 1e9),
                                    events)
        shift = 0.0 if bounds is None else 0.5 * (bounds[0] + bounds[1])
        if bounds is not None:
            out["device_clock_shift_s"] = {
                "lo": bounds[0] * 1e-9, "hi": bounds[1] * 1e-9,
                "used": shift * 1e-9}
        moved = [(name, s + shift, e + shift) for name, s, e in ops]
        add(out["idle_s"], idle_by_program_span(moved, program, lo, hi))
        for name, cover in covers.items():
            add(out["idle_under"].setdefault(name, {}),
                idle_by_program_span(moved, program, lo, hi, cover))
            if shift:
                add(out["idle_under_as_recorded"].setdefault(name, {}),
                    idle_by_program_span(ops, program, lo, hi, cover))
    if ring_path and os.path.exists(ring_path):
        with open(ring_path) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        out["clock"] = clock_offset(events, rows)
    return out


def named_child_share(split: Dict[str, float], parent: str) -> float:
    """Of the idle time in ``split``, the share under a span other than
    ``parent`` itself (a named child) and other than no span at all."""
    total = sum(split.values())
    named = sum(v for k, v in split.items() if k not in (parent, NO_SPAN))
    return named / total if total > 0 else 0.0


def _print_split(title: str, split: Dict[str, float]) -> None:
    total = sum(split.values())
    if total <= 0:
        return
    print(f"{title}: {total:.6f} s")
    for k, v in sorted(split.items(), key=lambda kv: -kv[1]):
        print(f"  {v:10.6f} s  {100 * v / total:5.1f}%  {k}")


def _print(result: dict) -> None:
    print(f"window {result['window_s']:.4f} s, "
          f"{result['program_events']} program spans in the trace")
    sh = result["device_clock_shift_s"]
    if sh:
        print(f"device events moved by {1e3 * sh['used']:+.4f} ms: causality "
              f"allows {1e3 * sh['lo']:+.4f} .. {1e3 * sh['hi']:+.4f} ms")
    else:
        print("device events as recorded (no shift bounded)")
    _print_split("device idle by innermost program span", result["idle_s"])
    for name, split in result["idle_under"].items():
        _print_split(f"idle under {name}", split)
    for name, split in result["idle_under_as_recorded"].items():
        _print_split(f"idle under {name}, device events AS RECORDED", split)
    if result.get("clock"):
        c = result["clock"]
        print(f"engine clock -> profiler clock: offset {c['offset_s']:.9f} s "
              f"from {c['joined']} spans in both, residual p95 "
              f"{1e3 * c['residual_p95_s']:.6f} ms, largest "
              f"{1e3 * c['residual_max_s']:.6f} ms")


def _run(trace_dir: str, workload: str, seed: int, seconds: float) -> None:
    """The cell traced into ``trace_dir``, in this process, with the ring
    and the window's bounds written beside the trace."""
    import contextlib
    import io

    from benchmarks import run
    from benchmarks.harness import cell as cells

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", "1", "--keep-trace", trace_dir])
    text = buf.getvalue()
    sys.stdout.write(text)
    result = json.loads(text.strip().splitlines()[-1])
    cell = cells.load_cell(run.ROOT, workload)
    lo = float(cell.traffic.get("preroll_s", 0.0))
    from tpu_dist.obs import trace

    trace.ring().dump(os.path.join(trace_dir, "spans.jsonl"))
    with open(os.path.join(trace_dir, "program_spans.json"), "w") as f:
        json.dump({"workload": workload, "seed": seed, "result": result,
                   "offsets_s": [lo, lo + result["device"]["window_s"]]}, f)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--run", default=None, metavar="CELL")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--offsets", default=None,
                    help="lo,hi seconds after bench:window's start")
    args = ap.parse_args(argv)
    if args.run:
        _run(args.trace_dir, args.run, args.seed, args.seconds)
    offsets = None
    kept = os.path.join(args.trace_dir, "program_spans.json")
    if args.offsets:
        offsets = tuple(float(x) for x in args.offsets.split(","))
    elif os.path.exists(kept):
        with open(kept) as f:
            offsets = tuple(json.load(f)["offsets_s"])
    result = analyse(trace_file(args.trace_dir), offsets,
                     os.path.join(args.trace_dir, "spans.jsonl"))
    _print(result)
    with open(os.path.join(args.trace_dir, "program_spans.out.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
