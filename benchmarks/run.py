#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Requires the cell's chips (any other platform than ``tpu``, or fewer
devices, exits non-zero with no result line), builds the engine objects
with weights made from ``--seed``, drives the training cells' first steps,
warms every shape the window uses, measures for ``--seconds``, frees the
program's state, compares with the plain reference, and prints as the last
line of stdout one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, and with ``--trace 1``
``breakdown``. ``BENCHMARK.json`` and the files it names say what a cell is;
nothing here is keyed on a cell's name.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()            # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional, Sequence  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness import check, device, host, window  # noqa: E402


def _require_program(root: str) -> None:
    """The system under test must be the checkout's own: a directory that
    holds only the benchmark has nothing to measure."""
    try:
        import tpu_dist
    except ImportError:
        tpu_dist = None
    here = os.path.realpath(os.path.join(root, "tpu_dist"))
    if tpu_dist is None or os.path.realpath(
            os.path.dirname(tpu_dist.__file__)) != here:
        print(f"benchmark: no tpu_dist package in {root}: run it from the "
              "root of a checkout", file=sys.stderr)
        raise SystemExit(3)


def _trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"the profiler left no .xplane.pb in "
                                f"{trace_dir}")
    return found[-1]


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devices: Sequence, workdir: str, t0: float = None,
             keep_trace: Optional[str] = None) -> dict:
    """Everything behind the command but the device check and the final
    print: returns the result object. ``devices`` are the chips the caller
    is entitled to (the command passes ``require_tpu``'s)."""
    import jax

    from tpu_dist.runtime import enable_compile_cache

    t0 = _T0 if t0 is None else t0
    enable_compile_cache()
    meter = device.CompileMeter()
    fam = cells.load_family(cell).Family(cell, seed, devices, workdir)
    phases = {}
    for phase in (fam.build, fam.first_steps, fam.warm):
        t = time.monotonic()
        phase()
        phases[phase.__name__] = round(time.monotonic() - t, 3)
    at_setup = meter.snapshot()

    trace_dir = keep_trace or os.path.join(workdir, "trace")
    if trace:
        # a traced run is a run of its own, a few seconds long: traces are
        # large and tracing slows the host
        seconds = min(seconds, float(cell.workload["trace_seconds"]))
        profile = contextlib.ExitStack()
        jax.profiler.start_trace(trace_dir)
        profile.callback(jax.profiler.stop_trace)
    else:
        profile = contextlib.nullcontext()
    with profile:
        host_open = host.read()
        t_enter = time.monotonic()
        with window.annotate("window"):
            win = fam.run_window(seconds)
        host_window = host.delta(host_open, host.read())
        host_window["wall_s"] = round(time.monotonic() - t_enter, 6)
    at_close = meter.snapshot()
    setup_s = win["t_open"] - t0
    program = fam.timed_program()
    memory = device.memory_fields(
        devices, program.memory_analysis().temp_size_in_bytes)

    attempted, failed = fam.attempted_failed(win)
    metrics = {}
    obs = {"compile_setup": at_setup, "compile_window": {
        k: at_close[k] - at_setup[k] for k in at_setup},
        "cell": cell, "trace": None, "hlo_text": None,
        "device_kind": devices[0].device_kind, **fam.observations(win)}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), **memory}
    result = {}
    if trace:
        from benchmarks.trace import reduce as trace_reduce

        summary = trace_reduce.reduce_file(
            _trace_file(trace_dir),
            (win["t_open"] - t_enter, win["t_close"] - t_enter))
        obs["trace"] = summary
        obs["hlo_text"] = program.as_text()
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": summary.top(summary.op_seconds),
            "idle_gaps": summary.top(summary.idle_seconds)}
        metrics = cells.read_layer_metrics(cell, obs)
    else:
        values = {"setup_s": setup_s, **fam.end_to_end(win)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    print(f"host over the window (with any pre-roll and drain): "
          f"{host_window}", flush=True)
    print(f"set-up: {setup_s:.3f} s (imports and device "
          f"{setup_s - sum(phases.values()):.3f}, {phases}), "
          f"{at_setup['count']} compilations "
          f"({at_setup['cache_hits']} from the cache) in "
          f"{at_setup['seconds']:.3f} s; in the window: "
          f"{obs['compile_window']['count']} compilations", flush=True)

    fam.release()
    t_check = time.monotonic()
    comparisons = fam.verify(win)
    ok = check.report(comparisons)
    in_check = meter.snapshot()
    print(f"reference check: {time.monotonic() - t_check:.3f} s, of which "
          f"{in_check['seconds'] - at_close['seconds']:.3f} s compiling "
          f"({in_check['cache_hits'] - at_close['cache_hits']} of "
          f"{in_check['count'] - at_close['count']} programs from the "
          "cache)", flush=True)
    correct = bool(ok and failed == 0 and attempted > 0
                   and obs["compile_window"]["count"] == 0)
    return {"correct": correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": dev,
            **result, "host": host_window}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler's trace here and leave it")
    args = ap.parse_args(argv)

    cell = cells.load_cell(ROOT, args.workload)
    _require_program(ROOT)
    devices = device.require_tpu(cell.chips)
    with tempfile.TemporaryDirectory(prefix="bench_") as workdir:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, workdir, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
