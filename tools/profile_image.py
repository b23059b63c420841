#!/usr/bin/env python
"""Capture + attribute an XLA profile of the image bench step.

Runs the SAME windowed ResNet-50 training step bench.py times (K steps per
dispatch, device-resident uint8 batch, bf16 compute), captures a device
trace with jax.profiler, then post-processes the xplane with xprof's
converter into a per-op-category time table so the ~71% non-MXU time is
ATTRIBUTED, not asserted. Usage:

    python tools/profile_image.py [out_dir]        # default /tmp/imgprof

Env knobs mirror bench.py: BENCH_ARCH / BENCH_PER_CHIP_BATCH / BENCH_STEPS /
BENCH_NORM / BENCH_CIFAR_STEM / BENCH_STEM.
"""

import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def capture(out_dir: str):
    import jax

    import bench

    k = int(os.environ.get("BENCH_STEPS", "20"))
    per_chip = int(os.environ.get("BENCH_PER_CHIP_BATCH", "1024"))
    kwargs = {}
    if os.environ.get("BENCH_CIFAR_STEM") == "1":
        kwargs["cifar_stem"] = True
    if os.environ.get("BENCH_NORM") and os.environ["BENCH_NORM"] != "bn":
        kwargs["norm"] = os.environ["BENCH_NORM"]
    if os.environ.get("BENCH_NORM_DTYPE") == "bf16":
        import jax.numpy as jnp
        kwargs["norm_dtype"] = jnp.bfloat16
    if os.environ.get("BENCH_STEM"):
        kwargs["stem"] = os.environ["BENCH_STEM"]
    batch = per_chip * jax.device_count()
    (step, single, state, images, labels,
     _host, _sh) = bench.build(kwargs, batch, k)
    key = jax.random.PRNGKey(0)
    state, m = step(state, images, labels, key)     # compile + warm
    jax.block_until_ready(m)

    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir):
        state, m = step(state, images, labels, key)
        jax.device_get(m)  # the window's completion barrier
    wall = time.perf_counter() - t0
    print(f"captured: {k}-step window, batch {batch}, wall {wall:.3f}s "
          f"-> {batch * k / wall:,.0f} img/s", file=sys.stderr)
    return wall, batch, k


def find_xplane(out_dir: str) -> str:
    hits = []
    for root, _, files in os.walk(out_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                p = os.path.join(root, f)
                hits.append((os.path.getmtime(p), p))
    if not hits:
        raise SystemExit(f"no .xplane.pb under {out_dir}")
    return max(hits)[1]


def op_table(xplane_path: str):
    """Device op rows from the xplane, via xprof's converter (the same
    backend the TensorBoard profile UI uses): list of dicts with op id,
    type, occurrences, self-time, flop rate, memory BW, bound_by."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xplane_path], "framework_op_stats", {})
    tables = json.loads(data) if isinstance(data, (str, bytes)) else data
    tbl = tables[0]
    cols = [c["id"] for c in tbl["cols"]]
    rows = []
    for r in tbl["rows"]:
        d = {k: cell.get("v") for k, cell in zip(cols, r["c"])}
        if d.get("host_or_device") == "Device":
            rows.append(d)
    return rows


def attribute(rows, k: int, batch: int, unit: str = "img"):
    """Aggregate device self-time by op type; print attribution tables.
    ``unit`` labels the rate line ("img" here, "tok" for profile_lm)."""
    by_type = defaultdict(lambda: [0.0, 0.0, 0])   # time, flops, count
    total = 0.0
    for d in rows:
        t = float(d["total_self_time"])
        fl = float(d.get("measured_flop_rate") or 0.0) * t / 1e6  # MFLOPs... rate*us
        by_type[d["type"]][0] += t
        by_type[d["type"]][1] += fl
        by_type[d["type"]][2] += int(d["occurrences"])
        total += t
    print(f"\n== device self-time by op type "
          f"(device busy total {total/1e3:.2f} ms over {k} steps; "
          f"{total/k/1e3:.3f} ms/step; "
          f"{batch*k/(total/1e6):,.0f} {unit}/s device-busy bound) ==")
    for typ, (t, fl, n) in sorted(by_type.items(), key=lambda kv: -kv[1][0]):
        print(f"  {typ:<28} {t/1e3:9.2f} ms  {100*t/total:5.1f}%  x{n}")
    print("\n== top 30 ops by self-time ==")
    top = sorted(rows, key=lambda d: -float(d["total_self_time"]))[:30]
    for d in top:
        name = d["operation"]
        if len(name) > 84:
            name = "..." + name[-81:]
        bw = float(d.get("measured_memory_bw") or 0)
        fr = float(d.get("measured_flop_rate") or 0) / 1e12
        print(f"  {float(d['total_self_time'])/1e3:8.2f} ms {100*float(d['total_self_time'])/total:5.1f}% "
              f"[{d.get('bound_by','?'):>4}] {fr:6.2f} TF/s {bw:7.1f} GB/s  {name}")
    return total


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/imgprof"
    if os.environ.get("PROFILE_PARSE_ONLY") != "1":
        wall, batch, k = capture(out_dir)
    else:
        batch = int(os.environ.get("BENCH_PER_CHIP_BATCH", "1024"))
        k = int(os.environ.get("BENCH_STEPS", "20"))
    xp = find_xplane(out_dir)
    print(f"xplane: {xp}", file=sys.stderr)
    rows = op_table(xp)
    attribute(rows, k, batch)


if __name__ == "__main__":
    main()
