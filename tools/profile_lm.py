#!/usr/bin/env python
"""Capture + attribute an XLA profile of the LM bench step (the round-5
image-profile methodology — tools/profile_image.py — applied to the LM
flagship, so its MFU gap is attributed rather than asserted).

Builds the EXACT windowed step bench.py's lm_bench times (ONE shared
builder, bench.lm_build — every BENCH_* knob including BENCH_OPTIMIZER,
BENCH_STEPS_PER_WINDOW and BENCH_LOSS_CHUNK behaves identically), captures
a device trace with jax.profiler, and post-processes the xplane with
xprof's converter into per-op-type device-time tables. Usage:

    python tools/profile_lm.py [out_dir]          # default /tmp/lmprof
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from profile_image import attribute, find_xplane, op_table  # noqa: E402


def capture(out_dir: str):
    import jax

    import bench

    b = bench.lm_build()
    window, state = b["window"], b["state"]
    rows_dev, idx_dev, key = b["rows_dev"], b["idx_dev"], b["key"]
    batch, L, k = b["batch"], b["L"], b["k"]

    state, m = window(state, rows_dev, idx_dev, key)    # compile + warm
    jax.device_get(m)
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir):
        state, m = window(state, rows_dev, idx_dev, key)
        jax.device_get(m)                               # completion barrier
    wall = time.perf_counter() - t0
    print(f"captured: {k}-step window, batch {batch}, L {L}, wall "
          f"{wall:.3f}s -> {batch * k * L / wall:,.0f} tok/s",
          file=sys.stderr)
    return batch * L, k


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/lmprof"
    if os.environ.get("PROFILE_PARSE_ONLY") != "1":
        tokens, k = capture(out_dir)
    else:
        # the SAME geometry parse the capture used (bench.lm_geometry) so a
        # parse-only rerun normalizes the trace to identical numbers
        import bench
        g = bench.lm_geometry()
        tokens, k = g["batch"] * g["L"], g["k"]
    xp = find_xplane(out_dir)
    print(f"xplane: {xp}", file=sys.stderr)
    rows = op_table(xp)
    attribute(rows, k, tokens, unit="tok")


if __name__ == "__main__":
    main()
