#!/usr/bin/env python3
"""Record the small trace the reduction's test reads:

    python3 benchmarks/trace/record.py <out_dir>

Eight dispatches of a 4096^3 bf16 matrix multiplication, each under a
``bench:step`` span and followed by a 30 ms ``bench:sleep``, all under
``bench:window``. Writes ``recorded_v5e.xplane.pb`` and, beside it,
``recorded_v5e.json`` with what the reduction read from it on the spot."""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import device, window  # noqa: E402
from benchmarks.trace import reduce as tr  # noqa: E402


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    device.require_tpu(1)
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    f = jax.jit(lambda a: (a @ a) * jnp.bfloat16(1.0 / 4096))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with window.annotate("window"):
            for _ in range(8):
                with window.annotate("step"):
                    f(x).block_until_ready()
                with window.annotate("sleep"):
                    time.sleep(0.03)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        os.makedirs(out_dir, exist_ok=True)
        dst = os.path.join(out_dir, "recorded_v5e.xplane.pb")
        shutil.copy(src, dst)
    s = tr.reduce_file(dst)
    with open(os.path.join(out_dir, "recorded_v5e.json"), "w") as fjson:
        json.dump({"window_s": s.window_s, "busy_s": s.busy_s,
                   "idle_seconds": s.idle_seconds,
                   "op_seconds": s.op_seconds}, fjson, indent=1)
    print(os.path.getsize(dst), "bytes;", json.dumps(
        {"window_s": s.window_s, "busy_s": s.busy_s,
         "idle": s.idle_seconds}))


if __name__ == "__main__":
    main(sys.argv[1])
