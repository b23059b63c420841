#!/usr/bin/env python3
"""Record the small trace that ``program_spans.py``'s test reads:

    python3 benchmarks/trace/record_spans.py <out_dir>

Eight ticks of a make-believe engine, each under the benchmark's
``bench:step`` span and, inside it, the program's own spans through
``tpu_dist.obs.trace`` as ``ServeEngine`` opens them: ``serve.step`` over
``tick.build`` (2 ms of host work, the device idle), ``tick.dispatch`` (a
4096^3 bf16 matrix multiplication), ``tick.wait`` (its
``block_until_ready``) and ``tick.emit`` (1 ms of host work); then a 30 ms
``bench:sleep`` that no program span covers. Writes
``recorded_spans_v5e.xplane.pb``, the ring's dump of the same run
(``recorded_spans_v5e.spans.jsonl``) and what the reduction read from both
on the spot (``recorded_spans_v5e.json``)."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import device, window  # noqa: E402
from benchmarks.trace import program_spans as ps  # noqa: E402

NAME = "recorded_spans_v5e"


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from tpu_dist.obs import trace

    device.require_tpu(1)
    ring = trace.ring()
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    f = jax.jit(lambda a: (a @ a) * jnp.bfloat16(1.0 / 4096))
    f(x).block_until_ready()
    with ring.span("mark") as mark:
        pass
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with window.annotate("window"):
            for tick in range(8):
                with window.annotate("step"), \
                        ring.span("serve.step", tick=tick):
                    with ring.span("tick.build"):
                        time.sleep(0.002)
                    with ring.span("tick.dispatch", first_call=False):
                        y = f(x)
                    with ring.span("tick.wait"):
                        y.block_until_ready()
                    with ring.span("tick.emit"):
                        time.sleep(0.001)
                with window.annotate("sleep"):
                    time.sleep(0.03)
        jax.profiler.stop_trace()
        os.makedirs(out_dir, exist_ok=True)
        dst = os.path.join(out_dir, NAME + ".xplane.pb")
        shutil.copy(ps.trace_file(d), dst)
    spans = os.path.join(out_dir, NAME + ".spans.jsonl")
    with open(spans, "w") as fspans:
        for sp in ring.snapshot():
            if sp.sid > mark.sid:
                fspans.write(json.dumps(trace.span_record(sp)) + "\n")
    result = ps.analyse(dst, ring_path=spans)
    with open(os.path.join(out_dir, NAME + ".json"), "w") as fjson:
        json.dump(result, fjson, indent=1)
    print(os.path.getsize(dst), "bytes;", json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1])
