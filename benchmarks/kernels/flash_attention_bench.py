#!/usr/bin/env python3
"""The causal flash kernels alone, at the cells' shapes, on the chip:

    python3 benchmarks/kernels/flash_attention_bench.py [--other <path to another flash_attention.py>] [--subs 256,512,1024] [--train 4,2048,16,128] [--prefills 256,1024,2048]

Forward and backward at the LM training cell's shape and forward at the
serving cells' prefill buckets (one prompt, 16 heads of 128), ``--calls``
calls chained in one jit. A row gives the time a call twice: from a
profiler trace, the device time of the Mosaic ``custom-call``s alone (what
``flash_roofline.train`` sums), and from the host's clock around the jit,
which also holds the ``_fold`` transposes around each kernel. Beside them
the floor of ``benchmarks/kernels/flash_attention.py``'s FLOPs and bytes and
the matrix work the schedule executes (``flash_work``) over the floor's.
``--subs`` re-runs the committed program at other sub-block widths (one
width for both directions);
``--other`` (repeatable) times another version of the file (the parent's)
in the same process and prints how far its results are from the committed
program's.
PERF.md section 6 has the table this printed for PR 29.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import device  # noqa: E402
from benchmarks.kernels import flash_attention as cost  # noqa: E402
from benchmarks.trace import program_spans, reduce  # noqa: E402
from tpu_dist.ops import flash_attention as committed  # noqa: E402
from tpu_dist.runtime import (enable_compile_cache,  # noqa: E402
                              pallas_interpret)

KERNEL = re.compile(r"\bcustom-call\(")
BLOCK = 1024          # the cells' attn_block, and block_q's default


def load(path):
    spec = importlib.util.spec_from_file_location(
        "flash_attention_" + re.sub(r"\W", "_", path), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def operands(shape, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return [jax.random.normal(k, shape, jnp.bfloat16) for k in ks]


def chained(fa, calls, backward):
    """``calls`` kernels, each fed by the one before it."""
    args = (True, 0, 0, BLOCK, BLOCK, pallas_interpret(None))

    def fwd(q, k, v, g):
        for _ in range(calls):
            q, _ = fa._fa_forward(q, k, v, *args)
        return q

    def bwd(q, k, v, g):
        out, lse = fa._fa_forward(q, k, v, *args)
        tiny = jnp.bfloat16(2.0 ** -20)     # keeps the chain's values finite
        for _ in range(calls):
            # every result feeds the next call: a version whose dk/dv come
            # from a kernel of their own must not lose it as dead code
            g, dk, dv = fa._fa_backward(q, k, v, out, lse, g, *args)
            k, v = k + tiny * dk, v + tiny * dv
        return g, k, v

    return jax.jit(bwd if backward else fwd)


def kernel_seconds(fn, xs):
    """Device seconds in Mosaic custom-calls over one traced call of fn."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        jax.block_until_ready(fn(*xs))
        jax.profiler.stop_trace()
        ops, _ = reduce.read_xplane(program_spans.trace_file(d))
    return sum((e - s) * 1e-9 for n, s, e in ops[0] if KERNEL.search(n))


def host_seconds(fn, xs, reps=5):
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*xs))
        best = min(best, time.perf_counter() - t)
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[],
                    help="another version's flash_attention.py (repeatable)")
    ap.add_argument("--subs", default="", help="sub-block widths to try")
    ap.add_argument("--train", default="4,2048,16,128")
    ap.add_argument("--prefills", default="256,1024,2048")
    ap.add_argument("--calls", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()
    device.require_tpu(1)
    peaks = device.peaks(jax.devices()[0].device_kind)
    b, l, h, d = (int(v) for v in args.train.split(","))
    shapes = [((b, l, h, d), True)] + [
        ((1, int(n), h, d), False) for n in args.prefills.split(",") if n]
    subs = [int(v) for v in args.subs.split(",") if v]
    widths = dict(committed._SUB)
    forms = [("committed", committed, None)]
    forms += [("committed", committed, s) for s in subs]
    forms += [(path, load(path), None) for path in args.other]
    base = {}
    for shape, with_backward in shapes:
        xs = operands(shape)
        for name, fa, sub in forms:
            if fa is committed:
                fa._SUB = (widths if sub is None
                           else dict(forward=sub, backward=sub))
            for backward in ([False, True] if with_backward else [False]):
                fn = chained(fa, args.calls, backward)
                out = jax.block_until_ready(fn(*xs))      # compiles
                kernels = kernel_seconds(fn, xs)
                if backward:    # the one forward that feeds the chain
                    kernels -= kernel_seconds(chained(fa, 1, False), xs)
                floor = cost.least_seconds(
                    (cost.backward if backward else cost.forward)(*shape),
                    peaks)["seconds"]
                direction = "backward" if backward else "forward"
                row = dict(
                    form=name, shape=list(shape), direction=direction,
                    kernel_us_a_call=1e6 * kernels / args.calls,
                    host_us_a_call=1e6 * host_seconds(fn, xs) / args.calls,
                    floor_us=1e6 * floor,
                    roofline_pct=100.0 * floor * args.calls / kernels)
                if fa is committed:
                    work = fa.flash_work(shape[1], shape[1], shape[3], BLOCK,
                                         BLOCK)[direction]
                    counted = (cost.backward if backward
                               else cost.forward)(*shape)["flops"]
                    row["sub_block"] = work["sub_block"][0]
                    row["executed_over_counted"] = (
                        shape[0] * shape[2] * work["flops"] / counted)
                key = (shape, backward)
                leaves = [x.astype(jnp.float32)
                          for x in jax.tree_util.tree_leaves(out)]
                if key in base:
                    row["max_abs_gap_to_first_form"] = max(
                        float(jnp.abs(x - y).max())
                        for x, y in zip(leaves, base[key]))
                else:
                    base[key] = leaves
                print(json.dumps(row), flush=True)
        committed._SUB = widths
    return 0


if __name__ == "__main__":
    sys.exit(main())
