#!/usr/bin/env python3
"""The selective-scan kernel alone, at a cell's shapes, on the chip:

    python3 benchmarks/kernels/selective_scan_bench.py [--channels 5120 --states 16 --layers 26 --lengths 256,1024 --slots 32]

One prompt, ``--layers`` calls chained in one jit (a prefill's), for the
committed blocks (``scan_blocks``), for other (time, channel) blocks handed
to the kernel directly, and for the ``lax.scan`` form; then ``ssm_step``
over ``--slots`` rows, chained the same way. Each row gives the time a call
and the HBM floor of ``benchmarks/kernels/selective_scan.py``'s bytes; a
state that differs from the committed blocks' is a fault. PERF.md section
6 has the table this printed for PR 26.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import device  # noqa: E402
from benchmarks.kernels import selective_scan as bytes_of  # noqa: E402
from tpu_dist.ops import selective_scan as ss  # noqa: E402
from tpu_dist.runtime import enable_compile_cache  # noqa: E402

BLOCKS = [(16, 512), (64, 512), (256, 512), (256, 256), (256, 128),
          (128, 1024), (256, 1024), (128, 2560)]


def inputs(b, length, ch, n, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return dict(
        u=jax.random.normal(ks[0], (b, length, ch), jnp.bfloat16),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (b, length, ch)) - 3.0),
        A=-jnp.exp(0.3 * jax.random.normal(ks[2], (ch, n))),
        B=jax.random.normal(ks[3], (b, length, n)),
        C=jax.random.normal(ks[4], (b, length, n)),
        D=jax.random.normal(ks[5], (ch,)),
        s0=jnp.zeros((b, n, ch), jnp.float32))


def best_of(fn, *args, reps=5):
    out = jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(reps):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t)
    return min(seconds), out


def chained(form, layers):
    """``layers`` scans of one prompt, each from the state the last left:
    ``form`` is None (the committed blocks), a (time, channel) pair, or
    "plain"."""
    def run(x, lengths):
        args = ss._scan_args(**x, lengths=lengths)
        s, y = args[-1], None
        for _ in range(layers):
            if form is None:
                y, s = ss.selective_scan(**dict(x, s0=s), lengths=lengths)
            elif form == "plain":
                y, s = ss._scan_plain(*args[:-1], s)
            else:
                y, s = ss._scan_pallas(*args[:-1], s, *form, None)
        return y, s
    return jax.jit(run)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--layers", type=int, default=26)
    ap.add_argument("--lengths", default="256,1024")
    ap.add_argument("--slots", type=int, default=32)
    args = ap.parse_args()
    enable_compile_cache()
    ch, n = args.channels, args.states
    gbs = device.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"] \
        if jax.default_backend() == "tpu" else float("nan")
    for length in (int(v) for v in args.lengths.split(",")):
        x = inputs(1, length, ch, n)
        lengths = jnp.asarray([length - 7], jnp.int32)
        floor = bytes_of.scan(1, length, ch, n, 2)["bytes"] / gbs
        base = None
        for form in [None] + BLOCKS + ["plain"]:
            if isinstance(form, tuple) and (length % form[0] or ch % form[1]):
                continue
            t, out = best_of(chained(form, args.layers), x, lengths,
                             reps=3 if form == "plain" else 5)
            base = out if base is None else base
            print(json.dumps(dict(
                length=length, blocks=form or ss.scan_blocks(length, ch),
                us_a_call=1e6 * t / args.layers, hbm_floor_us=1e6 * floor,
                state_differs_by=float(jnp.abs(out[1] - base[1]).max()))),
                flush=True)
    # the tick's one-step form, every slot's state read and written a layer
    x = inputs(args.slots, 1, ch, n, key=1)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (args.slots, n, ch))

    def tick(x, s):
        total = 0.0
        for i in range(args.layers):
            y, s = ss.ssm_step(x["u"][:, 0], x["delta"][:, 0] * (1 + 0.01 * i),
                               x["A"], x["B"][:, 0], x["C"][:, 0], x["D"], s)
            total = total + y
        return total, s

    t, _ = best_of(jax.jit(tick), x, s0)
    print(json.dumps(dict(
        ssm_step_us_a_layer=1e6 * t / args.layers,
        state_floor_us=1e6 * 2 * args.slots * n * ch * 4 / gbs)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
