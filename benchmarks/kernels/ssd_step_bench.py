#!/usr/bin/env python3
"""The one-step Mamba-2 state update of a tick alone, on the chip, by how
many of the slots decode:

    python3 benchmarks/kernels/ssd_step_bench.py [--live 8,16,24,32,48,64]
        [--tiles 16,32,64]

One layer's step at the ``nemotron-3-super-120b-a12b-ep4`` cell's shape (64
slots, 128 heads of 64 channels, 128 states in 8 groups, bfloat16
activations, float32 state) in its two forms: the kernel that walks the live
rows and updates their state in place (``ops.ssd.ssd_step_live``) at each
head tile asked for, and the plain form the parent's tick ran (``ssd_step``
over every slot with ``dt = 0`` where a slot sits out, behind a ``where``
that zeroes a fresh row). The live rows are a scattered subset of the
slots. Both are jitted with the state donated and handed from step to step,
as the tick program hands it on: a program is eight steps, each fed the
last one's ``y`` (times zero, which the compiler may not fold) so that
nothing of a step is shared with the next, because one step of the kernel
over a few rows is shorter than the host's dispatch of it. A line a live
count: ms a step (ten programs issued back to back and the last one waited
for, the best of three such trains), GB/s over the live rows' bytes alone
(the benchmark's floor counts those: ``benchmarks/kernels/ssd_step.py:
step``) and their share of the HBM rate, and the largest difference between
the two forms' ``y`` and live states after one step from the same state.
The last lines are the two the kernel has to show before a cell run:
its time falls with the live rows, and with every slot live it is no slower
than the plain form. PERF.md section 6 has the tables this printed for PR 45
(five forms of the kernel's body), which fixed the head tile
(``ops.ssd._STEP_BLOCK_BYTES``).
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
import numpy as np  # noqa: E402

from benchmarks.harness import device  # noqa: E402
from benchmarks.kernels import ssd_step as counts  # noqa: E402
from tpu_dist.ops import ssd  # noqa: E402
from tpu_dist.runtime import enable_compile_cache  # noqa: E402

TRAIN, STEPS = 10, 8


def steps(step):
    """``step(x, ..., s) -> (y, s)`` as one program of ``STEPS`` steps with
    the state donated."""
    def fn(x, *rest):
        *small, s = rest

        def one(_, carry):
            x, s = carry
            y, s = step(x, *small, s)
            return (x + 0 * y).astype(x.dtype), s

        return jax.lax.fori_loop(0, STEPS, one, (x, s))
    return jax.jit(fn, donate_argnums=(8,))


def ms_a_step(fn, small, s, trains=3):
    x, s = jax.block_until_ready(fn(*small, s))
    seconds = []
    for _ in range(trains):
        t = time.perf_counter()
        for _ in range(TRAIN):
            x, s = fn(*small, s)
        jax.block_until_ready((x, s))
        seconds.append((time.perf_counter() - t) / (TRAIN * STEPS))
    return 1e3 * min(seconds)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--live", default="8,16,24,32,48,64")
    ap.add_argument("--tiles", default="16,32,64")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--d-state", type=int, default=128)
    ap.add_argument("--groups", type=int, default=8)
    args = ap.parse_args()
    dev = device.require_tpu(1)[0]
    peaks = device.peaks(dev.device_kind)
    enable_compile_cache()
    slots, h, p, n, g = (args.slots, args.heads, args.head_dim, args.d_state,
                         args.groups)
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (slots, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    b, c = (jax.random.normal(k, (slots, g, n), jnp.bfloat16)
            for k in ks[3:5])
    d = jnp.ones((h,), jnp.float32)
    fresh = jnp.zeros((slots,), jnp.int32)
    state = lambda: jax.random.normal(ks[5], (slots, h, p, n), jnp.float32)
    tiles = [int(t) for t in args.tiles.split(",") if t]
    rng = np.random.default_rng(0)

    @jax.jit
    def apart(y0, s0, y1, s1, live):
        on = live > 0
        return (jnp.abs(jnp.where(on[:, None, None], y0.astype(jnp.float32)
                                  - y1.astype(jnp.float32), 0.0)).max(),
                jnp.abs(jnp.where(on[:, None, None, None], s0 - s1,
                                  0.0)).max())

    def plain(x, dt, a, b, c, d, live, fresh, s):
        s = jnp.where((fresh > 0)[:, None, None, None], 0.0, s)
        dt = jnp.where((live > 0)[:, None], dt, 0.0)
        return ssd.ssd_step(x, dt, a, b, c, d, s)

    def kernel(tile):
        return lambda x, dt, a, b, c, d, live, fresh, s: ssd.ssd_step_live(
            x, dt, a, b, c, d, s, ssd.live_rows(live), fresh, tile)

    forms = {"plain": plain, **{f"tile{t}": kernel(t) for t in tiles}}
    once = {k: jax.jit(f, donate_argnums=(8,)) for k, f in forms.items()}
    many = {k: steps(f) for k, f in forms.items()}
    table = {}
    for n_live in (int(v) for v in args.live.split(",")):
        live = np.zeros((slots,), np.int32)
        live[rng.choice(slots, n_live, replace=False)] = 1
        live = jnp.asarray(live)
        small = (x, dt, a, b, c, d, live, fresh)
        least = counts.step(n_live, h, p, n, g, x.dtype.itemsize)["bytes"]
        y_plain, s_plain = once["plain"](*small, state())
        line = dict(live=n_live, slots=slots, live_bytes=least)
        for name in forms:
            y_one, s_one = once[name](*small, state())
            dy, ds = apart(y_one, s_one, y_plain, s_plain, live)
            ms = ms_a_step(many[name], small, state())
            line.update({f"{name}_ms": ms,
                         f"{name}_gb_s": least / ms / 1e6,
                         f"{name}_floor_pct": 100.0 * least / ms / 1e-3
                         / peaks["hbm_bytes_per_s"],
                         f"{name}_y_apart": float(dy),
                         f"{name}_s_apart": float(ds)})
        table[n_live] = line
        print("ssd step " + json.dumps(line), flush=True)
    few, full = table[min(table)], table[max(table)]
    for t in tiles:
        k = f"tile{t}_ms"
        slower = full[k] > full["plain_ms"]
        print(f"tile {t}: {few['live']} live takes "
              f"{100 * few[k] / full[k]:.1f}% of {full['live']} live's time; "
              f"{full['live']} live of {slots}: kernel {full[k]:.3f} ms, "
              f"plain {full['plain_ms']:.3f} ms "
              f"({'SLOWER' if slower else 'no slower'})", flush=True)
    print(json.dumps({"ok": True, "device_kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
