#!/usr/bin/env python3
"""The routed products of a tick and of the short prefill buckets alone, on
the chip, by how many of the held experts are hit:

    python3 benchmarks/kernels/routed_tick_bench.py [--rows 64,128,256,512]
        [--hit 25,50,67,77,100] [--tiles 896,2688]

One expert layer's ``ops.routed_experts.routed_experts`` (latent 1024, 128
held experts of width 2688 out of 512, top 22: the
``nemotron-3-super-120b-a12b-ep4`` cell's) in its two forms up to
``DENSE_ROWS`` rows, here taken above that limit too: the kernel that walks
the hit list, and the einsum form over every held expert (what a layer with
dequantised weights still takes, ``stored=False``). The hit share is made by construction: every row's held
choices come from a chosen subset of the held experts, spread so that each
of the subset is chosen at least once, the subset scattered over the block;
the other choices name experts of other chips. A line a (rows, hit share):
ms a call (20 calls issued back to back and the last one waited for, the
best of three such trains, so a call's dispatch is hidden as it is in a
tick), GB/s over the hit experts' bytes alone (the benchmark's floor counts
those: ``benchmarks/kernels/routed_experts.py:tick``), and the largest
difference between the two forms' results; beside them the sorted form
(``DENSE_ROWS`` forced to 0), whose time goes with the assignments and not
with the experts hit. ``--tiles`` times the kernel at other width tiles too.
The last lines are the two the kernel has to show before a cell run: its
time follows the hit count, and with every expert hit it is no slower than
the einsum form. PERF.md section 6 has the table this printed for PR 41: the
kernel took the einsum form's place at every row count, and ``DENSE_ROWS``
came down to 256, where the sorted form starts to win.
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
from tpu_dist.ops import routed_experts as rx  # noqa: E402
from tpu_dist.runtime import enable_compile_cache  # noqa: E402

TRAIN = 20


def ms_a_call(fn, *args, trains=3):
    out = jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(trains):
        t = time.perf_counter()
        for _ in range(TRAIN):
            out = fn(*args)
        jax.block_until_ready(out)
        seconds.append((time.perf_counter() - t) / TRAIN)
    return 1e3 * min(seconds), out


def choices(rng, rows, n_hit, held, router, top_k):
    """``idx`` [rows, top_k] whose held choices hit exactly ``n_hit`` of the
    ``held`` experts (a scattered subset, each at least once), about a
    quarter of a row's choices as in a four-way share; the rest name experts
    of the other shares. ``w`` as :func:`rx.route` scales them."""
    subset = np.sort(rng.choice(held, n_hit, replace=False))
    here = min(max(top_k * held // router, -(-n_hit // rows)), n_hit, top_k)
    idx = np.empty((rows, top_k), np.int32)
    for r in range(rows):
        idx[r, :here] = subset[(r * here + np.arange(here)) % n_hit]
        idx[r, here:] = rng.choice(np.arange(held, router), top_k - here,
                                   replace=False)
    s = rng.uniform(0.2, 1.0, (rows, top_k)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(5.0 * s / s.sum(-1, keepdims=True))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="64,128,256,512")
    ap.add_argument("--hit", default="25,50,67,77,100")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--latent", type=int, default=1024)
    ap.add_argument("--width", type=int, default=2688)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--router", type=int, default=512)
    ap.add_argument("--top-k", type=int, default=22)
    args = ap.parse_args()
    kind = device.require_tpu(1)[0].device_kind
    enable_compile_cache()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    w_in = 0.02 * jax.random.normal(
        ks[0], (args.held, args.latent, args.width), jnp.bfloat16)
    w_out = 0.02 * jax.random.normal(
        ks[1], (args.held, args.width, args.latent), jnp.bfloat16)
    expert_bytes = 2 * args.latent * args.width * w_in.dtype.itemsize
    tiles = [int(t) for t in args.tiles.split(",") if t]
    rng = np.random.default_rng(0)
    table = {}

    # the weights are arguments: a closure would bake 1.4 GB constants into
    # every program. A function of its own a timing: jit caches by the
    # function, and the tile and the row limit are read while it is traced
    def form(stored):
        return jax.jit(lambda u, idx, w, live, w_in, w_out:
                       rx.routed_experts(u, idx, w, live, w_in, w_out, 0,
                                         stored=stored))

    tile, limit = rx._HIT_WIDTH_TILE, rx.DENSE_ROWS
    rx.DENSE_ROWS = 1 << 30
    for rows in (int(r) for r in args.rows.split(",")):
        u = jax.random.normal(ks[2], (rows, args.latent), jnp.bfloat16)
        live = jnp.ones((rows,), bool)
        for share in (int(s) for s in args.hit.split(",")):
            n_hit = max(1, round(args.held * share / 100))
            idx, w = choices(rng, rows, n_hit, args.held, args.router,
                             args.top_k)
            operands = (u, idx, w, live, w_in, w_out)
            t_kernel, got = ms_a_call(form(True), *operands)
            t_einsum, want = ms_a_call(form(False), *operands)
            assert int(got[2]) == int(want[2]) == n_hit, (got[2], n_hit)
            rx.DENSE_ROWS = 0
            t_sorted, _ = ms_a_call(form(True), *operands)
            rx.DENSE_ROWS = 1 << 30
            line = dict(
                rows=rows, hit_share=share, experts_hit=n_hit,
                assignments=int(got[1]), kernel_ms=t_kernel,
                einsum_ms=t_einsum, sorted_ms=t_sorted,
                kernel_gb_s=n_hit * expert_bytes / t_kernel / 1e6,
                einsum_gb_s=n_hit * expert_bytes / t_einsum / 1e6,
                kernel_less_einsum_max=float(
                    jnp.abs(got[0] - want[0]).max()),
                result_max=float(jnp.abs(want[0]).max()))
            for other in tiles:
                rx._HIT_WIDTH_TILE = other
                line[f"kernel_ms_tile{other}"] = ms_a_call(
                    form(True), *operands)[0]
            rx._HIT_WIDTH_TILE = tile
            table[rows, share] = line
            print("routed tick " + json.dumps(line), flush=True)
    rx.DENSE_ROWS = limit
    for rows in sorted({r for r, _ in table}):
        shares = sorted(s for r, s in table if r == rows)
        low, full = table[rows, shares[0]], table[rows, shares[-1]]
        print(f"rows {rows}: {shares[0]}% hit takes "
              f"{100 * low['kernel_ms'] / full['kernel_ms']:.1f}% of "
              f"{shares[-1]}% hit's time (to follow the hit count: under "
              f"40% at 25 of 100); {shares[-1]}% hit: kernel "
              f"{full['kernel_ms']:.3f} ms, einsum {full['einsum_ms']:.3f} "
              f"ms ({'no slower' if full['kernel_ms'] <= full['einsum_ms'] else 'SLOWER'}), "
              f"sorted {full['sorted_ms']:.3f} ms", flush=True)
    print(json.dumps({"ok": True, "device_kind": kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
