#!/usr/bin/env python3
"""The routed experts' products alone, at a cell's shapes, on the chip:

    python3 benchmarks/kernels/routed_experts_bench.py [--rows 64,128,256,512,1024,2048]

One expert layer's ``ops.routed_experts.routed_experts`` over ``rows`` rows
(latent 1024, 128 held experts of width 2688 out of 512, top 22: the
``nemotron-3-super-120b-a12b-ep4`` cell's), in the two committed forms (the
masked dense one forced above its row limit too, the sorted one below it)
and, for the sorted form, its parts: the sort, counts and inverse
permutation alone; the two grouped products alone over rows already sorted,
by ``jax.lax.ragged_dot`` and by the megablox ``gmm`` kernel. Each row gives
the time a call (the best of five, a call ends in ``block_until_ready``) and
the dense form's difference from the sorted one. PERF.md section 6 has the
table this printed for PR 40: it is what chose the prefill's grouped product
and the dense form's row limit.
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
from tpu_dist.ops import routed_experts as rx  # noqa: E402
from tpu_dist.runtime import enable_compile_cache  # noqa: E402


def best_of(fn, *args, reps=5):
    out = jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(reps):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t)
    return min(seconds), out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="64,256,512,1024,2048")
    ap.add_argument("--latent", type=int, default=1024)
    ap.add_argument("--width", type=int, default=2688)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--router", type=int, default=512)
    ap.add_argument("--top-k", type=int, default=22)
    args = ap.parse_args()
    device.require_tpu(1)
    enable_compile_cache()
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w_in = 0.02 * jax.random.normal(
        ks[0], (args.held, args.latent, args.width), jnp.bfloat16)
    w_out = 0.02 * jax.random.normal(
        ks[1], (args.held, args.width, args.latent), jnp.bfloat16)
    limit = rx.DENSE_ROWS
    for rows in (int(r) for r in args.rows.split(",")):
        u = jax.random.normal(ks[2], (rows, args.latent), jnp.bfloat16)
        logits = 1.28 * jax.random.normal(ks[3], (rows, args.router))
        idx, w = jax.jit(lambda x: rx.route(
            x, jnp.zeros((args.router,)), args.top_k, 5.0))(logits)
        live = jnp.ones((rows,), bool)
        out = {}

        # the weights are arguments: a closure would bake 1.4 GB constants
        # into every program
        for form, lim in (("dense", 1 << 30), ("sorted", 0)):
            rx.DENSE_ROWS = lim
            # a function of its own a form: jit caches by the function
            whole = lambda u, idx, w, live, w_in, w_out: rx.routed_experts(
                u, idx, w, live, w_in, w_out, 0)
            out[form] = best_of(jax.jit(whole), u, idx, w, live, w_in, w_out)
        rx.DENSE_ROWS = limit
        diff = float(jnp.abs(out["dense"][1][0] - out["sorted"][1][0]).max())

        def order_of(idx):
            key = jnp.where(idx < args.held, idx, args.held)
            sizes = jnp.zeros((args.held + 1,), jnp.int32).at[
                key.reshape(-1)].add(1)[:args.held]
            order = jnp.argsort(key.reshape(-1), stable=True)
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.size, dtype=order.dtype))
            return sizes, order, back

        t_sort, (sizes, order, back) = best_of(jax.jit(order_of), idx)
        xs = jnp.take(u, order // args.top_k, axis=0)

        def by_ragged(xs, sizes, w_in, w_out):
            h = jax.lax.ragged_dot(xs, w_in, sizes)
            return jax.lax.ragged_dot(
                jnp.square(jax.nn.relu(h)), w_out, sizes,
                preferred_element_type=jnp.float32)

        def by_gmm(tiling):
            def run(xs, sizes, w_in, w_out):
                h = gmm(xs, w_in, sizes, jnp.bfloat16, tiling)
                return gmm(jnp.square(jax.nn.relu(h)), w_out, sizes,
                           jnp.float32, tiling)
            return run

        t_ragged, o_ragged = best_of(jax.jit(by_ragged), xs, sizes, w_in,
                                     w_out)
        line = dict(rows=rows, assignments=int(order.size),
                    held_assignments=int(sizes.sum()),
                    experts_hit=int((sizes > 0).sum()),
                    dense_ms=1e3 * out["dense"][0],
                    sorted_ms=1e3 * out["sorted"][0],
                    dense_less_sorted_max=diff, sort_ms=1e3 * t_sort,
                    ragged_dot_pair_ms=1e3 * t_ragged)
        n = int(sizes.sum())
        for tiling in ((128, 1024, 896), (256, 1024, 896), (512, 1024, 896)):
            if xs.shape[0] % tiling[0]:
                continue
            try:
                t, o = best_of(jax.jit(by_gmm(tiling)), xs, sizes, w_in,
                               w_out)
                line["gmm_pair_ms_%dx%dx%d" % tiling] = 1e3 * t
                line["gmm_less_ragged_max"] = float(
                    jnp.abs(o[:n] - o_ragged[:n]).max())
            except Exception as e:  # a tiling the kernel refuses
                line["gmm_%dx%dx%d" % tiling] = repr(e)[:120]
        print("routed experts " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
