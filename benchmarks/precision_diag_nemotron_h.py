#!/usr/bin/env python3
"""Whose is the ``nemotron_h`` serving cell's logit gap: bfloat16's, the
routing's, or the engine's? Once, on the chip, at the published widths:

    python3 benchmarks/precision_diag_nemotron_h.py

A: the bfloat16 model's plain full forward (no cache, no engine) over 1024
tokens against ``reference/nemotron_h.py``, with the share of (row, expert
layer) pairs whose chosen set differs. C: a float32 copy of the model cut to
its first 8 layers, ``MEMEMEM*`` (4 Mamba-2, 3 expert layers of 128 held
experts, the attention layer: 12.1 GB; the float32 weights of all 11 are
18.6 GB and do not fit the chip), ``highest`` matmuls, its full forward and
24 tokens a request served through ``ServeEngine`` (chunked-form prefills in
four buckets, the sorted and the dense routed forms, the one-step ticks)
against the reference, again with the chosen sets. PERF.md section 6 has
what this printed for PR 40: it is what the cell's ``limits_from`` rests on.

    python3 benchmarks/precision_diag_nemotron_h.py --fault <name> [--seeds 21,22]

instead reads the cell's own comparison (``benchmarks/control.py``'s short
windows of the sound bfloat16 engine at the cell's load) over a program with
a fault planted under it: what the two limits see of a wrong routed layer,
which the lower-precision control cannot say of the widest gap (int8 weights
do not move it). ``shifted_block``: the program takes its held experts to be
the router's 1-128 where its weights are those of 0-127. ``dropped_last``:
the last of each row's 22 kept experts gets the weight 0 (the others are not
normalised again).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.families.nemotron_h_lm_server import (  # noqa: E402
    forward_with_chosen, model_fields)
from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness.trainers import as_engine_tree, fold_seed  # noqa: E402
from benchmarks.precision_diag import report  # noqa: E402
from benchmarks.reference import nemotron_h as ref  # noqa: E402

CELL = "nemotron-3-super-120b-a12b-ep4.serve-assistant"


def build(sizes, dtype, seed):
    from tpu_dist.models.nemotron_h import NemotronHLM
    from tpu_dist.ops.flash_attention import flash_attention_fn

    model = NemotronHLM(**model_fields(sizes), dtype=dtype,
                        attn_fn=flash_attention_fn(block_k=1024))
    like = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    w = jax.jit(lambda key: ref.make_weights(sizes, key, dtype))(
        fold_seed(seed))
    return model, w, as_engine_tree(w, like, ref.ref_name, dtype)


def differing(ours, theirs) -> str:
    pairs = sum(int(a.shape[0] * a.shape[1]) for a in theirs)
    differ = sum(int(jnp.sum(jnp.any(a != b, -1)))
                 for a, b in zip(ours, theirs))
    return f"chosen set differs in {differ} of {pairs} (row, layer) pairs"


def plant(fault: str) -> None:
    """Break the program underneath, where the model looks its routing up
    when a program is traced."""
    import tpu_dist.ops.routed_experts as rx
    import tpu_dist.parallel.ep as ep

    if fault == "shifted_block":
        real_share = ep.expert_share

        def expert_share(num_experts, of, index):
            lo, n = real_share(num_experts, of, index)
            return lo + 1, n

        ep.expert_share = expert_share
    elif fault == "dropped_last":
        real_route = rx.route

        def route(logits, b_sel, top_k, scale):
            idx, w = real_route(logits, b_sel, top_k, scale)
            return idx, w.at[:, -1].set(0.0)

        rx.route = route
    else:
        raise ValueError(f"fault {fault!r}: shifted_block | dropped_last")


def read_planted(cell, fault: str, seeds, devices, seconds: float) -> dict:
    from benchmarks import control

    plant(fault)
    print(f"planted: {fault}", flush=True)
    return control.read_serving(cell, list(seeds), (), devices, seconds)


def main() -> int:
    import argparse

    from benchmarks.harness import device
    from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
    from tpu_dist.runtime import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seeds", default="21")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    cell = cells.load_cell(ROOT, CELL)
    cfg = cell.config
    enable_compile_cache()
    if args.fault:
        read_planted(cell, args.fault, [int(s) for s in args.seeds.split(",")],
                     device.require_tpu(cell.chips), args.seconds)
        return 0
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (1, 1024)),
                       jnp.int32)

    model, w, params = build(cfg, jnp.bfloat16, 2147470001)
    theirs = []
    want = ref.forward(w, toks, cfg, chosen=theirs)[0]
    got, ours = forward_with_chosen(model, cfg["router_width"])(params, toks)
    report("A bf16, every layer, full forward", want, got[0])
    print("A", differing(ours, theirs), flush=True)
    del model, w, params, got, want, ours, theirs

    jax.config.update("jax_default_matmul_precision", "highest")
    cut = dict(cfg, num_hidden_layers=8,
               hybrid_override_pattern=cfg["hybrid_override_pattern"][:8])
    model, w, params = build(cut, jnp.float32, 7)
    theirs = []
    want = ref.forward(w, toks, cut, chosen=theirs)[0]
    got, ours = forward_with_chosen(model, cfg["router_width"])(params, toks)
    report("C fp32, 8 layers, full forward", want, got[0])
    print("C", differing(ours, theirs), flush=True)
    del got, want, ours, theirs
    eng = ServeEngine(model, params, ServeConfig(
        max_slots=4, page_size=16, num_pages=512, max_len=2048))
    done = eng.run([DecodeRequest(
        rid=i, prompt=rng.integers(0, cfg["vocab_size"], n).astype(np.int32),
        max_new_tokens=24) for i, n in enumerate([700, 77, 505, 1100, 40])])
    programs, gaps = ref.layer_programs(cut), []
    for c in done:
        x = jnp.asarray(c.tokens[None])
        lg = ref.forward(w, x, cut, programs)[0]
        gap = lg.max(-1) - jnp.take_along_axis(
            lg, jnp.roll(x[0], -1)[:, None], 1)[:, 0]
        gaps.append(np.asarray(gap[c.prompt_len - 1:len(c.tokens) - 1]))
    gaps = np.concatenate(gaps)
    st = eng.stats()
    print("C fp32, 8 layers, through ServeEngine", json.dumps(dict(
        tokens=int(gaps.size), gap_mean=float(gaps.mean()),
        gap_max=float(gaps.max()), flips=int((gaps > 0).sum()),
        read=eng.tick_read, expert_rows=st["expert_rows"],
        experts_hit_mean=st["experts_hit_mean"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
