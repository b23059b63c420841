#!/usr/bin/env python3
"""Whose is the ``phi4flash`` serving cell's logit gap: bfloat16's or the
engine's? Once, on the chip, at the published widths:

    python3 benchmarks/precision_diag_phi4flash.py

A: the bfloat16 model's plain full forward (no cache, no engine) over 1024
tokens against ``reference/phi4flash.py``, and the same with the logits
rounded to bfloat16. C: a float32 copy of the model cut to 12 layers (two
periods of each half beside the full layer and the Mamba layer before it:
the float32 weights of all 32 are 15.4 GB and do not fit the chip),
``highest`` matmuls, its full forward and 24 tokens a request served
through ``ServeEngine`` from prompts under, around and past the 512-token
window, against the reference. PERF.md section 6 has what this printed for
PR 35: it is what the cell's ``limits_from`` rests on.
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

from benchmarks.families.phi4flash_lm_server import model_fields  # noqa: E402
from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness.trainers import as_engine_tree, fold_seed  # noqa: E402
from benchmarks.precision_diag import report  # noqa: E402
from benchmarks.reference import phi4flash as ref  # noqa: E402

CELL = "phi-4-mini-flash-reasoning.serve-reason"


def build(sizes, dtype, seed):
    from tpu_dist.models.phi4flash import Phi4FlashLM
    from tpu_dist.ops.flash_attention import flash_attention_fn

    model = Phi4FlashLM(**model_fields(sizes), dtype=dtype,
                        attn_fn=flash_attention_fn(block_k=1024))
    like = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    w = jax.jit(lambda key: ref.make_weights(sizes, key, dtype))(
        fold_seed(seed))
    return model, w, as_engine_tree(w, like, ref.ref_name, dtype)


def main() -> int:
    from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
    from tpu_dist.runtime import enable_compile_cache

    cfg = cells.load_cell(ROOT, CELL).config
    enable_compile_cache()
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (1, 1024)),
                       jnp.int32)
    forward = lambda model: jax.jit(
        lambda p, x: model.apply({"params": p}, x))

    model, w, params = build(cfg, jnp.bfloat16, 2147470001)
    got, want = forward(model)(params, toks)[0], ref.forward(w, toks, cfg)[0]
    report("A bf16, every layer, full forward", want, got)
    report("A' the same, logits rounded to bf16", want,
           got.astype(jnp.bfloat16).astype(jnp.float32))
    del model, w, params, got, want

    jax.config.update("jax_default_matmul_precision", "highest")
    cut = dict(cfg, num_hidden_layers=12)
    model, w, params = build(cut, jnp.float32, 7)
    report("C fp32, 12 layers, full forward",
           ref.forward(w, toks, cut)[0], forward(model)(params, toks)[0])
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
    print("C fp32, 12 layers, through ServeEngine", json.dumps(dict(
        tokens=int(gaps.size), gap_mean=float(gaps.mean()),
        gap_max=float(gaps.max()), flips=int((gaps > 0).sum()),
        read=eng.tick_read)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
