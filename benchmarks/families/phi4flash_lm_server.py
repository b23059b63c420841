"""Family ``phi4flash_lm_server``: a ``phi4flash`` configuration (Mamba-1,
window and full differential attention, cross layers over one shared KV
layer, gated memory units: ``tpu_dist.models.phi4flash``) served by
``ServeEngine``: window rings, one layer's pages and per-slot recurrent
state.

Everything of the serving family that is not the model's construction and
the reference is ``lm_server.Family``'s own code, by import: the warm-up,
the timed program, the open loop, the end-to-end numbers and the
comparison's sampling and limits.
"""

from __future__ import annotations

import os
import sys
from typing import List

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmarks.families import lm_server  # noqa: E402
from benchmarks.harness.trainers import as_engine_tree, fold_seed  # noqa: E402
from benchmarks.reference import phi4flash as ref  # noqa: E402


def model_fields(sizes: dict) -> dict:
    """The configuration's keys under the model's field names (the file's
    ``source_keys``: the published ones and the assumed sizes)."""
    return {ours: sizes[theirs]
            for ours, theirs in sizes["source_keys"].items()}


class Family(lm_server.Family):

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        from tpu_dist.engine.serve import ServeConfig, ServeEngine
        from tpu_dist.models.phi4flash import Phi4FlashLM
        from tpu_dist.models.transformer import full_attention
        from tpu_dist.ops.flash_attention import flash_attention_fn

        s, e = self.sizes, self.engine
        if e["attn"] == "flash":
            attn = flash_attention_fn(block_k=int(e["attn_block"]))
        elif e["attn"] == "full":
            attn = full_attention
        else:
            raise ValueError(f"attn {e['attn']!r}: flash | full")
        dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[e["precision"]]
        model = Phi4FlashLM(**model_fields(s), dtype=dtype, attn_fn=attn)
        like = jax.eval_shape(
            lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
            ["params"], jax.random.PRNGKey(0))
        self._weights_fn = jax.jit(
            lambda key: ref.make_weights(s, key, dtype))
        self._params = lambda: as_engine_tree(
            self._weights_fn(fold_seed(self.seed)), like, ref.ref_name, dtype)
        self._model = model
        with jax.default_device(self.devices[0]):
            self.eng = ServeEngine(model, self._params(),
                                   ServeConfig(**self.serve))
        self.max_len = self.eng.max_len

    def token_gaps(self, sample) -> List[float]:
        """Per served token of the sampled requests, the gap by which its
        reference logit lies below the reference's best at its position:
        one float32 forward of ``reference/phi4flash.py`` over prompt +
        answer, the served bfloat16 values cast one layer at a time, the
        head a block of rows at a time."""
        import jax
        import jax.numpy as jnp

        def below_best(logits, nxt):
            # row t predicts token t + 1
            return logits.max(-1) - jnp.take_along_axis(
                logits, nxt[:, None], 1)[:, 0]

        served_gaps = []
        with jax.default_device(self.devices[0]):
            weights = self._weights_fn(fold_seed(self.seed))
            programs = ref.layer_programs(self.sizes)
            tail = jax.jit(below_best)
            for plen, toks in sample:
                # padded to a power of two: every layer is causal, so the
                # padding stays out of the rows read, and a few compiled
                # lengths serve every request
                width = min(self.max_len,
                            max(128, 1 << (len(toks) - 1).bit_length()))
                padded = np.zeros((1, width), np.int32)
                padded[0, :len(toks)] = toks
                x = ref.hidden(weights, jnp.asarray(padded), self.sizes,
                               programs)
                nxt = jnp.asarray(np.roll(padded[0], -1))
                served = np.concatenate([
                    jax.device_get(tail(blk[0], nxt[lo:lo + blk.shape[1]]))
                    for lo, blk in ref.head_blocks(weights, x, programs)])
                served_gaps.extend(served[plen - 1:len(toks) - 1].tolist())
        return served_gaps
