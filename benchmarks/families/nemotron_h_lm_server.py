"""Family ``nemotron_h_lm_server``: a ``nemotron_h`` configuration (Mamba-2
layers, latent mixture-of-experts layers held as one rank of an
expert-parallel group, grouped-head attention:
``tpu_dist.models.nemotron_h``) served by ``ServeEngine``: per-slot
Mamba-2 state, one layer's pages, expert layers that cache nothing.

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
from benchmarks.reference import nemotron_h as ref  # noqa: E402


def model_fields(sizes: dict) -> dict:
    """The configuration's keys under the model's field names (the file's
    ``source_keys``), with the share of each expert layer that is here."""
    fields = {ours: sizes[theirs]
              for ours, theirs in sizes["source_keys"].items()}
    share = sizes["expert_share"]
    return {**fields, "expert_share": (share["of"], share["index"])}


def forward_with_chosen(model, router_width: int):
    """The model's plain forward, jitted: ``(params, tokens) -> (logits,
    [chosen [B, L, router_width] bool, one an expert layer in layer
    order])``, the chosen sets from what the routed layers sow."""
    import jax
    import jax.numpy as jnp

    def run(params, x):
        logits, sown = model.apply({"params": params}, x,
                                   mutable=["intermediates"])
        flat = jax.tree_util.tree_flatten_with_path(sown["intermediates"])[0]
        # 'layer10' comes after 'layer2': by the number, not the string
        picked = sorted((int(str(path[0].key)[5:]), idx) for path, idx in flat)
        return logits, [
            jnp.zeros(idx.shape[:2] + (router_width,), bool).at[
                jnp.arange(idx.shape[0])[:, None, None],
                jnp.arange(idx.shape[1])[None, :, None], idx].set(True)
            for _, idx in picked]

    return jax.jit(run)


class Family(lm_server.Family):

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        from tpu_dist.engine.serve import ServeConfig, ServeEngine
        from tpu_dist.models.nemotron_h import NemotronHLM
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
        model = NemotronHLM(**model_fields(s), dtype=dtype, attn_fn=attn)
        like = jax.eval_shape(
            lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
            ["params"], jax.random.PRNGKey(0))
        self._weights_fn = jax.jit(
            lambda key: ref.make_weights(s, key, dtype))
        self._as_tree = lambda weights: as_engine_tree(
            weights, like, ref.ref_name, dtype)
        self._params = lambda: self._as_tree(
            self._weights_fn(fold_seed(self.seed)))
        self._model = model
        with jax.default_device(self.devices[0]):
            self.eng = ServeEngine(model, self._params(),
                                   ServeConfig(**self.serve))
        self.max_len = self.eng.max_len

    def token_gaps(self, sample) -> List[float]:
        """Per served token of the sampled requests, the gap by which its
        reference logit lies below the reference's best at its position:
        one float32 forward of ``reference/nemotron_h.py`` over prompt +
        answer, the served bfloat16 values cast one layer (one expert of an
        expert layer) at a time.

        Routing makes the logits discontinuous: a near-tie at the last
        chosen place sends a row to another expert. So that a wide gap can
        be told from a wrong layer, the share of (row, expert layer) pairs
        whose chosen set differs between the program (the model's plain
        forward over the same tokens, in the served precision) and the
        reference is printed beside the check: information, no limit."""
        import jax
        import jax.numpy as jnp

        def below_best(logits, x):
            # row t predicts token t + 1
            return logits.max(-1) - jnp.take_along_axis(
                logits, jnp.roll(x, -1)[:, None], 1)[:, 0]

        served_gaps, differ, pairs = [], 0, 0
        with jax.default_device(self.devices[0]):
            weights = self._weights_fn(fold_seed(self.seed))
            params = self._as_tree(weights)      # the same arrays, as a tree
            programs = ref.layer_programs(self.sizes)
            tail = jax.jit(below_best)
            chosen_by = forward_with_chosen(self._model,
                                            self.sizes["router_width"])
            # rows below n whose sets differ (n traced: one program a width)
            count = jax.jit(lambda a, b, n: jnp.sum(
                jnp.any(a[0] != b[0], axis=-1)
                & (jnp.arange(a.shape[1]) < n)))
            for plen, toks in sample:
                # padded to a power of two: every layer is causal, so the
                # padding stays out of the rows read, and a few compiled
                # lengths serve every request
                width = min(self.max_len,
                            max(128, 1 << (len(toks) - 1).bit_length()))
                padded = np.zeros((1, width), np.int32)
                padded[0, :len(toks)] = toks
                x = jnp.asarray(padded)
                theirs = []
                logits = ref.forward(weights, x, self.sizes, programs,
                                     chosen=theirs)
                served = jax.device_get(tail(logits[0], x[0]))
                served_gaps.extend(served[plen - 1:len(toks) - 1].tolist())
                _, ours = chosen_by(params, x)
                differ += sum(int(count(a, b, len(toks) - 1))
                              for a, b in zip(ours, theirs))
                pairs += len(theirs) * (len(toks) - 1)
        if pairs:
            print(f"routing: the chosen set differs between the program's "
                  f"plain forward and the reference in {differ} of {pairs} "
                  f"(row, expert layer) pairs ({100.0 * differ / pairs:.3f}%)"
                  " on the sampled requests", flush=True)
        return served_gaps
