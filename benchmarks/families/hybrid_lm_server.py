"""Family ``hybrid_lm_server``: a Jamba-family configuration (Mamba-1 layers
beside grouped-head attention layers, ``tpu_dist.models.hybrid``) served by
``ServeEngine``: pages for the attention layers, per-slot recurrent state
for the Mamba layers.

Everything of the serving family that is not the model's construction, the
reference and the reading of the traffic mix is ``lm_server.Family``'s own
code, by import: the warm-up, the timed program, the open loop, the
end-to-end numbers and the comparison's sampling and limits.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmarks.families import lm_server  # noqa: E402
from benchmarks.harness import burst, traffic, window  # noqa: E402
from benchmarks.harness.trainers import as_engine_tree, fold_seed  # noqa: E402
from benchmarks.reference import jamba as ref  # noqa: E402


def model_fields(sizes: dict) -> dict:
    """The configuration's published keys under the model's field names
    (the file's ``source_keys``), with its assumed ``head_dim``."""
    fields = {ours: sizes[theirs]
              for ours, theirs in sizes["source_keys"].items()}
    return {**fields, "head_dim": sizes["head_dim"]}


class Family(lm_server.Family):

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        from tpu_dist.engine.serve import ServeConfig, ServeEngine
        from tpu_dist.models.hybrid import HybridLM
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
        model = HybridLM(**model_fields(s), dtype=dtype, attn_fn=attn)
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

    def run_window(self, seconds: float, mix: dict = None) -> dict:
        """``lm_server``'s window; an open-loop mix that states a ``burst``
        group is read by ``harness/burst.py``, one without by
        ``harness/traffic.py`` (the steady sweep that finds the knee)."""
        mix = mix or self.cell.traffic
        make = (burst.burst_schedule if mix.get("burst")
                else traffic.open_loop_schedule)
        sched = make(mix, self.seed, seconds, self.sizes["vocab_size"],
                     self.max_len)
        adapter = lm_server._Adapter(self.eng, sched)
        t_open = time.monotonic() + float(mix.get("preroll_s", 0.0))
        res = window.drive_open_loop(
            adapter, sched.due, t_open,
            deadline=seconds + float(mix.get("drain_limit_s", 120.0)))
        return {"schedule": sched, "result": res, "t_open": t_open,
                "t_close": t_open + seconds, "seconds": seconds,
                "rate_per_s": mix["rate_per_s"]}

    def token_gaps(self, sample) -> List[float]:
        """Per served token of the sampled requests, the gap by which its
        reference logit lies below the reference's best at its position:
        one float32 forward of ``reference/jamba.py`` over prompt + answer,
        the served bfloat16 values cast one layer at a time."""
        import jax
        import jax.numpy as jnp

        def below_best(logits, x):
            # row t predicts token t + 1
            return logits.max(-1) - jnp.take_along_axis(
                logits, jnp.roll(x, -1)[:, None], 1)[:, 0]

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
                x = jnp.asarray(padded)
                logits = ref.forward(weights, x, self.sizes, programs)
                served = jax.device_get(tail(logits[0], x[0]))
                served_gaps.extend(served[plen - 1:len(toks) - 1].tolist())
        return served_gaps
