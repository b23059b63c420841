"""Train state pytree.

The reference's mutable triple (model, optimizer, amp state) spread across
wrapper objects (reference 2.distributed.py:114-120, 4.apex_distributed2.py:
177-178) becomes one immutable pytree threaded through the jitted step —
the functional JAX idiom. ``batch_stats`` carries BatchNorm running stats
(torch buffers); ``loss_scale`` is the optional apex-style dynamic scale.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax

from tpu_dist.ops.precision import LossScaleState


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    loss_scale: Optional[LossScaleState] = None

    @classmethod
    def create(cls, params, batch_stats, tx: optax.GradientTransformation,
               loss_scale: Optional[LossScaleState] = None) -> "TrainState":
        return cls(step=jnp.int32(0), params=params, batch_stats=batch_stats,
                   opt_state=tx.init(params), loss_scale=loss_scale)


def init_model(model, rng: jax.Array, input_shape, train: bool = True):
    """Initialize params/batch_stats with a dummy batch (static shapes), as
    ONE compiled program: eager flax init dispatches a tiny program an
    initializer, a reshape and a cast (some 200 backend compilations for
    ResNet-50, most under the persistent cache's 0.1 s floor, so every
    process paid them again). On the CPU backend the values are bitwise the
    eager ones (``tests/test_engine.py``: resnet18/50, vit_tiny) but for
    leaves drawn as ``random.normal`` times a constant deviation (a ViT's
    ``pos_embed``; ``LMTrainer``'s embeddings, lm_loop.py): one program
    rounds the product of the normal's own sqrt(2) and the deviation once
    where eager calls round twice, a last bit in part of the leaf. On the
    chip they differ in the last bits, as any fused program may.
    Traceable: ``Trainer`` calls it inside the program that makes its whole
    state, where the inner jit is inlined."""
    def init(key):
        dummy = jnp.zeros(input_shape, jnp.float32)
        variables = model.init({"params": key, "dropout": key}, dummy,
                               train=False)
        return variables.get("params"), variables.get("batch_stats", {})
    return jax.jit(init)(rng)
