"""Optimizer + LR schedule (reference components C19 and the SGD setup).

Reference recipe: SGD momentum 0.9, weight decay 1e-4, lr 0.1 stepped x0.1
every 30 epochs by mutating param_groups (reference 1.dataparallel.py:114-116,
332-336); horovod scales base lr by world size (reference
5.2.horovod_pytorch_mnist.py:159-171) and supports a gradient predivide factor
(reference 5.2...py:185).

TPU-first: the schedule is a pure function of the step counter evaluated
*inside* the jitted update (no host mutation of optimizer state), built on
optax. Weight decay matches torch SGD semantics exactly: wd*param is added to
the gradient *before* momentum (optax.add_decayed_weights ordering).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
import optax


def step_decay_schedule(base_lr: float, steps_per_epoch: int,
                        step_epochs: int = 30, factor: float = 0.1
                        ) -> Callable:
    """lr = base * factor^(epoch // step_epochs)  (reference 1.dataparallel.py:332-336)."""
    def schedule(step):
        epoch = step // max(steps_per_epoch, 1)
        return base_lr * factor ** (epoch // step_epochs)
    return schedule


def lm_lr_schedule(base_lr: float, kind: str = "constant",
                   warmup_steps: int = 0, total_steps: int = 0,
                   steps_per_epoch: int = 1, step_epochs: int = 30,
                   factor: float = 0.1, min_frac: float = 0.0) -> Callable:
    """LM learning-rate schedule: linear warmup into constant | cosine |
    step decay (VERDICT r3 #2 — the LM engine had no schedule at all).

    A pure function of the optimizer step, evaluated INSIDE the jitted
    update like :func:`step_decay_schedule`; resume-safe because the step
    count lives in the checkpointed optax state, so the trajectory
    continues exactly across a --resume boundary.

    * warmup: lr ramps linearly from base/warmup_steps to base over the
      first ``warmup_steps`` updates (step 0 applies a nonzero lr).
    * constant: base thereafter.
    * cosine: half-cosine from base to ``min_frac * base`` over
      ``total_steps - warmup_steps`` updates, flat at the floor after.
    * step: the reference's C19 decay — x ``factor`` every ``step_epochs``
      epochs of ``steps_per_epoch`` (reference 1.dataparallel.py:332-336).
    """
    if kind not in ("constant", "cosine", "step"):
        raise ValueError(f"unknown lr schedule {kind!r} "
                         "(constant|cosine|step)")
    if kind == "cosine" and total_steps <= warmup_steps:
        raise ValueError(f"cosine needs total_steps ({total_steps}) > "
                         f"warmup_steps ({warmup_steps})")

    def schedule(step):
        s = jnp.asarray(step, jnp.float32)
        if kind == "cosine":
            horizon = jnp.float32(max(total_steps - warmup_steps, 1))
            t = jnp.clip((s - warmup_steps) / horizon, 0.0, 1.0)
            lr = base_lr * (min_frac
                            + (1.0 - min_frac) * 0.5 * (1.0 + jnp.cos(
                                jnp.float32(jnp.pi) * t)))
        elif kind == "step":
            epoch = jnp.floor(s / max(steps_per_epoch, 1))
            lr = base_lr * jnp.power(jnp.float32(factor),
                                     jnp.floor(epoch / step_epochs))
        else:
            lr = jnp.float32(base_lr)
        if warmup_steps:
            warm = base_lr * (s + 1.0) / jnp.float32(warmup_steps)
            lr = jnp.where(s < warmup_steps, warm, lr)
        return lr

    return schedule


def make_optimizer(lr: float, momentum: float = 0.9, weight_decay: float = 1e-4,
                   steps_per_epoch: int = 1, lr_step_epochs: int = 30,
                   schedule: Optional[Callable] = None, kind: str = "sgd",
                   b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                   grad_clip: float = 0.0
                   ) -> optax.GradientTransformation:
    """torch.optim.SGD(momentum, weight_decay)-equivalent with step-decay LR,
    or decoupled AdamW (``kind='adamw'``) — the transformer-family default
    the reference (image-only, SGD throughout) never needed. b2 defaults to
    0.95, the large-LM convention, not torch's 0.999.

    Horovod's gradient_predivide_factor lives in the explicit-psum step
    (tpu_dist.plan.compile._image_explicit_train), matching horovod's
    placement around the allreduce — NOT here, so it cannot double-apply.
    """
    sched = schedule or step_decay_schedule(lr, steps_per_epoch, lr_step_epochs)
    # grad_clip > 0: clip the RAW gradient by global norm BEFORE any
    # momentum/adam statistics (torch.nn.utils.clip_grad_norm_ placement)
    clip = ([optax.clip_by_global_norm(grad_clip)] if grad_clip > 0 else [])
    if kind == "adamw":
        # decoupled wd (AdamW): applied AFTER the adam scaling, with lr.
        # Unwrapped when no clip so the opt_state pytree structure (and
        # therefore existing adamw checkpoints) is unchanged at the default.
        adamw = optax.adamw(learning_rate=sched, b1=b1, b2=b2, eps=eps,
                            weight_decay=weight_decay)
        return optax.chain(*clip, adamw) if clip else adamw
    if kind != "sgd":
        raise ValueError(f"unknown optimizer kind {kind!r} (sgd|adamw)")
    chain = list(clip)
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    # torch SGD momentum: buf = mu*buf + grad; update = -lr*buf
    chain.append(optax.trace(decay=momentum, nesterov=False))
    chain.append(optax.scale_by_learning_rate(sched))
    return optax.chain(*chain)


def current_lr(schedule: Callable, step) -> jnp.ndarray:
    return jnp.asarray(schedule(step))
