"""Language-model train/eval steps: DP, DP x TP, and DP x SP (ring attention).

Extends the image engine (tpu_dist.engine.steps) to token sequences — the
long-context, model-parallel half of the framework the reference never had.

This module holds the LM engine's step TEMPLATES — the ONE shared
objective (:func:`_lm_grads_and_metrics`) wrapped as the gspmd template
(:func:`_lm_step_fn`) and its explicit/ring/sp per-device flavors
(:func:`_lm_explicit_dp_step_fn` / :func:`_lm_tp_ring_step_fn` /
:func:`_lm_sp_step_fn`) — plus the eval kernel. The plan compiler
(``tpu_dist.plan.compile``) wraps them: its lowerings hold the jit /
shard_map / scan bodies and document each program's signature, and
``LMTrainer`` reaches them through ``compile_train_step(plan, bindings)``.

Mode map (``plan.ir.plan_from_config`` picks by mesh axes, like scripts/8):

* ``layout='dp'|'tp'``, ``sync='gspmd'`` — jit over a (data[, model]) mesh.
  Batch sharded on 'data'; with TP param shardings (tpu_dist.parallel.tp)
  GSPMD emits the Megatron collectives. Works for pure DP (no 'model' axis)
  unchanged.
* ``layout='sp'`` — shard_map over (data, seq): each device holds a sequence
  shard, attention runs as a ring over 'seq'
  (tpu_dist.parallel.ring_attention), grads/metrics psum over both axes.
  This is the blockwise/ring long-context regime: per-device activation
  memory scales with L/n_seq.

Loss: next-token cross entropy. Shift-by-one happens ON THE HOST over the
global (B, L+1) token rows BEFORE any sharding (:func:`make_lm_batches`):
inputs = rows[:, :-1], targets = rows[:, 1:]. A sequence shard's targets
therefore already contain the first token of the following shard, so
interior shard boundaries need no masking and the SP step's per-shard loss
sums are exact — only the final position of the global sequence is consumed
by the shift itself.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from tpu_dist.engine.state import TrainState
from tpu_dist.engine.steps import _apply_update
from tpu_dist.ops.fused_xent import chunked_softmax_xent
from tpu_dist.parallel.mesh import DATA_AXIS
from tpu_dist.plan.ir import Plan


LM_METRIC_KEYS = ("loss_sum", "correct1", "count")


def zeros_lm_metrics():
    """Additive identity for lm_loss_and_metrics sums — THE definition of
    the metric-key set (every eval/accumulator path builds from it, so a
    new metric key cannot silently desynchronize a tree.map)."""
    return {k: jnp.float32(0.0) for k in LM_METRIC_KEYS}


def lm_loss_and_metrics(logits, targets, mask):
    """Per-token CE sums. logits (B,L,V) fp32; targets (B,L); mask (B,L).

    nll = logsumexp - target_logit, NOT -log_softmax[target]: the
    log_softmax form materializes a second (B,L,V) fp32 tensor just to
    gather one column of it — the round-5 LM profile attributed ~4.8
    ms/step of pure HBM `sub` traffic to exactly that at the bench
    geometry. logsumexp reduces on the fly; same max-shifted math, same
    softmax-minus-onehot backward."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    tgt = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    loss_sum = jnp.sum(nll * mask)
    correct = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    return loss_sum, {
        "loss_sum": loss_sum,
        "correct1": jnp.sum(correct * mask),
        "count": jnp.sum(mask),
    }


def _apply_collect_aux(model, params, inputs, dropout_rng, pos_offset=0,
                       return_features=False):
    """Forward pass that also collects sown MoE intermediates.

    Returns (logits, aux, mass_sum, mass_n): only leaves sown under
    ``aux_loss`` enter the objective; ``combine_mass`` leaves (per-token
    combine weight — <1 when capacity dropped a token) are summed separately
    as a DIAGNOSTIC so training can report the dropped-token fraction
    without it ever leaking into the loss. Dense models return zeros.
    ``return_features=True`` yields post-ln_f features instead of logits
    (the chunked-loss path applies the head itself — ops.fused_xent).
    """
    logits, muts = model.apply(
        {"params": params}, inputs, train=True, rngs={"dropout": dropout_rng},
        pos_offset=pos_offset, return_features=return_features,
        mutable=["intermediates"])
    aux = jnp.float32(0.0)
    mass_sum = jnp.float32(0.0)
    mass_n = jnp.float32(0.0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            muts.get("intermediates", {}))[0]:
        if any(getattr(k, "key", None) == "aux_loss" for k in path):
            aux = aux + jnp.sum(leaf)
        elif any(getattr(k, "key", None) == "combine_mass" for k in path):
            mass_sum = mass_sum + jnp.sum(leaf.astype(jnp.float32))
            mass_n = mass_n + jnp.float32(leaf.size)
    return logits, aux, mass_sum, mass_n


def make_lm_batches(tokens: np.ndarray):
    """Host-side: (B, L+1) token rows -> (inputs (B,L), targets (B,L)).

    Shifting happens BEFORE any sharding so sequence shards stay consistent:
    each shard's targets include the first token of the next shard.
    """
    return tokens[:, :-1], tokens[:, 1:]


def _chunked_loss_metrics(model, params, feats, targets, mask,
                          loss_chunk: int):
    """loss_sum + metric sums via the chunked head (ops.fused_xent): the
    (B, L, V) logits never materialize; the head kernel comes straight from
    the param tree so its gradient flows through the chunked vjp."""
    loss_sum, correct = chunked_softmax_xent(
        feats, params["lm_head"]["kernel"], targets, mask,
        loss_chunk, model.dtype)
    return loss_sum, {"loss_sum": loss_sum, "correct1": correct,
                      "count": jnp.sum(mask)}


def _lm_objective_metrics(model, params, out, targets, loss_chunk: int):
    """THE chunked-vs-full loss dispatch for the train steps: ``out`` is
    logits (loss_chunk == 0) or post-ln_f features (loss_chunk > 0, from
    _apply_collect_aux(return_features=True)). One definition shared by the
    jit and sp step fns so the two objectives cannot drift — the eval twin
    is _lm_eval_metrics."""
    mask = jnp.ones(targets.shape, jnp.float32)
    # named in the compiled program, for a trace's readers (metadata only)
    with jax.named_scope("loss"):
        if loss_chunk:
            return _chunked_loss_metrics(model, params, out, targets, mask,
                                         loss_chunk)
        return lm_loss_and_metrics(out, targets, mask)


def _lm_grads_and_metrics(model, aux_weight: float, params, inputs, targets,
                          dropout_rng, loss_chunk: int = 0):
    """(grads, metrics): value_and_grad of THE LM objective (CE mean +
    aux_weight x sown aux losses, router-mass diagnostics attached) —
    shared by the single-step, windowed, AND grad-accum wrappers so the
    objective cannot drift between them. ``loss_chunk`` > 0 switches the
    head+CE to the chunked recompute path (ops.fused_xent) — identical math,
    O(chunk * V) instead of O(B * L * V) logits memory."""

    def loss_fn(p):
        out, aux, mass_sum, mass_n = _apply_collect_aux(
            model, p, inputs, dropout_rng,
            return_features=bool(loss_chunk))
        loss_sum, metrics = _lm_objective_metrics(
            model, p, out, targets, loss_chunk)
        metrics = {**metrics,
                   "router_mass_sum": jax.lax.stop_gradient(mass_sum),
                   "router_mass_n": mass_n}
        mean = loss_sum / jnp.maximum(metrics["count"], 1.0)
        return mean + aux_weight * aux, metrics

    (_, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return grads, metrics


def _lm_step_fn(model, tx, aux_weight: float, loss_chunk: int = 0,
                health: str = "record") -> Callable:
    """THE pure LM train step shared by every jit wrapper (single-batch and
    indexed-window) — the lm twin of steps.py _train_step_fn, so the
    windowed path's 'identical math to K sequential steps' contract is
    enforced structurally, not by parallel copies."""

    def step(state: TrainState, inputs, targets, rng):
        dropout_rng = jax.random.fold_in(rng, state.step)
        grads, metrics = _lm_grads_and_metrics(
            model, aux_weight, state.params, inputs, targets, dropout_rng,
            loss_chunk)
        return _apply_update(tx, state, grads, {}, metrics, health)

    return step


# ---- explicit-collective per-device step templates (parallel.overlap) ------

def _lm_explicit_dp_step_fn(model, tx, aux_weight: float, data_axis: str,
                            axis_size: int, grad_bucket_mb: float,
                            loss_chunk: int = 0,
                            health: str = "record") -> Callable:
    """Per-device dp step with EXPLICIT gradient sync: local-batch grads,
    then either one monolithic per-leaf pmean (bucket_mb <= 0) or DDP-style
    bucketed reduce-scatter+all-gather collectives
    (parallel.overlap.bucketed_grad_sync). Same math as the jit/GSPMD dp
    step — the local mean pmean'd equals the global-batch mean."""
    from tpu_dist.parallel.overlap import bucketed_grad_sync

    def step(state: TrainState, inputs, targets, rng):
        dropout_rng = jax.random.fold_in(rng, state.step)
        grads, metrics = _lm_grads_and_metrics(
            model, aux_weight, state.params, inputs, targets, dropout_rng,
            loss_chunk)
        if grad_bucket_mb > 0:
            grads = bucketed_grad_sync(grads, data_axis, grad_bucket_mb,
                                       mean=True, axis_size=axis_size)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, data_axis), grads)
        metrics = jax.tree.map(lambda m: jax.lax.psum(m, data_axis), metrics)
        return _apply_update(tx, state, grads, {}, metrics, health)

    return step


def _lm_tp_ring_step_fn(model, tx, aux_weight: float, data_axis: str,
                        model_axis: str, n_model: int,
                        loss_chunk: int = 0,
                        health: str = "record") -> Callable:
    """Per-device dp x ring-TP step: ``model`` must be built with
    tp_impl='ring' (parallel.overlap), so its projections run the
    AG-matmul / matmul-RS collective matmuls over ``model_axis`` and its
    outputs are this device's (B, L/n_model, ...) sequence chunk — the
    targets are sliced to match. Params stay replicated (ring trades
    GSPMD-TP's param sharding for explicit overlap); like the sp step,
    equal static shard sizes make the pmean of local-mean grads the global
    mean, with ``model_axis`` joining the reduction because every device
    holds the full param copy."""

    def step(state: TrainState, inputs, targets, rng):
        m_idx = jax.lax.axis_index(model_axis)
        shard_len = targets.shape[1] // n_model
        tgt = jax.lax.dynamic_slice_in_dim(targets, m_idx * shard_len,
                                           shard_len, axis=1)
        dropout_rng = jax.random.fold_in(rng, state.step)

        def loss_fn(p):
            out, aux, mass_sum, mass_n = _apply_collect_aux(
                model, p, inputs, dropout_rng,
                return_features=bool(loss_chunk))
            loss_sum, metrics = _lm_objective_metrics(
                model, p, out, tgt, loss_chunk)
            metrics = {**metrics,
                       "router_mass_sum": jax.lax.stop_gradient(mass_sum),
                       "router_mass_n": mass_n}
            # LOCAL mean over this device's (batch shard x seq chunk);
            # collectives stay OUT of the differentiated function (the
            # _lm_sp_step_fn contract — mean-of-local-means == global mean)
            mean = loss_sum / jnp.maximum(metrics["count"], 1.0)
            return mean + aux_weight * aux, metrics

        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(jax.lax.pmean(g, model_axis), data_axis),
            grads)
        metrics = jax.tree.map(
            lambda m: jax.lax.psum(jax.lax.psum(m, model_axis), data_axis),
            metrics)
        return _apply_update(tx, state, grads, {}, metrics, health)

    return step


def _lm_eval_metrics(model, params, inputs, targets, mask,
                     loss_chunk: int = 0, pos_offset=0):
    """Forward-only metric sums, chunked-head when loss_chunk > 0 — the
    shared eval kernel so every eval wrapper (jit/indexed/sp) dispatches the
    loss path the same way the train steps do."""
    if loss_chunk:
        feats = model.apply({"params": params}, inputs, train=False,
                            pos_offset=pos_offset, return_features=True)
        _, metrics = _chunked_loss_metrics(model, params, feats, targets,
                                           mask, loss_chunk)
        return metrics
    logits = model.apply({"params": params}, inputs, train=False,
                         pos_offset=pos_offset)
    _, metrics = lm_loss_and_metrics(logits, targets, mask)
    return metrics


def _lm_sp_step_fn(model, tx, aux_weight: float, data_axis: str,
                   seq_axis: str, loss_chunk: int = 0,
                   health: str = "record") -> Callable:
    """THE per-device sp train step shared by the single-batch and
    indexed-window wrappers (the sp twin of _lm_step_fn): runs INSIDE
    shard_map on a (data, seq) mesh with (B/data, L/seq) token shards.
    ``loss_chunk`` chunks each device's LOCAL head+CE (the head kernel is
    replicated under sp, so the chunked vjp needs no collectives; grads
    pmean over both axes exactly as before)."""

    def step(state: TrainState, inputs, targets, rng):
        seq_idx = jax.lax.axis_index(seq_axis)
        dp_idx = jax.lax.axis_index(data_axis)
        dropout_rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(rng, state.step), seq_idx),
            dp_idx)
        shard_len = inputs.shape[1]
        pos_offset = seq_idx * shard_len

        def loss_fn(p):
            out, aux, mass_sum, mass_n = _apply_collect_aux(
                model, p, inputs, dropout_rng, pos_offset=pos_offset,
                return_features=bool(loss_chunk))
            loss_sum, metrics = _lm_objective_metrics(
                model, p, out, targets, loss_chunk)
            # router-mass diagnostic rides the metric sums (psum'd below)
            # so sp-MoE runs report a real RMass, like the jit modes
            metrics = {**metrics,
                       "router_mass_sum": jax.lax.stop_gradient(mass_sum),
                       "router_mass_n": mass_n}
            # LOCAL mean; collectives stay OUT of the differentiated function
            # (psum's transpose under shard_map would rescale the cotangent).
            # Equal static shard sizes make mean-of-local-means == global mean.
            mean = loss_sum / jnp.maximum(metrics["count"], 1.0)
            return mean + aux_weight * aux, ({}, metrics)

        (_, (stats, metrics)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(jax.lax.pmean(g, seq_axis), data_axis), grads)
        metrics = jax.tree.map(
            lambda m: jax.lax.psum(jax.lax.psum(m, seq_axis), data_axis), metrics)
        return _apply_update(tx, state, grads, stats, metrics, health)

    return step


def _sp_window_slices(rows, seq_idx, shard_len):
    """Device-side shift+shard: from replicated (B_local, L+1) token rows,
    this seq shard's (inputs, targets) — the same slices the host-side
    make_lm_batches + (data, seq) sharding would deliver (a shard's targets
    include the first token of the next shard, so no boundary masking)."""
    start = seq_idx * shard_len
    inputs = jax.lax.dynamic_slice_in_dim(rows, start, shard_len, axis=1)
    targets = jax.lax.dynamic_slice_in_dim(rows, start + 1, shard_len, axis=1)
    return inputs, targets


# ---- the last builder shim ----------------------------------------------------
# tests/benchmarks/test_cellbench_aot_compile.py and test_cellbench_spans.py
# import this name, and only a `benchmark` PR may edit those files (ROADMAP
# S9 moves them to compile_train_step, and this goes with them). Everything
# else calls the plan compiler.

def make_lm_train_step(model, tx, mesh: Mesh, data_axis: str = DATA_AXIS,
                       aux_weight: float = 0.01,
                       donate: bool = True, loss_chunk: int = 0,
                       health: str = "record") -> Callable:
    """The gspmd LM train step of ``Plan(engine='lm')``: see
    ``plan.compile._lm_train``."""
    from tpu_dist.plan.compile import Bindings, compile_train_step

    plan = Plan(engine="lm", data_axis=data_axis, aux_weight=aux_weight,
                donate=donate, loss_chunk=loss_chunk, health=health)
    return compile_train_step(plan, Bindings(mesh=mesh, model=model, tx=tx))
