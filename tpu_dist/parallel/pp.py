"""Pipeline parallelism over a 'stage' mesh axis (GPipe + 1F1B, shard_map).

The last parallelism axis the framework lacked (absent upstream too —
SURVEY.md §2c). Two schedules over the same stage-stacked param layout:
GPipe (autodiff through the tick scan — simplest, activation stash O(M))
and 1F1B/PipeDream-flush (make_lm_pp_1f1b_train_step: manual jax.vjp per
stage, activation stash O(S) independent of the microbatch count — the
schedule that makes large-M, long-context pipeline runs fit in HBM).
TPU-first formulation: no per-stage processes, no RPC
schedulers — ONE shard_map program per device where

* each device along ``stage`` holds ``num_layers/num_stages`` consecutive
  transformer blocks, stage-stacked so every leaf carries a leading
  (stages, layers_per_stage) block of dims sharded ``P('stage')``;
* microbatches flow through a ``lax.scan`` over M + S - 1 ticks; activations
  hop stage->stage+1 via ``jax.lax.ppermute`` (ICI neighbor exchange);
* the whole pipeline — including the bubble — is differentiated by JAX
  autodiff: the transpose of ppermute is the reverse ppermute, so the
  backward pass is automatically the mirrored pipeline (GPipe schedule);
* embedding/head/final-LN are replicated as PARAMETERS, but their COMPUTE
  is gated with per-device ``lax.cond``: the embedding gather runs on
  stage 0 only, the ``ln_f`` + full-vocab ``lm_head`` matmul (and its vjp)
  on stage S-1 only, and bubble ticks skip the stage's block compute
  entirely. All collectives (ppermute / psum) stay OUTSIDE the branches, so
  every device still participates in every collective; a stage psum over
  the (exactly-zero elsewhere) embed/head gradients restores the replicated
  update. Block gradients stay stage-local. At a real vocabulary the head
  is ~25% of model FLOPs, so this gating is what makes S stages cost ~1x
  head work instead of Sx.

Composes with data parallelism as a ('data', 'stage') mesh: batch rows
shard over 'data', gradients pmean over 'data' exactly like the other
engines. Validated equal to the pure-DP jit step in tests/test_pp.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from tpu_dist._compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.engine.state import TrainState
from tpu_dist.engine.steps import _apply_update
from tpu_dist.parallel.mesh import DATA_AXIS, STAGE_AXIS


def _uses_tp(mesh: Mesh, model_axis: str = "model") -> bool:
    """True when the mesh carries a >1 tensor-parallel axis — the pipeline
    then leaves it to GSPMD as an *auto* axis, and block compute must not
    be branched around (its 'model' collectives would deadlock a cond)."""
    return model_axis in mesh.axis_names and mesh.shape[model_axis] > 1


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _tree_unstack(tree, n):
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def stack_pipeline_params(params, num_stages: int):
    """TransformerLM params -> pipeline layout.

    {tok_emb, pos_emb, block0..N-1, ln_f, lm_head} becomes
    {embed_head: {tok_emb, pos_emb, ln_f, lm_head},
     blocks: leaves (S, N/S, ...)} — consecutive blocks per stage.
    """
    n_blocks = sum(1 for k in params if k.startswith("block"))
    if n_blocks % num_stages:
        raise ValueError(f"{n_blocks} blocks not divisible by "
                         f"{num_stages} stages")
    per = n_blocks // num_stages
    stages = [_tree_stack([params[f"block{s * per + i}"] for i in range(per)])
              for s in range(num_stages)]
    return {
        "embed_head": {k: params[k] for k in
                       ("tok_emb", "pos_emb", "ln_f", "lm_head")},
        "blocks": _tree_stack(stages),
    }


def unstack_pipeline_params(pp_params):
    """Inverse of stack_pipeline_params (tests / checkpoint interop)."""
    blocks = pp_params["blocks"]
    s = jax.tree.leaves(blocks)[0].shape[0]
    per = jax.tree.leaves(blocks)[0].shape[1]
    out = dict(pp_params["embed_head"])
    for si, stage_tree in enumerate(_tree_unstack(blocks, s)):
        for li, block_tree in enumerate(_tree_unstack(stage_tree, per)):
            out[f"block{si * per + li}"] = block_tree
    return out


def pp_state_specs(state, stage_axis: str = STAGE_AXIS) -> TrainState:
    """PartitionSpec pytree for a pipeline-layout tree (a TrainState, or a
    bare params dict — the rule is structural): 'blocks' subtrees
    P(stage_axis), the rest replicated."""
    from jax.tree_util import tree_map_with_path

    def spec(path, leaf):
        under_blocks = any(getattr(k, "key", None) == "blocks" for k in path)
        if under_blocks:
            return P(stage_axis, *([None] * (leaf.ndim - 1)))
        return P()

    return tree_map_with_path(spec, state)


def pp_tp_placement_specs(state, stage_axis: str = STAGE_AXIS,
                          model_axis: str = "model"):
    """PLACEMENT specs for pp x tp: blocks' leading dim on 'stage' AND the
    Megatron column/row dims on 'model' (tp.py's rules, applied under the
    stage-stacked (S, layers, ...) layout). Used only for device_put — the
    shard_map in_specs stay stage-only because 'model' runs as a GSPMD
    *auto* axis inside the manual pipeline program."""
    from jax.tree_util import keystr, tree_map_with_path

    from tpu_dist.parallel.mesh import MODEL_AXIS
    from tpu_dist.parallel.tp import _RULES

    def spec(path, leaf):
        k = keystr(path)
        if "'blocks'" not in k:
            # embed_head stays replicated over 'model' by design: the
            # pipeline program computes embedding/head on every stage
            return P()
        base = [stage_axis] + [None] * (leaf.ndim - 1)
        if leaf.ndim == 4:  # stacked (S, layers, in, out) KERNELS only
            for key, rule in _RULES:
                if f"'{key}'" in k and len(rule) == 2:
                    # map tp.py's canonical 2-dim kernel rule onto the last
                    # two dims of the stage-stacked leaf — ONE rule table
                    base[-2] = model_axis if rule[0] == MODEL_AXIS else None
                    base[-1] = model_axis if rule[1] == MODEL_AXIS else None
                    break
        elif leaf.ndim == 5:
            # stacked (S, layers, E, in, out) expert kernels: the MoE x tp
            # rule (parallel.ep._moe_leaf_spec) under the stage stacking —
            # w_in column-parallel on f, w_out row-parallel; the gate stays
            # replicated (it is a 2-dim kernel with no _RULES entry)
            if "'w_in'" in k:
                base[-1] = model_axis
            elif "'w_out'" in k:
                base[-2] = model_axis
        return P(*base)

    return tree_map_with_path(spec, state)


def shard_state_pp(mesh: Mesh, state, stage_axis: str = STAGE_AXIS,
                   model_axis: str = "model"):
    """Place a pipeline-layout TrainState: blocks (+ their optimizer state)
    sharded over 'stage', everything else replicated. When the mesh also
    carries a >1 'model' axis, block weights additionally shard
    Megatron-style over it (pp x tp composition)."""
    specs = (pp_tp_placement_specs(state, stage_axis, model_axis)
             if _uses_tp(mesh, model_axis)
             else pp_state_specs(state, stage_axis))
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        state, specs)


def _pp_shard_map(mesh: Mesh, per_device, in_specs, out_specs,
                  data_axis: str, stage_axis: str):
    """shard_map with 'data'/'stage' MANUAL and — when the mesh carries a
    >1 'model' axis — 'model' left as a GSPMD *auto* axis: the pipeline
    schedule stays hand-written while XLA partitions each stage's block
    math Megatron-style over 'model' (pp x tp composition; round-2 gap)."""
    kwargs = {}
    if _uses_tp(mesh):
        kwargs["axis_names"] = frozenset({data_axis, stage_axis})
    return shard_map(per_device, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False, **kwargs)


def _is_moe(model) -> bool:
    return getattr(model, "num_experts", 0) > 0


def _clip_pp_grads(grads, grad_clip: float, stage_axis: str):
    """optax.clip_by_global_norm semantics under the pipeline layout (runs
    INSIDE the pp shard_map, after grad reduction): block grads are
    stage-local while embed/head grads are already stage-replicated, so the
    TRUE global squared norm is psum('stage') of the block term plus ONE
    embed/head term. Every stage then scales by the same factor — which is
    what keeps the replicated embed/head update synchronized (the reason a
    naive per-device optax clip was rejected in round 4; the pp engine
    builds its optax chain WITHOUT the clip and applies this instead)."""
    block_sq = sum(jnp.sum(jnp.square(g))
                   for g in jax.tree.leaves(grads["blocks"]))
    eh_sq = sum(jnp.sum(jnp.square(g))
                for g in jax.tree.leaves(grads["embed_head"]))
    norm = jnp.sqrt(jax.lax.psum(block_sq, stage_axis) + eh_sq)
    scale = jnp.where(norm > grad_clip,
                      grad_clip / jnp.maximum(norm, 1e-30), 1.0)
    return jax.tree.map(lambda g: g * scale, grads)


def _head_logits(model, x, kernel, dtype):
    """The last stage's lm_head matmul under the model's quant mode — the
    same ops.quant treatment the non-pp head gets from make_dense, so the
    pipeline run trains the SAME program per layer (the chunked-CE path
    keeps its fp head in every mode, as documented on LMConfig.quant)."""
    from tpu_dist.ops.quant import quant_matmul

    quant = getattr(model, "quant", "none")
    return quant_matmul(x.astype(dtype), kernel.astype(dtype),
                        quant).astype(jnp.float32)


def _stage_apply_builder(model):
    """(apply_stage, ln_f, dtype) shared by every pipeline schedule: the
    per-stage block scan (remat-aware) and the final-norm module — ONE
    definition so GPipe and 1F1B can never diverge on what a stage computes."""
    import flax.linen as nn

    from tpu_dist.models.transformer import Block

    block = Block(num_heads=model.num_heads, dtype=model.dtype,
                  attn_fn=model.attn_fn,
                  quant=getattr(model, "quant", "none"))
    ln_f = nn.LayerNorm(dtype=jnp.float32)

    def apply_stage(blocks_local, x):
        # blocks_local leaves: (layers_per_stage, ...) — homogeneous scan
        def one(h, bp):
            return block.apply({"params": bp}, h), None
        if model.remat:  # same per-block checkpointing as the dense path
            one = jax.checkpoint(one)
        x, _ = jax.lax.scan(one, x, blocks_local)
        return x

    return apply_stage, ln_f, model.dtype


def _stage_apply_aux_builder(model):
    """MoE twin of :func:`_stage_apply_builder`: the stage scan runs
    MoEBlocks and ACCUMULATES their sown load-balancing aux losses —
    ``apply_stage(blocks_local, x) -> (x, aux_sum)``. Used by the GPipe
    forward (autodiff carries the aux gradient back into each stage's
    routers); the manual-vjp 1F1B schedule stays dense-only."""
    import flax.linen as nn

    from tpu_dist.models.moe import MoEBlock

    block = MoEBlock(num_heads=model.num_heads,
                     num_experts=model.num_experts, dtype=model.dtype,
                     attn_fn=model.attn_fn,
                     router_top_k=model.router_top_k,
                     group_size=model.group_size,
                     capacity_factor=model.capacity_factor,
                     quant=getattr(model, "quant", "none"))
    ln_f = nn.LayerNorm(dtype=jnp.float32)

    def apply_stage(blocks_local, x):
        def one(carry, bp):
            h, aux, mass, mass_n = carry
            out, muts = block.apply({"params": bp}, h,
                                    mutable=["intermediates"])
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    muts.get("intermediates", {}))[0]:
                keys = [getattr(k, "key", None) for k in path]
                if "aux_loss" in keys:
                    aux = aux + jnp.sum(leaf)
                elif "combine_mass" in keys:  # router health (RMass)
                    mass = mass + jnp.sum(leaf.astype(jnp.float32))
                    mass_n = mass_n + jnp.float32(leaf.size)
            return (out, aux, mass, mass_n), None
        if model.remat:
            one = jax.checkpoint(one)
        zero = jnp.float32(0.0)
        (x, aux, mass, mass_n), _ = jax.lax.scan(
            one, (x, zero, zero, zero), blocks_local)
        return x, (aux, mass, mass_n)

    return apply_stage, ln_f, model.dtype


def _zeros_metrics():
    from tpu_dist.engine.lm_steps import zeros_lm_metrics
    return zeros_lm_metrics()


def _pp_forward_builder(model, mesh: Mesh, num_microbatches: int,
                        stage_axis: str = STAGE_AXIS,
                        loss_chunk: int = 0) -> Callable:
    """Shared pipeline forward+loss for the train AND eval steps: returns
    ``fwd_loss(params, inputs, targets, row_valid) -> (loss_sum,
    metrics, aux)`` to run INSIDE shard_map. loss_sum and the CE metric
    sums are real on the LAST stage only (exact zeros elsewhere — the
    head never runs, via ``lax.cond`` — so a stage psum reassembles
    them); ``aux`` is the STAGE-LOCAL MoE router loss, nonzero on every
    stage that holds MoE blocks (0.0 for dense models), and the metrics
    carry per-stage router_mass sums the same way. ``row_valid`` (B,)
    masks sampler wrap-padding rows (ones for training)."""
    from tpu_dist.engine.lm_steps import (_chunked_loss_metrics,
                                          lm_loss_and_metrics)

    n_stages = mesh.shape[stage_axis]
    m = num_microbatches
    moe = _is_moe(model)
    if moe:
        apply_aux, ln_f, dtype = _stage_apply_aux_builder(model)
    else:
        apply_dense, ln_f, dtype = _stage_apply_builder(model)

        def apply_aux(blocks_local, x):
            zero = jnp.float32(0.0)
            return apply_dense(blocks_local, x), (zero, zero, zero)
    # lax.cond branches must contain NO collectives: a collective reached by
    # only some devices deadlocks the global rendezvous. With pp x tp the
    # block math carries GSPMD 'model' all-reduces, so bubble-tick gating
    # falls back to where() there; embed/head are 'model'-replicated by
    # design (pp_tp_placement_specs) so THEIR gating is always safe.
    gate_blocks = not _uses_tp(mesh)

    def fwd_loss(params, inputs, targets, row_valid):
        stage = jax.lax.axis_index(stage_axis)
        b_local, seq_len = inputs.shape
        if b_local % m:
            raise ValueError(f"local batch {b_local} not divisible by "
                             f"{m} microbatches")
        mb = b_local // m
        eh = params["embed_head"]
        blocks_local = jax.tree.map(lambda x: x[0], params["blocks"])
        d_model = eh["tok_emb"]["embedding"].shape[1]
        is_first = stage == 0
        is_last = stage == n_stages - 1

        # embedding gather runs on stage 0 ONLY (its vjp — the big vocab
        # scatter-add — is then stage-0-only too, via the cond transpose)
        def compute_emb():
            tok = eh["tok_emb"]["embedding"][inputs]      # (B, L, D) f32
            pos = eh["pos_emb"]["embedding"][jnp.arange(seq_len)][None]
            return (tok + pos).astype(dtype).reshape(
                m, mb, seq_len, d_model)

        emb_mb = jax.lax.cond(
            is_first, compute_emb,
            lambda: jnp.zeros((m, mb, seq_len, d_model), dtype))

        zeros_act = jnp.zeros((mb, seq_len, d_model), dtype)
        zeros_out = jnp.zeros((m, mb, seq_len, d_model), dtype)

        zeros3 = (jnp.float32(0.0),) * 3

        def tick(carry, t):
            recv, outs, acc = carry
            inp = jnp.where(is_first,
                            emb_mb[jnp.clip(t, 0, m - 1)], recv)
            # stage s works on microbatch t-s; outside [0, M) it's bubble —
            # and bubble ticks SKIP the block compute (cond, not where)
            valid = (t - stage >= 0) & (t - stage < m)
            if gate_blocks:
                out, aux3 = jax.lax.cond(
                    valid, lambda: apply_aux(blocks_local, inp),
                    lambda: (zeros_act, zeros3))
            else:  # tp: 'model' collectives forbid branching around blocks
                out, aux3 = apply_aux(blocks_local, inp)
                out = jnp.where(valid, out, 0.0)
                aux3 = tuple(jnp.where(valid, a, 0.0) for a in aux3)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            outs = jnp.where(
                is_last & (t >= n_stages - 1),
                jax.lax.dynamic_update_index_in_dim(outs, out, out_idx, 0),
                outs)
            nxt = jax.lax.ppermute(
                out, stage_axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            acc = tuple(a + b for a, b in zip(acc, aux3))
            return (nxt, outs, acc), None

        (_, outs, (aux_sum, mass_sum, mass_n)), _ = jax.lax.scan(
            tick, (zeros_act, zeros_out, zeros3),
            jnp.arange(m + n_stages - 1))

        # ln_f + full-vocab head matmul + loss run on the LAST stage only;
        # other stages return exact zeros so grads/metrics psum correctly
        def head():
            x = ln_f.apply({"params": eh["ln_f"]},
                           outs.reshape(b_local, seq_len, -1))
            mask = jnp.broadcast_to(row_valid[:, None],
                                    targets.shape).astype(jnp.float32)
            if loss_chunk:
                # chunked head+CE (ops.fused_xent): the custom_vjp has
                # no collectives, so it is cond-safe on the last stage;
                # the SHARED helper builds the metric dict so the key
                # set cannot drift from the jit/sp paths
                return _chunked_loss_metrics(model, eh, x, targets,
                                             mask, loss_chunk)
            logits = _head_logits(model, x, eh["lm_head"]["kernel"], dtype)
            return lm_loss_and_metrics(logits, targets, mask)

        loss_sum, metrics = jax.lax.cond(
            is_last, head, lambda: (jnp.float32(0.0), _zeros_metrics()))
        # router-mass diagnostic rides the metric sums (stage psum adds
        # each stage's contribution) so pp-MoE runs report a real RMass
        metrics = {**metrics,
                   "router_mass_sum": jax.lax.stop_gradient(mass_sum),
                   "router_mass_n": mass_n}
        # per-device aux: mean over this stage's microbatches (matching the
        # dp path's one-batch aux scale); stage-local — each stage's grads
        # carry its own routers' balance term, psum'd with the block grads
        return loss_sum, metrics, aux_sum / jnp.float32(m)

    return fwd_loss


def make_lm_pp_train_step(model, tx, mesh: Mesh, num_microbatches: int,
                          data_axis: str = DATA_AXIS,
                          stage_axis: str = STAGE_AXIS,
                          donate: bool = True,
                          aux_weight: float = 0.01,
                          loss_chunk: int = 0,
                          grad_clip: float = 0.0,
                          health: str = "record") -> Callable:
    """GPipe train step: (state, inputs (B,L), targets (B,L), rng) ->
    (state, metric sums). ``state.params`` must be in pipeline layout
    (stack_pipeline_params) and placed by shard_state_pp.

    ``model`` is the TransformerLM whose geometry the params came from (its
    Block/embedding hyperparameters are reused functionally here).
    ``grad_clip`` > 0 clips by the cross-stage global norm (_clip_pp_grads);
    ``tx`` must then be built WITHOUT its own clip.
    """
    per_device = _pp_gpipe_step_builder(model, tx, mesh, num_microbatches,
                                        data_axis, stage_axis, aux_weight,
                                        loss_chunk, grad_clip, health)

    def call(state, inputs, targets, rng):
        # specs are structural, so the caller's state pytree defines them
        # (manual axes only — a 'model' mesh axis rides as GSPMD auto)
        specs = pp_state_specs(state, stage_axis)
        sharded = _pp_shard_map(
            mesh, per_device,
            (specs, P(data_axis, None), P(data_axis, None), P()),
            (specs, P()), data_axis, stage_axis)
        return sharded(state, inputs, targets, rng)

    return jax.jit(call, donate_argnums=(0,) if donate else ())


def _pp_gpipe_step_builder(model, tx, mesh: Mesh, num_microbatches: int,
                           data_axis: str, stage_axis: str,
                           aux_weight: float = 0.01,
                           loss_chunk: int = 0,
                           grad_clip: float = 0.0,
                           health: str = "record") -> Callable:
    """Per-device GPipe train step (runs INSIDE shard_map), shared by the
    single-batch and indexed-window wrappers."""
    fwd_loss = _pp_forward_builder(model, mesh, num_microbatches,
                                   stage_axis, loss_chunk)

    def per_device(state: TrainState, inputs, targets, rng):
        del rng  # blocks are dropout-free; kept for engine-signature parity

        def loss_fn(params):
            ones = jnp.ones((inputs.shape[0],), jnp.float32)
            loss_sum, metrics, aux = fwd_loss(params, inputs, targets, ones)
            mean = loss_sum / jnp.float32(targets.size)  # local-shard mean
            return mean + aux_weight * aux, ({}, metrics)

        (_, (stats, metrics)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # stage-local block grads average over data replicas only; the
        # replicated embed/head grads are nonzero on one stage each -> the
        # stage psum reassembles the full gradient on every stage
        grads = {
            "blocks": jax.tree.map(
                lambda g: jax.lax.pmean(g, data_axis), grads["blocks"]),
            "embed_head": jax.tree.map(
                lambda g: jax.lax.pmean(jax.lax.psum(g, stage_axis),
                                        data_axis), grads["embed_head"]),
        }
        if grad_clip > 0:
            grads = _clip_pp_grads(grads, grad_clip, stage_axis)
        metrics = jax.tree.map(
            lambda v: jax.lax.psum(jax.lax.psum(v, stage_axis), data_axis),
            metrics)
        # block grads are stage-local: psum the health probes over 'stage'
        # so they (and any skip gate) are identical on every device
        return _apply_update(
            tx, state, grads, stats, metrics, health,
            probe_sync=lambda p: {k: jax.lax.psum(v, stage_axis)
                                  for k, v in p.items()})

    return per_device


def make_lm_pp_1f1b_train_step(model, tx, mesh: Mesh, num_microbatches: int,
                               data_axis: str = DATA_AXIS,
                               stage_axis: str = STAGE_AXIS,
                               donate: bool = True,
                               aux_weight: float = 0.01,
                               loss_chunk: int = 0,
                               grad_clip: float = 0.0,
                               health: str = "record") -> Callable:
    """1F1B pipeline train step (PipeDream-flush schedule).

    Same signature/state layout as :func:`make_lm_pp_train_step`, different
    schedule: each of the ``M + 2(S-1)`` lockstep ticks runs ONE forward and
    ONE backward microbatch per stage (stage s forwards microbatch ``t-s``
    and backwards microbatch ``t - (2(S-1)-s)``), with the backward hand-
    rolled through ``jax.vjp`` and the activation stash bounded by
    ``2(S-1)+1`` microbatches — **independent of M**. GPipe-by-autodiff
    stashes all ``M+S-1`` tick inputs (plus block intermediates unless
    remat), so its activation memory grows linearly with the microbatch
    count; this schedule holds it constant, which is what buys large-M runs
    (small bubble fraction (S-1)/(M+S-1)) at long sequence lengths. The
    backward RECOMPUTES the stage forward from the stashed input (flash-
    style), the standard memory/FLOPs trade for 1F1B.

    Numerics match GPipe/DP exactly (tests/test_pp.py): per-microbatch
    losses are normalized by the local shard size so their sum is the local
    mean; block grads stay stage-local, embed/head grads psum over 'stage',
    everything pmeans over 'data'.

    Round 5 closes the three 1f1b composition holes: MoE
    router aux losses thread through the manual vjp as an explicit
    cotangent, ``loss_chunk`` > 0 runs the chunked CE (ops.fused_xent) on
    the last-stage head, and ``grad_clip`` > 0 clips by the cross-stage
    global norm (_clip_pp_grads; ``tx`` must then carry no clip of its own).
    """
    per_device = _pp_1f1b_step_builder(model, tx, mesh, num_microbatches,
                                       data_axis, stage_axis, aux_weight,
                                       loss_chunk, grad_clip, health)

    def call(state, inputs, targets, rng):
        specs = pp_state_specs(state, stage_axis)
        sharded = _pp_shard_map(
            mesh, per_device,
            (specs, P(data_axis, None), P(data_axis, None), P()),
            (specs, P()), data_axis, stage_axis)
        return sharded(state, inputs, targets, rng)

    return jax.jit(call, donate_argnums=(0,) if donate else ())


def _pp_1f1b_step_builder(model, tx, mesh: Mesh, num_microbatches: int,
                          data_axis: str, stage_axis: str,
                          aux_weight: float = 0.01,
                          loss_chunk: int = 0,
                          grad_clip: float = 0.0,
                          health: str = "record") -> Callable:
    """Per-device 1F1B train step (runs INSIDE shard_map), shared by the
    single-batch and indexed-window wrappers.

    MoE models thread the router aux losses through the manual vjp: each
    backward microbatch differentiates the stage forward's (activation,
    aux) pair with cotangents (dy, aux_weight/M) — exactly the coefficient
    autodiff gives each stage-local aux term in the GPipe objective (loss =
    CE mean + aux_weight * sum_over_microbatch_auxes / M), and the aux
    path's input cotangent rides the backward ppermute ring to earlier
    stages the same way the CE cotangent does."""
    from tpu_dist.engine.lm_steps import (_chunked_loss_metrics,
                                          lm_loss_and_metrics)

    S = mesh.shape[stage_axis]
    M = num_microbatches
    stash_depth = 2 * (S - 1) + 1  # max in-flight per stage, +1 tick slack
    moe = _is_moe(model)
    if moe:
        apply_aux, ln_f, dtype = _stage_apply_aux_builder(model)

        def stage_fwd(bp, x):
            return apply_aux(bp, x)          # (y, (aux, mass, mass_n))
    else:
        apply_dense, ln_f, dtype = _stage_apply_builder(model)

        def stage_fwd(bp, x):
            zero = jnp.float32(0.0)
            return apply_dense(bp, x), (zero, zero, zero)

    def stage_va(bp, x):
        # THE differentiated per-stage forward: (activation, aux). The mass
        # diagnostics are excluded so the vjp needs no zero cotangents for
        # them (XLA dead-code-eliminates their recompute in the backward).
        y, (aux, _, _) = stage_fwd(bp, x)
        return y, aux

    aux_ct = jnp.float32(aux_weight / M if moe else 0.0)
    # same collective-safety rule as the GPipe builder: block compute is
    # cond-gated only when it contains no 'model' collectives; the head /
    # embedding branches are 'model'-replicated so they are always gated
    gate_blocks = not _uses_tp(mesh)

    def per_device(state: TrainState, inputs, targets, rng):
        del rng
        stage = jax.lax.axis_index(stage_axis)
        is_first = stage == 0
        is_last = stage == S - 1
        b_local, seq_len = inputs.shape
        if b_local % M:
            raise ValueError(f"local batch {b_local} not divisible by "
                             f"{M} microbatches")
        mb = b_local // M
        params = state.params
        eh = params["embed_head"]
        blocks_local = jax.tree.map(lambda x: x[0], params["blocks"])
        d_model = eh["tok_emb"]["embedding"].shape[1]

        ids_mb = inputs.reshape(M, mb, seq_len)
        tgt_mb = targets.reshape(M, mb, seq_len)
        pos_ids = jnp.arange(seq_len)

        def embed(m):
            tok = eh["tok_emb"]["embedding"][ids_mb[m]]
            pos = eh["pos_emb"]["embedding"][pos_ids][None]
            return (tok + pos).astype(dtype)

        def head_loss(eh_p, y, m):
            """Per-microbatch mean-normalized loss + metric sums (real on
            the last stage only; the caller masks)."""
            x = ln_f.apply({"params": eh_p["ln_f"]}, y)
            mask = jnp.ones((mb, seq_len), jnp.float32)
            if loss_chunk:
                # chunked head+CE (ops.fused_xent): its custom_vjp is
                # collective-free, so it is cond-safe on the last stage
                loss_sum, metrics = _chunked_loss_metrics(
                    model, eh_p, x, tgt_mb[m], mask, loss_chunk)
            else:
                logits = _head_logits(model, x, eh_p["lm_head"]["kernel"],
                                      dtype)
                loss_sum, metrics = lm_loss_and_metrics(logits, tgt_mb[m],
                                                        mask)
            # normalize by the FULL local shard so the M losses sum to the
            # local mean (the GPipe step's mean = loss_sum / targets.size)
            return loss_sum / jnp.float32(b_local * seq_len), metrics

        zeros_act = jnp.zeros((mb, seq_len, d_model), dtype)
        zeros_blocks_g = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), blocks_local)
        zeros_eh_g = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), eh)
        zeros_metrics = _zeros_metrics()

        def tick(carry, t):
            fwd_recv, bwd_recv, stash, g_blocks, g_eh, macc, mass2 = carry

            # ---- forward half: stage s forwards microbatch t - s ----
            # Bubble ticks (valid_f false) skip the block compute AND the
            # stash write; the embedding gather runs on stage 0 only. All
            # gating is per-device lax.cond — collectives stay outside.
            m_f = t - stage
            valid_f = (m_f >= 0) & (m_f < M)
            mf_c = jnp.clip(m_f, 0, M - 1)

            if gate_blocks:
                def fwd_do(sm):
                    stash, mass2 = sm
                    x_in = jax.lax.cond(is_first, lambda: embed(mf_c),
                                        lambda: fwd_recv)
                    y, (_, ms, mn) = stage_fwd(blocks_local, x_in)
                    stash = jax.lax.dynamic_update_index_in_dim(
                        stash, x_in, m_f % stash_depth, 0)
                    return y, (stash, (mass2[0] + ms, mass2[1] + mn))

                y, (stash, mass2) = jax.lax.cond(
                    valid_f, fwd_do, lambda sm: (zeros_act, sm),
                    (stash, mass2))
            else:  # tp: block compute runs unconditionally, embed still gated
                x_in = jax.lax.cond(is_first, lambda: embed(mf_c),
                                    lambda: fwd_recv)
                y_raw, (_, ms, mn) = stage_fwd(blocks_local, x_in)
                y = jnp.where(valid_f, y_raw, 0.0)
                gate_f = jnp.where(valid_f, 1.0, 0.0)
                mass2 = (mass2[0] + ms * gate_f, mass2[1] + mn * gate_f)
                stash = jnp.where(
                    valid_f,
                    jax.lax.dynamic_update_index_in_dim(
                        stash, x_in, m_f % stash_depth, 0),
                    stash)

            # ---- backward half: microbatch t - (2(S-1) - s) ----
            m_b = t - (2 * (S - 1) - stage)
            valid_b = (m_b >= 0) & (m_b < M)
            mb_c = jnp.clip(m_b, 0, M - 1)

            def head_vjp_acc(eh_macc, y_b):
                """Head fwd+vjp + metric accumulation (last stage, valid
                ticks only — the callers' cond guarantees it). 'model'-
                replicated, so always safe to branch around."""
                g_eh, macc = eh_macc
                _, vjp_head, metrics = jax.vjp(
                    lambda ehp, yy: head_loss(ehp, yy, mb_c), eh, y_b,
                    has_aux=True)
                d_eh, dy_head = vjp_head(jnp.float32(1.0))
                g_eh = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_eh, d_eh)
                macc = jax.tree.map(jnp.add, macc, metrics)
                return (g_eh, macc), dy_head.astype(y_b.dtype)

            def emb_scatter(g_eh, dx):
                """Embedding backward (stage 0, valid ticks only): scatter
                dx into the tok_emb rows, reduce over batch for pos_emb
                (scatter, not add: max_len may exceed L)."""
                dxf = dx.astype(jnp.float32)
                g_eh = {**g_eh, "tok_emb": {"embedding":
                        g_eh["tok_emb"]["embedding"]
                        .at[ids_mb[mb_c]].add(dxf)}}
                g_eh["pos_emb"] = {"embedding":
                                   g_eh["pos_emb"]["embedding"]
                                   .at[pos_ids].add(jnp.sum(dxf, axis=0))}
                return g_eh

            def bwd_do(acc):
                g_blocks, g_eh, macc = acc
                x_b = stash[mb_c % stash_depth]
                # recompute this stage's forward from the stashed input and
                # differentiate it (activation memory stays O(S), not O(M));
                # the (y, aux) pair takes the router-aux cotangent too
                (y_b, _), vjp_stage = jax.vjp(stage_va, blocks_local, x_b)
                # head fwd+vjp and metrics run on the LAST stage only; the
                # other stages' cotangent is what arrived over the ring
                (g_eh, macc), dy = jax.lax.cond(
                    is_last, lambda c: head_vjp_acc(c, y_b),
                    lambda c: (c, bwd_recv), (g_eh, macc))
                d_blocks, dx = vjp_stage((dy, aux_ct))
                g_blocks = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32),
                    g_blocks, d_blocks)
                g_eh = jax.lax.cond(
                    is_first, lambda g: emb_scatter(g, dx),
                    lambda g: g, g_eh)
                return (g_blocks, g_eh, macc), dx

            if gate_blocks:
                (g_blocks, g_eh, macc), dx = jax.lax.cond(
                    valid_b, bwd_do, lambda acc: (acc, zeros_act),
                    (g_blocks, g_eh, macc))
            else:
                # tp: the stage vjp carries 'model' collectives, so it runs
                # unconditionally with multiply-gating; head/embedding
                # branches stay cond-gated (collective-free)
                x_b = stash[mb_c % stash_depth]
                (y_b, _), vjp_stage = jax.vjp(stage_va, blocks_local, x_b)
                (g_eh, macc), dy = jax.lax.cond(
                    valid_b & is_last, lambda c: head_vjp_acc(c, y_b),
                    lambda c: (c, bwd_recv), (g_eh, macc))
                d_blocks, dx = vjp_stage((dy, aux_ct))
                gate_b = jnp.where(valid_b, 1.0, 0.0)
                g_blocks = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * gate_b,
                    g_blocks, d_blocks)
                g_eh = jax.lax.cond(
                    valid_b & is_first, lambda g: emb_scatter(g, dx),
                    lambda g: g, g_eh)

            fwd_send = jax.lax.ppermute(
                y, stage_axis, [(i, i + 1) for i in range(S - 1)])
            bwd_send = jax.lax.ppermute(
                dx, stage_axis, [(i + 1, i) for i in range(S - 1)])
            return (fwd_send, bwd_send, stash, g_blocks, g_eh, macc,
                    mass2), None

        stash0 = jnp.zeros((stash_depth, mb, seq_len, d_model), dtype)
        mass0 = (jnp.float32(0.0), jnp.float32(0.0))
        (_, _, _, g_blocks, g_eh, metrics, mass2), _ = jax.lax.scan(
            tick,
            (zeros_act, zeros_act, stash0, zeros_blocks_g, zeros_eh_g,
             zeros_metrics, mass0),
            jnp.arange(M + 2 * (S - 1)))

        # same reduction structure as the GPipe step: blocks stage-local,
        # embed/head reassembled across stages, everything data-averaged
        grads = {
            "blocks": jax.tree.map(
                lambda g: jax.lax.pmean(g, data_axis), g_blocks),
            "embed_head": jax.tree.map(
                lambda g: jax.lax.pmean(jax.lax.psum(g, stage_axis),
                                        data_axis), g_eh),
        }
        # restore the stacked (1, layers, ...) leading dim of the blocks
        # leaves so the grad tree matches the P('stage')-sharded params
        grads["blocks"] = jax.tree.map(lambda g: g[None], grads["blocks"])
        if grad_clip > 0:
            grads = _clip_pp_grads(grads, grad_clip, stage_axis)
        # router-mass diagnostic rides the metric sums exactly like the
        # GPipe step's (zeros for dense models) so the two schedules return
        # the same metric pytree
        metrics = {**metrics,
                   "router_mass_sum": mass2[0], "router_mass_n": mass2[1]}
        metrics = jax.tree.map(
            lambda v: jax.lax.psum(jax.lax.psum(v, stage_axis), data_axis),
            metrics)
        # stage-local block grads: see the gpipe builder's probe_sync note
        return _apply_update(
            tx, state, grads, {}, metrics, health,
            probe_sync=lambda p: {k: jax.lax.psum(v, stage_axis)
                                  for k, v in p.items()})

    return per_device


def make_lm_pp_indexed_multi_train_step(model, tx, mesh: Mesh,
                                        num_microbatches: int,
                                        schedule: str = "gpipe",
                                        data_axis: str = DATA_AXIS,
                                        stage_axis: str = STAGE_AXIS,
                                        donate: bool = True,
                                        aux_weight: float = 0.01,
                                        loss_chunk: int = 0,
                                        grad_clip: float = 0.0,
                                        health: str = "record"
                                        ) -> Callable:
    """K pipeline optimizer steps per dispatch from HBM-resident rows: a lax.scan over (K, B) index windows INSIDE the
    shard_map program, so pipeline runs amortize the host round-trip the
    same way the jit modes do.

    signature: (state, rows_all (N, L+1) i32 REPLICATED, idx (K, B) i32
    sharded (None, data), rng) -> (state, metric sums over K steps).
    Identical math to K sequential per-batch pp steps (parameter equality
    asserted to rtol 1e-5 in tests/test_lm_loop.py)."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pp schedule {schedule!r} (gpipe|1f1b)")
    if schedule == "1f1b":
        one_step = _pp_1f1b_step_builder(model, tx, mesh,
                                         num_microbatches, data_axis,
                                         stage_axis, aux_weight,
                                         loss_chunk, grad_clip, health)
    else:
        one_step = _pp_gpipe_step_builder(model, tx, mesh,
                                          num_microbatches, data_axis,
                                          stage_axis, aux_weight,
                                          loss_chunk, grad_clip, health)

    def per_device(state: TrainState, rows_all, idx, rng):
        def body(st, idx_b):
            rows = jnp.take(rows_all, idx_b, axis=0)   # (B_local, L+1)
            return one_step(st, rows[:, :-1], rows[:, 1:], rng)

        state, metrics_k = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)

    def call(state, rows_all, idx, rng):
        specs = pp_state_specs(state, stage_axis)
        sharded = _pp_shard_map(
            mesh, per_device,
            (specs, P(), P(None, data_axis), P()),
            (specs, P()), data_axis, stage_axis)
        return sharded(state, rows_all, idx, rng)

    return jax.jit(call, donate_argnums=(0,) if donate else ())


def make_lm_pp_indexed_eval_step(model, mesh: Mesh, num_microbatches: int,
                                 data_axis: str = DATA_AXIS,
                                 stage_axis: str = STAGE_AXIS,
                                 loss_chunk: int = 0) -> Callable:
    """Whole-val-set perplexity in ONE dispatch through the pipeline:
    (params, rows_all (N, L+1) REPLICATED, idx (K, B) sharded (None, data),
    valid (K, B) f32 same sharding) -> metric sums over all K batches,
    real on the last stage only, psum'd over 'stage' and 'data'."""
    fwd_loss = _pp_forward_builder(model, mesh, num_microbatches,
                                   stage_axis, loss_chunk)

    def per_device(params, rows_all, idx, valid):
        def body(sums, blk):
            idx_b, valid_b = blk
            rows = jnp.take(rows_all, idx_b, axis=0)
            _, m, _ = fwd_loss(params, rows[:, :-1], rows[:, 1:],
                            valid_b.astype(jnp.float32))
            # eval reports the CE metric sums only (the router-mass keys
            # the train path attaches are a training-time diagnostic)
            return {k: sums[k] + m[k] for k in sums}, None

        sums, _ = jax.lax.scan(body, _zeros_metrics(), (idx, valid))
        return jax.tree.map(
            lambda v: jax.lax.psum(jax.lax.psum(v, stage_axis), data_axis),
            sums)

    def call(params, rows_all, idx, valid):
        p_specs = pp_state_specs(params, stage_axis)
        sharded = _pp_shard_map(
            mesh, per_device,
            (p_specs, P(), P(None, data_axis), P(None, data_axis)),
            P(), data_axis, stage_axis)
        return sharded(params, rows_all, idx, valid)

    return jax.jit(call)


def make_lm_pp_eval_step(model, mesh: Mesh, num_microbatches: int,
                         data_axis: str = DATA_AXIS,
                         stage_axis: str = STAGE_AXIS,
                         loss_chunk: int = 0) -> Callable:
    """Held-out eval through the pipeline: (params, inputs, targets, valid)
    -> psum'd metric sums. ``valid`` (B,) masks sampler wrap-padding rows;
    the head (and loss) run on the last stage only — other stages
    contribute exact zeros to the psum — the round-2 gap where pp had no
    eval path."""
    from tpu_dist.engine.lm_steps import LM_METRIC_KEYS

    fwd_loss = _pp_forward_builder(model, mesh, num_microbatches,
                                   stage_axis, loss_chunk)

    def per_device(params, inputs, targets, valid):
        _, metrics, _ = fwd_loss(params, inputs, targets,
                              valid.astype(jnp.float32))
        # eval reports the CE metric sums only: the router-mass keys the
        # train forward attaches are a training-time diagnostic, and every
        # other eval path returns exactly the zeros_lm_metrics key set
        metrics = {k: metrics[k] for k in LM_METRIC_KEYS}
        return jax.tree.map(
            lambda v: jax.lax.psum(jax.lax.psum(v, stage_axis), data_axis),
            metrics)

    def call(params, inputs, targets, valid):
        p_specs = pp_state_specs(params, stage_axis)
        sharded = _pp_shard_map(
            mesh, per_device,
            (p_specs, P(data_axis, None), P(data_axis, None),
             P(data_axis)),
            P(), data_axis, stage_axis)
        return sharded(params, inputs, targets, valid)

    return jax.jit(call)
