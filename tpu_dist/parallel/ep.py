"""Expert-parallel sharding rules (GShard-style, compiler-partitioned).

Shards every MoE expert weight's leading experts dimension over the
``expert`` mesh axis; GSPMD turns the dispatch/combine einsums
(tpu_dist.models.moe) into the all-to-all exchanges of classic expert
parallelism. Everything non-expert stays replicated (or combines with the
other axes' specs when meshes are stacked).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.parallel.mesh import EXPERT_AXIS


def expert_share(num_experts: int, of: int, index: int) -> tuple:
    """``(held_lo, held_n)``: the contiguous block of a layer's
    ``num_experts`` routed experts that rank ``index`` of ``of`` holds, as
    ``P(axis, None, None)`` over the leading experts dimension lays them
    out. What a served expert layer is told of its place in the group
    (``models.nemotron_h``): it routes over all ``num_experts`` and computes
    its own block's part."""
    if num_experts % of or not 0 <= index < of:
        raise ValueError(f"{num_experts} experts over {of} ranks, rank "
                         f"{index}: whole equal blocks only")
    held_n = num_experts // of
    return index * held_n, held_n


def _moe_leaf_spec(key: str, leaf, axis: str,
                   model_axis: str | None) -> P:
    """Spec for one MoE param leaf: expert weights shard their leading
    experts dim over ``axis``; with an active tensor-parallel axis the
    expert MLP additionally splits Megatron-style over ``model_axis``
    (w_in column-parallel on f, w_out row-parallel on f) and the attention
    qkv/proj + lm_head follow tpu_dist.parallel.tp's rules. The gate stays
    replicated (its output feeds the token-local routing argmax)."""
    if key in ("w_in", "w_out") and leaf.ndim == 3:
        if model_axis is None:
            return P(axis, None, None)
        return (P(axis, None, model_axis) if key == "w_in"
                else P(axis, model_axis, None))
    if model_axis is not None and leaf.ndim == 2:
        if key in ("qkv", "lm_head"):
            return P(None, model_axis)   # column-parallel
        if key == "proj":
            return P(model_axis, None)   # row-parallel
    return P()


def ep_param_specs(params, axis: str = EXPERT_AXIS,
                   model_axis: str | None = None) -> Any:
    """P(axis, ...) for expert weights (w_in/w_out carry the leading experts
    dim — tpu_dist.models.moe.MoEMLP); with ``model_axis`` set, the MoE x TP
    composition (VERDICT r3 #4); P() for everything else, including the
    gate projection (its dim 0 is d_model, not experts)."""
    names = ("w_in", "w_out", "qkv", "proj", "lm_head")

    def build(tree, path=()):
        if isinstance(tree, dict):
            return {k: build(v, path + (k,)) for k, v in tree.items()}
        if path and path[-1].endswith("_scale"):
            return P()  # weight-only int8 decode scales: tiny, replicated
        key = next((n for n in reversed(path) if n in names), "")
        return _moe_leaf_spec(key, tree, axis, model_axis)
    return build(params)


def shard_moe_params(mesh: Mesh, params, axis: str = EXPERT_AXIS,
                     model_axis: str | None = None):
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             ep_param_specs(params, axis, model_axis),
                             is_leaf=lambda x: isinstance(x, P))
    # distlint: disable=DL008 -- param placement at setup/resume, not a per-step input upload
    return jax.device_put(params, shardings)


def shard_state_ep(mesh: Mesh, state, axis: str = EXPERT_AXIS,
                   model_axis: str = "model"):
    """Place a TrainState for expert parallelism: expert weights AND their
    optimizer state sharded over ``axis`` (the momentum buffers are the bulk
    of an MoE model's memory — leaving them replicated would defeat EP's
    scaling); everything else replicated. When the mesh also carries a >1
    ``model_axis``, the MoE x TP composition applies (expert MLPs split
    Megatron-style over 'model' on top of their 'expert' shard; attention
    qkv/proj and lm_head follow the tp rules — VERDICT r3 #4).

    Optimizer-state pytrees (e.g. optax trace) mirror the params dict, so the
    sharded leaves are identified by their tree PATH — never by shape (two
    tensors can share a shape without both being expert weights).
    """
    from jax.tree_util import tree_map_with_path

    from tpu_dist.engine.state import TrainState

    use_tp = model_axis in mesh.axis_names and mesh.shape[model_axis] > 1
    tp_axis = model_axis if use_tp else None
    repl = NamedSharding(mesh, P())

    def place(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        key = next((n for n in reversed(names)
                    if n in ("w_in", "w_out", "qkv", "proj", "lm_head")), "")
        spec = _moe_leaf_spec(key, leaf, axis, tp_axis) \
            if hasattr(leaf, "ndim") else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    # distlint: disable=DL008 -- state placement at setup/resume, not a per-step input upload
    return TrainState(
        step=jax.device_put(state.step, repl),
        params=shard_moe_params(mesh, state.params, axis, tp_axis),
        batch_stats=jax.device_put(state.batch_stats, repl),
        opt_state=tree_map_with_path(place, state.opt_state),
        loss_scale=(None if state.loss_scale is None
                    else jax.device_put(state.loss_scale, repl)))
