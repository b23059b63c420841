"""Autoregressive decoding for the LM family (greedy / temperature).

The reference is a training-only cookbook; a framework user still expects to
sample from the model they trained. TPU-first constraints shape the design:

* static shapes end to end — the (B, prompt+steps) token buffer is
  allocated once and a ``lax.scan`` fills one position per tick, so the
  whole decode is ONE compiled program (no per-token host round-trip);
* full-recompute attention per tick (O(steps * L^2)): causal masking makes
  positions > current length invisible to the read position, so the padded
  buffer is safe. At cookbook scales this is MXU-cheap; a KV-cache path is
  the obvious extension and slots behind the same signature;
* works with any attn_fn flavor and any mesh placement the params carry
  (replicated for decode is the normal case).

This module is the ONE-SHOT batch call; the serving layer
(``engine.serve`` + ``engine.kv_cache``) runs the same model under
continuous batching with a paged KV cache, sharing this module's sampling
(:func:`_sample`) and weight-quantization (:func:`_quantize_for_decode`)
helpers — the contiguous flax-cache program here is the single-request
degenerate case of that paged path, and greedy tokens are bit-identical
across the two (tests/test_serve.py).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _sample(nxt_logits, temperature, rng, top_k=0, top_p=0.0):
    if temperature <= 0.0:
        return jnp.argmax(nxt_logits, axis=-1), rng
    logits = nxt_logits / temperature
    if top_k:
        # keep the k best logits per row, mask the rest (static k)
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p > 0.0:
        # nucleus: smallest prefix of the sorted distribution with mass >=
        # top_p stays; everything after it is masked
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p  # first token always kept
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1)[:, None]
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, logits), rng


def generate(model, params, prompt: jax.Array, steps: int,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             use_cache: bool = False,
             top_k: int = 0, top_p: float = 0.0,
             mesh: Optional[Mesh] = None,
             quant: str = "none",
             ledger=None) -> jax.Array:
    """Continue ``prompt`` (B, P) int32 by ``steps`` tokens.

    temperature 0 = greedy argmax (deterministic); > 0 = categorical over
    logits/temperature, optionally truncated to the ``top_k`` best tokens
    and/or the ``top_p`` nucleus. Returns the full (B, P+steps) buffer.
    P+steps must not exceed the model's max_len.

    ``use_cache=True`` decodes through the model's per-block KV cache
    (``decode=True``): each tick embeds ONE token and attends over the
    cached keys/values — O(L·d) per token instead of the full-recompute
    path's O(L²·d). Both the dense TransformerLM and MoETransformerLM are
    cache-capable (they share models.transformer.attend_maybe_cached). MoE
    caveat: per-expert capacity is GROUP-LENGTH-dependent (cap = S/E *
    capacity_factor * k) and the cached prefill groups only the prompt
    while the full path groups the whole padded buffer, so the two paths
    can drop DIFFERENT tokens. Drop-free capacity (capacity_factor >= E/k,
    --moe-capacity-factor) makes them bitwise equal at any batch size —
    every token is admitted, so grouping can't matter. Under capacity
    pressure both remain valid decodes with training's dropped-token
    semantics, just not bitwise equal to each other.

    ``quant`` (ops.quant) decodes through quantized matmuls: ``int8_wo``
    pre-quantizes every dense kernel / MoE expert tensor to int8 with fp32
    per-channel scales (weights stay int8 in HBM — the decode tick is
    expected to be weight-bandwidth-bound, so weight bytes halve vs
    bf16), ``int8`` additionally quantizes activations
    dynamically inside the tick. Pass the TRAINED (fp/bf16) params; they
    are quantized here once. Greedy tokens match the unquantized decode on
    trained models (per-channel int8 keeps argmax margins —
    tests/test_quant.py pins this).

    ``mesh`` runs the SAME compiled programs sharded: the
    token buffer batch-shards over 'data' (when it divides B), the weights
    take the Megatron TP layout over 'model' (tpu_dist.parallel.tp rules:
    heads column/row-split, vocab-sharded lm_head) and the KV cache shards
    its heads axis to match — GSPMD inserts the collectives; no new decode
    code path exists. jit re-lowers per input-sharding layout, so the
    single-device memoized program and its mesh variants coexist in the
    same cache. The decode tick reads every weight once per token (~340 MB
    a tick at 0.9B), the regime where TP's 1/n_model weight traffic per
    chip should cut ms/token (not measured on the installed machine).

    ``ledger`` (an :class:`tpu_dist.obs.ledger.Ledger`) records the call as
    one ``decode`` event — tokens, wall seconds, tok/s, dispatch vs
    device-block split. Observability implies a sync: the buffer is blocked
    on before returning (the same array is returned, now ready).
    """
    b, p = prompt.shape
    if steps <= 0:
        # nothing to generate: return the prompt untouched (the cache
        # path's prefill would otherwise clamp its first-token write into
        # the last prompt column, and burn an rng split)
        return prompt
    if quant != "none":
        model, params = _quantize_for_decode(model, params, quant)
    else:
        _refuse_wo_tree(getattr(model, "quant", "none"), params)
    total = p + steps
    if rng is None:
        rng = jax.random.PRNGKey(0)
    buf = jnp.zeros((b, total), jnp.int32).at[:, :p].set(prompt)

    data_ax = model_ax = None
    if mesh is not None:
        params, buf, rng, data_ax, model_ax = _shard_decode_inputs(
            model, mesh, params, buf, rng)

    if use_cache:
        if mesh is not None:
            # allocate each leaf DIRECTLY under its sharding — building the
            # full replicated cache on one device first could OOM device 0
            # at exactly the scales sharded decode exists for
            cache = jax.tree.map(
                lambda s: jnp.zeros(
                    s.shape, s.dtype,
                    device=NamedSharding(
                        mesh, P(data_ax, None, model_ax, None)
                        if len(s.shape) == 4 else P())),
                _cache_shapes(model, b, total))
        else:
            cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 _cache_shapes(model, b, total))
        decode = _cache_decode_program(model, b, p, total, temperature,
                                       top_k, top_p)
        args = (params, cache, buf, rng)
    else:
        decode = _full_decode_program(model, b, p, total, temperature,
                                      top_k, top_p)
        args = (params, buf, rng)
    if ledger is None:
        return decode(*args)
    t0 = time.perf_counter()
    out = decode(*args)
    dispatch_s = time.perf_counter() - t0
    jax.block_until_ready(out)
    total_s = time.perf_counter() - t0
    tokens = b * steps
    ledger.emit("decode", tokens=tokens, seconds=round(total_s, 6),
                throughput=round(tokens / max(total_s, 1e-9), 1),
                dispatch_s=round(dispatch_s, 6),
                device_s=round(total_s - dispatch_s, 6),
                cached=use_cache, batch=b, prompt_len=p, steps=steps,
                quant=quant)
    return out


def prepare_draft(base_model, draft_model, draft_params, quant: str):
    """Validate + quantize a speculative-decoding DRAFT tree against its
    base (``engine.serve`` calls this once at engine construction).

    The draft proposes token IDS the base verifies, so the vocabularies
    must be literally the same space — a mismatched draft would propose
    ids the base never emits and silently decode at acceptance ~0. Depth,
    width and heads are free to differ (that is the whole point: a
    shallower draft makes k cheap proposals per one base verification).
    The draft rides the same weight-quant mode as the base, through the
    same memoized :func:`_quantize_for_decode` path, so a serving process
    holding base+draft trees quantizes each exactly once."""
    if getattr(draft_model, "vocab_size", None) != base_model.vocab_size:
        raise ValueError(
            f"draft vocab_size={getattr(draft_model, 'vocab_size', None)} "
            f"!= base vocab_size={base_model.vocab_size}: speculative "
            "verification compares token ids, so the vocabularies must "
            "be the same space")
    if draft_model.max_len < base_model.max_len:
        raise ValueError(
            f"draft max_len={draft_model.max_len} < base "
            f"max_len={base_model.max_len}: the draft must be able to "
            "sit at every position the base serves")
    if quant != "none":
        return _quantize_for_decode(draft_model, draft_params, quant)
    _refuse_wo_tree(getattr(draft_model, "quant", "none"), draft_params)
    return draft_model, draft_params


def _refuse_wo_tree(effective_mode: str, params) -> None:
    """Raise when a wo-quantized tree meets any decode mode but 'int8_wo':
    plain nn.Dense would silently use the raw int8 kernels as weights
    (flax ignores the extra scale leaves) and decode garbage, and the
    dynamic-int8 program cannot be built without the fp weights."""
    from tpu_dist.ops.quant import params_are_wo_quantized

    if effective_mode != "int8_wo" and params_are_wo_quantized(params):
        raise ValueError(
            "params are wo-quantized (int8 kernels + kernel_scale leaves) "
            f"but the decode mode is {effective_mode!r}; pass "
            "generate(..., quant='int8_wo') for a pre-quantized tree, or "
            "keep the fp params.")


def _quantize_for_decode(model, params, quant: str):
    """Rebind the model's quant mode for decode; for weight-only int8,
    pre-quantize the params (ops.quant.wo_quantize_params) so dense kernels
    and MoE expert tensors sit int8 in HBM with fp32 scale leaves — the
    decode tick is weight-bandwidth-bound, so halving the weight bytes is
    THE quant win here. Cloned modules hash by field value, so the memoized
    decode programs still cache-hit across generate() calls — and the
    quantized TREE is memoized too: a small LRU keyed on (treedef, mode,
    fp-leaf identities), so a long-lived serving process alternating
    between quant modes or between several live base trees (engine.serve
    keeps one per deployed model) never re-quantizes a live tree — the
    round-10 single-entry memo thrashed on exactly that alternation. Each
    entry holds only weakrefs to its fp leaves and self-evicts when any is
    collected, so neither tree copy is pinned past its natural lifetime."""
    from tpu_dist.ops.quant import (params_are_wo_quantized, validate_quant,
                                    wo_quantize_params)

    validate_quant(quant)
    _refuse_wo_tree(quant, params)
    if getattr(model, "quant", "none") != quant:
        if not hasattr(model, "quant"):
            raise ValueError(
                f"quant={quant!r} decode needs a quant-capable model "
                "(TransformerLM / MoETransformerLM)")
        model = model.clone(quant=quant)
    if quant == "int8_wo" and not params_are_wo_quantized(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        # id()s make the key hashable; the stored weakrefs then verify the
        # leaves are genuinely the same objects (an id can be recycled
        # after gc — the eviction callback removes the entry first, but
        # the identity check makes a lost race a re-quantize, never a
        # wrong-tree hit)
        key = (treedef, quant, tuple(id(l) for l in leaves))
        with _wo_memo_lock:
            hit = _wo_memo.get(key)
            if (hit is not None
                    and all(r() is l for r, l in zip(hit[0], leaves))):
                _wo_memo.move_to_end(key)
                return model, hit[1]
        quantized = wo_quantize_params(params)

        def _evict(_ref, _key=key):  # a fp leaf died: drop its entry
            with _wo_memo_lock:
                _wo_memo.pop(_key, None)

        # evicted entries are DESTROYED outside the lock: dropping a
        # quantized tree can trigger gc, gc can fire another entry's
        # weakref _evict on this same thread, and _evict takes the lock —
        # an RLock makes the re-entry safe and the deferred del keeps the
        # critical section free of arbitrary destructor work (the DL101
        # hazard class, in allocator form)
        evicted = []
        with _wo_memo_lock:
            _wo_memo[key] = (tuple(weakref.ref(l, _evict) for l in leaves),
                             quantized)
            _wo_memo.move_to_end(key)
            while len(_wo_memo) > _WO_MEMO_MAX:
                evicted.append(_wo_memo.popitem(last=False))
        del evicted
        params = quantized
    return model, params


# (treedef, mode, leaf ids) -> (leaf weakrefs, quantized tree): the small
# LRU of _quantize_for_decode. A serving process keeps a handful of live
# base trees at most; beyond that the caller should pre-quantize
# (wo_quantize_params) and pass the quantized tree in.
_WO_MEMO_MAX = 4
_wo_memo: "OrderedDict" = OrderedDict()
# RLock, not Lock: gc may run a weakref _evict on the thread that already
# holds the lock (see the eviction note in _quantize_for_decode)
_wo_memo_lock = threading.RLock()


def _shard_decode_inputs(model, mesh: Mesh, params, buf, rng):
    """device_put the decode inputs onto their mesh shardings.

    Returns (params, buf, rng, data_axis_or_None, model_axis_or_None).
    'data' shards the batch when it divides B; 'model' > 1 applies the
    training TP rules to the params (requires num_heads divisible). Axes
    the mesh doesn't carry (or that don't divide) fall back to replication,
    so a ('data',)-only mesh and a ('model',)-only mesh both just work.
    """
    from tpu_dist.parallel.ep import EXPERT_AXIS, shard_moe_params
    from tpu_dist.parallel.mesh import DATA_AXIS, MODEL_AXIS
    from tpu_dist.parallel.tp import shard_lm_params

    b = buf.shape[0]
    data_ax = (DATA_AXIS if DATA_AXIS in mesh.shape
               and mesh.shape[DATA_AXIS] > 1 and b % mesh.shape[DATA_AXIS] == 0
               else None)
    model_ax = (MODEL_AXIS if MODEL_AXIS in mesh.shape
                and mesh.shape[MODEL_AXIS] > 1 else None)
    experts = getattr(model, "num_experts", 0)
    expert_ax = (EXPERT_AXIS if experts and EXPERT_AXIS in mesh.shape
                 and mesh.shape[EXPERT_AXIS] > 1 else None)
    if model_ax:
        heads = getattr(model, "num_heads", 0)
        if heads % mesh.shape[MODEL_AXIS]:
            raise ValueError(
                f"TP decode shards attention heads: num_heads={heads} "
                f"must divide by mesh 'model' size {mesh.shape[MODEL_AXIS]}")
    if expert_ax:
        if experts % mesh.shape[EXPERT_AXIS]:
            raise ValueError(
                f"EP decode shards experts: num_experts={experts} must "
                f"divide by mesh 'expert' size {mesh.shape[EXPERT_AXIS]}")
        # training EP placement (+ Megatron split when 'model' rides along);
        # GSPMD turns the dispatch/combine einsums into decode all-to-alls
        params = shard_moe_params(mesh, params, model_axis=model_ax)
    elif model_ax:
        params = shard_lm_params(mesh, params)  # THE training TP placement
    else:
        params = jax.device_put(params, NamedSharding(mesh, P()))
    buf = jax.device_put(buf, NamedSharding(mesh, P(data_ax)))
    rng = jax.device_put(rng, NamedSharding(mesh, P()))
    return params, buf, rng, data_ax, model_ax


# The compiled programs are memoized per (model, geometry, sampling)
# signature: a fresh `jax.jit` closure per generate() call would make EVERY
# call retrace and recompile (jit caches by function identity) — measured at
# ~13 ms/token vs the 0.7 ms/token the compiled tick actually costs.
#
# Flax modules hash by field VALUE, and the attn_fn field hashes by function
# identity — so the attn-fn factories (flash/blockwise/ring) are lru_cached
# at their definitions: same-config factories return the same callable,
# making logically identical models (fresh LMTrainer, sp rebind) hit this
# cache instead of silently recompiling (ADVICE r4). A hand-rolled closure
# passed as attn_fn still misses; that's inherent to identity keying.


@lru_cache(maxsize=32)
def _cache_shapes(model, b, total):
    """KV-cache shape tree via eval_shape — no real init forward, and
    memoized so a sampling loop does not re-trace the whole model per call
    just to learn shapes that depend only on (model, b, total)."""
    return jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((b, total), jnp.int32), train=False,
                           decode=True))["cache"]

@lru_cache(maxsize=32)
def _cache_decode_program(model, b, p, total, temperature, top_k, top_p):
    @jax.jit
    def decode(params, cache, buf, rng):
        # prefill: ONE forward over the whole prompt writes cache[0:p)
        # (the per-block dynamic_update_slice handles a (B, P, ...) write)
        # and its last position's logits sample the first generated token —
        # P times fewer ticks than feeding the prompt one token at a time
        prompt = jax.lax.dynamic_slice(buf, (0, 0), (b, p))
        logits, muts = model.apply(
            {"params": params, "cache": cache}, prompt, train=False,
            pos_offset=0, decode=True, mutable=["cache"])
        cache = muts["cache"]
        if temperature > 0.0:
            nxt, rng = _sample(logits[:, -1], temperature, rng, top_k, top_p)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        buf = jax.lax.dynamic_update_slice(
            buf, nxt[:, None].astype(jnp.int32), (0, p))

        def tick(carry, pos):
            buf, cache, rng = carry
            tok = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))
            logits, muts = model.apply(
                {"params": params, "cache": cache}, tok, train=False,
                pos_offset=pos, decode=True, mutable=["cache"])
            # rng splits once per generated token, in generation order —
            # the same stream as the full-recompute path
            if temperature > 0.0:
                nxt, rng = _sample(logits[:, 0], temperature, rng,
                                   top_k, top_p)
            else:
                nxt = jnp.argmax(logits[:, 0], axis=-1)
            buf = jax.lax.dynamic_update_slice(
                buf, nxt[:, None].astype(jnp.int32), (0, pos + 1))
            return (buf, muts["cache"], rng), None

        (buf, _, _), _ = jax.lax.scan(
            tick, (buf, cache, rng), jnp.arange(p, total - 1))
        return buf

    return decode


@lru_cache(maxsize=32)
def _full_decode_program(model, b, p, total, temperature, top_k, top_p):
    @jax.jit
    def decode(params, buf, rng):
        def tick(carry, pos):
            buf, rng = carry
            logits = model.apply({"params": params}, buf, train=False)
            nxt_logits = jnp.take_along_axis(
                logits, pos[None, None, None].astype(jnp.int32)
                .repeat(b, 0), axis=1)[:, 0]          # (B, V) at position pos
            tok, rng = _sample(nxt_logits, temperature, rng, top_k, top_p)
            buf = jax.lax.dynamic_update_slice(
                buf, tok[:, None].astype(jnp.int32), (0, pos + 1))
            return (buf, rng), tok

        (buf, _), _ = jax.lax.scan(
            tick, (buf, rng), jnp.arange(p - 1, total - 1))
        return buf

    return decode
