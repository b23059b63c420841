"""Causal transformer LM family (long-context / parallelism testbed).

The reference trains only CNN image classifiers (SURVEY.md §2c: no attention,
no sequence dimension anywhere). tpu_dist adds a transformer family because
long-context and model parallelism are first-class in this framework: this
model is the substrate for sequence parallelism (ring attention over a 'seq'
mesh axis — tpu_dist.parallel.ring_attention) and tensor parallelism (head/
mlp sharding over a 'model' axis — tpu_dist.parallel.tp).

TPU-first design choices:
* pre-LN blocks, GELU MLP (4x), learned positional embeddings — all shapes
  static, MXU-friendly (head_dim and mlp sized in multiples of 128 at real
  scales);
* ``attn_fn`` is pluggable: the module computes qkv/out projections and
  delegates the attention contraction, so the SAME parameters run under full
  attention (single device), ring attention (seq-sharded shard_map), or any
  future pallas flash kernel — sharding changes never touch the weights;
* fp32 softmax/logits regardless of compute dtype.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_dist.ops.quant import make_dense
from tpu_dist.parallel.mesh import MODEL_AXIS


def full_attention(q, k, v, *, causal: bool = True,
                   q_offset: int = 0, kv_offset: int = 0):
    """Reference attention: (B, L, H, D) tensors, fp32 softmax.

    ``q_offset``/``kv_offset`` give the global position of the first row of
    q/k when the sequence axis is sharded (ring attention passes these).
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)


def attend_maybe_cached(mdl: nn.Module, q, k, v, *, decode: bool,
                        attn_fn: Callable, dtype, paged=None,
                        paged_prefill: bool = False):
    """Attention contraction, maintaining ``mdl``'s per-block KV cache when
    ``decode`` (the standard flax decode pattern): the cache is allocated
    at init time from the full-length input, then one position is written
    per step, and attention runs over the whole buffer with the causal mask
    hiding positions > cache_index (they are zeros anyway). Shared by the
    dense Block and MoEBlock so both families decode through ONE cache
    implementation. Decode always uses exact full attention over the cache:
    the attn_fn plug-in (flash/blockwise/ring) exists for TRAINING-time
    memory, and flash's custom_vjp can't take the traced cache index as its
    static offset anyway.

    ``paged`` (engine.kv_cache / ops.paged_attention) swaps the flax cache
    for this layer's slice of an EXTERNAL paged KV pool: the pack carries
    the layer's page arenas plus per-row block tables and positions, so
    every batch row can sit at its own position — the continuous-batching
    serving path, where the flax cache's scalar ``cache_index`` is exactly
    what doesn't work. Returns ``(out, updated_layer)`` in that mode; the
    flax-cache contiguous path remains the single-batch degenerate case
    (engine.generate) and is bit-identical on greedy tokens
    (tests/test_serve.py pins it)."""
    if paged is not None:
        from tpu_dist.ops.paged_attention import paged_attend

        return paged_attend(q, k, v, paged, prefill=paged_prefill,
                            attn_fn=attn_fn, dtype=dtype)
    if not decode:
        return attn_fn(q, k, v)
    is_init = mdl.has_variable("cache", "cached_k")
    ck = mdl.variable("cache", "cached_k", jnp.zeros, k.shape, dtype)
    cv = mdl.variable("cache", "cached_v", jnp.zeros, v.shape, dtype)
    ci = mdl.variable("cache", "cache_index",
                      lambda: jnp.zeros((), jnp.int32))
    if not is_init:
        return attn_fn(q, k, v)
    idx = ci.value
    z = jnp.zeros((), idx.dtype)  # match idx dtype (x64-safe)
    ck.value = jax.lax.dynamic_update_slice(
        ck.value, k.astype(dtype), (z, idx, z, z))
    cv.value = jax.lax.dynamic_update_slice(
        cv.value, v.astype(dtype), (z, idx, z, z))
    ci.value = idx + q.shape[1]
    return full_attention(q, ck.value, cv.value, q_offset=idx, kv_offset=0)


class Block(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = full_attention
    quant: str = "none"  # none | int8 | int8_wo — dense/attention
                         # projections via ops.quant (the attention
                         # contraction itself and the norms stay fp)
    tp_impl: str = "gspmd"  # gspmd (compiler-partitioned, the default) |
                            # ring (AG-matmul / matmul-RS collective matmul
                            # over a seq-sharded residual, inside shard_map
                            # with the 'model' axis bound) | ring_ar
                            # (full-token residual, chunked ring allreduce
                            # of the row partials — parallel.overlap)

    @nn.compact
    def __call__(self, x, train: bool = True, decode: bool = False,
                 paged=None, paged_prefill: bool = False):
        ring = self.tp_impl != "gspmd"
        if ring and decode:
            raise ValueError("tp_impl='ring' is a training path; decode "
                             "rides the GSPMD layers")
        if ring:
            # fail with the real constraint, not a reshape error three ops
            # later: each shard's qkv slice must hold whole heads
            from tpu_dist.parallel.overlap import static_axis_size
            n = static_axis_size(MODEL_AXIS)
            if self.num_heads % n:
                raise ValueError(
                    f"tp_impl='{self.tp_impl}' shards attention heads: "
                    f"num_heads {self.num_heads} must divide by the "
                    f"'{MODEL_AXIS}' axis ({n})")
        # under tp_impl='ring' the residual x is this device's SEQUENCE
        # chunk (B, L/n, D): the column projections gather the full
        # sequence for a head/feature shard, the row projections scatter
        # it back reduced — all shapes below derive from the inputs, so
        # one body serves the replicated and both ring dataflows
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        tp = dict(tp_impl=self.tp_impl) if ring else {}
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        qkv = make_dense(3 * d_model, use_bias=False, dtype=self.dtype,
                         name="qkv", quant=self.quant,
                         tp_kind="column", tp_fused=3, **tp)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (q.shape[0], q.shape[1], -1, head_dim)  # local heads if ring
        q, k, v = q.reshape(shp), k.reshape(shp), v.reshape(shp)
        out = attend_maybe_cached(self, q, k, v, decode=decode,
                                  attn_fn=self.attn_fn, dtype=self.dtype,
                                  paged=paged, paged_prefill=paged_prefill)
        new_layer = None
        if paged is not None:
            out, new_layer = out
        out = out.reshape(out.shape[0], out.shape[1], -1)
        x = x + make_dense(d_model, use_bias=False, dtype=self.dtype,
                           name="proj", quant=self.quant,
                           tp_kind="row", **tp)(out)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        h = make_dense(4 * d_model, dtype=self.dtype, name="mlp_in",
                       quant=self.quant, tp_kind="column", **tp)(h)
        h = nn.gelu(h)
        x = x + make_dense(d_model, dtype=self.dtype, name="mlp_out",
                           quant=self.quant, tp_kind="row", **tp)(h)
        if paged is not None:
            return x, new_layer
        return x


class TransformerLM(nn.Module):
    """Decoder-only LM. Input: int32 tokens (B, L); output fp32 logits."""

    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 8
    max_len: int = 2048
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = full_attention
    remat: bool = False  # rematerialize each block's activations in the
                         # backward pass (jax.checkpoint): trades FLOPs for
                         # HBM — the long-context memory lever
    quant: str = "none"  # none | int8 | int8_wo (ops.quant): int8 dense/
                         # attention projections + lm_head; param tree is
                         # IDENTICAL to the unquantized model, so the knob
                         # composes with checkpoints and every sharding
    tp_impl: str = "gspmd"  # gspmd (declarative TP via parallel.tp specs)
                            # | ring (manual collective-matmul TP inside
                            # shard_map over the 'model' axis — parallel.
                            # overlap; param tree IDENTICAL, so both impls
                            # load the same checkpoints). Under ring the
                            # residual stream is seq-sharded between the
                            # projections; outputs are this device's
                            # (B, L/n, ...) sequence chunk.

    def cache_layout(self) -> tuple:
        """What each layer keeps for a sequence being served, asked once by
        ``ServeEngine``: in every layer ``("pages", kv_heads, head_dim, query
        heads a KV head)``, here K and V pages of every head."""
        return (("pages", self.num_heads, self.d_model // self.num_heads,
                 1),) * self.num_layers

    @nn.compact
    def __call__(self, tokens, train: bool = True, pos_offset=0,
                 decode: bool = False, return_features: bool = False,
                 paged=None, paged_prefill: bool = False):
        # pos_offset: global position of this shard's first token (sequence
        # parallelism passes axis_index * shard_len, a traced scalar; 0 when
        # the sequence axis is unsharded; the paged serving tick passes a
        # (B,) vector — every slot sits at its own position). decode=True
        # enables the per-block KV cache ('cache' collection) for
        # autoregressive generation; `paged` instead threads an EXTERNAL
        # paged KV pool through the blocks (engine.kv_cache) and makes the
        # call return (logits, updated_layers). return_features=True skips
        # lm_head and returns the (B, L, D) post-ln_f features — the
        # chunked-loss path (ops.fused_xent) applies the head itself, one
        # row-chunk at a time, so the full (B, L, V) logits never
        # materialize.
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="tok_emb")(tokens)
        pos_emb = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                           name="pos_emb")
        off = jnp.asarray(pos_offset)
        if off.ndim:  # per-row positions: (B,) + (L,) -> (B, L) lookups
            pos = off[:, None] + jnp.arange(tokens.shape[1])[None, :]
            x = x + pos_emb(pos)
        else:
            x = x + pos_emb(pos_offset + jnp.arange(tokens.shape[1]))[None]
        if self.tp_impl == "ring":
            # enter the seq-sharded ring residual: from here each device
            # carries its (B, L/n, D) chunk; the blocks' column/row ring
            # projections gather/scatter around it (parallel.overlap)
            if decode:
                raise ValueError("tp_impl='ring' is a training path; "
                                 "decode rides the GSPMD layers")
            from tpu_dist.parallel.overlap import seq_shard
            x = seq_shard(x)
        # remat exists for the training backward; the paged serving path
        # never differentiates, and remat's static_argnums would try to
        # make the traced `paged` pack static — plain blocks there, always
        block_cls = (nn.remat(Block, static_argnums=(2, 3))
                     if self.remat and paged is None else Block)
        new_layers = []
        ctx = (None if paged is None else
               {k: paged.get(k) for k in ("block_tables", "positions",
                                          "lengths", "valid", "sp_mesh")})
        for i in range(self.num_layers):
            blk = block_cls(self.num_heads, self.dtype, self.attn_fn,
                            self.quant, self.tp_impl, name=f"block{i}")
            if paged is None:
                x = blk(x, train, decode)
            else:
                x, nl = blk(x, train, decode,
                            {**ctx, "layer": paged["layers"][i]},
                            paged_prefill)
                new_layers.append(nl)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_features:
            if paged is not None:
                # the early return would silently DROP the updated arenas
                # (stale KV on every later tick, no error) — refuse until
                # a chunked-head serving path actually threads them
                raise ValueError("return_features=True cannot ride the "
                                 "paged cache path: the updated page "
                                 "arenas would be discarded")
            return x
        # the head stays a full local matmul under ring (kernel replicated,
        # rows = this device's seq chunk), so the fp32 softmax/loss math is
        # untouched; parity with GSPMD's vocab-sharded head is exact
        logits = make_dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                            name="lm_head", quant=self.quant)(x)
        logits = logits.astype(jnp.float32)
        if paged is not None:
            return logits, tuple(new_layers)
        return logits


def tiny_lm(vocab_size=256, num_layers=2, d_model=64, num_heads=4,
            max_len=512, dtype=jnp.float32, attn_fn=full_attention,
            remat=False, quant="none", tp_impl="gspmd", **_):
    return TransformerLM(vocab_size=vocab_size, num_layers=num_layers,
                        d_model=d_model, num_heads=num_heads, max_len=max_len,
                        dtype=dtype, attn_fn=attn_fn, remat=remat,
                        quant=quant, tp_impl=tp_impl)
