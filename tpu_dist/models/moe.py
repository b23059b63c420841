"""Mixture-of-Experts MLP + expert parallelism (GShard/Switch-style).

Absent from the reference (SURVEY.md §2c: EP/MoE ABSENT). TPU-first MoE is
the GShard dispatch pattern: top-1 (Switch) or top-2 (GShard) gating, fixed
expert capacity so every shape is static, and one-hot dispatch/combine
einsums that XLA turns into all-to-alls when the expert dimension is sharded
over the ``expert`` mesh axis (tpu_dist.parallel.ep) — no dynamic
gather/scatter, no host routing.

Load-balancing: the Switch auxiliary loss (fraction-of-tokens x mean-gate
per expert) is ``sow``n into the 'intermediates' collection under
``aux_loss``; the LM train step picks every sown aux_loss up generically and
adds ``aux_weight`` times their sum to the objective.

What is served and what is not. This module is the TRAINED path: a capacity
factor, ``router_top_k`` 1 or 2, softmax scores, ``(G, S, E, C)`` one-hot
dispatch. ``ServeEngine`` refuses ``MoETransformerLM`` (its ``MoEBlock``
takes no ``paged``, the model answers no ``cache_layout()``, and a dropped
row would make a served token depend on who shares its tick); it runs
through ``engine.generate`` alone. The engine does serve an expert model
whose routed layer takes rows and drops nothing:
``models.nemotron_h`` over ``ops.routed_experts`` (sigmoid scores, any
``top_k`` of any router width, a shared expert, the experts HELD here as one
rank of an expert-parallel group; no capacity factor, no exchange across
the ``expert`` mesh axis, no trainer).
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_dist.ops.quant import dequantize, make_dense, moe_expert_matmul


def moe_group_geometry(total_tokens: int, seq_len: int, num_experts: int,
                       router_top_k: int, group_size: int = 512,
                       capacity_factor: float = 1.25):
    """(group tokens S, per-expert capacity C) — THE dispatch geometry,
    shared by MoEMLP and the analytical MFU accounting
    (tpu_dist.utils.mfu.moe_lm_flops_per_token) so they cannot drift."""
    s = min(group_size, total_tokens)
    if total_tokens % s:  # group size must divide tokens; fall back to rows
        s = seq_len
    cap = max(1, int(s / num_experts * capacity_factor * router_top_k))
    return s, cap


class MoEMLP(nn.Module):
    """MoE feed-forward: top-1 (Switch) or top-2 (GShard) gate,
    capacity-bounded dispatch.

    Input (B, L, D) -> (B, L, D). Expert weights carry a leading experts dim
    sharded over the 'expert' axis by tpu_dist.parallel.ep.ep_param_specs.

    GShard grouping: tokens are processed in groups of ``group_size`` with
    per-group capacity, so the dispatch/combine tensors are (G, S, E, C) with
    C = S/E * factor — memory O(T * S * factor) instead of the O(T^2) a
    global dispatch would cost, and the cumsum that assigns capacity slots is
    group-local (no cross-shard sequential dependency when the group dim is
    sharded over 'data'). Dispatch one-hots are kept in the compute dtype
    (bf16 halves their footprint under the bf16 policy).
    """

    num_experts: int = 4
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    group_size: int = 512
    router_top_k: int = 1      # 1 = Switch, 2 = GShard-style top-2
    z_loss_coef: float = 1e-3  # router z-loss weight RELATIVE to the balance
                               # loss (both ride the single sown aux_loss,
                               # scaled by the step's aux_weight)
    dtype: jnp.dtype = jnp.float32
    quant: str = "none"        # none | int8 | int8_wo (ops.quant): the
                               # expert matmuls only — the fp32 router gate
                               # and the one-hot dispatch/combine einsums
                               # are selection, not compute, and stay fp

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.router_top_k not in (1, 2):
            raise ValueError("router_top_k must be 1 or 2")
        b, l, d = x.shape
        t = b * l
        e = self.num_experts
        f = self.mlp_ratio * d
        s, cap = moe_group_geometry(t, l, e, self.router_top_k,
                                    self.group_size, self.capacity_factor)
        g = t // s

        tokens = x.reshape(g, s, d)
        gate_logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                               name="gate")(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, axis=-1)          # (G, S, E) fp32
        expert_idx = jnp.argmax(probs, axis=-1)               # (G, S)
        gate = jnp.max(probs, axis=-1)                        # (G, S)

        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (G, S, E)
        # position of each token in its expert's queue within the group
        pos = (jnp.cumsum(onehot, axis=1) * onehot - onehot).astype(jnp.int32)
        keep = (pos < cap).astype(jnp.float32) * onehot
        # dispatch tensor (G, S, E, C): one-hot over capacity slots
        disp = keep[..., None] * jax.nn.one_hot(pos, cap, dtype=jnp.float32)

        if self.router_top_k == 1:
            combine = disp * gate[..., None, None]
        else:
            # second choice: argmax with the first expert masked out; its
            # tokens queue BEHIND every first-choice token of that expert
            # (GShard order), and the two gates renormalize to sum to 1
            probs2 = probs * (1.0 - onehot)
            idx2 = jnp.argmax(probs2, axis=-1)
            gate2 = jnp.max(probs2, axis=-1)
            denom = jnp.maximum(gate + gate2, 1e-9)
            combine = disp * (gate / denom)[..., None, None]
            onehot2 = jax.nn.one_hot(idx2, e, dtype=jnp.float32)
            count1 = jnp.sum(keep, axis=1, keepdims=True)     # (G, 1, E)
            pos2 = (jnp.cumsum(onehot2, axis=1) * onehot2 - onehot2
                    + count1 * onehot2).astype(jnp.int32)
            keep2 = (pos2 < cap).astype(jnp.float32) * onehot2
            disp2 = keep2[..., None] * jax.nn.one_hot(pos2, cap,
                                                      dtype=jnp.float32)
            disp = disp + disp2
            combine = combine + disp2 * (gate2 / denom)[..., None, None]

        # Switch aux loss: E * sum_e( token_fraction_e * mean_prob_e ),
        # plus the router z-loss mean(logsumexp(logits)^2) that keeps gate
        # logits from drifting to magnitudes where softmax saturates
        frac = jnp.mean(onehot, axis=(0, 1))
        mean_prob = jnp.mean(probs, axis=(0, 1))
        z = jnp.mean(jax.scipy.special.logsumexp(gate_logits, axis=-1) ** 2)
        self.sow("intermediates", "aux_loss",
                 e * jnp.sum(frac * mean_prob) + self.z_loss_coef * z)
        # diagnostic (NOT part of the objective — the step only sums
        # 'aux_loss' leaves): per-token combine mass, ~gate1 for top-1 and
        # ~1.0 for top-2 when capacity admits both choices
        self.sow("intermediates", "combine_mass",
                 jnp.sum(combine, axis=(-2, -1)))

        w_in = self.param("w_in", nn.initializers.lecun_normal(), (e, d, f))
        w_out = self.param("w_out", nn.initializers.lecun_normal(), (e, f, d))
        if self.has_variable("params", "w_in_scale"):
            # pre-quantized weight-only decode (ops.quant.wo_quantize_params):
            # experts live int8 in HBM, dequantized on the fly
            w_in = dequantize(w_in, self.get_variable("params", "w_in_scale"),
                              self.dtype)
            w_out = dequantize(w_out,
                               self.get_variable("params", "w_out_scale"),
                               self.dtype)
            expert_quant = "none"
        else:
            w_in, w_out = w_in.astype(self.dtype), w_out.astype(self.dtype)
            expert_quant = self.quant

        disp_c = disp.astype(self.dtype)
        expert_in = jnp.einsum("gsec,gsd->gecd", disp_c,
                               tokens.astype(self.dtype))      # (G, E, C, D)
        h = moe_expert_matmul("gecd,edf->gecf", expert_in, w_in,
                              quant=expert_quant)
        h = nn.gelu(h)
        expert_out = moe_expert_matmul("gecf,efd->gecd", h, w_out,
                                       quant=expert_quant)     # (G, E, C, D)
        out = jnp.einsum("gsec,gecd->gsd", combine.astype(self.dtype),
                         expert_out)
        # dropped tokens (over capacity) pass through the residual unchanged
        return out.reshape(b, l, d)


class MoEBlock(nn.Module):
    """Transformer block whose MLP is a MoEMLP (attention unchanged)."""

    num_heads: int
    num_experts: int = 4
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = None  # default set in __call__ to avoid import cycle
    router_top_k: int = 1
    group_size: int = 512
    capacity_factor: float = 1.25
    quant: str = "none"
    tp_impl: str = "gspmd"  # ring = collective-matmul attention projections
                            # over a seq-sharded residual (parallel.overlap);
                            # the MoE MLP then routes SHARD-LOCALLY, the same
                            # composition contract as MoE x sp

    @nn.compact
    def __call__(self, x, train: bool = True, decode: bool = False):
        from tpu_dist.models.transformer import (attend_maybe_cached,
                                                 full_attention)

        ring = self.tp_impl != "gspmd"
        if ring and decode:
            raise ValueError("tp_impl='ring' is a training path; decode "
                             "rides the GSPMD layers")
        attn = self.attn_fn or full_attention
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        tp = dict(tp_impl=self.tp_impl) if ring else {}
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        qkv = make_dense(3 * d_model, use_bias=False, dtype=self.dtype,
                         name="qkv", quant=self.quant,
                         tp_kind="column", tp_fused=3, **tp)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (q.shape[0], q.shape[1], -1, head_dim)  # local heads if ring
        out = attend_maybe_cached(self, q.reshape(shp), k.reshape(shp),
                                  v.reshape(shp), decode=decode,
                                  attn_fn=attn, dtype=self.dtype)
        out = out.reshape(out.shape[0], out.shape[1], -1)
        x = x + make_dense(d_model, use_bias=False, dtype=self.dtype,
                           name="proj", quant=self.quant,
                           tp_kind="row", **tp)(out)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        x = x + MoEMLP(self.num_experts, dtype=self.dtype,
                       router_top_k=self.router_top_k,
                       group_size=self.group_size,
                       capacity_factor=self.capacity_factor,
                       quant=self.quant,
                       name="moe")(h, train)
        return x


class MoETransformerLM(nn.Module):
    """Decoder-only LM with MoE feed-forward in every block."""

    vocab_size: int = 256
    num_layers: int = 2
    d_model: int = 64
    num_heads: int = 4
    num_experts: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = None
    router_top_k: int = 1
    group_size: int = 512  # router group tokens (GShard grouping; under
                           # sequence parallelism groups are shard-local,
                           # so a group_size dividing the shard's tokens
                           # keeps routing identical to the dp grouping)
    capacity_factor: float = 1.25  # per-expert queue = S/E * factor * k.
                           # Capacity is GROUP-LENGTH-dependent, so paths
                           # that group the same tokens differently (e.g.
                           # KV-cache prefill vs full-recompute decode)
                           # only agree exactly when capacity admits every
                           # token; factor >= E/k makes dispatch drop-free.
    remat: bool = False  # rematerialize each MoE block in the backward pass
                         # (the expert dispatch/combine tensors are the
                         # memory hogs — jax.checkpoint per block is the
                         # same HBM lever the dense LM has)
    quant: str = "none"  # none | int8 | int8_wo (ops.quant): attention
                         # projections + expert matmuls + lm_head; router
                         # gate and dispatch/combine stay fp
    tp_impl: str = "gspmd"  # ring = seq-sharded collective-matmul attention
                            # with shard-local expert routing (MoEBlock;
                            # group_size must divide the shard's tokens)

    @nn.compact
    def __call__(self, tokens, train: bool = True, pos_offset=0,
                 decode: bool = False, return_features: bool = False):
        # decode=True enables the per-block KV cache (same pattern as the
        # dense TransformerLM — engine.generate's use_cache path); the MoE
        # MLP itself is per-token/stateless, so routing a single decode
        # position is exact (its group is just the current batch column)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="tok_emb")(tokens)
        pos = pos_offset + jnp.arange(tokens.shape[1])
        x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                         name="pos_emb")(pos)[None]
        if self.tp_impl == "ring":
            if decode:
                raise ValueError("tp_impl='ring' is a training path; "
                                 "decode rides the GSPMD layers")
            from tpu_dist.parallel.overlap import seq_shard
            x = seq_shard(x)
        block_cls = (nn.remat(MoEBlock, static_argnums=(2, 3)) if self.remat
                     else MoEBlock)
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, self.num_experts, self.dtype,
                          self.attn_fn, self.router_top_k, self.group_size,
                          self.capacity_factor, self.quant, self.tp_impl,
                          name=f"block{i}")(x, train, decode)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_features:
            # chunked-loss path (ops.fused_xent): head applied per row-chunk
            return x
        logits = make_dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                            name="lm_head", quant=self.quant)(x)
        return logits.astype(jnp.float32)
