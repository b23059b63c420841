"""Hybrid state-space / attention decoder LM (the Jamba family's block).

A second LM block family beside ``models.transformer``: RMSNorm, a gated
(SiLU) MLP, grouped-head attention with an explicit ``head_dim`` and NO
positional encoding of any kind, a Mamba-1 mixer, a per-layer
``layer_types`` pattern and a head tied to the embedding. Layer ``i`` is an
attention layer iff ``i % attn_layer_period == attn_layer_offset``, else a
Mamba layer (``JambaConfig.layers_block_type``); every layer's feed-forward
is the dense gated MLP (the sources this block serves have one expert).

Residual layer: ``x = x + Mixer(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``,
``MLP(h) = W_down(silu(W_gate h) * (W_up h))``, no biases; final RMSNorm,
logits ``x E^T``.

Mamba mixer over ``h[0..L)``: ``[u, z] = W_in h``; ``u =
silu(conv1d_causal(u; k = d_conv, depthwise, bias))``; ``[d, B, C] = W_x u``
(dt_rank, d_state, d_state), each through its own RMSNorm (Jamba's three
inner norms; ``MambaMixer.inner_norms``, which ``models.phi4flash`` turns
off); ``delta = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; the
selective scan (``ops.selective_scan``); output ``W_out(y * silu(z))``.
RMSNorm, softplus, exp and the scan's state are float32 whatever ``dtype``
is; ``W_dt`` runs in float32 too (its output sits at the bias, -7 .. -2,
where a bfloat16 result would move ``delta`` by percents).

Serving (``paged=``, as ``TransformerLM`` takes it): an attention layer's
slice of ``paged["layers"]`` is a ``PagedLayer`` (K and V pages of
``num_kv_heads`` heads), a Mamba layer's is a dict of per-SLOT arrays
``{"ssm": [slots, d_state, d_inner] f32, "conv": [slots, d_conv - 1,
d_inner]}``. ``paged["live"]`` (B,) counts the rows of this call that are
real tokens (a prompt's length inside its bucket; 0 for a slot the tick
carries but does not decode), and ``paged["slots"]`` (B,) names the slot
each batch row's state lives in (absent in the tick: row ``b`` is slot
``b``). A call whose rows start at position 0 starts from zero state, so
admission needs no reset. :meth:`HybridLM.cache_layout` tells the engine
all of this.

Training this block (``LMTrainer``, the scan's backward pass, tp/fsdp
specs) is not built: the registry lists it for serving and for the plain
full-sequence forward the tests use.
"""

from __future__ import annotations

from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_dist.models.transformer import attend_maybe_cached, full_attention
from tpu_dist.ops.quant import make_dense


def layer_types(num_layers: int, period: int, offset: int) -> Tuple[str, ...]:
    """("mamba", ..., "attention", ...): attention where ``i % period ==
    offset``."""
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(num_layers))


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + self.eps) * g.astype(jnp.float32))


class GroupedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype
    attn_fn: Callable
    quant: str

    @nn.compact
    def __call__(self, h, paged, paged_prefill):
        dense = lambda n, name: make_dense(
            n, use_bias=False, dtype=self.dtype, name=name, quant=self.quant)
        b, l, _ = h.shape
        q = dense(self.num_heads * self.head_dim, "q")(h).reshape(
            b, l, self.num_heads, self.head_dim)
        k = dense(self.num_kv_heads * self.head_dim, "k")(h).reshape(
            b, l, self.num_kv_heads, self.head_dim)
        v = dense(self.num_kv_heads * self.head_dim, "v")(h).reshape(
            b, l, self.num_kv_heads, self.head_dim)
        new_layer = None
        if paged is not None:
            out, new_layer = attend_maybe_cached(
                self, q, k, v, decode=False, attn_fn=self.attn_fn,
                dtype=self.dtype, paged=paged, paged_prefill=paged_prefill)
        else:
            g = self.num_heads // self.num_kv_heads
            out = self.attn_fn(q, jnp.repeat(k, g, axis=2),
                               jnp.repeat(v, g, axis=2))
        out = dense(h.shape[-1], "o")(out.reshape(b, l, -1))
        return out, new_layer


class MambaMixer(nn.Module):
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    eps: float
    dtype: jnp.dtype
    quant: str
    # the three RMSNorms on dt, B and C (the Jamba family has them; plain
    # Mamba-1, as models.phi4flash runs it, does not)
    inner_norms: bool = True
    # also return the scan's output y (with its D u, before the gate): what
    # a gated memory unit of a later layer reads
    hand_on: bool = False

    @nn.compact
    def __call__(self, h, paged):
        from tpu_dist.ops.selective_scan import (causal_conv1d,
                                                 selective_scan, ssm_step)

        b, l, d_model = h.shape
        d_inner, n, k = self.expand * d_model, self.d_state, self.d_conv
        dense = lambda feat, name, dtype=self.dtype: make_dense(
            feat, use_bias=False, dtype=dtype, name=name, quant=self.quant)
        with jax.named_scope("mamba_mixer"):
            u, z = jnp.split(dense(2 * d_inner, "in_proj")(h), 2, axis=-1)
            conv_w = self.param("conv_w", nn.initializers.lecun_normal(),
                                (k, d_inner))
            conv_b = self.param("conv_b", nn.initializers.zeros, (d_inner,))
            a_log = self.param(
                "A_log", lambda *_: jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (d_inner, n))))
            d_skip = self.param("D", nn.initializers.ones, (d_inner,))
            dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                 (d_inner,))

            # the state this call starts from and the rows that are real
            new_state = None
            if paged is None:
                live = jnp.full((b,), l, jnp.int32)
                s0 = jnp.zeros((b, n, d_inner), jnp.float32)
                tail = jnp.zeros((b, k - 1, d_inner), u.dtype)
            else:
                state, live, slots = (paged["layer"], paged["live"],
                                      paged.get("slots"))
                rows = (lambda x: x) if slots is None else (
                    lambda x: jnp.take(x, slots, axis=0))
                # a row that starts a sequence starts from zero state; a
                # row the call carries but does not feed (live 0: a slot
                # the tick does not decode, such as one parked between
                # prefill chunks) keeps what it holds
                fresh = ((paged["positions"] == 0)
                         & (live > 0))[:, None, None]
                s0 = jnp.where(fresh, 0.0, rows(state["ssm"]))
                tail = jnp.where(fresh, 0, rows(state["conv"]))

            conv, new_tail = causal_conv1d(u, conv_w, conv_b, tail, live)
            u = jax.nn.silu(conv).astype(self.dtype)
            dbc = dense(self.dt_rank + 2 * n, "x_proj")(u)
            dt, bmat, cmat = jnp.split(
                dbc, [self.dt_rank, self.dt_rank + n], axis=-1)
            if self.inner_norms:
                dt = RMSNorm(self.eps, name="dt_norm")(dt)
                bmat = RMSNorm(self.eps, name="b_norm")(bmat)
                cmat = RMSNorm(self.eps, name="c_norm")(cmat)
            delta = jax.nn.softplus(
                dense(d_inner, "dt_proj", jnp.float32)(dt)
                + dt_bias.astype(jnp.float32))
            a = -jnp.exp(a_log.astype(jnp.float32))
            if paged is not None and l == 1:
                # the tick: one token a slot, every slot's row in place
                delta = jnp.where((live > 0)[:, None], delta[:, 0], 0.0)
                y, s_last = ssm_step(u[:, 0], delta, a, bmat[:, 0],
                                     cmat[:, 0], d_skip, s0)
                y = y[:, None]
            else:
                y, s_last = selective_scan(u, delta, a, bmat, cmat, d_skip,
                                           s0, live)
            if paged is not None:
                put = ((lambda old, new: new.astype(old.dtype))
                       if slots is None else
                       (lambda old, new: old.at[slots].set(
                           new.astype(old.dtype))))
                new_state = {"ssm": put(state["ssm"], s_last),
                             "conv": put(state["conv"], new_tail)}
            gated = (y.astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(self.dtype)
            out = dense(d_model, "out_proj")(gated)
            return (out, new_state, y) if self.hand_on else (out, new_state)


class HybridBlock(nn.Module):
    kind: str                    # "attention" | "mamba"
    attention: tuple             # (num_heads, num_kv_heads, head_dim)
    mamba: tuple                 # (d_state, d_conv, expand, dt_rank)
    mlp_dim: int
    rms_eps: float
    dtype: jnp.dtype
    attn_fn: Callable
    quant: str

    @nn.compact
    def __call__(self, x, paged=None, paged_prefill: bool = False):
        h = RMSNorm(self.rms_eps, name="norm1")(x)
        if self.kind == "attention":
            out, new = GroupedAttention(
                *self.attention, self.dtype, self.attn_fn, self.quant,
                name="attn")(h, paged, paged_prefill)
        else:
            out, new = MambaMixer(
                *self.mamba, self.rms_eps, self.dtype, self.quant,
                name="mamba")(h, paged)
        x = x + out.astype(x.dtype)
        h = RMSNorm(self.rms_eps, name="norm2")(x)
        dense = lambda n, name: make_dense(
            n, use_bias=False, dtype=self.dtype, name=name, quant=self.quant)
        h = (jax.nn.silu(dense(self.mlp_dim, "gate")(h))
             * dense(self.mlp_dim, "up")(h))
        x = x + dense(x.shape[-1], "down")(h).astype(x.dtype)
        return x, new


class HybridLM(nn.Module):
    """Decoder-only hybrid LM. Input: int32 tokens (B, L); output float32
    logits (with ``paged``: ``(logits, new_layers)``)."""

    vocab_size: int = 65536
    num_layers: int = 28
    d_model: int = 2560
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    mlp_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    rms_eps: float = 1e-6
    max_len: int = 262144        # no position table: a cap the server reads
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = full_attention
    quant: str = "none"          # none | int8 | int8_wo (ops.quant), every
                                 # projection; the tied embedding stays fp

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return layer_types(self.num_layers, self.attn_layer_period,
                           self.attn_layer_offset)

    def cache_layout(self) -> tuple:
        """What each layer keeps for a sequence being served, asked once by
        ``ServeEngine``: ``("pages", kv_heads, head_dim, query heads a KV
        head)`` or ``("slot_state", {name: (shape a slot, dtype)})``."""
        d_inner = self.expand * self.d_model
        state = {"ssm": ((self.d_state, d_inner), jnp.float32),
                 "conv": ((self.d_conv - 1, d_inner), self.dtype)}
        pages = ("pages", self.num_kv_heads, self.head_dim,
                 self.num_heads // self.num_kv_heads)
        return tuple(pages if t == "attention" else ("slot_state", state)
                     for t in self.layer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pos_offset=0,
                 paged=None, paged_prefill: bool = False):
        # pos_offset is accepted for the serving programs' sake and unused:
        # this model has no positional encoding (the Mamba layers carry
        # order, the attention layers' causal mask the rest)
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="tok_emb")
        x = emb(tokens)
        ctx = (None if paged is None else
               {k: paged.get(k) for k in (
                   "block_tables", "positions", "lengths", "valid", "sp_mesh",
                   "live", "slots")})
        new_layers = []
        for i, kind in enumerate(self.layer_types):
            blk = HybridBlock(
                kind, (self.num_heads, self.num_kv_heads, self.head_dim),
                (self.d_state, self.d_conv, self.expand, self.dt_rank),
                self.mlp_dim, self.rms_eps, self.dtype, self.attn_fn,
                self.quant, name=f"layer{i}")
            if paged is None:
                x, _ = blk(x)
            else:
                x, new = blk(x, {**ctx, "layer": paged["layers"][i]},
                             paged_prefill)
                new_layers.append(new)
        x = RMSNorm(self.rms_eps, name="norm_f")(x)
        # the tied head: operands in ``dtype``, the product kept in float32
        # (a bfloat16 result would round a logit of 4 to steps of 0.03)
        logits = jnp.einsum("bld,vd->blv", x.astype(self.dtype),
                            emb.embedding.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        if paged is not None:
            return logits, tuple(new_layers)
        return logits


def hybrid_lm(vocab_size=256, num_layers=8, d_model=64, num_heads=4,
              num_kv_heads=2, head_dim=16, mlp_dim=128, d_state=16, d_conv=4,
              expand=2, dt_rank=8, attn_layer_period=4, attn_layer_offset=2,
              max_len=512, dtype=jnp.float32, attn_fn=full_attention,
              quant="none", **_):
    """A toy preset that keeps the pattern (8 layers, attention at 2 and
    6, 2 KV heads under 4 query heads)."""
    return HybridLM(vocab_size=vocab_size, num_layers=num_layers,
                    d_model=d_model, num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    mlp_dim=mlp_dim, d_state=d_state, d_conv=d_conv,
                    expand=expand, dt_rank=dt_rank,
                    attn_layer_period=attn_layer_period,
                    attn_layer_offset=attn_layer_offset, max_len=max_len,
                    dtype=dtype, attn_fn=attn_fn, quant=quant)
