"""Hybrid Mamba-2 / latent mixture-of-experts / attention decoder LM (the
``nemotron_h`` family: NVIDIA-Nemotron-3-Super-120B-A12B, whose published
``config.json`` gives every size below; the layer equations are the
family's modeling code and the Nemotron-H and Nemotron 3 reports, as
recalled).

A fourth LM block family, built from ``models.hybrid``'s parts (its
``RMSNorm`` and ``GroupedAttention``; ``ops.quant.make_dense``). A block has
ONE sublayer, its kind the ``i``-th character of ``pattern`` (the published
``hybrid_override_pattern``) and nothing else: ``x = x + Mixer_i(RMS_i(x))``
with ``x`` the residual stream in the activations' type and ``RMS`` an
RMSNorm with gain (eps 1e-5, float32 inside). After the last block a final
``RMS``, then logits ``x W_head^T`` with float32 accumulation; ``W_head`` is
NOT tied to the embedding. No positional encoding of any kind: the Mamba
layers carry order, the attention layers' causal mask the rest.

``M``, Mamba-2 (``d_in = mamba_heads x mamba_head_dim``, ``G`` groups, ``N``
states): ``[z, xBC, dt] = W_in h`` (``d_in + (d_in + 2 G N) + mamba_heads``
wide, no bias); ``xBC = silu(conv1d_causal(xBC) + b)`` (depthwise, kernel
``d_conv``, over all ``d_in + 2 G N`` channels); ``[x, B, C] = xBC``, ``x``
as ``[mamba_heads, mamba_head_dim]``, ``B``, ``C`` as ``[G, N]``; ``dt =
softplus(dt + dt_bias)`` (not clamped), ``A = -exp(A_log)`` (one scalar a
head); the recurrence of ``ops.ssd`` with ``D`` a head; ``y = y * silu(z)``
(the gate BEFORE the norm), an RMSNorm over each group of ``d_in / G``
channels separately (one gain of ``d_in``), ``W_out``. A slot keeps the
state ``[mamba_heads, mamba_head_dim, N]`` float32 and the convolution's
last ``d_conv - 1`` rows.

``*``, attention: ``hybrid.GroupedAttention`` (``num_heads`` query heads
over ``num_kv_heads`` KV heads of ``head_dim``, no bias, no positions).

``E``, latent experts, with ``h`` the normed block input: the router
(``ops.routed_experts.route``: ``sigmoid(W_g h)`` over ``router_width``
experts in float32, the ``top_k`` largest of ``score + b_sel``, weights
``routed_scale * s_e / (sum of the kept s + 1e-20)``); ``u = W_dn h`` (the
latent, ``latent`` wide); expert ``e``: ``W2_e relu(W1_e u)^2`` (no gate, no
bias); ``out = W_up(sum over the kept e of w_e f_e(u)) + W2_s relu(W1_s
h)^2`` with the shared expert on the full width. Nothing is cached.

**Expert parallelism.** ``expert_share = (of, index)`` says that ``of``
chips share each expert layer and this one is rank ``index``: it holds the
contiguous block ``parallel.ep.expert_share`` names (``router_width / of``
experts: the only expert weights the model has), routes over all
``router_width``, keeps ``top_k`` of them, and adds what ITS experts give;
``w_e`` is normalised over all kept experts, held here or not. What the
absent experts would add is left out (their chips add it; no code stands in
for them or for the exchange), and the partial sum with the shared expert,
which every chip computes, goes on to the next layer. ``vocab_size`` is the
rows of embedding and head held here (a slice of the vocabulary is a
smaller vocabulary).

**Multi-token prediction.** The source's draft head (``num_nextn_predict_
layers`` 1, ``mtp_hybrid_override_pattern`` ``*E``) is a module for
self-speculation. The main model's logits do not depend on it, the engine
refuses ``spec_k`` over slot state, and no weight of it is made here.

Serving (``paged=``, as ``HybridLM`` takes it): :meth:`NemotronHLM.
cache_layout` answers ``slot_state`` with the two arrays above for ``M``,
``pages`` laid out in ROWS for ``*`` (a token's KV heads side by side,
``ops.paged_attention.PagedLayer``) and ``slot_state`` with no array for
``E``. ``ops.paged_attention.paged_attend``, which ``GroupedAttention``
reaches, takes the rows layer by its rank: the tick reads it in place with
the grouped kernel where a head fills the lanes (the published 2 KV heads
of 128 under 32 query heads do; a toy head does not and takes the gathered
twin), a prefill chunk's window (Lq > 1) gathers the slot's rows and views
them by head, and int8 pages (``kv_quant="int8"``) are refused by the pool,
which builds no int8 rows. The tick's Mamba-2 layers update the state of
the rows that decode IN PLACE (``ops.ssd.ssd_step_live``: a slot that sits
out is neither read nor written) where the state fills the lanes (the
published 128 float32 states do, ``ops.ssd.head_tile``; the toy's 16 take
the plain step over every row), all from ONE list of the live rows, made in
:meth:`NemotronHLM.__call__` once a tick program. A prefill
runs the final norm and the head on each prompt's LAST LIVE row only and
returns logits ``[B, 1, V]``. Where a call can mutate the ``expert_counts``
collection (the engine's tick and prefill programs ask for it), each ``E``
layer sows three int32 scalars there: ``rows``, the assignments of live rows
that landed on held experts, ``hit``, the held experts with at least one,
and ``grouped``, the calls of the grouped product it made (2 in the sorted
form, 0 in the masked dense one: static, the form the program was traced
in); ``chosen`` (the kept experts a row) goes to ``intermediates``.

Training this block is not built (no scan here has a backward); the
registry lists it for serving and for the plain full-sequence forward.
"""

from __future__ import annotations

from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_dist.models.hybrid import GroupedAttention, RMSNorm
from tpu_dist.models.transformer import full_attention
from tpu_dist.ops.quant import dequantize, make_dense, wo_fake_quant

KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


def layer_types(pattern: str) -> Tuple[str, ...]:
    """The kinds from the pattern string and nothing else."""
    return tuple(KINDS[c] for c in pattern)


def squared_relu(x):
    return jnp.square(jax.nn.relu(x))


class Mamba2Mixer(nn.Module):
    heads: int
    head_dim: int
    d_state: int
    groups: int
    d_conv: int
    chunk: int
    eps: float
    dtype: jnp.dtype
    quant: str

    @nn.compact
    def __call__(self, h, paged):
        from tpu_dist.ops.selective_scan import causal_conv1d
        from tpu_dist.ops.ssd import (head_tile, ssd_scan, ssd_step,
                                      ssd_step_live)

        b, l, d_model = h.shape
        nh, p, n, g, k = (self.heads, self.head_dim, self.d_state,
                          self.groups, self.d_conv)
        d_in, bc = nh * p, g * n
        dense = lambda feat, name: make_dense(
            feat, use_bias=False, dtype=self.dtype, name=name,
            quant=self.quant)
        with jax.named_scope("mamba_mixer"):
            z, xbc, dt = jnp.split(
                dense(2 * d_in + 2 * bc + nh, "in_proj")(h),
                [d_in, 2 * d_in + 2 * bc], axis=-1)
            conv_w = self.param("conv_w", nn.initializers.lecun_normal(),
                                (k, d_in + 2 * bc))
            conv_b = self.param("conv_b", nn.initializers.zeros,
                                (d_in + 2 * bc,))
            a_log = self.param("A_log", lambda *_: jnp.log(
                jnp.arange(1, nh + 1, dtype=jnp.float32)))
            d_skip = self.param("D", nn.initializers.ones, (nh,))
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (nh,))
            gain = self.param("y_norm", nn.initializers.ones, (d_in,))

            # the state this call starts from and the rows that are real
            # (``hybrid.MambaMixer``'s rules: a row that starts a sequence
            # starts from zero state, a row the call carries but does not
            # feed keeps what it holds)
            new_state, tile = None, 0
            if paged is None:
                live = jnp.full((b,), l, jnp.int32)
                s0 = jnp.zeros((b, nh, p, n), jnp.float32)
                tail = jnp.zeros((b, k - 1, d_in + 2 * bc), xbc.dtype)
            else:
                state, live, slots = (paged["layer"], paged["live"],
                                      paged.get("slots"))
                rows = (lambda x: x) if slots is None else (
                    lambda x: jnp.take(x, slots, axis=0))
                fresh = (paged["positions"] == 0) & (live > 0)
                # the tick (its live rows come listed: every row IS a slot)
                # where the kernel takes the state's shape: the live rows'
                # state is updated in place, and nothing here passes over
                # the whole array
                tile = (head_tile(state["ssm"], g)
                        if paged.get("live_rows") is not None else 0)
                s0 = state["ssm"] if tile else jnp.where(
                    fresh[:, None, None, None], 0.0, rows(state["ssm"]))
                tail = jnp.where(fresh[:, None, None], 0,
                                 rows(state["conv"]))

            conv, new_tail = causal_conv1d(xbc, conv_w, conv_b, tail, live)
            xbc = jax.nn.silu(conv).astype(self.dtype)
            x, bmat, cmat = jnp.split(xbc, [d_in, d_in + bc], axis=-1)
            x = x.reshape(b, l, nh, p)
            bmat, cmat = (v.reshape(b, l, g, n) for v in (bmat, cmat))
            delta = jax.nn.softplus(dt.astype(jnp.float32)
                                    + dt_bias.astype(jnp.float32))
            a = -jnp.exp(a_log.astype(jnp.float32))
            if tile:
                y, s_last = ssd_step_live(
                    x[:, 0], delta[:, 0], a, bmat[:, 0], cmat[:, 0], d_skip,
                    s0, paged["live_rows"], fresh, tile)
                y = y[:, None]
            elif paged is not None and l == 1:
                # one token a row, a row that sits out keeps its state
                delta = jnp.where((live > 0)[:, None], delta[:, 0], 0.0)
                y, s_last = ssd_step(x[:, 0], delta, a, bmat[:, 0],
                                     cmat[:, 0], d_skip, s0)
                y = y[:, None]
            else:
                y, s_last = ssd_scan(x, delta, a, bmat, cmat, d_skip, s0,
                                     live, chunk=self.chunk)
            if paged is not None:
                put = ((lambda old, new: new.astype(old.dtype))
                       if slots is None else
                       (lambda old, new: old.at[slots].set(
                           new.astype(old.dtype))))
                new_state = {"ssm": put(state["ssm"], s_last),
                             "conv": put(state["conv"], new_tail)}
            with jax.named_scope("gated_norm"):
                y = (y.reshape(b, l, g, d_in // g).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32)).reshape(
                         b, l, g, d_in // g))
                y = (y * jax.lax.rsqrt(
                    jnp.mean(y * y, -1, keepdims=True) + self.eps)).reshape(
                        b, l, d_in) * gain.astype(jnp.float32)
            return dense(d_model, "out_proj")(y.astype(self.dtype)), new_state


class LatentExperts(nn.Module):
    router_width: int            # experts the router scores
    held: tuple                  # (held_lo, held_n): the block that is here
    top_k: int
    routed_scale: float
    latent: int
    expert_dim: int
    shared_dim: int
    dtype: jnp.dtype
    quant: str

    @nn.compact
    def __call__(self, h, live):
        """``h`` [b, l, d] float32 (the normed block input), ``live`` [b]:
        rows of each batch row that are real tokens."""
        from tpu_dist.ops.routed_experts import (grouped_calls, route,
                                                 routed_experts)

        if self.quant == "int8":
            raise NotImplementedError(
                "quant='int8' (int8 activations) over the routed experts: "
                "their grouped products take weight-only int8 (int8_wo) or "
                "none")
        b, l, d = h.shape
        held_lo, held_n = self.held
        dense = lambda feat, name: make_dense(
            feat, use_bias=False, dtype=self.dtype, name=name,
            quant=self.quant)
        with jax.named_scope("moe"):
            b_sel = self.param("b_sel", nn.initializers.zeros,
                               (self.router_width,))
            with jax.named_scope("moe_router"):
                # float32 operands and result at the highest precision: a
                # near-tie at the top_k-th place sends a row elsewhere
                scores = nn.Dense(
                    self.router_width, use_bias=False, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST, name="gate")(
                        h.reshape(b * l, d).astype(jnp.float32))
            idx, w = route(scores, b_sel, self.top_k, self.routed_scale)
            self.sow("intermediates", "chosen", idx.reshape(b, l, -1))
            with jax.named_scope("latent_proj"):
                u = dense(self.latent, "down")(h)
            w_in = self.param("w_in", nn.initializers.lecun_normal(),
                              (held_n, self.latent, self.expert_dim))
            w_out = self.param("w_out", nn.initializers.lecun_normal(),
                               (held_n, self.expert_dim, self.latent))
            # the experts as they lie in HBM go to the routed products as
            # they are; what is computed from them on the way (dequantised,
            # fake-quantised, cast) is for XLA to fuse into the products
            stored = (w_in.dtype == self.dtype and self.quant == "none")
            if self.has_variable("params", "w_in_scale"):
                # pre-quantized weight-only decode (ops.quant.
                # wo_quantize_params): the experts live int8 in HBM
                w_in = dequantize(
                    w_in, self.get_variable("params", "w_in_scale"),
                    self.dtype)
                w_out = dequantize(
                    w_out, self.get_variable("params", "w_out_scale"),
                    self.dtype)
            else:
                w_in, w_out = w_in.astype(self.dtype), w_out.astype(self.dtype)
                if self.quant == "int8_wo":
                    # a scale an expert and output channel, as the
                    # pre-quantized tree has them
                    w_in, w_out = (wo_fake_quant(v, (1,))
                                   for v in (w_in, w_out))
            rows_live = (jnp.arange(l, dtype=jnp.int32)[None, :]
                         < live.astype(jnp.int32)[:, None]).reshape(b * l)
            mixed, n_rows, n_hit = routed_experts(
                u.reshape(b * l, self.latent), idx, w, rows_live, w_in,
                w_out, held_lo, stored)
            self.sow("expert_counts", "rows", n_rows)
            self.sow("expert_counts", "hit", n_hit)
            self.sow("expert_counts", "grouped",
                     jnp.int32(grouped_calls(b * l)))
            with jax.named_scope("latent_proj"):
                out = dense(d, "up")(
                    mixed.astype(self.dtype).reshape(b, l, self.latent))
            with jax.named_scope("shared_expert"):
                out = out + dense(d, "shared_out")(
                    squared_relu(dense(self.shared_dim, "shared_in")(h)))
            return out


class NemotronHBlock(nn.Module):
    kind: str                    # "mamba2" | "experts" | "attention"
    attention: tuple             # (num_heads, num_kv_heads, head_dim)
    mamba: tuple                 # (heads, head_dim, d_state, groups, d_conv,
                                 #  chunk)
    experts: tuple               # (router_width, held, top_k, routed_scale,
                                 #  latent, expert_dim, shared_dim)
    eps: float
    dtype: jnp.dtype
    attn_fn: Callable
    quant: str

    @nn.compact
    def __call__(self, x, paged=None, paged_prefill: bool = False):
        h = RMSNorm(self.eps, name="norm")(x)
        new = None
        if self.kind == "attention":
            out, new = GroupedAttention(
                *self.attention, self.dtype, self.attn_fn, self.quant,
                name="attn")(h, paged, paged_prefill)
        elif self.kind == "mamba2":
            out, new = Mamba2Mixer(
                *self.mamba, self.eps, self.dtype, self.quant,
                name="mamba")(h, paged)
        else:
            # rows of each batch row that are real tokens (a prompt's
            # length in its bucket, a chunk's live rows, the tick's 1 or 0)
            live = (jnp.full((x.shape[0],), x.shape[1], jnp.int32)
                    if paged is None else paged["live"])
            out = LatentExperts(*self.experts, self.dtype, self.quant,
                                name="moe")(h, live)
        return x + out.astype(x.dtype), new


class NemotronHLM(nn.Module):
    """Decoder-only hybrid LM of one-sublayer blocks. Input: int32 tokens
    (B, L); output float32 logits (with ``paged``: ``(logits, new_layers)``;
    a prefill's logits are its prompts' last live rows, ``[B, 1, V]``)."""

    vocab_size: int = 131072     # rows of embedding and head held here
    pattern: str = "MEMEMEM*EME"
    d_model: int = 4096
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    d_state: int = 128
    n_groups: int = 8
    d_conv: int = 4
    chunk: int = 128
    router_width: int = 512      # experts the router scores
    top_k: int = 22
    routed_scale: float = 5.0
    latent: int = 1024
    expert_dim: int = 2688
    shared_dim: int = 5376
    expert_share: tuple = (1, 0)    # (chips that share a layer, this rank)
    eps: float = 1e-5
    max_len: int = 262144        # no position table: a cap the server reads
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = full_attention
    quant: str = "none"          # none | int8_wo (ops.quant): every
                                 # projection and the experts; the router,
                                 # the embedding and the head stay fp

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return layer_types(self.pattern)

    @property
    def held(self) -> tuple:
        """``(held_lo, held_n)`` of the router's experts that are here."""
        from tpu_dist.parallel.ep import expert_share

        return expert_share(self.router_width, *self.expert_share)

    def routed_layers(self) -> tuple:
        """``(expert layers, experts held in each)``: what the engine's
        counters are summed over."""
        return self.layer_types.count("experts"), self.held[1]

    def cache_layout(self) -> tuple:
        """What each layer keeps for a sequence being served (``HybridLM.
        cache_layout`` has the kinds)."""
        d_in = self.mamba_heads * self.mamba_head_dim
        entry = {
            "mamba2": ("slot_state", {
                "ssm": ((self.mamba_heads, self.mamba_head_dim,
                         self.d_state), jnp.float32),
                "conv": ((self.d_conv - 1,
                          d_in + 2 * self.n_groups * self.d_state),
                         self.dtype)}),
            "attention": ("pages", self.num_kv_heads, self.head_dim,
                          self.num_heads // self.num_kv_heads, "rows"),
            "experts": ("slot_state", {})}
        return tuple(entry[t] for t in self.layer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pos_offset=0,
                 paged=None, paged_prefill: bool = False):
        # pos_offset: accepted for the serving programs' sake and unused
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="tok_emb")(tokens)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.vocab_size, self.d_model))
        ctx = (None if paged is None else
               {k: paged.get(k) for k in (
                   "block_tables", "positions", "lengths", "valid", "sp_mesh",
                   "live", "slots")})
        if (paged is not None and tokens.shape[1] == 1
                and ctx["slots"] is None and "mamba2" in self.layer_types):
            # the tick: its live rows listed once for every Mamba-2 layer
            from tpu_dist.ops.ssd import live_rows

            ctx["live_rows"] = live_rows(ctx["live"])
        new_layers = []
        for i, kind in enumerate(self.layer_types):
            blk = NemotronHBlock(
                kind, (self.num_heads, self.num_kv_heads, self.head_dim),
                (self.mamba_heads, self.mamba_head_dim, self.d_state,
                 self.n_groups, self.d_conv, self.chunk),
                (self.router_width, self.held, self.top_k, self.routed_scale,
                 self.latent, self.expert_dim, self.shared_dim),
                self.eps, self.dtype, self.attn_fn, self.quant,
                name=f"layer{i}")
            if paged is None:
                x, _ = blk(x)
            else:
                x, new = blk(x, {**ctx, "layer": paged["layers"][i]},
                             paged_prefill)
                # a layer that keeps nothing hands back what it was given
                new_layers.append(paged["layers"][i] if new is None else new)
        if paged_prefill:
            # nothing after the last block is cached: a prefill owes its
            # prompt's last live row and no other
            x = jnp.take_along_axis(x, jnp.maximum(
                paged["live"].astype(jnp.int32) - 1, 0)[:, None, None], axis=1)
        x = RMSNorm(self.eps, name="norm_f")(x)
        # operands in ``dtype``, the product kept in float32 (``HybridLM``)
        logits = jnp.einsum("bld,vd->blv", x.astype(self.dtype),
                            head.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        if paged is not None:
            return logits, tuple(new_layers)
        return logits


def nemotron_h_lm(vocab_size=256, pattern="MEME*EMEME*E", d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, mamba_heads=8,
                  mamba_head_dim=16, d_state=16, n_groups=2, d_conv=4,
                  chunk=8, router_width=16, top_k=3, routed_scale=5.0,
                  latent=32, expert_dim=48, shared_dim=96,
                  expert_share=(4, 0), max_len=512, dtype=jnp.float32,
                  attn_fn=full_attention, quant="none", **_):
    """A toy preset that keeps the pattern: two periods with all three
    kinds, 16 experts of which 4 are held, top 3, 2 groups, chunks of 8."""
    return NemotronHLM(
        vocab_size=vocab_size, pattern=pattern, d_model=d_model,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        d_state=d_state, n_groups=n_groups, d_conv=d_conv, chunk=chunk,
        router_width=router_width, top_k=top_k, routed_scale=routed_scale,
        latent=latent, expert_dim=expert_dim, shared_dim=shared_dim,
        expert_share=tuple(expert_share), max_len=max_len, dtype=dtype,
        attn_fn=attn_fn, quant=quant)
