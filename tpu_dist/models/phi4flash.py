"""Decoder-hybrid-decoder LM (the ``phi4flash`` family: SambaY, arXiv:2507.06607).

A third LM block family, built from ``models.hybrid``'s parts: a
self-decoder of Mamba-1 layers alternating with sliding-window attention and
ending in ONE full-attention layer, then a cross-decoder whose attention
layers project queries only and read that one layer's keys and values, and
whose other layers are gated memory units over the last Mamba layer's scan
output. Attention is differential. No positional encoding of any kind.

Layer ``i`` of ``num_layers`` (``x`` the residual stream, ``LN`` = LayerNorm
with gain and bias): ``x = x + Mixer_i(LN1(x))``, ``x = x + MLP(LN2(x))``,
``MLP(h) = W2(silu(g) * u)`` with ``[g, u] = W1 h``, no bias. A final ``LN``,
logits ``x E^T`` with the tied embedding (float32 accumulation).

:func:`layer_types` gives the kinds from ``num_layers`` and ``mb_per_layer``
alone (32 and 2 published: 9 / 8 / 1 / 7 / 7):

* ``mamba`` (even layers up to ``num_layers // 2``): ``hybrid.MambaMixer``
  without the Jamba family's three inner norms. The LAST of them also hands
  on ``m = y``, its scan's output (with ``D u``, before the gate).
* ``window`` (odd layers below ``num_layers // 2``): differential attention,
  key ``t`` visible to query ``s`` iff ``s - window < t <= s``.
* ``full`` (layer ``num_layers // 2 + 1``): differential attention, causal.
  ITS keys and values are what the ``cross`` layers read.
* ``cross`` (odd layers after it): differential attention with queries
  only: ``q = W_q h + b_q``; K and V are the ``full`` layer's, same causal
  mask; then the output projection. No K/V projection, nothing cached.
* ``gmu`` (even layers after it): ``W_2(m * silu(W_1 h))``, ``m`` from the
  last Mamba layer at the same position. Nothing cached.

Differential attention: ``[q, k, v] = W_qkv h + b``; heads pair by stripe,
pair ``j`` being heads ``2j`` (index 1) and ``2j + 1`` (index 2), and query
pair ``j`` reads KV pair ``j // (num_heads // num_kv_heads)``. For a pair:
``A1 = softmax(q1 k1^T / sqrt(d) + mask)``, ``A2 = softmax(q2 k2^T /
sqrt(d) + mask)``, ``o = (A1 - lambda A2) [v1 | v2]`` (``2d`` wide), ``o =
RMSNorm_2d(o; gain) * (1 - lambda_init)``, with ``lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda_init`` (four learned ``d``-vectors a layer) and
``lambda_init = 0.8 - 0.6 exp(-0.3 i)``. The pairs' outputs go back to
``num_heads x d`` in stripe order and through ``W_o`` (bias). Softmaxes,
lambda, the RMSNorm and every LayerNorm are float32 whatever ``dtype`` is;
the Mamba mixer's float32 rules are ``models.hybrid``'s.

Serving (``paged=``). :meth:`Phi4FlashLM.cache_layout` gives ``ServeEngine``
one entry a layer, of the FOUR kinds the engine knows:

* ``("pages", kv_heads, head_dim, group[, "rows"])``: K and V rows behind
  the scheduler's block tables (the ``full`` layer, alone). ``"rows"``: a
  token's KV heads side by side in one lane-wide row, the layout
  ``ops.paged_attention.paged_grouped_decode_attention`` reads in place.
* ``("slot_state", {name: (shape a slot, dtype)})``: arrays a slot (the
  ``mamba`` layers; a ``gmu`` layer answers with no array at all: ``m``
  lives inside one program call).
* ``("window", kv_heads, head_dim, group, window)``: a ring a slot of
  ``window + page_size`` rows, whatever the sequence's length.
* ``("shared", layer)``: nothing of its own; the layer is handed layer
  ``layer``'s pages as written earlier in the same program (``cross``).

The rows hold KV PAIRS: ``[k1 | k2]`` of a pair is one "KV head" of ``2d``
lanes (a token's ``num_kv_heads x d`` keys as they come out of the
projection ARE that layout), and a pair's query heads are padded to it,
``(q1 | 0)`` and ``(0 | q2)``, so that a differential layer is a grouped
read of ``num_kv_heads / 2`` heads of ``2d``, ``2 * num_heads /
num_kv_heads`` query heads each, under the scale ``1 / sqrt(d)``.

Prefill (``paged_prefill``) runs the self-decoder over every prompt row and
everything after the ``full`` layer on each prompt's LAST LIVE row only:
nothing after it is cached, so that row's logits are all a prefill owes,
and the call returns logits ``[B, 1, V]``. A chunked prefill (a call of
several rows that is no prefill) is not built over these kinds and the
engine refuses it by name.

Training this block is not built (``models.hybrid`` says why); the registry
lists it for serving and for the plain full-sequence forward.
"""

from __future__ import annotations

import math
from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_dist.models.hybrid import MambaMixer
from tpu_dist.models.transformer import full_attention
from tpu_dist.ops.flash_attention import window_attention
from tpu_dist.ops.paged_attention import (grouped_read, grouped_write,
                                          ring_block_tables)
from tpu_dist.ops.quant import make_dense


def layer_types(num_layers: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """The five kinds in two halves: every ``mb_per_layer``-th layer is
    recurrent, the others attend; the self-decoder is layers ``0 ..
    num_layers // 2 + 1`` and ends in the one ``full`` layer, the
    cross-decoder is the rest."""
    last = num_layers // 2 + 1                  # the full-attention layer
    kinds = []
    for i in range(num_layers):
        recurrent = i % mb_per_layer == 0
        if i <= last:
            kinds.append("mamba" if recurrent
                         else "full" if i == last else "window")
        else:
            kinds.append("gmu" if recurrent else "cross")
    return tuple(kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class LayerNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        b = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        mean = jnp.mean(x, -1, keepdims=True)
        x = x - mean
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + self.eps) * g.astype(jnp.float32)
                + b.astype(jnp.float32))


def pair_attention(q, k, v, attn):
    """Every head's softmax over its own key head, times its KV PAIR's
    values: ``q`` (B, L, H, d), ``k``/``v`` (B, Lk, KV, d) -> (B, L, H,
    2d), head ``h`` (index ``h % 2`` of query pair ``h // 2``) reading key
    head ``2p + h % 2`` and values ``[v_2p | v_2p+1]`` of KV pair ``p =
    (h // 2) // (H // KV)``. ``attn(q, k, v)`` is a plain attention over
    equal head counts whose value width may be its own."""
    h, kv = q.shape[2], k.shape[2]
    pair = jnp.arange(h) // 2 // (h // kv)
    keys = jnp.take(k, 2 * pair + jnp.arange(h) % 2, axis=2)
    values = jnp.concatenate([jnp.take(v, 2 * pair, axis=2),
                              jnp.take(v, 2 * pair + 1, axis=2)], axis=-1)
    return attn(q, keys, values)


def _halves(attn_fn):
    """``attn_fn`` (equal widths of q, k and v: the flash kernels') over
    values twice as wide: the two halves as heads of one call."""
    def attn(q, k, v):
        d = q.shape[-1]
        out = attn_fn(jnp.concatenate([q, q], axis=2),
                      jnp.concatenate([k, k], axis=2),
                      jnp.concatenate([v[..., :d], v[..., d:]], axis=2))
        h = q.shape[2]
        return jnp.concatenate([out[:, :, :h], out[:, :, h:]], axis=-1)
    return attn


def pair_queries(q):
    """(B, H, d) -> (B, H, 2d): ``(q | 0)`` for a pair's first head, ``(0 |
    q)`` for its second, as the rows of KV pairs want them."""
    b, h, d = q.shape
    q = q.reshape(b, h // 2, 2, d)
    zero = jnp.zeros_like(q[:, :, 0])
    return jnp.stack([jnp.concatenate([q[:, :, 0], zero], axis=-1),
                      jnp.concatenate([zero, q[:, :, 1]], axis=-1)],
                     axis=2).reshape(b, h, 2 * d)


class DiffAttention(nn.Module):
    kind: str                    # "window" | "full" | "cross"
    layer: int                   # its index: lambda_init's
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    eps: float
    dtype: jnp.dtype
    attn_fn: Callable
    quant: str

    @nn.compact
    def __call__(self, h, paged, paged_prefill, shared):
        """``shared``: what the ``full`` layer handed on, its (k, v) in the
        plain forward, its updated pages when served. Returns ``(out, new
        layer, hand-on)``."""
        dense = lambda n, name: make_dense(
            n, use_bias=True, dtype=self.dtype, name=name, quant=self.quant)
        b, l, _ = h.shape
        nh, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        if self.kind == "cross":
            q = dense(nh * d, "q")(h).reshape(b, l, nh, d)
        else:
            q, k, v = jnp.split(dense((nh + 2 * kv) * d, "qkv")(h),
                                [nh * d, (nh + kv) * d], axis=-1)
            q = q.reshape(b, l, nh, d)
        vec = lambda name: self.param(
            name, nn.initializers.normal(0.1), (d,)).astype(jnp.float32)
        lq1, lk1, lq2, lk2 = map(vec, ("lambda_q1", "lambda_k1",
                                       "lambda_q2", "lambda_k2"))
        gain = self.param("subln", nn.initializers.ones, (2 * d,))
        window = self.window if self.kind == "window" else None

        def dense_attention(k, v):
            attn = ((lambda q, k, v: window_attention(q, k, v, window))
                    if window else _halves(self.attn_fn))
            return pair_attention(q, k.reshape(b, -1, kv, d),
                                  v.reshape(b, -1, kv, d), attn)

        new, hand = None, None
        if paged is None:
            # the plain forward: every row against the rows before it
            if self.kind == "cross":
                k, v = shared
            out = dense_attention(k, v)
            hand = (k, v) if self.kind == "full" else None
        else:
            layer = shared if self.kind == "cross" else paged["layer"]
            tables, pos = paged["block_tables"], paged["positions"]
            if layer.ring:
                slots = paged.get("slots")
                tables = ring_block_tables(
                    jnp.arange(b) if slots is None else slots, layer.ring)
            if self.kind != "cross":
                rows = (pos[:, None].astype(jnp.int32)
                        + jnp.arange(l, dtype=jnp.int32)[None, :])
                live = paged["live"].astype(jnp.int32)[:, None]
                # a prefill's rows below its prompt's length (a ring: the
                # last ring's worth of them); a tick's row where the slot
                # decodes
                valid = ((rows < live) if paged_prefill
                         else jnp.broadcast_to(live > 0, rows.shape))
                if layer.ring:
                    valid &= rows >= live - layer.ring * layer.k.shape[1]
                elif paged.get("valid") is not None:
                    valid &= paged["valid"]
                new = layer = grouped_write(layer, k, v, tables, rows, valid)
                hand = new if self.kind == "full" else None
            if paged_prefill and self.kind != "cross":
                out = dense_attention(k, v)
            else:
                if l != 1:
                    raise NotImplementedError(
                        "a served call of several rows that is no prefill "
                        "(a prefill chunk, a verify window) over a window "
                        "ring or a shared KV layer")
                # a prefill's cross layer: its one row is the prompt's last
                at = paged["live"] - 1 if paged_prefill else pos
                with jax.named_scope("paged_read"), jax.named_scope(
                        "window_read" if layer.ring else "shared_kv_read"):
                    out = grouped_read(
                        pair_queries(q[:, 0]), layer, tables,
                        jnp.maximum(at, 0), kv_heads=kv // 2,
                        scale=1.0 / math.sqrt(d), window=window)[:, None]
        with jax.named_scope("diff_attn"):
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lambda_init(self.layer))
            out = out.astype(jnp.float32)
            o = out[:, :, 0::2] - lam * out[:, :, 1::2]       # (B, L, H/2, 2d)
            o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                   + self.eps) * gain.astype(jnp.float32)
                 * (1.0 - lambda_init(self.layer)))
            o = o.reshape(b, l, nh * d).astype(self.dtype)
        return dense(h.shape[-1], "o")(o), new, hand


class GatedMemoryUnit(nn.Module):
    d_inner: int
    dtype: jnp.dtype
    quant: str

    @nn.compact
    def __call__(self, h, m):
        dense = lambda n, name: make_dense(
            n, use_bias=False, dtype=self.dtype, name=name, quant=self.quant)
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(dense(self.d_inner, "in_proj")(h).astype(
                jnp.float32))
            return dense(h.shape[-1], "out_proj")(
                (m.astype(jnp.float32) * gate).astype(self.dtype))


class Phi4FlashBlock(nn.Module):
    kind: str
    layer: int
    attention: tuple             # (num_heads, num_kv_heads, head_dim, window)
    mamba: tuple                 # (d_state, d_conv, expand, dt_rank)
    hand_on: bool                # a Mamba layer: also return its scan output
    mlp_dim: int
    eps: float
    dtype: jnp.dtype
    attn_fn: Callable
    quant: str

    @nn.compact
    def __call__(self, x, paged, paged_prefill, memory, shared):
        """Returns ``(x, new layer, hand-on)``: ``memory`` is the scan
        output a ``gmu`` layer gates, ``shared`` what a ``cross`` layer
        reads."""
        h = LayerNorm(self.eps, name="norm1")(x)
        new, hand = None, None
        if self.kind == "mamba":
            out, new, *hand = MambaMixer(
                *self.mamba, self.eps, self.dtype, self.quant,
                inner_norms=False, hand_on=self.hand_on, name="mamba")(
                    h, paged)
            hand = hand[0] if hand else None
        elif self.kind == "gmu":
            out = GatedMemoryUnit(self.mamba[2] * x.shape[-1], self.dtype,
                                  self.quant, name="gmu")(h, memory)
        else:
            out, new, hand = DiffAttention(
                self.kind, self.layer, *self.attention, self.eps, self.dtype,
                self.attn_fn, self.quant, name="attn")(
                    h, paged, paged_prefill, shared)
        x = x + out.astype(x.dtype)
        h = LayerNorm(self.eps, name="norm2")(x)
        dense = lambda n, name: make_dense(
            n, use_bias=False, dtype=self.dtype, name=name, quant=self.quant)
        g, u = jnp.split(dense(2 * self.mlp_dim, "fc1")(h), 2, axis=-1)
        x = x + dense(x.shape[-1], "fc2")(jax.nn.silu(g) * u).astype(x.dtype)
        return x, new, hand


class Phi4FlashLM(nn.Module):
    """Decoder-hybrid-decoder LM. Input: int32 tokens (B, L); output float32
    logits (with ``paged``: ``(logits, new_layers)``; a prefill's logits are
    its prompts' last live rows, ``[B, 1, V]``)."""

    vocab_size: int = 200064
    num_layers: int = 32
    d_model: int = 2560
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    mlp_dim: int = 10240
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    mb_per_layer: int = 2
    window: int = 512
    ln_eps: float = 1e-5
    max_len: int = 262144        # no position table: a cap the server reads
    dtype: jnp.dtype = jnp.float32
    attn_fn: Callable = full_attention
    quant: str = "none"          # none | int8 | int8_wo (ops.quant), every
                                 # projection; the tied embedding stays fp

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return layer_types(self.num_layers, self.mb_per_layer)

    def cache_layout(self) -> tuple:
        """What each layer keeps for a sequence being served (the module
        docstring has the four kinds)."""
        kinds = self.layer_types
        d_inner = self.expand * self.d_model
        # KV pairs as heads of 2d, a pair's query heads padded to them
        pair = (self.num_kv_heads // 2, 2 * self.head_dim,
                2 * self.num_heads // self.num_kv_heads)
        entry = {
            "mamba": ("slot_state", {
                "ssm": ((self.d_state, d_inner), jnp.float32),
                "conv": ((self.d_conv - 1, d_inner), self.dtype)}),
            "gmu": ("slot_state", {}),
            "window": ("window", *pair, self.window),
            "full": ("pages", *pair, "rows"),
            "cross": ("shared", kinds.index("full"))}
        return tuple(entry[t] for t in kinds)

    @nn.compact
    def __call__(self, tokens, train: bool = False, pos_offset=0,
                 paged=None, paged_prefill: bool = False):
        # pos_offset: accepted for the serving programs' sake and unused (no
        # positional encoding: the Mamba layers carry order, the masks the
        # rest)
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="tok_emb")
        x = emb(tokens)
        kinds = self.layer_types
        ctx = (None if paged is None else
               {k: paged.get(k) for k in (
                   "block_tables", "positions", "valid", "live", "slots")})
        last_mamba = max(i for i, t in enumerate(kinds) if t == "mamba")
        memory = shared = None
        new_layers = []
        for i, kind in enumerate(kinds):
            if paged_prefill and i == kinds.index("full") + 1:
                # nothing from here on is cached: a prefill owes its prompt's
                # last live row and no other
                at = jnp.maximum(paged["live"].astype(jnp.int32) - 1,
                                 0)[:, None, None]
                x = jnp.take_along_axis(x, at, axis=1)
                memory = jnp.take_along_axis(memory, at, axis=1)
            blk = Phi4FlashBlock(
                kind, i, (self.num_heads, self.num_kv_heads, self.head_dim,
                          self.window),
                (self.d_state, self.d_conv, self.expand, self.dt_rank),
                i == last_mamba, self.mlp_dim, self.ln_eps, self.dtype,
                self.attn_fn, self.quant, name=f"layer{i}")
            x, new, hand = blk(
                x, None if paged is None else
                {**ctx, "layer": paged["layers"][i]},
                paged_prefill, memory, shared)
            if kind == "mamba" and hand is not None:
                memory = hand
            elif kind == "full":
                shared = hand
            # a layer that keeps nothing hands back what it was given
            new_layers.append(
                new if new is not None or paged is None
                else paged["layers"][i])
        x = LayerNorm(self.ln_eps, name="norm_f")(x)
        logits = jnp.einsum("bld,vd->blv", x.astype(self.dtype),
                            emb.embedding.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        if paged is not None:
            return logits, tuple(new_layers)
        return logits


def phi4flash_lm(vocab_size=256, num_layers=12, d_model=64, num_heads=4,
                 num_kv_heads=2, head_dim=64, mlp_dim=128, d_state=16,
                 d_conv=4, expand=2, dt_rank=8, mb_per_layer=2, window=8,
                 max_len=512, dtype=jnp.float32, attn_fn=full_attention,
                 quant="none", **_):
    """A toy preset that keeps the pattern: 12 layers are two periods of
    each half beside the full layer and the Mamba layer before it (4 / 3 /
    1 / 2 / 2), two query pairs over one KV pair of 128
    lanes (heads of 64: the in-place read's own tiling, interpreted off the
    TPU), a window of 8."""
    return Phi4FlashLM(
        vocab_size=vocab_size, num_layers=num_layers, d_model=d_model,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        mlp_dim=mlp_dim, d_state=d_state, d_conv=d_conv, expand=expand,
        dt_rank=dt_rank, mb_per_layer=mb_per_layer, window=window,
        max_len=max_len, dtype=dtype, attn_fn=attn_fn, quant=quant)
