"""Plain reference of the ``nemotron_h`` hybrid decoder (Mamba-2 layers,
latent mixture-of-experts layers and grouped-head attention layers, one
sublayer a block): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, Mamba-2 as the token-by-token
recurrence (a ``lax.scan`` over time), the routed layer as a loop over the
held experts with a 0/1 mask, attention a full masked softmax; no cache, no
kernels, no batching tricks, and none of the program's forms (no chunked
matrix form, no sort, no grouped product). It imports nothing of
``tpu_dist`` and makes its own weights from the seed (the benchmark hands
the same values to the program).

``sizes`` is the configuration's file with the source's own keys. The layer
equations, from NVIDIA-Nemotron-3-Super-120B-A12B-BF16's ``config.json``
(``model_type`` nemotron_h) and, for what that file does not say (listed in
the configuration under ``assumed``), the family's modeling code and the
Nemotron-H and Nemotron 3 reports as recalled:

* Block ``i``: ``x = x + Mixer_i(RMS_i(x))``, ONE sublayer, its kind the
  ``i``-th character of ``hybrid_override_pattern`` (``M`` Mamba-2, ``E``
  experts, ``*`` attention); RMSNorm ``x * rsqrt(mean(x^2) + norm_eps) *
  g``. After the last block a final RMSNorm, logits ``x W_head^T`` with
  ``W_head`` untied from the embedding. No positional encoding of any kind.
* ``M``: ``d_in = mamba_num_heads x mamba_head_dim``, ``G = n_groups``, ``N
  = ssm_state_size``. ``[z, xBC, dt] = W_in h`` (``d_in``, ``d_in + 2 G N``,
  ``mamba_num_heads``; no bias); ``xBC = silu(conv1d_causal(xBC) + b)``
  (depthwise, ``conv_kernel`` taps); ``[x, B, C] = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (a scalar a head). For head
  ``h`` in group ``g = h // (heads / G)`` with state ``S`` in ``R^{P x N}``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``.
  Then ``y * silu(z)``, an RMSNorm over each group of ``d_in / G`` channels
  separately (one gain of ``d_in``), ``W_out``.
* ``*``: ``q, k, v = W_q h, W_k h, W_v h`` (``num_attention_heads`` query
  heads over ``num_key_value_heads`` KV heads of ``head_dim``, no bias),
  causal ``softmax(q k^T / sqrt(head_dim)) v``, ``W_o``.
* ``E``: ``s = sigmoid(W_g h)`` over ``router_width`` experts; the
  ``num_experts_per_tok`` largest of ``s + b_sel`` are chosen; ``w_e =
  routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``; ``u = W_dn
  h`` (``moe_latent_size``); expert ``e``: ``W2_e relu(W1_e u)^2``; ``out =
  W_up(sum over the chosen e of w_e f_e(u)) + W2_s relu(W1_s h)^2``.

**The share.** ``expert_share = {of, index}`` and ``n_routed_experts`` (the
experts HELD: ``router_width / of``): the weights hold experts ``index *
held .. (index + 1) * held`` of the router's and the sum runs over the
chosen experts among them; a chosen expert that lives elsewhere adds
nothing, and ``w_e`` is normalised over all chosen experts. ``of`` 1 is the
uncut layer. ``vocab_size`` is the rows of embedding and head held.

At the published widths the float32 weights of the cut are 18.6 GB, so the
reference keeps the values it is given (the served bfloat16 ones) and casts
ONE layer at a time to float32 inside its layer loop, an expert layer one
expert at a time: the layers are calls of three small jitted programs (one a
kind), not one unrolled program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_MIN, A_MAX = 1.0, 16.0
B_SEL_STD = 0.01

Weights = Dict[str, jax.Array]      # flat: "tok_emb", "layer3.in_proj", ...

_KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


def layer_kinds(sizes: dict) -> tuple:
    pattern = sizes["hybrid_override_pattern"]
    assert len(pattern) == sizes["num_hidden_layers"], (
        len(pattern), sizes["num_hidden_layers"])
    return tuple(_KINDS[c] for c in pattern)


def held_block(sizes: dict) -> tuple:
    """``(held_lo, held_n)`` of the router's experts whose weights exist."""
    share = sizes["expert_share"]
    held_n = sizes["router_width"] // share["of"]
    assert held_n == sizes["n_routed_experts"], (
        held_n, sizes["n_routed_experts"])
    return share["index"] * held_n, held_n


def weight_shapes(sizes: dict) -> Dict[str, tuple]:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    mh, p, n, g, k = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                      sizes["ssm_state_size"], sizes["n_groups"],
                      sizes["conv_kernel"])
    d_in, conv = mh * p, mh * p + 2 * g * n
    lat, f, fs = (sizes["moe_latent_size"], sizes["moe_intermediate_size"],
                  sizes["moe_shared_expert_intermediate_size"])
    held = held_block(sizes)[1]
    shapes = {"tok_emb": (v, d), "lm_head": (v, d), "norm_f": (d,)}
    for i, kind in enumerate(layer_kinds(sizes)):
        q = f"layer{i}."
        shapes[q + "norm"] = (d,)
        if kind == "attention":
            shapes.update({q + "wq": (d, heads * hd), q + "wk": (d, kv * hd),
                           q + "wv": (d, kv * hd), q + "wo": (heads * hd, d)})
        elif kind == "mamba2":
            shapes.update({
                q + "in_proj": (d, d_in + conv + mh), q + "conv_w": (k, conv),
                q + "conv_b": (conv,), q + "dt_bias": (mh,),
                q + "A_log": (mh,), q + "D": (mh,), q + "y_norm": (d_in,),
                q + "out_proj": (d_in, d)})
        else:
            shapes.update({
                q + "gate": (d, sizes["router_width"]),
                q + "b_sel": (sizes["router_width"],),
                q + "down": (d, lat), q + "w_in": (held, lat, f),
                q + "w_out": (held, f, lat), q + "up": (lat, d),
                q + "shared_in": (d, fs), q + "shared_out": (fs, d)})
    return shapes


def make_weights(sizes: dict, key: jax.Array, dtype=jnp.float32) -> Weights:
    """From ``key``: normal(0, 0.02) matrices, embedding and head; unit
    gains and ``D``; ``A_log`` the log of values uniform in [1, 16] a head
    and the ``dt`` bias the inverse softplus of values log-uniform in
    [1e-3, 1e-1] floored at 1e-4 (Mamba-2's published initialisation,
    both); the depthwise convolution's weight and bias uniform in +-1/2
    (1 / sqrt(conv_kernel), the source framework's default for it); the
    selection bias normal(0, 0.01). ``sizes["init_std"]`` replaces the 0.02
    (a toy width's matrices need a larger one to move a logit as the
    published width's do). Call it under ``jax.jit``: one program makes
    every leaf on the device in ``dtype``."""
    out = {}
    std = sizes.get("init_std", INIT_STD)
    bound = 1.0 / math.sqrt(sizes["conv_kernel"])
    for i, (name, shape) in enumerate(sorted(weight_shapes(sizes).items())):
        leaf, sub = name.split(".")[-1], jax.random.fold_in(key, i)
        if leaf in ("norm", "norm_f", "y_norm", "D"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf == "A_log":
            w = jnp.log(jax.random.uniform(sub, shape, jnp.float32,
                                           A_MIN, A_MAX))
        elif leaf == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                sub, shape, jnp.float32, math.log(DT_MIN),
                math.log(DT_MAX))), DT_FLOOR)
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf in ("conv_w", "conv_b"):
            w = jax.random.uniform(sub, shape, jnp.float32, -bound, bound)
        elif leaf == "b_sel":
            w = B_SEL_STD * jax.random.normal(sub, shape, jnp.float32)
        else:
            w = std * jax.random.normal(sub, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out


#: the reference's leaf name for the engine's parameter path
_ENGINE_LEAVES = {("attn", "q"): "wq", ("attn", "k"): "wk",
                  ("attn", "v"): "wv", ("attn", "o"): "wo"}


def ref_name(path: tuple) -> str:
    """('layer3', 'mamba', 'in_proj', 'kernel') -> 'layer3.in_proj'."""
    path = tuple(p for p in path if p not in ("kernel", "scale", "embedding"))
    if len(path) == 1:
        return path[0]
    leaf = _ENGINE_LEAVES.get(path[1:], path[-1])
    return f"{path[0]}.{leaf}"


# ------------------------------------------------------------------ layers

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _attention_layer(x, w, sizes):
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    w = _f32(w)
    b, l, _ = x.shape
    h = _rms(x, w["norm"], sizes["norm_eps"])
    q = (h @ w["wq"]).reshape(b, l, kv, heads // kv, hd)
    k = (h @ w["wk"]).reshape(b, l, kv, hd)
    v = (h @ w["wv"]).reshape(b, l, kv, hd)
    causal = jnp.tril(jnp.ones((l, l), bool))

    def kv_head(qkv):
        # one KV head's query heads at a time: [b, g, l, l] scores
        qj, kj, vj = qkv
        s = jnp.einsum("bqgd,bkd->bgqk", qj, kj) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vj)

    o = jax.lax.map(kv_head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, l, heads * hd)
    return x + o @ w["wo"]


def _mamba2_layer(x, w, sizes):
    mh, p, n, g, k = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                      sizes["ssm_state_size"], sizes["n_groups"],
                      sizes["conv_kernel"])
    eps = sizes["norm_eps"]
    w = _f32(w)
    b, l, _ = x.shape
    d_in, bc = mh * p, g * n
    h = _rms(x, w["norm"], eps)
    zxd = h @ w["in_proj"]
    z, xbc, dt = (zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * bc],
                  zxd[..., 2 * d_in + 2 * bc:])
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[:, j:j + l] for j in range(k)))
    xs = xbc[..., :d_in].reshape(b, l, mh, p)
    per_head = lambda v: jnp.repeat(v.reshape(b, l, g, n), mh // g, axis=2)
    bm, cm = per_head(xbc[..., d_in:d_in + bc]), per_head(xbc[..., d_in + bc:])
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [b, l, mh]
    a = -jnp.exp(w["A_log"])                                  # [mh]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp       # [b, mh, p], [b, mh], [b, mh, n] x2
        s = (jnp.exp(dt_t * a)[:, :, None, None] * s
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, c_t) + w["D"][:, None] * x_t
        return s, y

    t = lambda v: jnp.swapaxes(v, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((b, mh, p, n), jnp.float32),
                        (t(xs), t(dt), t(bm), t(cm)))
    y = t(y).reshape(b, l, d_in) * jax.nn.silu(z)
    y = y.reshape(b, l, g, d_in // g)
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
         ).reshape(b, l, d_in) * w["y_norm"]
    return x + y @ w["out_proj"]


def _experts_layer(x, w, sizes):
    """Returns ``(x, chosen [b, l, router_width] bool)``."""
    top_k, scale = (sizes["num_experts_per_tok"],
                    float(sizes["routed_scaling_factor"]))
    held_lo, held_n = held_block(sizes)
    f32 = lambda v: v.astype(jnp.float32)
    h = _rms(x, f32(w["norm"]), sizes["norm_eps"])
    s = jax.nn.sigmoid(h @ f32(w["gate"]))                   # [b, l, E]
    # the top_k largest of s + b_sel, the lower index first among equals
    rank = jnp.argsort(jnp.argsort(-(s + f32(w["b_sel"])), axis=-1,
                                   stable=True), axis=-1, stable=True)
    chosen = rank < top_k
    weight = scale * s * chosen / (
        jnp.sum(s * chosen, -1, keepdims=True) + 1e-20)
    u = h @ f32(w["down"])

    def expert(e, acc):
        # one held expert over every row, folded by its 0/weight column
        w1, w2 = f32(w["w_in"][e]), f32(w["w_out"][e])
        col = jax.lax.dynamic_index_in_dim(weight, held_lo + e, axis=-1)
        return acc + col * (_relu2(u @ w1) @ w2)

    mixed = jax.lax.fori_loop(0, held_n, expert, jnp.zeros_like(u))
    out = (mixed @ f32(w["up"])
           + _relu2(h @ f32(w["shared_in"])) @ f32(w["shared_out"]))
    return x + out, chosen


def _highest(fn):
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return wrapped


def layer_programs(sizes: dict) -> dict:
    """The jitted program of each layer kind (the weights are cast to
    float32 inside, one layer, or one expert of a layer, at a time), the
    embedding and the head."""
    frozen = dict(sizes)
    return {
        "attention": jax.jit(_highest(
            lambda x, w: _attention_layer(x, w, frozen))),
        "mamba2": jax.jit(_highest(
            lambda x, w: _mamba2_layer(x, w, frozen))),
        "experts": jax.jit(_highest(
            lambda x, w: _experts_layer(x, w, frozen))),
        "embed": jax.jit(lambda e, tokens: e.astype(jnp.float32)[tokens]),
        "head": jax.jit(_highest(lambda x, g, e: _rms(
            x, g.astype(jnp.float32), frozen["norm_eps"])
            @ e.astype(jnp.float32).T)),
    }


def layer_weights(weights: Weights, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def forward(weights: Weights, tokens: jax.Array, sizes: dict,
            programs: dict = None, chosen: list = None) -> jax.Array:
    """Logits (B, L, V) in float32 for int tokens (B, L); ``weights`` flat,
    in any floating type. ``chosen``, a list, receives each expert layer's
    chosen set ``[B, L, router_width]`` bool, in layer order."""
    programs = programs or layer_programs(sizes)
    x = programs["embed"](weights["tok_emb"], tokens)
    for i, kind in enumerate(layer_kinds(sizes)):
        x = programs[kind](x, layer_weights(weights, i))
        if kind == "experts":
            x, picked = x
            if chosen is not None:
                chosen.append(picked)
    return programs["head"](x, weights["norm_f"], weights["lm_head"])
