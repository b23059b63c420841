"""Plain reference of the Jamba-family hybrid decoder (Mamba-1 layers beside
grouped-head attention layers): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, the scan a ``lax.scan`` over
time, attention a full masked softmax, no cache, no kernels, no batching
tricks. It imports nothing of ``tpu_dist`` and makes its own weights from
the seed (the benchmark hands the same values to the program).

``sizes`` is the configuration's file with the source's own keys
(``hidden_size``, ``mamba_d_state``, ...; ``head_dim`` is the file's
assumed 128).

The layer equations, from AI21-Jamba2-3B's ``config.json`` (``model_type``
jamba; Lieber et al. 2024, arXiv:2403.19887; Gu & Dao 2023,
arXiv:2312.00752):

* Layer ``i`` is an attention layer iff ``i % attn_layer_period ==
  attn_layer_offset`` (``JambaConfig.layers_block_type``), else a Mamba
  layer; ``num_experts`` is 1, so every layer's feed-forward is the dense
  gated MLP.
* Residual layer: ``x = x + Mixer(RMSNorm(x))``, then ``x = x +
  MLP(RMSNorm(x))``; RMSNorm ``x * rsqrt(mean(x^2) + eps) * g``; ``MLP(h) =
  W_down(silu(W_gate h) * (W_up h))``, no biases. Final RMSNorm, logits
  ``x E^T`` with the tied embedding ``E``. No positional encoding of any
  kind.
* Attention mixer: ``q = W_q h`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = W_k h``, ``v = W_v h`` (``num_key_value_heads``
  heads), no biases; causal ``softmax(q k^T / sqrt(head_dim)) v`` with query
  heads ``j*g .. (j+1)*g - 1`` reading KV head ``j``; ``W_o``.
* Mamba mixer over ``h[0..L)``: ``[u, z] = W_in h`` (``d_inner = expand *
  hidden`` each); ``u = silu(conv1d_causal(u; k = d_conv, depthwise, with
  bias))``; ``[d, B, C] = W_x u`` (dt_rank, d_state, d_state); ``d, B, C =
  RMSNorm_dt(d), RMSNorm_B(B), RMSNorm_C(C)`` (Jamba's three inner norms,
  same epsilon); ``delta = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``;
  per channel ``c`` and state ``n``: ``s_t[c, n] = exp(delta_t[c] A[c, n])
  s_{t-1}[c, n] + delta_t[c] B_t[n] u_t[c]``, ``y_t[c] = sum_n C_t[n]
  s_t[c, n] + D[c] u_t[c]``; output ``W_out(y * silu(z))``.

At the published widths the float32 weights are 12.1 GB, so the reference
keeps the values it is given (the served bfloat16 ones) and casts ONE layer
at a time to float32 inside its layer loop: the layers are calls of two
small jitted programs (one a kind), not one unrolled program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02
DT_MIN, DT_MAX = 1e-3, 1e-1

Weights = Dict[str, jax.Array]      # flat: "tok_emb", "layer3.in_proj", ...


def layer_kinds(sizes: dict) -> tuple:
    return tuple(
        "attention" if i % sizes["attn_layer_period"]
        == sizes["attn_layer_offset"] else "mamba"
        for i in range(sizes["num_hidden_layers"]))


def weight_shapes(sizes: dict) -> Dict[str, tuple]:
    d, v, inner = (sizes["hidden_size"], sizes["vocab_size"],
                   sizes["intermediate_size"])
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    dc = sizes["mamba_expand"] * d
    n, k, r = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    shapes = {"tok_emb": (v, d), "norm_f": (d,)}
    for i, kind in enumerate(layer_kinds(sizes)):
        p = f"layer{i}."
        shapes.update({p + "norm1": (d,), p + "norm2": (d,),
                       p + "gate": (d, inner), p + "up": (d, inner),
                       p + "down": (inner, d)})
        if kind == "attention":
            shapes.update({p + "wq": (d, heads * hd), p + "wk": (d, kv * hd),
                           p + "wv": (d, kv * hd), p + "wo": (heads * hd, d)})
        else:
            shapes.update({
                p + "in_proj": (d, 2 * dc), p + "conv_w": (k, dc),
                p + "conv_b": (dc,), p + "x_proj": (dc, r + 2 * n),
                p + "dt_norm": (r,), p + "b_norm": (n,), p + "c_norm": (n,),
                p + "dt_proj": (r, dc), p + "dt_bias": (dc,),
                p + "A_log": (dc, n), p + "D": (dc,),
                p + "out_proj": (dc, d)})
    return shapes


def make_weights(sizes: dict, key: jax.Array, dtype=jnp.float32) -> Weights:
    """From ``key``: normal(0, 0.02) matrices and embedding; unit norm
    gains and ``D``; ``A_log = log(1..d_state)`` in every channel; the
    ``dt`` bias the inverse softplus of values log-uniform in [1e-3, 1e-1]
    (Mamba's published initialisation, both); the depthwise convolution's
    weight and bias uniform in +-1/sqrt(d_conv) (the source framework's
    default for it). ``sizes["init_std"]`` replaces the 0.02 (a toy width's
    matrices need a larger one to move a logit as the published width's
    do). Call it under ``jax.jit``: one program makes every leaf on the
    device in ``dtype``."""
    out = {}
    std = sizes.get("init_std", INIT_STD)
    n, k = sizes["mamba_d_state"], sizes["mamba_d_conv"]
    for i, (name, shape) in enumerate(sorted(weight_shapes(sizes).items())):
        leaf, sub = name.split(".")[-1], jax.random.fold_in(key, i)
        if leaf in ("norm1", "norm2", "norm_f", "dt_norm", "b_norm",
                    "c_norm", "D"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf == "A_log":
            w = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                sub, shape, jnp.float32, math.log(DT_MIN), math.log(DT_MAX)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(k)
            w = jax.random.uniform(sub, shape, jnp.float32, -bound, bound)
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


def _mlp(x, w, eps):
    h = _rms(x, w["norm2"], eps)
    return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


def _attention_layer(x, w, sizes):
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    b, l, _ = x.shape
    h = _rms(x, w["norm1"], eps)
    q = (h @ w["wq"]).reshape(b, l, kv, heads // kv, hd)
    k = (h @ w["wk"]).reshape(b, l, kv, hd)
    v = (h @ w["wv"]).reshape(b, l, kv, hd)
    s = jnp.einsum("bqjgd,bkjd->bjgqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((l, l), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bjgqk,bkjd->bqjgd", p, v).reshape(b, l, heads * hd)
    return _mlp(x + o @ w["wo"], w, eps)


def _mamba_layer(x, w, sizes):
    n, k, r = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    eps = sizes["rms_norm_eps"]
    b, l, _ = x.shape
    h = _rms(x, w["norm1"], eps)
    u, z = jnp.split(h @ w["in_proj"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[:, j:j + l] for j in range(k)))
    dbc = u @ w["x_proj"]
    d = _rms(dbc[..., :r], w["dt_norm"], eps)
    bm = _rms(dbc[..., r:r + n], w["b_norm"], eps)
    cm = _rms(dbc[..., r + n:], w["c_norm"], eps)
    delta = jax.nn.softplus(d @ w["dt_proj"] + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                  # [dc, n]

    def step(s, xs):
        u_t, delta_t, b_t, c_t = xs               # [b, dc], [b, dc], [b, n]
        s = (jnp.exp(delta_t[:, :, None] * a) * s
             + (delta_t * u_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("bcn,bn->bc", s, c_t) + w["D"] * u_t

    t = lambda v: jnp.swapaxes(v, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((b, u.shape[-1], n), jnp.float32),
                        (t(u), t(delta), t(bm), t(cm)))
    y = t(y) * jax.nn.silu(z)
    return _mlp(x + y @ w["out_proj"], w, eps)


def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _highest(fn):
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return wrapped


def layer_programs(sizes: dict) -> dict:
    """The jitted program of each layer kind (the weights are cast to
    float32 inside, one layer at a time), the embedding and the head."""
    frozen = dict(sizes)
    return {
        "attention": jax.jit(_highest(
            lambda x, w: _attention_layer(x, _f32(w), frozen))),
        "mamba": jax.jit(_highest(
            lambda x, w: _mamba_layer(x, _f32(w), frozen))),
        "embed": jax.jit(lambda e, tokens: e.astype(jnp.float32)[tokens]),
        "head": jax.jit(_highest(lambda x, g, e: _rms(
            x, g.astype(jnp.float32), frozen["rms_norm_eps"])
            @ e.astype(jnp.float32).T)),
    }


def layer_weights(weights: Weights, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def forward(weights: Weights, tokens: jax.Array, sizes: dict,
            programs: dict = None) -> jax.Array:
    """Logits (B, L, V) in float32 for int tokens (B, L); ``weights`` flat,
    in any floating type."""
    programs = programs or layer_programs(sizes)
    x = programs["embed"](weights["tok_emb"], tokens)
    for i, kind in enumerate(layer_kinds(sizes)):
        x = programs[kind](x, layer_weights(weights, i))
    return programs["head"](x, weights["norm_f"], weights["tok_emb"])
