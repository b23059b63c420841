"""Plain reference of the ``phi4flash`` decoder-hybrid-decoder (SambaY,
arXiv:2507.06607; Phi-4-mini-flash-reasoning's ``config.json``): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, the scan a
``lax.scan`` over time, attention a full masked softmax a head pair, no
cache, no kernels, no batching tricks. It imports nothing of ``tpu_dist`` and
makes its own weights from the seed (the benchmark hands the same values to
the program).

``sizes`` is the configuration's file with the source's own keys
(``hidden_size``, ``sliding_window``, ``mb_per_layer``, ...) and the file's
assumed ``head_dim`` and ``mamba_*`` sizes.

Layer ``i`` of ``L = num_hidden_layers`` (``x`` the residual stream, ``LN``
= LayerNorm with gain and bias, eps ``layer_norm_eps``): ``x = x +
Mixer_i(LN1(x))``, ``x = x + MLP(LN2(x))``, ``MLP(h) = W2(silu(g) * u)`` with
``[g, u] = W1 h``, no bias. After the last layer a final ``LN``, logits ``x
E^T`` with the tied embedding ``E``. No positional encoding of any kind.

Kinds (``mb_per_layer`` 2: even layers recurrent, odd layers attention):

* ``mamba``, even ``i <= L/2``: Mamba-1 (Gu & Dao 2023, arXiv:2312.00752)
  with no inner norms: ``[u, z] = W_in h``; ``u = silu(conv1d_causal(u))``
  (depthwise, k ``mamba_d_conv``, bias); ``[d, B, C] = W_x u``; ``delta =
  softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; per channel ``c`` and state
  ``n``: ``s_t[c, n] = exp(delta_t[c] A[c, n]) s_{t-1}[c, n] + delta_t[c]
  B_t[n] u_t[c]``, ``y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]``; output
  ``W_out(y * silu(z))``. Layer ``L/2`` also hands on ``m = y``.
* ``window``, odd ``i < L/2``: differential attention, key ``t`` visible to
  query ``s`` iff ``s - sliding_window < t <= s``.
* ``full``, ``i = L/2 + 1``: differential attention, causal; its keys and
  values are what the ``cross`` layers read.
* ``cross``, odd ``i > L/2 + 1``: differential attention with queries only:
  ``q = W_q h + b_q``, K and V the ``full`` layer's (same causal mask), then
  the output projection.
* ``gmu``, even ``i > L/2 + 1``: ``W_2(m * silu(W_1 h))``, ``m`` from layer
  ``L/2`` at the same position.

Differential attention: ``[q, k, v] = W_qkv h + b``, ``num_attention_heads``
query heads and ``num_key_value_heads`` KV heads of ``head_dim`` = ``d``.
Heads pair by stripe: pair ``j`` is heads ``2j`` (index 1) and ``2j + 1``
(index 2), and query pair ``j`` reads KV pair ``j // (heads / kv heads)``.
For a pair: ``A1 = softmax(q1 k1^T / sqrt(d) + mask)``, ``A2 = softmax(q2
k2^T / sqrt(d) + mask)``, ``o = (A1 - lambda A2) [v1 | v2]`` (``2d`` wide),
``o = RMSNorm_2d(o; gain, eps) * (1 - lambda_init)``, ``lambda = exp(lq1 .
lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3
i)``. The pairs' outputs go back to heads of ``d`` in stripe order and through
``W_o`` (bias).

At the published widths the float32 weights are 15.4 GB, more than the chip
holds, so the reference keeps the values it is given (the served bfloat16
ones) and casts ONE layer at a time to float32 inside its layer loop (the
layers are calls of five small jitted programs, one a kind); attention runs a
head pair at a time (``lax.map``) and the 200064-column head in row blocks
(:func:`head_blocks`), so that it fits beside nothing but those 7.7 GB.
"""

from __future__ import annotations

import math
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp

INIT_STD = 0.02
LAMBDA_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
HEAD_ROWS = 512                     # rows of logits a head block

Weights = Dict[str, jax.Array]      # flat: "tok_emb", "layer3.qkv", ...


def layer_kinds(sizes: dict) -> tuple:
    n, per = sizes["num_hidden_layers"], sizes["mb_per_layer"]
    full = n // 2 + 1
    kinds = []
    for i in range(n):
        recurrent = i % per == 0
        if i <= full:
            kinds.append("mamba" if recurrent
                         else "full" if i == full else "window")
        else:
            kinds.append("gmu" if recurrent else "cross")
    return tuple(kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def weight_shapes(sizes: dict) -> Dict[str, tuple]:
    d, v, inner = (sizes["hidden_size"], sizes["vocab_size"],
                   sizes["intermediate_size"])
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    dc = sizes["mamba_expand"] * d
    n, k, r = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    shapes = {"tok_emb": (v, d), "norm_f": (d,), "norm_f_bias": (d,)}
    for i, kind in enumerate(layer_kinds(sizes)):
        p = f"layer{i}."
        shapes.update({p + "norm1": (d,), p + "norm1_bias": (d,),
                       p + "norm2": (d,), p + "norm2_bias": (d,),
                       p + "fc1": (d, 2 * inner), p + "fc2": (inner, d)})
        if kind == "mamba":
            shapes.update({
                p + "in_proj": (d, 2 * dc), p + "conv_w": (k, dc),
                p + "conv_b": (dc,), p + "x_proj": (dc, r + 2 * n),
                p + "dt_proj": (r, dc), p + "dt_bias": (dc,),
                p + "A_log": (dc, n), p + "D": (dc,),
                p + "out_proj": (dc, d)})
        elif kind == "gmu":
            shapes.update({p + "gmu_in": (d, dc), p + "gmu_out": (dc, d)})
        else:
            wide = heads * hd if kind == "cross" else (heads + 2 * kv) * hd
            name = "wq" if kind == "cross" else "qkv"
            shapes.update({
                p + name: (d, wide), p + name + "_bias": (wide,),
                p + "wo": (heads * hd, d), p + "wo_bias": (d,),
                p + "lambda_q1": (hd,), p + "lambda_k1": (hd,),
                p + "lambda_q2": (hd,), p + "lambda_k2": (hd,),
                p + "subln": (2 * hd,)})
    return shapes


def make_weights(sizes: dict, key: jax.Array, dtype=jnp.float32) -> Weights:
    """From ``key``: normal(0, 0.02) matrices and embedding; the lambda
    vectors normal(0, 0.1); unit gains and ``D``, zero biases; Mamba as
    ``jamba2-3b``'s ``assumed.weights`` has it (``A_log = log(1..d_state)``
    in every channel, the ``dt`` bias the inverse softplus of values
    log-uniform in [1e-3, 1e-1], the depthwise convolution's weight and bias
    uniform in +-1/sqrt(d_conv)). ``sizes["init_std"]`` replaces the 0.02 (a
    toy width's matrices need a larger one to move a logit as the published
    width's do). Call it under ``jax.jit``: one program makes every leaf on
    the device in ``dtype``."""
    out = {}
    std = sizes.get("init_std", INIT_STD)
    n, k = sizes["mamba_d_state"], sizes["mamba_d_conv"]
    for i, (name, shape) in enumerate(sorted(weight_shapes(sizes).items())):
        leaf, sub = name.split(".")[-1], jax.random.fold_in(key, i)
        if leaf in ("norm1", "norm2", "norm_f", "subln", "D"):
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
        elif leaf.endswith("_bias"):
            w = jnp.zeros(shape, jnp.float32)
        elif leaf.startswith("lambda_"):
            w = LAMBDA_STD * jax.random.normal(sub, shape, jnp.float32)
        else:
            w = std * jax.random.normal(sub, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out


#: the reference's leaf name for the engine's parameter path
_ENGINE_LEAVES = {("attn", "qkv"): "qkv", ("attn", "q"): "wq",
                  ("attn", "o"): "wo", ("gmu", "in_proj"): "gmu_in",
                  ("gmu", "out_proj"): "gmu_out"}


def ref_name(path: tuple) -> str:
    """('layer3', 'attn', 'qkv', 'bias') -> 'layer3.qkv_bias'; ('layer2',
    'mamba', 'in_proj', 'kernel') -> 'layer2.in_proj'; ('norm_f', 'bias')
    -> 'norm_f_bias'."""
    bias = path[-1] == "bias"
    path = tuple(p for p in path
                 if p not in ("kernel", "scale", "embedding", "bias"))
    leaf = path[0] if len(path) == 1 else (
        f"{path[0]}.{_ENGINE_LEAVES.get(path[1:], path[-1])}")
    return leaf + "_bias" if bias else leaf


# ------------------------------------------------------------------ layers

def _ln(x, g, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g + b


def _mlp(x, w, eps):
    h = _ln(x, w["norm2"], w["norm2_bias"], eps)
    g, u = jnp.split(h @ w["fc1"], 2, axis=-1)
    return x + (jax.nn.silu(g) * u) @ w["fc2"]


def _mamba_layer(x, w, sizes):
    """Returns ``(x, y)``: ``y`` the scan's output before the gate."""
    n, k, r = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    eps = sizes["layer_norm_eps"]
    b, l, _ = x.shape
    h = _ln(x, w["norm1"], w["norm1_bias"], eps)
    u, z = jnp.split(h @ w["in_proj"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[:, j:j + l] for j in range(k)))
    dbc = u @ w["x_proj"]
    d, bm, cm = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
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
    y = t(y)
    return _mlp(x + (y * jax.nn.silu(z)) @ w["out_proj"], w, eps), y


def _gmu_layer(x, w, m, sizes):
    eps = sizes["layer_norm_eps"]
    h = _ln(x, w["norm1"], w["norm1_bias"], eps)
    return _mlp(x + (m * jax.nn.silu(h @ w["gmu_in"])) @ w["gmu_out"], w, eps)


def _differential(q, k, v, w, lam0, sizes, window=None):
    """``q`` [b, l, heads, d], ``k``/``v`` [b, l, kv, d] -> [b, l, heads *
    d]: one query pair at a time, two softmaxes a pair. ``lam0``: the
    layer's ``lambda_init``."""
    heads, kv, hd = q.shape[2], k.shape[2], q.shape[3]
    b, l = q.shape[:2]
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0)
    s_pos, t_pos = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    mask = t_pos <= s_pos
    if window is not None:
        mask &= s_pos - window < t_pos
    qp = jnp.moveaxis(q.reshape(b, l, heads // 2, 2, hd), 2, 0)
    kp = jnp.moveaxis(k.reshape(b, l, kv // 2, 2, hd), 2, 0)
    vp = jnp.moveaxis(v.reshape(b, l, kv // 2, 2 * hd), 2, 0)
    share = heads // kv                       # query pairs a KV pair

    def pair(j):
        q12, k12, v12 = qp[j], kp[j // share], vp[j // share]
        a = [jax.nn.softmax(jnp.where(
            mask, jnp.einsum("bsd,btd->bst", q12[:, :, i], k12[:, :, i])
            / math.sqrt(hd), -jnp.inf), axis=-1) for i in (0, 1)]
        o = jnp.einsum("bst,bte->bse", a[0] - lam * a[1], v12)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + sizes["layer_norm_eps"]) * w["subln"]
        return o * (1.0 - lam0)

    o = jax.lax.map(pair, jnp.arange(heads // 2))     # [pairs, b, l, 2 hd]
    return jnp.moveaxis(o, 0, 2).reshape(b, l, heads * hd)


def _attention_layer(x, w, lam0, sizes, kind, kv_in=None):
    """``window`` and ``full`` project q, k, v; ``cross`` projects q and
    takes ``kv_in``. Returns ``(x, (k, v))``."""
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    eps = sizes["layer_norm_eps"]
    b, l, _ = x.shape
    h = _ln(x, w["norm1"], w["norm1_bias"], eps)
    if kind == "cross":
        q = h @ w["wq"] + w["wq_bias"]
        k, v = kv_in
    else:
        q, k, v = jnp.split(h @ w["qkv"] + w["qkv_bias"],
                            [heads * hd, (heads + kv) * hd], axis=-1)
        k, v = k.reshape(b, l, kv, hd), v.reshape(b, l, kv, hd)
    o = _differential(q.reshape(b, l, heads, hd), k, v, w, lam0, sizes,
                      sizes["sliding_window"] if kind == "window" else None)
    return _mlp(x + o @ w["wo"] + w["wo_bias"], w, eps), (k, v)


def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _highest(fn):
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


def layer_programs(sizes: dict) -> dict:
    """The jitted program of each layer kind (the weights are cast to
    float32 inside, one layer at a time), the embedding and the head. The
    attention programs take the layer's ``lambda_init`` as an argument, so
    one program serves every layer of a kind."""
    frozen = dict(sizes)
    attn = lambda kind: jax.jit(_highest(
        lambda x, w, kv_in, lam0: _attention_layer(
            x, _f32(w), lam0, frozen, kind, kv_in)))
    return {
        "mamba": jax.jit(_highest(
            lambda x, w: _mamba_layer(x, _f32(w), frozen))),
        "gmu": jax.jit(_highest(
            lambda x, w, m: _gmu_layer(x, _f32(w), m, frozen))),
        "window": attn("window"), "full": attn("full"),
        "cross": attn("cross"),
        "embed": jax.jit(lambda e, tokens: e.astype(jnp.float32)[tokens]),
        "head": jax.jit(_highest(lambda x, g, b, e: _ln(
            x, g.astype(jnp.float32), b.astype(jnp.float32),
            frozen["layer_norm_eps"]) @ e.astype(jnp.float32).T)),
    }


def layer_weights(weights: Weights, i: int) -> dict:
    p = f"layer{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden(weights: Weights, tokens: jax.Array, sizes: dict,
           programs: dict = None) -> jax.Array:
    """The residual stream after the last layer, (B, L, hidden) float32."""
    programs = programs or layer_programs(sizes)
    x = programs["embed"](weights["tok_emb"], tokens)
    kinds = layer_kinds(sizes)
    last_mamba = max(i for i, k in enumerate(kinds) if k == "mamba")
    memory = shared = None
    for i, kind in enumerate(kinds):
        w = layer_weights(weights, i)
        if kind == "mamba":
            x, y = programs[kind](x, w)
            memory = y if i == last_mamba else memory
        elif kind == "gmu":
            x = programs[kind](x, w, memory)
        else:
            x, kv = programs[kind](x, w, shared,
                                   jnp.float32(lambda_init(i)))
            shared = kv if kind == "full" else shared
    return x


def head_blocks(weights: Weights, x: jax.Array, programs: dict,
                rows: int = HEAD_ROWS) -> Iterator[Tuple[int, jax.Array]]:
    """``(first row, logits [B, <= rows, V])`` block by block over ``x``'s
    rows: the whole of a long request's logits need not exist at once."""
    for lo in range(0, x.shape[1], rows):
        yield lo, programs["head"](x[:, lo:lo + rows], weights["norm_f"],
                                   weights["norm_f_bias"],
                                   weights["tok_emb"])


def forward(weights: Weights, tokens: jax.Array, sizes: dict,
            programs: dict = None) -> jax.Array:
    """Logits (B, L, V) in float32 for int tokens (B, L); ``weights`` flat,
    in any floating type."""
    programs = programs or layer_programs(sizes)
    x = hidden(weights, tokens, sizes, programs)
    return jnp.concatenate(
        [blk for _, blk in head_blocks(weights, x, programs)], axis=1)
