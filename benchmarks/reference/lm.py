"""Plain reference of the GPT-2-shaped decoder: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It imports nothing of ``tpu_dist`` and makes its own
weights from the seed (the benchmark hands the same values to the program).

Follows the GPT-2 description (Radford et al. 2019; Cerebras-GPT,
arXiv:2304.03208 section 2) with the repo's departures, each of which the
program's block makes too:

* no biases on the attention projections and none on the head; the MLP
  keeps its two biases;
* the head is untied from the token embedding;
* LayerNorm's epsilon is flax's 1e-6, not GPT-2's 1e-5;
* GELU is the tanh form (GPT-2's ``gelu_new``).

The LM cells' controls are the program's own int8 paths
(``benchmarks/control.py``), so this reference has no lower precision.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
INIT_STD = 0.02

Weights = Dict[str, jax.Array]      # flat: "tok_emb", "block3.wqkv", ...
BLOCK_LEAVES = ("ln1_g", "ln1_b", "wqkv", "wo", "ln2_g", "ln2_b", "w1", "b1",
                "w2", "b2")


def weight_shapes(sizes: dict) -> Dict[str, tuple]:
    d, v = sizes["d_model"], sizes["vocab_size"]
    inner, n_pos = sizes["mlp_dim"], sizes["max_positions"]
    shapes = {"tok_emb": (v, d), "pos_emb": (n_pos, d),
              "lnf_g": (d,), "lnf_b": (d,), "head": (d, v)}
    for i in range(sizes["num_layers"]):
        p = f"block{i}."
        shapes.update({
            p + "ln1_g": (d,), p + "ln1_b": (d,), p + "wqkv": (d, 3 * d),
            p + "wo": (d, d), p + "ln2_g": (d,), p + "ln2_b": (d,),
            p + "w1": (d, inner), p + "b1": (inner,),
            p + "w2": (inner, d), p + "b2": (d,)})
    return shapes


def make_weights(sizes: dict, key: jax.Array, dtype=jnp.float32) -> Weights:
    """GPT-2's initialisation from ``key``: normal(0, 0.02) matrices and
    embeddings, the two residual projections scaled by 1/sqrt(2 n_layer),
    unit gains, zero biases. Call it under ``jax.jit``: one program makes
    every leaf on the device in ``dtype``."""
    out = {}
    resid = 1.0 / math.sqrt(2.0 * sizes["num_layers"])
    for i, (name, shape) in enumerate(sorted(weight_shapes(sizes).items())):
        leaf = name.split(".")[-1]
        if leaf.endswith("_g"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf.endswith("_b") or leaf in ("b1", "b2"):
            w = jnp.zeros(shape, jnp.float32)
        else:
            std = INIT_STD * (resid if leaf in ("wo", "w2") else 1.0)
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        out[name] = w.astype(dtype)
    return out


def stack_blocks(weights: Weights) -> dict:
    """The reference's own layout: the top-level leaves as they are and
    every block leaf stacked over the layers, so that the layers are one
    ``lax.scan`` (a small program that compiles in seconds at any depth)."""
    n_layer = 1 + max(int(k.split(".")[0][5:]) for k in weights
                      if k.startswith("block"))
    out = {k: v for k, v in weights.items() if not k.startswith("block")}
    out["blocks"] = {leaf: jnp.stack([weights[f"block{i}.{leaf}"]
                                      for i in range(n_layer)])
                     for leaf in BLOCK_LEAVES}
    return out


def leaf_norms(tree: dict) -> Dict[str, jax.Array]:
    """L2 norm of every leaf under its flat name ("block3.wqkv")."""
    norm = lambda x, axes=None: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)), axis=axes))
    out = {k: norm(v) for k, v in tree.items() if k != "blocks"}
    for leaf, v in tree["blocks"].items():
        per_layer = norm(v, tuple(range(1, v.ndim)))
        out.update({f"block{i}.{leaf}": per_layer[i]
                    for i in range(v.shape[0])})
    return out


def leaf_samples(tree: dict, sample) -> Dict[str, jax.Array]:
    """``sample`` of every leaf under its flat name, layer by layer."""
    out = {k: sample(v) for k, v in tree.items() if k != "blocks"}
    for leaf, v in tree["blocks"].items():
        out.update({f"block{i}.{leaf}": sample(v[i])
                    for i in range(v.shape[0])})
    return out


def _layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, n_head):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, l, d = x.shape
    h = _layer_norm(x, w["ln1_g"], w["ln1_b"])
    q, k, v = jnp.split(h @ w["wqkv"], 3, axis=-1)
    q, k, v = (t.reshape(b, l, n_head, d // n_head) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((l, l), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, l, d)
    x = x + o @ w["wo"]
    h = _layer_norm(x, w["ln2_g"], w["ln2_b"])
    h = _gelu_tanh(h @ w["w1"] + w["b1"])
    return x + h @ w["w2"] + w["b2"]


def forward(stacked: dict, tokens: jax.Array, n_head: int) -> jax.Array:
    """Logits (B, L, V) in float32 for int tokens (B, L); ``stacked`` is
    ``stack_blocks``'s layout, in any floating type."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda k: stacked[k].astype(jnp.float32)
        l = tokens.shape[1]
        x = f32("tok_emb")[tokens] + f32("pos_emb")[:l][None]
        block = jax.checkpoint(lambda x, bw: _block(x, bw, n_head))
        x, _ = jax.lax.scan(lambda x, bw: (block(x, bw), None), x,
                            stacked["blocks"])
        x = _layer_norm(x, f32("lnf_g"), f32("lnf_b"))
        return x @ f32("head")


def loss_fn(stacked: dict, inputs, targets, n_head: int) -> jax.Array:
    """Mean next-token cross-entropy over every position of the batch."""
    logits = forward(stacked, inputs, n_head)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def adamw_step(weights, mu, nu, grads, t: int, *, lr, b1, b2, eps, wd):
    """Decoupled AdamW (Loshchilov & Hutter 2019), step ``t`` counted from
    1, weight decay on every leaf: what ``optax.adamw`` with no mask does."""
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    weights = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                  + wd * p), weights, mu, nu)
    return weights, mu, nu
