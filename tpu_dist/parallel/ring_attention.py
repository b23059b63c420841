"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context support the reference never had (SURVEY.md §5 'Long-context':
absent upstream; first-class here). Each device holds one contiguous shard of
the sequence (Q fixed, K/V rotating): at ring step i the local K/V block is
``ppermute``'d to the next device while attention scores against the current
block are folded into an online-softmax accumulator (log-sum-exp rescaling,
fp32). After ``axis_size`` steps every Q row has attended to every K row —
numerically exact full attention, with O(L/n) memory per device and
communication that XLA overlaps with the block contractions on the ICI ring.

Causality is enforced by global positions: block pairs entirely in the future
are skipped-by-masking (their contribution is -inf before the fold), the
diagonal block gets the triangular mask.

Layout: q, k, v are (B, L_shard, H, D) inside shard_map; the axis name is the
mesh's sequence axis. Use with models whose attention fn is pluggable
(tpu_dist.models.transformer.TransformerLM(attn_fn=ring_attention_fn(axis))).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.parallel.mesh import SEQ_AXIS

NEG_INF = -1e30  # avoid nan from (-inf) - (-inf) in online-softmax rescaling


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    q/k/v: (B, L_shard, H, D) — this device's sequence shard.
    Returns (B, L_shard, H, D), fp32-accumulated, cast back to q.dtype.
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qf = q.astype(jnp.float32)

    # right-rotation permutation: device p sends to p+1; after i steps the
    # resident K/V block originated at (my_idx - i) mod n
    perm = [(p, (p + 1) % axis_size) for p in range(axis_size)]

    def fold(carry, i):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % axis_size

        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = my_idx * lq + jnp.arange(lq)
            kpos = kv_idx * k_cur.shape[1] + jnp.arange(k_cur.shape[1])
            mask = kpos[None, :] <= qpos[:, None]
            scores = jnp.where(mask[None, None], scores, NEG_INF)

        # online softmax fold (flash-attention accumulation, fp32)
        m_new = jnp.maximum(m_acc, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_acc - m_new)                       # rescale old
        p = jnp.exp(scores - m_new[..., None])               # (B,H,Q,K)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_new = o_acc * alpha.transpose(0, 2, 1)[..., None] + pv

        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros((b, lq, h, d), jnp.float32)
    m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    # the accumulators leave the first fold varying over every manual axis
    # q/k/v vary over, and shard_map's check_vma wants the scan carry typed
    # the same going in (empty under check_vma=False: nothing to cast)
    vma = tuple(jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma)
    if vma:
        o0, m0, l0 = (lax.pcast(x, vma, to="varying") for x in (o0, m0, l0))
    (o, m, l, _, _), _ = lax.scan(fold, (o0, m0, l0, k, v),
                                  jnp.arange(axis_size))
    # rows with no visible keys (can't happen causally: every row sees itself)
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)


@lru_cache(maxsize=None)
def ring_attention_fn(axis_name: str = SEQ_AXIS,
                      causal: bool = True) -> Callable:
    """attn_fn factory for TransformerLM: plugs the ring in for full_attention.

    Memoized so same-config calls return the SAME callable: flax modules
    hash by field value, so a per-call closure here would make two
    identical models compare unequal and defeat every module-keyed program
    cache downstream (engine.generate memoization; ADVICE r4)."""
    return partial(ring_attention, axis_name=axis_name, causal=causal)
