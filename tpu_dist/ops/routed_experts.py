"""A routed expert layer's two ops, as one chip of an expert-parallel group
runs them: the router over ALL experts, and the part of the result that the
experts HELD here give. Nothing is dropped and there is no capacity factor.

:func:`route` scores every expert of the published router width with a
sigmoid of the router's float32 logits, keeps the ``top_k`` largest of
``score + selection bias`` (the bias only selects; it is left out of the
weights) and normalises the kept scores over all ``top_k`` of them, held
here or not: ``w_e = scale * s_e / (sum of the kept s + 1e-20)``.

:func:`routed_experts` is told which contiguous block of experts lives here
(``held_lo`` and the leading dimension of the weights; ``parallel.ep.
expert_share`` names the block) and computes ``sum over the kept e that are
held of w_e W2_e relu(W1_e u)^2``. A kept expert that lives on another chip
adds nothing here: that chip adds it, and on one chip without the exchange
the partial sum is the layer's result. Rows past a prompt's live length
(``live`` false) reach no expert and count in no counter. Two forms of one
function, picked from the static row count alone:

* up to :data:`DENSE_ROWS` rows (a decode tick: one row a slot; the prefill
  buckets up to it): every held expert runs over every row and a ``[rows,
  held]`` matrix of weights, zero where a row did not choose the expert,
  folds the result. A tick's products are bound by reading the experts'
  weights once, which a grouped product has to do too, and nothing can be
  dropped by construction.
* more rows (the longer prefill buckets, a long plain forward): the ``rows
  x top_k`` assignments are sorted by expert, those of absent experts and
  dead rows parked after the held ones, and the held groups run as one
  grouped product a matrix (the megablox ``gmm`` Pallas kernel that ships
  with JAX, interpreted off the TPU as the repo's own kernels are; it
  visits the row tiles that hold a group and no others); the rows go back
  by the inverse permutation.

Both return, beside the result, two int32 counters: the assignments of live
rows that landed on held experts, and the held experts with at least one.
:func:`grouped_calls` says which form a row count takes, for the fork itself
and for whoever reports it (the serving engine's ``serve.prefill`` spans).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_dist.runtime import pallas_interpret

#: the most rows the masked dense form takes, set from the chip
#: (``benchmarks/kernels/routed_experts_bench.py`` at the published expert,
#: 1024 -> 2688 -> 1024, 128 held of 512, top 22, on one v5e; PERF.md section
#: 6, PR 40), ms a layer at 64 / 256 / 512 / 1024 / 2048 rows: the dense form
#: 2.7 / 3.6 / 5.0 / 8.9 / 16.7 (it reads the 1.41 GB of weights once and
#: then grows with the MXU's work: it multiplies 23 times what the routing
#: needs); the sorted form 4.1 / 4.2 / 4.6 / 5.5 / 7.3 (its two ``gmm``
#: products 3.0 / 3.3 / 3.5 / 4.3 / 5.7, the sort, the counts and the two
#: gathers around them 0.9-1.6). ``jax.lax.ragged_dot`` in ``gmm``'s place
#: took 5.8 / 9.4 / 9.8 / 10.6 / 11.9 for the pair
DENSE_ROWS = 512
#: ``gmm``'s (rows, contraction, output) tile: the best of those tried at
#: the published expert (rows of 256 and 512 cost 0.3-2.7 ms more a pair,
#: 128-wide tiles five times as much); a dimension that a tile does not
#: divide is masked by the kernel
_GMM_TILING = (128, 1024, 896)


def grouped_calls(rows: int) -> int:
    """Calls of the grouped product that :func:`routed_experts` makes over
    ``rows`` rows: 0 in the masked dense form, 2 in the sorted one."""
    return 0 if rows <= DENSE_ROWS else 2


def route(logits, b_sel, top_k: int, scale: float):
    """The router's ``logits`` [rows, experts] (``W_g h``, float32) ->
    ``(idx [rows, top_k] i32, w [rows, top_k] f32)``."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, idx = jax.lax.top_k(s + b_sel.astype(jnp.float32), top_k)
        kept = jnp.take_along_axis(s, idx, axis=-1)
        w = scale * kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w


def routed_experts(u, idx, w, live, w_in, w_out, held_lo: int):
    """``u`` [rows, latent] through the held experts ``w_in`` [held, latent,
    f] and ``w_out`` [held, f, latent] (experts ``held_lo .. held_lo +
    held`` of the router's), under ``idx``/``w`` from :func:`route` and
    ``live`` [rows] bool. Returns ``(out [rows, latent] f32, expert_rows,
    experts_hit)``."""
    rows, latent = u.shape
    held_n, k = w_in.shape[0], idx.shape[1]
    with jax.named_scope("moe_router"):
        local = idx - held_lo
        held = (local >= 0) & (local < held_n) & live[:, None]
        # an assignment's group: its held expert, or the one past them
        key = jnp.where(held, local, held_n)
        sizes = jnp.zeros((held_n + 1,), jnp.int32).at[
            key.reshape(-1)].add(1)[:held_n]
        w = jnp.where(held, w, 0.0)
    counts = (jnp.sum(sizes), jnp.sum((sizes > 0).astype(jnp.int32)))
    if not grouped_calls(rows):
        with jax.named_scope("moe_router"):
            by_expert = jnp.sum(jnp.where(
                key[:, :, None] == jnp.arange(held_n, dtype=jnp.int32),
                w[:, :, None], 0.0), axis=1)                # [rows, held]
        with jax.named_scope("routed_experts"):
            h = jnp.einsum("rd,edf->erf", u, w_in)
            h = (jnp.square(jax.nn.relu(h))
                 * by_expert.T[:, :, None].astype(h.dtype))
            out = jnp.einsum("erf,efd->rd", h, w_out,
                             preferred_element_type=jnp.float32)
        return (out, *counts)
    with jax.named_scope("moe_router"):
        order = jnp.argsort(key.reshape(-1), stable=True)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(rows * k, dtype=order.dtype))
    with jax.named_scope("routed_experts"):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        grouped = functools.partial(gmm, tiling=_GMM_TILING,
                                    interpret=pallas_interpret(None))
        # the kernel takes whole row tiles: the sorted rows padded up
        xs = jnp.pad(jnp.take(u, order // k, axis=0),
                     ((0, -(rows * k) % _GMM_TILING[0]), (0, 0)))
        h = grouped(xs, w_in, sizes, preferred_element_type=u.dtype)
        out = grouped(jnp.square(jax.nn.relu(h)), w_out, sizes,
                      preferred_element_type=jnp.float32)[:rows * k]
        # the parked assignments belong to no group and the kernel visits
        # none of their rows: what it left there is not a result
        out = jnp.where((jnp.arange(rows * k) < counts[0])[:, None], out, 0.0)
        out = jnp.sum(jnp.take(out, back, axis=0).reshape(rows, k, latent)
                      * w[:, :, None], axis=1)
    return (out, *counts)
