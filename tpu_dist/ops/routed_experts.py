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
  buckets up to it): every HIT expert (held, and chosen by some live row)
  runs over every row, and a ``[rows, held]`` matrix of weights, zero where
  a row did not choose the expert, folds the result, so nothing can be
  dropped by construction and nothing is sorted. ONE Pallas call walks the
  compacted list of the hit experts and does both products an expert: the
  weights of an expert no row chose are never read (a third of the held
  experts at the serving cell's load) and the ``[held, rows, width]``
  hidden values never leave vector memory. With few rows the products are
  bound by reading the hit experts' weights once, and the call reads them
  at 78-89% of the HBM rate at a tick's 64 rows (PR 41's table below). A
  layer whose weights are COMPUTED on the way in (dequantised int8, a
  cast) says so (``stored=False``) and keeps the two einsums over every
  held expert: XLA fuses the producer into them, where a kernel would have
  it write the 1.4 GB of bfloat16 out first.
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

#: the most rows the form over the hit list takes, set from the chip at the
#: published expert (1024 -> 2688 -> 1024, 128 held of 512, top 22, one v5e;
#: ``benchmarks/kernels/routed_tick_bench.py``, calls issued back to back;
#: PERF.md section 6, PR 41). ms a layer at 64 / 128 / 256 / 512 rows with
#: every held expert hit, as a prefill's rows hit them: the kernel 1.93 /
#: 1.94 / 2.08 / 3.93 (from 512 rows an expert's products, 5.6 GFLOP,
#: outweigh its 11 MB), the sorted form 2.54 / 2.66 / 2.77 / 3.18 (its
#: products go with the assignments, a twenty-third of the kernel's), the
#: einsum form 1.94 / 1.94 / 2.59 / 4.01 whatever is hit; the kernel's time
#: follows the hit count (0.55 / 0.57 / 0.63 / 1.14 with a quarter hit). So
#: the kernel up to 256 rows and the sorted form from the 512 bucket on (512
#: until PR 41: PR 40's blocking calls read the einsum form 5.0 against the
#: sorted one's 4.6 there, and 8.9 / 16.7 against 5.5 / 7.3 at 1024 / 2048;
#: ``jax.lax.ragged_dot`` in ``gmm``'s place took 5.8-11.9 for the pair)
DENSE_ROWS = 256
#: ``gmm``'s (rows, contraction, output) tile: the best of those tried at
#: the published expert (rows of 256 and 512 cost 0.3-2.7 ms more a pair,
#: 128-wide tiles five times as much); a dimension that a tile does not
#: divide is masked by the kernel
_GMM_TILING = (128, 1024, 896)
#: the hit list's kernel: its tile of an expert's width (2688 = 3 x 896; a
#: whole expert a step is 0-4% faster from 256 rows and no faster at 64, for
#: three times the buffers)
_HIT_WIDTH_TILE = 896


def grouped_calls(rows: int) -> int:
    """Calls of the grouped product that :func:`routed_experts` makes over
    ``rows`` rows: 0 in the form over the hit list, 2 in the sorted one."""
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


def _hit_list(sizes):
    """``sizes`` [held] (live assignments an expert) -> the experts with at
    least one, ascending, then the last of them again up to ``held``
    entries (zeros where none is hit)."""
    slot = jnp.arange(sizes.shape[0], dtype=jnp.int32)
    hit = sizes > 0
    # hit expert j stands at place (hit ones below it): no sort, no scatter
    place = jnp.cumsum(hit.astype(jnp.int32)) - 1
    ids = jnp.sum(jnp.where(hit & (place == slot[:, None]), slot, 0), axis=1)
    return jnp.where(slot <= place[-1], ids, jnp.max(jnp.where(hit, slot, 0)))


def _hit_experts_kernel(ids_ref, n_ref, u_ref, g_ref, w_in_ref, w_out_ref,
                        o_ref):
    """One step of the walk over the hit list: hit expert ``ids[e]``, width
    tile ``t``. The block specs brought that expert's tile of both matrices;
    ``o_ref`` [rows, latent] float32 stays resident over the whole grid."""
    import jax.experimental.pallas as pl

    e = pl.program_id(0)

    @pl.when((e == 0) & (pl.program_id(1) == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(e < n_ref[0])
    def _expert():
        u = u_ref[...]
        h = jnp.dot(u, w_in_ref[0],
                    preferred_element_type=jnp.float32).astype(u.dtype)
        # column ids[e] of the [rows, held] weights, without a dynamic lane
        # index: the other columns masked out of a sum over the lanes
        g = g_ref[...]
        mine = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) == ids_ref[e]
        gate = jnp.sum(jnp.where(mine, g, 0.0), axis=1, keepdims=True)
        h = jnp.square(jax.nn.relu(h)) * gate.astype(h.dtype)
        o_ref[...] += jnp.dot(h, w_out_ref[0],
                              preferred_element_type=jnp.float32)


def _hit_experts(u, by_expert, hit_ids, n_hit, w_in, w_out, interpret=None):
    """``sum over the hit experts j of (relu(u W1_j)^2 * by_expert[:, j])
    W2_j`` as ONE Pallas call: grid ``(held, width tiles)`` over the
    compacted ``hit_ids`` [held] (ascending, the last hit one repeated past
    ``n_hit`` [1]); a step past ``n_hit`` names the block the last real step
    named, so the pipeline copies nothing for it, and skips its body."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, latent = u.shape
    held_n, _, width = w_in.shape
    tile = _HIT_WIDTH_TILE if width % _HIT_WIDTH_TILE == 0 else width
    tiles = width // tile
    # whole sublane tiles of the narrowest operand (bfloat16: 16 rows)
    padded = rows + -rows % 16
    if padded != rows:
        u = jnp.pad(u, ((0, padded - rows), (0, 0)))
        by_expert = jnp.pad(by_expert, ((0, padded - rows), (0, 0)))
    whole = lambda e, t, ids, n: (0, 0)
    # past the hit list: the last real step's tile too, not only its expert
    at = lambda e, t, n: jnp.where(e < n[0], t, tiles - 1)
    out = pl.pallas_call(
        _hit_experts_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(held_n, tiles),
            in_specs=[
                pl.BlockSpec((padded, latent), whole),
                pl.BlockSpec((padded, held_n), whole),
                pl.BlockSpec((1, latent, tile),
                             lambda e, t, ids, n: (ids[e], 0, at(e, t, n))),
                pl.BlockSpec((1, tile, latent),
                             lambda e, t, ids, n: (ids[e], at(e, t, n), 0)),
            ],
            out_specs=pl.BlockSpec((padded, latent), whole)),
        out_shape=jax.ShapeDtypeStruct((padded, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two buffers of each matrix's tile (7.3 MB at the published
            # expert) beside the rows, the float32 result and a hidden tile:
            # the compiler's default holds them up to 512 rows, and the
            # benches force the form over more
            vmem_limit_bytes=16 * 2**20 + 24 * padded * latent),
        interpret=pallas_interpret(interpret),
        name="hit_experts",
    )(hit_ids, n_hit, u, by_expert, w_in, w_out)
    return out[:rows]


def routed_experts(u, idx, w, live, w_in, w_out, held_lo: int,
                   stored: bool = True):
    """``u`` [rows, latent] through the held experts ``w_in`` [held, latent,
    f] and ``w_out`` [held, f, latent] (experts ``held_lo .. held_lo +
    held`` of the router's), under ``idx``/``w`` from :func:`route` and
    ``live`` [rows] bool. ``stored``: the two are arrays as they lie in HBM;
    False where they are computed on the way in (a dequantisation, a cast),
    which XLA fuses into the einsum form's products and would have to write
    out whole before a kernel (the kernel is forward-only, as the served
    model is: whoever differentiates through the layer passes False too).
    Returns ``(out [rows, latent] f32, expert_rows, experts_hit)``."""
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
            hit_ids = _hit_list(sizes) if stored else None
        with jax.named_scope("routed_experts"):
            if stored:
                out = _hit_experts(u, by_expert, hit_ids, counts[1][None],
                                   w_in, w_out)
            else:
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
