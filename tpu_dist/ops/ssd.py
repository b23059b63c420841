"""The state-space ops of a Mamba-2 mixer: the recurrence in its chunked
matrix form (a prefill, a chunk, the plain forward) and in its one-step form
(the decode tick), each carrying its state in and out.

The recurrence, per batch row, head ``h`` of ``P`` channels in group ``g = h
// (H // G)`` and state ``n`` (Dao & Gu 2024, "Transformers are SSMs",
section 6: ONE scalar decay a head where Mamba-1 has one a channel and
state)::

    S_t[p, n] = exp(dt_t[h] A[h]) S_{t-1}[p, n] + dt_t[h] x_t[h, p] B_t[g, n]
    y_t[h, p] = sum_n S_t[p, n] C_t[g, n] + D[h] x_t[h, p]

:func:`ssd_scan` runs it a chunk of ``chunk`` steps at a time as matrix
products. With ``a_t = dt_t A`` and ``cum`` its running sum inside a chunk::

    Y_intra[t] = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
    S_c        = exp(cum_last) S_{c-1} + sum_s exp(cum_last - cum_s) dt_s x_s B_s^T
    Y_inter[t] = exp(cum_t) S_{c-1} C_t

so that only the chunks' end states are walked in order (``L / chunk``
steps, not ``L``). Every exponent is a sum of ``a <= 0`` over a span that
ends at or after it begins, so nothing overflows. Decays, sums and the state
are float32 whatever the activations' type; the products take float32
operands at the backend's matmul precision.

A server needs of it what it needs of ``ops.selective_scan``: it starts from
a state handed in (``s0``: a slot that continues), it stops at each row's
own ``lengths`` (``dt`` is 0 at and past them, so ``exp(0) = 1`` keeps the
state and nothing is added: the state at the true length reaches the end),
and it runs one token a slot per tick, under the ``ssm_step`` scope as
Mamba-1's does. The tick has two forms of that step, picked from what the
call can see (:func:`head_tile`: the state's type and shape):

* :func:`ssd_step_live`, where every row of the state IS a slot and the
  state is float32 with whole lane tiles of states: ONE Pallas call a layer
  walks the compacted list of the rows that decode (:func:`live_rows`, made
  once a tick program for all its layers) and updates their state IN PLACE;
  a slot that sits out is neither read nor written. The published mixer's
  state is 4.19 MB a slot and layer, so a tick moves what its live rows
  hold and no longer all 64 slots' 2.7 GB (PERF.md section 6, PR 45, has
  the chip's table by live rows and head tile).
* :func:`ssd_step`, plain XLA over every row (``dt = 0`` keeps a row's
  state): the reference the kernel is tested against, and what every other
  shape and caller runs.

The state is laid out ``[batch, H, P, N]``: the ``N`` states on the TPU's
lanes (128 published), a head's channels on the sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_dist.ops.routed_experts import _hit_list
from tpu_dist.runtime import pallas_interpret

#: the most bytes of state one step of :func:`ssd_step_live` holds (its head
#: tile: whole groups, see :func:`head_tile`): 32 heads at the published
#: mixer. On one v5e 64 heads a step are within 3% of it at every live
#: count and 16 are 7-22% slower (``benchmarks/kernels/ssd_step_bench.py``;
#: PERF.md section 6, PR 45), and the step's body is traced once a head of
#: the tile
_STEP_BLOCK_BYTES = 2**20


def _heads(x, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: each group's row for its heads."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def ssd_scan(x, dt, A, B, C, D, s0, lengths, *, chunk: int = 128):
    """The recurrence over ``x`` [b, L, H, P] from state ``s0`` [b, H, P, N]:
    ``dt`` [b, L, H] f32 (after its softplus), ``A`` [H] f32 (negative),
    ``B``/``C`` [b, L, G, N], ``D`` [H], ``lengths`` [b] i32: rows at or
    past a row's length leave its state untouched (their ``y`` means
    nothing). Returns ``(y [b, L, H, P] in x's dtype, s_last [b, H, P, N]
    f32)``. Any ``L``: the last chunk is padded with ``dt = 0``."""
    with jax.named_scope("ssd_scan"):
        b, length, h, p = x.shape
        g, n = B.shape[-2:]
        q = min(chunk, length)
        nc = -(-length // q)
        pad = nc * q - length
        live = (jnp.arange(length, dtype=jnp.int32)[None, :]
                < lengths.astype(jnp.int32)[:, None])
        dt = jnp.where(live[:, :, None], dt.astype(jnp.float32), 0.0)
        f32 = lambda v: v.astype(jnp.float32)
        chunks = lambda v: jnp.pad(
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)).reshape(
                b, nc, q, *v.shape[2:])
        xc, dtc, bc, cc = map(chunks, (f32(x), dt, f32(B), f32(C)))
        cum = jnp.cumsum(dtc * f32(A), axis=2)              # [b, nc, q, H]
        dx = dtc[..., None] * xc                            # dt_s x_s
        # inside a chunk: (C_t . B_s) by group, the decay from s to t by head
        cb = jnp.repeat(jnp.einsum("bctgn,bcsgn->bctsg", cc, bc), h // g,
                        axis=-1)
        span = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [b,nc,t,s,H]
        causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
        y = jnp.einsum("bctsh,bcshp->bcthp", decay * cb, dx)
        # what each chunk adds to the state by its end, then the end states
        # in order: S_c = exp(cum_last) S_{c-1} + added_c
        to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # [b, nc, q, H]
        added = jnp.einsum("bcshp,bcshn->bchpn", dx * to_end[..., None],
                           _heads(bc, h))
        shrink = jnp.exp(cum[:, :, -1, :])                  # [b, nc, H]

        def carry(s, xs):
            shrink_c, added_c = xs
            return shrink_c[:, :, None, None] * s + added_c, s

        s_last, entering = jax.lax.scan(
            carry, f32(s0),
            (jnp.moveaxis(shrink, 1, 0), jnp.moveaxis(added, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)             # [b, nc, H, P, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bchpn,bcthn->bcthp", entering, _heads(cc, h))
        y = y + f32(D)[:, None] * xc
        return (y.reshape(b, nc * q, h, p)[:, :length].astype(x.dtype),
                s_last)


def ssd_step(x_t, dt_t, A, B_t, C_t, D, s):
    """The recurrence for one token a row: ``x_t`` [b, H, P], ``dt_t``
    [b, H], ``B_t``/``C_t`` [b, G, N], ``s`` [b, H, P, N] f32 -> ``(y_t
    [b, H, P] in x's dtype, s [b, H, P, N])``. Plain XLA: a tick is bound by
    reading and writing every slot's state once. A row whose ``dt_t`` is 0
    keeps its state."""
    with jax.named_scope("ssm_step"):
        h = x_t.shape[1]
        f32 = lambda v: v.astype(jnp.float32)
        x, dt = f32(x_t), f32(dt_t)
        s = (jnp.exp(dt * f32(A))[:, :, None, None] * s
             + (dt[:, :, None] * x)[..., None]
             * _heads(f32(B_t), h)[:, :, None, :])
        y = (jnp.sum(s * _heads(f32(C_t), h)[:, :, None, :], axis=-1)
             + f32(D)[:, None] * x)
        return y.astype(x_t.dtype), s


def live_rows(live):
    """``live`` [rows] (nonzero: the row decodes this tick) -> ``(ids [rows]
    i32, n [1] i32)``: the live rows ascending, then the last of them again
    up to ``rows`` entries (zeros where none is live), and how many there
    are. No sort, no scatter."""
    live = live.astype(jnp.int32) > 0
    return _hit_list(live), jnp.sum(live.astype(jnp.int32))[None]


def head_tile(s, groups: int) -> int:
    """The heads one step of :func:`ssd_step_live` takes from a state ``s``
    [rows, H, P, N], or 0 where the kernel does not take the shape: float32
    state, the ``N`` states whole lane tiles, a head's ``P`` channels whole
    sublane tiles, and the largest number of whole groups' heads that
    divides ``H`` and holds at most :data:`_STEP_BLOCK_BYTES`."""
    _, h, p, n = s.shape
    if s.dtype != jnp.float32 or n % 128 or p % 8 or h % groups:
        return 0
    per = h // groups
    return max((t for t in range(per, h + 1, per)
                if h % t == 0 and 4 * t * p * n <= _STEP_BLOCK_BYTES),
               default=0)


def _ssd_step_kernel(ids_ref, n_ref, fresh_ref, x_ref, dt_ref, a_ref, d_ref,
                     b_ref, c_ref, s_ref, _, y_ref, o_ref):
    """One step of the walk over the live rows: row ``ids[i]``, head tile
    ``t``. ``x_ref``/``y_ref`` [P, tile] hold the tile's heads on the lanes,
    ``s_ref``/``o_ref`` [tile, P, N] one block of the state, which the call
    aliases: a block no step names is neither read nor written."""
    import jax.experimental.pallas as pl

    i, t = pl.program_id(0), pl.program_id(1)
    tile, p, n = s_ref.shape[1:]
    per = tile // b_ref.shape[2]

    @pl.when((i == 0) & (t == 0) & (n_ref[0] == 0))
    def _none():
        # no row is live: the one block the walk names goes back as it came
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_ref[0])
    def _row():
        x, dt = x_ref[0, 0], dt_ref[0, 0]               # [P, tile], [1, tile]
        decay, dx = jnp.exp(dt * a_ref[0]), dt * x
        fresh = fresh_ref[ids_ref[i]] != 0
        lane = jax.lax.broadcasted_iota(jnp.int32, (p, tile), 1)
        # the tile's heads walked at trace time: a loop the compiler keeps
        # (``fori_loop`` over the heads, their rows of the block and their
        # columns indexed by the traced head) ran 1.6 times as long on the
        # chip in each of four forms (PERF.md section 6, PR 45)
        y = jnp.zeros((p, tile), jnp.float32)
        for h in range(tile):
            b, c = b_ref[0, 0, h // per], c_ref[0, 0, h // per]     # [1, N]
            s = jnp.where(fresh, 0.0, s_ref[0, h])                  # [P, N]
            s = decay[:, h:h + 1] * s + dx[:, h:h + 1] * b
            o_ref[0, h] = s
            y = jnp.where(lane == h, jnp.sum(s * c, axis=1, keepdims=True),
                          y)
        y_ref[0, 0] = y + d_ref[0] * x


def ssd_step_live(x_t, dt_t, A, B_t, C_t, D, s, rows, fresh, tile: int,
                  interpret=None):
    """:func:`ssd_step` over the rows that ``rows`` (:func:`live_rows`)
    lists, the state updated IN PLACE: ONE Pallas call, grid ``(rows, head
    tiles)`` over the compacted list, the state's block ``(1, tile, P, N)``
    named by ``ids[i]`` for input and output and the two aliased, so a row
    that sits out keeps its bits without being read or written (its ``y`` is
    zero). A step past ``n`` names the block the last real step named, so
    the pipeline copies nothing for it, and skips its body. ``fresh`` [rows]
    (nonzero: the row starts from zero state whatever it holds); ``tile``
    from :func:`head_tile`. All in float32, term by term as :func:`ssd_step`
    has them."""
    return _step_call(x_t, dt_t, A, B_t, C_t, D, s, rows, fresh, tile=tile,
                      interpret=pallas_interpret(interpret))


# jitted, so that a program traces and lowers the kernel once and not once a
# layer (as the flash calls are, PR 29); the mode is settled before, not
# inside the cache
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _step_call(x_t, dt_t, A, B_t, C_t, D, s, rows, fresh, *, tile, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ids, n = rows
    slots, h, p, d_state = s.shape
    tiles, groups = h // tile, B_t.shape[1] * tile // h
    f32 = lambda v: v.astype(jnp.float32)
    # a tile's heads on the lanes: x and y as [rows, tiles, P, tile]
    by_tile = lambda v: f32(v).reshape(*v.shape[:-1], tiles, -1)
    at = lambda i, t, n: jnp.where(i < n[0], t, tiles - 1)
    row = lambda i, t, ids, n, fresh: (ids[i], at(i, t, n), 0, 0)
    head = lambda i, t, ids, n, fresh: (at(i, t, n), 0, 0)
    group = lambda i, t, ids, n, fresh: (ids[i], at(i, t, n), 0, 0, 0)
    with jax.named_scope("ssm_step"):
        y, s = pl.pallas_call(
            _ssd_step_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(slots, tiles),
                in_specs=[
                    pl.BlockSpec((1, 1, p, tile), row),
                    pl.BlockSpec((1, 1, 1, tile), row),
                    pl.BlockSpec((1, 1, tile), head),
                    pl.BlockSpec((1, 1, tile), head),
                    pl.BlockSpec((1, 1, groups, 1, d_state), group),
                    pl.BlockSpec((1, 1, groups, 1, d_state), group),
                    pl.BlockSpec((1, tile, p, d_state), row),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=[pl.BlockSpec((1, 1, p, tile), row),
                           pl.BlockSpec((1, tile, p, d_state), row)]),
            out_shape=[jax.ShapeDtypeStruct((slots, tiles, p, tile),
                                            jnp.float32),
                       jax.ShapeDtypeStruct(s.shape, s.dtype)],
            # the state, and the zeros that a row the walk does not visit
            # answers with
            input_output_aliases={9: 1, 10: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="ssd_step",
        )(ids, n, fresh.astype(jnp.int32),
          jnp.swapaxes(by_tile(jnp.swapaxes(x_t, 1, 2)), 1, 2),
          by_tile(dt_t)[:, :, None], by_tile(A)[:, None], by_tile(D)[:, None],
          f32(B_t).reshape(slots, tiles, groups, 1, d_state),
          f32(C_t).reshape(slots, tiles, groups, 1, d_state), s,
          jnp.zeros((slots, tiles, p, tile), jnp.float32))
        y = jnp.swapaxes(y, 2, 3).reshape(slots, h, p)
        return y.astype(x_t.dtype), s
