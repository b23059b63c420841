"""The state-space ops of a Mamba-1 mixer: the selective scan, its one-step
form for the decode tick, and the causal depthwise convolution in front of
it, each carrying its state in and out.

The recurrence, per batch row, channel ``c`` and state ``n`` (Gu & Dao 2023,
"Mamba", section 3.2, as the Jamba family runs it)::

    s_t[n, c] = exp(delta_t[c] * A[c, n]) * s_{t-1}[n, c]
                + delta_t[c] * B_t[n] * u_t[c]
    y_t[c]    = sum_n C_t[n] * s_t[n, c] + D[c] * u_t[c]

A server needs three things of it that a trainer does not: it starts from a
state handed in (``s0``: a chunked prefill's carry), it stops at each row's
own ``lengths`` (a prompt shorter than its bucket must leave the state of
its last live token, not of the padding: ``delta`` is masked to 0 there, so
``exp(0) = 1`` keeps the state and nothing is added), and it runs one token
a slot per tick (:func:`ssm_step`).

The state is laid out ``[batch, d_state, channels]``: channels on the TPU's
lanes, the 16 states on the sublanes. Everything of the recurrence is
float32 whatever the activations' dtype is.

:func:`selective_scan` has two forms of one function, picked from the
shapes alone: a Pallas TPU kernel (interpreted off the TPU,
``runtime.pallas_interpret``) where the channels fill whole lane rows and
the length whole 16-step groups, and a plain ``lax.scan`` over time for
everything else (the tiny widths of the CPU tests, a length of 1 or 8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_dist.runtime import pallas_interpret

#: time steps a kernel group loads as one dense tile and walks unrolled (a
#: bf16 tile is 16 sublanes deep, so a group starts on a tile boundary
#: whatever the activations' dtype)
_GROUP = 16
#: the kernel's blocks: time steps a grid step streams in, channels a tile
_BLOCK_T = (256, 128, 64, 32, 16)
_BLOCK_C = (1024, 512, 256, 128)


def _step(s, u_t, delta_t, b_t, c_t, a_t, d):
    """One step of the recurrence: ``s`` [b, N, C] f32, ``u_t``/``delta_t``
    [b, C] f32, ``b_t``/``c_t`` [b, N] f32, ``a_t`` [N, C], ``d`` [C]."""
    da = jnp.exp(delta_t[:, None, :] * a_t[None])
    s = da * s + (delta_t * u_t)[:, None, :] * b_t[:, :, None]
    y = jnp.sum(c_t[:, :, None] * s, axis=1) + d[None] * u_t
    return s, y


def _scan_plain(u, delta, a_t, b, c, d, s0):
    def body(s, xs):
        u_t, delta_t, b_t, c_t = xs
        return _step(s, u_t.astype(jnp.float32), delta_t, b_t, c_t, a_t, d)

    time_major = lambda x: jnp.swapaxes(x, 0, 1)
    s_last, y = jax.lax.scan(
        body, s0, (time_major(u), time_major(delta), time_major(b),
                   time_major(c)))
    return time_major(y).astype(u.dtype), s_last


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                 y_ref, sl_ref, s_scr, *, block_t):
    """One (batch row, channel tile, time chunk) grid step: the state tile
    [N, C_tile] stays in VMEM scratch across the chunks of a row (the
    grid's last axis is sequential); a chunk is walked in groups of
    ``_GROUP`` steps, each loaded as dense tiles and unrolled."""
    import jax.experimental.pallas as pl

    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_scr[...] = s0_ref[0]

    a = a_ref[...]                                   # [N, C]
    d = d_ref[...]                                   # [1, C]

    def group(g, s):
        r0 = pl.multiple_of(g * _GROUP, _GROUP)
        u = u_ref[0, pl.ds(r0, _GROUP), :].astype(jnp.float32)   # [G, C]
        dt = dt_ref[0, pl.ds(r0, _GROUP), :]
        bg = b_ref[0, g]                             # [N, G]: states x steps
        cg = c_ref[0, g]
        du = dt * u
        rows = []
        for i in range(_GROUP):
            s = (jnp.exp(dt[i:i + 1, :] * a) * s
                 + du[i:i + 1, :] * bg[:, i:i + 1])
            rows.append(jnp.sum(cg[:, i:i + 1] * s, axis=0, keepdims=True))
        y = jnp.concatenate(rows, axis=0) + d * u
        y_ref[0, pl.ds(r0, _GROUP), :] = y.astype(y_ref.dtype)
        return s

    s = jax.lax.fori_loop(0, block_t // _GROUP, group, s_scr[...])
    s_scr[...] = s

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        sl_ref[0] = s


def _scan_pallas(u, delta, a_t, b, c, d, s0, block_t, block_c, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, length, ch = u.shape
    n = a_t.shape[0]
    # B and C as [batch, group, state, step]: a group's [N, G] tile is one
    # index on an untiled axis, and a step's column broadcasts over the
    # channel lanes inside the kernel (16 x 4 bytes a token: nothing next
    # to the channels' streams)
    grouped = lambda x: jnp.swapaxes(
        x.reshape(bsz, length // _GROUP, _GROUP, n), 2, 3)
    stream = pl.BlockSpec((1, block_t, block_c), lambda i, j, k: (i, k, j))
    groups = pl.BlockSpec((1, block_t // _GROUP, n, _GROUP),
                          lambda i, j, k: (i, k, 0, 0))
    state = pl.BlockSpec((1, n, block_c), lambda i, j, k: (i, 0, j))
    y, s_last = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t),
        grid=(bsz, ch // block_c, length // block_t),
        in_specs=[stream, stream,
                  pl.BlockSpec((n, block_c), lambda i, j, k: (0, j)),
                  groups, groups,
                  pl.BlockSpec((1, block_c), lambda i, j, k: (0, j)),
                  state],
        out_specs=[stream, state],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(interpret),
    )(u, delta, a_t, grouped(b), grouped(c), d[None], s0)
    return y, s_last


def scan_blocks(length: int, channels: int):
    """The kernel's (time, channel) blocks for these shapes, or None where
    the plain form runs: channels that do not fill lane rows, or a length
    that is not whole 16-step groups."""
    block_t = next((t for t in _BLOCK_T if length % t == 0), None)
    block_c = next((c for c in _BLOCK_C if channels % c == 0), None)
    return None if block_t is None or block_c is None else (block_t, block_c)


def _scan_args(u, delta, A, B, C, D, s0, lengths):
    """The two forms' arguments: ``delta`` zeroed at and past a row's
    length, ``A`` as [N, C], everything but ``u`` in float32."""
    live = (jnp.arange(u.shape[1], dtype=jnp.int32)[None, :]
            < lengths.astype(jnp.int32)[:, None])
    delta = jnp.where(live[:, :, None], delta.astype(jnp.float32), 0.0)
    return (u, delta, jnp.swapaxes(A.astype(jnp.float32), 0, 1),
            B.astype(jnp.float32), C.astype(jnp.float32),
            D.astype(jnp.float32), s0.astype(jnp.float32))


def selective_scan(u, delta, A, B, C, D, s0, lengths, *, interpret=None):
    """The recurrence over ``u`` [b, L, C] from state ``s0`` [b, N, C]:
    ``delta`` [b, L, C] f32 (after its softplus), ``A`` [C, N] f32
    (negative), ``B``/``C`` [b, L, N] f32, ``D`` [C] f32, ``lengths`` [b]
    i32: rows at or past a row's length leave its state untouched (their
    ``y`` means nothing). Returns ``(y [b, L, C] in u's dtype, s_last
    [b, N, C] f32)``; ``interpret`` as the repo's other kernels take it."""
    with jax.named_scope("selective_scan"):
        args = _scan_args(u, delta, A, B, C, D, s0, lengths)
        blocks = scan_blocks(u.shape[1], u.shape[2])
        if blocks is None:
            return _scan_plain(*args)
        return _scan_pallas(*args, *blocks, interpret)


def ssm_step(u_t, delta_t, A, B_t, C_t, D, s):
    """The recurrence for one token a row: ``u_t``/``delta_t`` [b, C],
    ``B_t``/``C_t`` [b, N], ``s`` [b, N, C] f32 -> ``(y_t [b, C] in u's
    dtype, s [b, N, C])``. Plain XLA: a tick is bound by reading and
    writing every slot's state once. A row whose ``delta_t`` is 0 keeps
    its state."""
    with jax.named_scope("ssm_step"):
        s, y = _step(s, u_t.astype(jnp.float32),
                     delta_t.astype(jnp.float32), B_t.astype(jnp.float32),
                     C_t.astype(jnp.float32),
                     jnp.swapaxes(A.astype(jnp.float32), 0, 1),
                     D.astype(jnp.float32))
        return y.astype(u_t.dtype), s


def causal_conv1d(u, w, b, tail, lengths):
    """Depthwise causal convolution over time: ``u`` [b, L, C], ``w``
    [K, C] (``w[K-1]`` weighs the current step), ``b`` [C], ``tail``
    [b, K-1, C] the rows before the sequence (zeros for a fresh prompt).
    Returns ``(out [b, L, C] f32, new_tail)``: ``new_tail`` holds the last
    K-1 LIVE rows, those before position ``lengths`` [b], not before the
    end of a padded bucket; a row of length 0 keeps its tail. The tick's
    form is the same call with L = 1."""
    k = w.shape[0]
    joined = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    x = joined.astype(jnp.float32)
    length = u.shape[1]
    out = b.astype(jnp.float32)[None, None, :] + sum(
        w[j].astype(jnp.float32)[None, None, :] * x[:, j:j + length]
        for j in range(k))
    idx = (lengths.astype(jnp.int32)[:, None]
           + jnp.arange(k - 1, dtype=jnp.int32)[None, :])
    new_tail = jnp.take_along_axis(joined, idx[:, :, None], axis=1)
    return out, new_tail.astype(tail.dtype)
