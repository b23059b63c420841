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
and it runs one token a slot per tick (:func:`ssd_step`, under the
``ssm_step`` scope as Mamba-1's is).

The state is laid out ``[batch, H, P, N]``: the ``N`` states on the TPU's
lanes (128 published), a head's channels on the sublanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
