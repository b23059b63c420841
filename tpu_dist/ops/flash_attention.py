"""Memory-efficient attention: blockwise (online softmax) + Pallas flash.

The reference has no attention at all (SURVEY.md §2c); tpu_dist's LM family
takes a pluggable ``attn_fn`` (tpu_dist.models.transformer), so these drop
into the SAME weights as full attention:

* :func:`blockwise_attention_fn` — pure-JAX flash-attention math: a
  ``lax.scan`` over KV blocks with a running (max, sum, acc) online softmax.
  Never materializes the (B,H,L,L) score matrix — peak activation memory is
  O(L * block) — and autodiff/remat work out of the box. Runs on any
  backend; this is the long-context workhorse and the ground truth for the
  kernel below.
* :func:`flash_attention_fn` — Pallas TPU FlashAttention-2: forward grid
  (batch*head, q tiles, kv tiles) with VMEM scratch accumulators carried
  across the innermost KV dimension (scores never touch HBM; O(bq*bk)
  working set at ANY sequence length), fp32 online math, per-row logsumexp
  written out. Backward is ONE Pallas kernel that re-derives probabilities
  from the stashed logsumexp once a block pair and feeds dq, dk and dv from
  them: five products and one exp pass, score recompute only, not a second
  full forward. Inside a grid tile both walk 512- or 256-wide sub-blocks, so
  that work above the causal diagonal is not executed and only sub-blocks
  the diagonal crosses pay for a mask (:func:`flash_work` counts it).

Both are numerically validated against full attention (tests/test_flash.py)
and compose with the causal offsets ring attention uses.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp

from tpu_dist.runtime import pallas_interpret

NEG_INF = -1e30  # avoids -inf - -inf = nan in the online max updates


def _causal_mask(scores, q_pos, k_pos):
    return jnp.where(k_pos[None, :] <= q_pos[:, None], scores, NEG_INF)


@functools.lru_cache(maxsize=None)
def blockwise_attention_fn(block_size: int = 512):
    """Returns attn(q, k, v, causal=True, q_offset=0, kv_offset=0).

    Shapes follow the model convention: (B, L, H, D). fp32 softmax state
    regardless of input dtype, like tpu_dist.models.transformer.full_attention.
    Memoized per config so identical-hyperparameter models (which carry
    this closure as a hash field) compare equal — see ring_attention_fn.
    """

    def attn(q, k, v, *, causal: bool = True, q_offset=0, kv_offset=0):
        b, lq, h, d = q.shape
        lk = k.shape[1]
        # same fit rule as the flash kernels (_blocks): clamp to the kv
        # length, shrink to gcd when it doesn't divide (lk=1536 with
        # block 1024 -> 512), so the shared attn_block default works here
        blk = min(block_size, lk)
        if lk % blk:
            blk = math.gcd(blk, lk)
        if blk < 1:
            raise ValueError(f"kv length {lk} has no usable block "
                             f"<= {block_size}")
        nk = lk // blk
        scale = 1.0 / math.sqrt(d)

        # (B, L, H, D) -> (B, H, L, D) once; scan over KV blocks
        qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
        kh = jnp.swapaxes(k, 1, 2).reshape(b, h, nk, blk, d)
        vh = jnp.swapaxes(v, 1, 2).reshape(b, h, nk, blk, d)
        kh = jnp.moveaxis(kh, 2, 0)  # (nk, B, H, blk, D)
        vh = jnp.moveaxis(vh, 2, 0)

        q_pos = q_offset + jnp.arange(lq)

        def body(carry, blk_in):
            acc, m, l, i = carry
            kb, vb = blk_in
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, kb.astype(jnp.float32))
            if causal:
                k_pos = kv_offset + i * blk + jnp.arange(blk)
                s = jnp.where(k_pos[None, None, None, :]
                              <= q_pos[None, None, :, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            # masked scores must contribute ZERO probability even when the
            # whole row is masked (m_new == NEG_INF -> exp(s - m_new) would
            # be 1 for every masked key, yielding the unmasked mean of V
            # instead of zeros — reachable via q_offset/kv_offset composition)
            p = jnp.where(s <= NEG_INF / 2, 0.0,
                          jnp.exp(s - m_new[..., None]))
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
            return (acc, m_new, l, i + 1), None

        acc0 = jnp.zeros((b, h, lq, d), jnp.float32)
        m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, lq), jnp.float32)
        (acc, _, l, _), _ = jax.lax.scan(
            body, (acc0, m0, l0, jnp.int32(0)), (kh, vh))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.swapaxes(out, 1, 2).astype(v.dtype)

    return attn


def window_attention(q, k, v, window: int):
    """Causal attention inside a sliding window, in plain JAX: key ``t`` is
    visible to query ``s`` iff ``s - window < t <= s``. ``q``/``k`` (B, L, H,
    D), ``v`` (B, L, H, Dv) (the value width is its own), float32 scores and
    softmax, the weights rounded to ``v``'s dtype before the weighted sum as
    ``full_attention`` does. Banded: the queries go in blocks of ``window``
    rows, and a block multiplies against its own keys and the block before
    them (every key one of its rows can see), so the work and the scores'
    memory are ``2 * window`` a row whatever ``L`` is, not ``L``.

    The Pallas flash kernels below have no window bound (their schedule
    places a band against the causal diagonal only); this is what a window
    layer's PREFILL runs (``models.phi4flash``). The decode read of a
    window is ``ops.paged_attention``'s."""
    b, l, h, d = q.shape
    blk = min(window, l)
    pad = -l % blk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    nb = (l + pad) // blk
    blocks = lambda x: x.reshape(b, nb, blk, h, x.shape[-1])
    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    r = jnp.arange(blk)[:, None]
    if nb == 1:
        live = jnp.arange(blk)[None, :] <= r                     # (blk, blk)
        live = live[None]
    else:
        before = lambda x: jnp.concatenate(
            [jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
        kb = jnp.concatenate([before(kb), kb], axis=2)   # (b, nb, 2 blk, h, .)
        vb = jnp.concatenate([before(vb), vb], axis=2)
        # column c of block i is key (i - 1) * blk + c; row r is query
        # i * blk + r: its age is blk + r - c, in [0, window) (blk == window)
        c = jnp.arange(2 * blk)[None, :]
        live = (c > r) & (c <= r + blk)
        first = jnp.arange(nb)[:, None, None] == 0       # no block before it
        live = live[None] & ~(first & (c < blk)[None])           # (nb, ., .)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kb,
                   preferred_element_type=jnp.float32) / jnp.sqrt(d).astype(
                       jnp.float32)
    w = jax.nn.softmax(jnp.where(live[None, :, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", w.astype(v.dtype), vb)
    return out.reshape(b, nb * blk, h, v.shape[-1])[:, :l]


# ---------------------------------------------------------------------------
# Pallas flash attention (FlashAttention-2 schedule, forward + backward)
# ---------------------------------------------------------------------------
#
# Both kernels tile the (lq, lk) score square twice. The GRID's tile is
# (bq, bk) (``block_q``/``block_k`` clamped to the lengths by ``_blocks``):
# what one grid step holds in VMEM. Inside a tile the program walks BANDS of
# sq query rows against the tile's keys in chunks of sk (``_SUB``: 512
# forward, 256 backward, where the tile allows), and places each band
# against the causal diagonal from scalars alone:
#
# * chunks strictly above the diagonal are not computed: a band multiplies
#   against its first n live chunks only, and a band with none runs nothing;
# * chunks strictly below it take NO iota, compare or select;
# * only the last m chunks, the ones the diagonal crosses, are masked.
#
# Shapes are static, so every grid tile that the call's shapes and offsets
# can produce (``_schedule``: the kinds (n, m) of its bands, top to bottom)
# is a straight-line body of its own, picked by scalars; the aligned
# square has two, the diagonal tile and the interior one. A band is ONE
# product of width n * sk and one softmax update, because the lane
# reductions and the accumulator's rescaling cost a row, not an element:
# chunk-by-chunk updates at 512 ran the forward 1.4 times slower than one
# update a band (v5e, PR 29). No branch stands between a tile's bands, so
# the scheduler may run one band's products under another's vector passes.
# A tile with no live band runs nothing, and its index maps name the last
# (forward) / first (backward) live tile, so no DMA is issued for it.
#
# Forward: grid (B*H, q tiles, kv tiles), kv INNERMOST: the VMEM scratch
# accumulators (acc, running max m, running sum l) carry across the kv steps
# of one q tile. Scores stay unscaled; the softmax scale enters inside the
# exponent ((s - m) * scale), so the running max is of raw scores. The
# per-row logsumexp is written out for the backward.
#
# Backward: ONE kernel, grid (B*H, kv tiles, q tiles), q INNERMOST. Scores,
# p = exp(s * scale - lse), dP = dO V^T and dS = p * (dP - delta) are made
# once a band and feed dV += P^T dO, dK += dS^T Q and dQ += dS K: five
# products and one exp pass. dK/dV accumulate in VMEM over the inner q
# steps; dQ accumulates in a float32 (lq, D) scratch that stays in VMEM for
# the whole head, and each q tile's rows are written out during the last kv
# tile's steps. The scale is applied once to the dQ/dK accumulators when
# they are written. delta = rowsum(o * dout) is a cheap fused elementwise
# pass outside Pallas.

_LANES = 128      # TPU vector lane count: scratch row-stats are (bq, _LANES)
_STAT_LANES = 8   # lse/delta HBM layout: (B*H, L, 8) — Mosaic block tiling
                  # wants the last dim either 128-divisible or equal to the
                  # array's, so an 8-wide stat lane keeps blocks legal while
                  # costing 8 (not 128) floats per row
# band height and key-chunk width inside a grid tile (_sub_block). One v5e,
# B4 L2048 H16 D128, us a call (PR 29): forward 799 at 512, 864 at 256 (each
# band pays a softmax update a row: a lane reduction and the accumulator's
# rescaling); the fused backward, which keeps no such state, 1550 at 1024,
# 1290 at 512, 1185 at 256, 1284 at 128 (forward 1041). The two-kernel
# program at 1024: 977 and 2701.
_SUB = {"forward": 512, "backward": 256}
# the backward's float32 dQ accumulator is resident for a whole head: lq rows
# of max(D, 128) lanes. 8 MiB holds L 16384 at D <= 128; a longer lq is cut
# into q chunks outside the kernel (_fa_backward). The kernel's other VMEM
# (double-buffered tiles, dK/dV accumulators, a band's float32
# intermediates) is about 14 MiB at 1024 x 1024 x D 128.
_DQ_RESIDENT_BYTES = 8 * 2**20
_BWD_TILE_BYTES = 24 * 2**20


def _causal_bounds(causal, q_start, k_start, bq, bk):
    """(skip_block, needs_mask) for one (q block, kv block) pair."""
    if not causal:
        return False, False
    skip = k_start > q_start + bq - 1          # entirely above the diagonal
    needs_mask = k_start + bk - 1 > q_start    # straddles the diagonal
    return skip, needs_mask


def _sub_block(block, sub):
    """The band height (or key-chunk width) of a grid tile ``block`` wide:
    ``sub`` where it divides the tile, else their gcd; a tile whose gcd is
    not a whole number of lanes is one band."""
    sub = math.gcd(block, sub)
    return sub if sub % _LANES == 0 else block


def _whole(x, n, most):
    """How many whole ``n`` fit in ``x``, kept within [0, most]. ``x`` is an
    int (the static schedule) or a traced scalar (the kernel, an index map);
    clipping first keeps the traced division, which truncates, a floor."""
    if isinstance(x, int):
        return max(0, min(x // n, most))
    return jax.lax.div(jax.lax.max(jax.lax.min(x, most * n), 0), n)


def _first_gap(i, j, bq, bk, q_offset, kv_offset):
    """First query position of q tile ``i`` minus first key position of kv
    tile ``j``."""
    return i * bq - j * bk + (q_offset - kv_offset)


def _last_live_kv(iq, bq, bk, nk, q_offset, kv_offset):
    """The last kv tile with a key that q tile ``iq`` may see."""
    return _whole(_first_gap(iq, 0, bq, bk, q_offset, kv_offset) + (bq - 1),
                  bk, nk - 1)


def _first_live_q(ik, bq, bk, nq, q_offset, kv_offset):
    """The first q tile with a row that may see kv tile ``ik``."""
    return _whole(-_first_gap(0, ik, bq, bk, q_offset, kv_offset), bq, nq - 1)


def _band_kind(gap, sq, sk, nc):
    """(n, m) of the band whose first row sits ``gap`` positions after the
    tile's first key: it sees the tile's first n key chunks of sk (chunk c
    is live when c * sk <= gap + sq - 1, its first key no later than the
    band's last row), the last m of them through the diagonal (chunk c is
    clear of it when c * sk + sk - 1 <= gap). n = 0: it sees none."""
    n = _whole(gap + (sq - 1 + sk), sk, nc)
    return n, n - _whole(gap + 1, sk, nc)


def _schedule(lq, lk, bq, bk, sq, sk, causal, q_offset, kv_offset):
    """The kernels' static schedule: {tile: count} over every grid tile of
    one (batch, head), a tile being the kinds (n, m) of its bands of sq query
    rows, top to bottom. The kernels hold one straight-line body a tile that
    occurs here, and no other."""
    nc, bands = bk // sk, bq // sq
    if not causal:
        return {((nc, 0),) * bands: (lq // bq) * (lk // bk)}
    tiles = collections.Counter()
    for iq in range(lq // bq):
        for ik in range(lk // bk):
            rel = _first_gap(iq, ik, bq, bk, q_offset, kv_offset)
            tiles[tuple(_band_kind(rel + a * sq, sq, sk, nc)
                        for a in range(bands))] += 1
    return dict(tiles)


def _rows(i, n):
    """Rows [i * n, (i + 1) * n) of a ref, ``i`` static or traced."""
    import jax.experimental.pallas as pl

    if isinstance(i, int):
        return pl.ds(i * n, n)
    return pl.ds(pl.multiple_of(i * n, n), n)


def _walk(band, tiles, rel, bq, bk, sq, sk, causal):
    """``band(a, n, m, gap)`` on every live band of one grid tile. ``rel`` is
    the tile's first query position minus its first key position; ``gap`` is
    the band's own (key j of row i is live iff j - i <= gap). Shapes are
    static, so every tile of ``tiles`` is a body of its own, its bands one
    after the other with no branch between them (the scheduler may then run
    one band's products under another's vector passes), and the bands' kinds,
    from scalars, pick the body."""
    import jax.experimental.pallas as pl

    nc, bands = bk // sk, range(bq // sq)
    if not causal:
        for a in bands:
            band(a, nc, 0, None)
        return
    gaps = [rel + a * sq for a in bands]
    now = [_band_kind(gap, sq, sk, nc) for gap in gaps]

    def run(tile):
        for a, (n, m) in enumerate(tile):
            if n:
                band(a, n, m, gaps[a])

    for tile in sorted(t for t in tiles if any(n for n, _ in t)):
        hit = functools.reduce(jnp.logical_and, [
            jnp.logical_and(now[a][0] == n, now[a][1] == m)
            for a, (n, m) in enumerate(tile)])
        pl.when(hit)(functools.partial(run, tile))


def _where_live(x, m, sk, gap, masked):
    """``x`` (a band's scores or probabilities) with ``masked`` where the key
    is later than the row, in the last ``m`` chunks; the chunks before them
    are below the diagonal and are not touched."""
    if not m:
        return x
    sq, w = x.shape
    t = w - m * sk
    live = (jax.lax.broadcasted_iota(jnp.int32, (sq, m * sk), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (sq, m * sk), 0)) <= gap - t
    tail = jnp.where(live, x[:, t:], masked)
    return tail if t == 0 else jnp.concatenate([x[:, :t], tail], axis=1)


def _lane_sums(p):
    """(sq, w) -> (sq, _LANES) whose lanes add up to the rows' sums: whole
    registers added, the one reduction across lanes left to the finalize (a
    lane reduction costs a row as much as eight score columns)."""
    w = p.shape[1]
    if w % _LANES:
        return jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True) * (1.0 / _LANES),
            (p.shape[0], _LANES))
    lanes = p[:, :_LANES]
    for i in range(1, w // _LANES):
        lanes = lanes + p[:, i * _LANES:(i + 1) * _LANES]
    return lanes


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, tiles,
                   bq, bk, sq, sk, nk, scale, causal, q_offset, kv_offset):
    import jax.experimental.pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a row with no live key at all (its m stays NEG_INF, so exp(s - m) of a
    # masked score would be 1) exists only when the keys start after the
    # queries; the offsets are static, so no cell pays for the guard
    dead_rows = causal and kv_offset > q_offset

    def band(a, n, m, gap):
        rows, w = _rows(a, sq), n * sk
        # inputs stay in their storage dtype (bf16 at real scales): the MXU
        # takes bf16 x bf16 -> fp32 natively; upcasting first would force
        # the ~4x-slower fp32 matmul path
        s = _dot(q_ref[0, rows, :], k_ref[0, :w, :], (1, 1))    # (sq, w) f32
        s = _where_live(s, m, sk, gap, NEG_INF)
        m_prev = m_ref[rows, :]                 # (sq, LANES), lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp((m_prev - m_new) * scale)
        p = jnp.exp((s - m_new[:, :1]) * scale)
        if dead_rows:
            p = _where_live(p, m, sk, gap, 0.0)
        l_ref[rows, :] = l_ref[rows, :] * alpha + _lane_sums(p)
        acc_ref[rows, :] = (acc_ref[rows, :] * alpha[:, :1]
                            + _dot(p.astype(v_ref.dtype), v_ref[0, :w, :],
                                   (1, 0)))
        m_ref[rows, :] = m_new

    _walk(band, tiles, _first_gap(iq, ik, bq, bk, q_offset, kv_offset),
          bq, bk, sq, sk, causal)

    # finalize ONCE, at this q tile's last live kv step (nk-1 when not
    # causal or when the diagonal lies beyond the kv range)
    last_live = (_last_live_kv(iq, bq, bk, nk, q_offset, kv_offset)
                 if causal else nk - 1)

    @pl.when(ik == last_live)
    def _finalize():
        l_cur = jnp.maximum(jnp.sum(l_ref[...], axis=-1, keepdims=True),
                            1e-30)
        o_ref[0] = (acc_ref[...] / l_cur).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_ref[..., :1] * scale + jnp.log(l_cur), (bq, _STAT_LANES))


def _fa_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, tiles,
                   bq, bk, sq, sk, nq, nk, scale, causal, q_offset,
                   kv_offset):
    import jax.experimental.pallas as pl

    ik, iq = pl.program_id(1), pl.program_id(2)   # q tiles INNERMOST

    @pl.when(jnp.logical_and(ik == 0, iq == 0))
    def _init_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def band(a, n, m, gap):
        rows, w = _rows(a, sq), n * sk
        head_rows = _rows(iq * (bq // sq) + a, sq)
        q, g = q_ref[0, rows, :], g_ref[0, rows, :]
        k, v = k_ref[0, :w, :], v_ref[0, :w, :]
        s = _dot(q, k, (1, 1))                               # (sq, w) f32
        # masked on p, not on s: a dead row's lse is about NEG_INF * scale,
        # and exp(masked score - that) would be 1
        p = _where_live(jnp.exp(s * scale - lse_ref[0, rows, :][:, :1]),
                        m, sk, gap, 0.0)
        ds = p * (_dot(g, v, (1, 1)) - delta_ref[0, rows, :][:, :1])
        dv_acc[:w, :] += _dot(p.astype(g.dtype), g, (0, 0))       # (w, D)
        ds = ds.astype(q.dtype)
        dk_acc[:w, :] += _dot(ds, q, (0, 0))
        dq_acc[head_rows, :] += _dot(ds, k, (1, 0))               # (sq, D)

    _walk(band, tiles, _first_gap(iq, ik, bq, bk, q_offset, kv_offset),
          bq, bk, sq, sk, causal)

    # every kv tile's LAST live q tile is the final one (later rows see all
    # earlier keys), so dk/dv are complete exactly at iq == nq - 1
    @pl.when(iq == nq - 1)
    def _finalize_kv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    # and a q tile's dq is complete once the last kv tile has met it
    @pl.when(ik == nk - 1)
    def _finalize_q():
        dq_ref[0] = (dq_acc[_rows(iq, bq), :] * scale).astype(dq_ref.dtype)


def _fold(x):
    """(B, L, H, D) -> (B*H, L, D)."""
    b, l, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, l, d)


def _blocks(lq, lk, block_q, block_k):
    """Largest usable block sizes <= the requested ones: when the requested
    block doesn't divide the sequence, shrink to gcd so every length that is
    a multiple of a small power of two still works (e.g. lq=768 with
    block_q=512 -> 256)."""
    def fit(block, length):
        b = min(block, length)
        if length % b:
            b = math.gcd(b, length)
        if b < 8 and b != length:  # Mosaic sublane minimum
            raise ValueError(
                f"sequence length {length} has no usable block <= {block} "
                "(needs a divisor that is a multiple of 8)")
        return b
    return fit(block_q, lq), fit(block_k, lk)


def _plan(sub, lq, lk, block_q, block_k, causal, q_offset, kv_offset):
    """Tile, bands and schedule of a call, from its shapes alone."""
    bq, bk = _blocks(lq, lk, block_q, block_k)
    sq, sk = _sub_block(bq, sub), _sub_block(bk, sub)
    return dict(bq=bq, bk=bk, sq=sq, sk=sk, tiles=_schedule(
        lq, lk, bq, bk, sq, sk, causal, q_offset, kv_offset))


def flash_work(lq, lk, d, block_q=1024, block_k=1024, causal=True,
               q_offset=0, kv_offset=0):
    """What the kernels' static schedules execute for one (batch, head), by
    direction: the (sq, sk) sub-block pairs skipped, run unmasked and run
    masked, and the matrix FLOPs of the pairs that run (two products a pair
    forward, five backward, 2 * sq * sk * d each). Read from ``_schedule``,
    which the kernels are built from; they have no other schedule."""
    work = {}
    for direction, products in (("forward", 2), ("backward", 5)):
        plan = _plan(_SUB[direction], lq, lk, block_q, block_k, causal,
                     q_offset, kv_offset)
        sq, sk = plan["sq"], plan["sk"]
        bands = [(kind, c) for tile, c in plan["tiles"].items()
                 for kind in tile]
        unmasked = sum(c * (n - m) for (n, m), c in bands)
        masked = sum(c * m for (n, m), c in bands)
        work[direction] = {
            "sub_block": (sq, sk), "unmasked": unmasked, "masked": masked,
            "skipped": (lq // sq) * (lk // sk) - unmasked - masked,
            "flops": products * 2.0 * sq * sk * d * (unmasked + masked)}
    return work


def _fa_forward(q, k, v, causal, q_offset, kv_offset, block_q, block_k,
                interpret):
    return _fa_forward_call(q, k, v, causal, q_offset, kv_offset, block_q,
                            block_k, interpret, _SUB["forward"])


# jitted, like _fa_backward_call: a model calls attention once a layer with
# the same shapes, and a jitted callee is traced and lowered once a program,
# not once a layer (the kernels' bodies are most of a step program's tracing)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fa_forward_call(q, k, v, causal, q_offset, kv_offset, block_q, block_k,
                     interpret, sub):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, h, d = q.shape
    lk = k.shape[1]
    plan = _plan(sub, lq, lk, block_q, block_k, causal, q_offset, kv_offset)
    bq, bk = plan["bq"], plan["bk"]
    nk = lk // bk
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    grid = (b * h, lq // bq, nk)                # kv INNERMOST: scratch carries

    def kv_tile(bh, iq, ik):                    # a skipped tile: no new DMA
        if causal:
            ik = jax.lax.min(ik, _last_live_kv(iq, bq, bk, nk, q_offset,
                                               kv_offset))
        return bh, ik, 0

    forward = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, **plan, nk=nk,
                          scale=1.0 / math.sqrt(d), causal=causal,
                          q_offset=q_offset, kv_offset=kv_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_tile),
            pl.BlockSpec((1, bk, d), kv_tile),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _STAT_LANES),
                         lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, v.dtype),
            jax.ShapeDtypeStruct((b * h, lq, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),        # acc
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max (raw)
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running sum, by lane
        ],
        interpret=interpret,
    )
    # the kernels' name in a trace, whatever the backend calls them
    with jax.named_scope("flash_attention"):
        out, lse = forward(qf, kf, vf)
    return jnp.swapaxes(out.reshape(b, h, lq, d), 1, 2), lse


@functools.partial(jax.jit, static_argnames=(
    "causal", "q_offset", "kv_offset", "block_q", "block_k", "kv_dtype",
    "interpret", "sub"))
def _fa_backward_call(qf, kf, vf, gf, lse, delta, causal, q_offset,
                      kv_offset, block_q, block_k, kv_dtype, interpret, sub):
    """dq, dk, dv of folded (B*H, L, D) operands in one fused call; dk/dv
    come out as ``kv_dtype``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, lq, d), lk = qf.shape, kf.shape[1]
    plan = _plan(sub, lq, lk, block_q, block_k, causal, q_offset, kv_offset)
    bq, bk = plan["bq"], plan["bk"]
    nq, nk = lq // bq, lk // bk

    def q_tile(bh, ik, iq):                     # a skipped tile: no new DMA
        if causal:
            iq = jax.lax.max(iq, _first_live_q(ik, bq, bk, nq, q_offset,
                                               kv_offset))
        return bh, iq, 0

    q_spec = pl.BlockSpec((1, bq, d), q_tile)
    row_spec = pl.BlockSpec((1, bq, _STAT_LANES), q_tile)
    k_spec = pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0))
    # dq's tiles leave during the last kv tile's steps; until then the
    # output's block index stands still, so nothing is written back
    dq_spec = pl.BlockSpec(
        (1, bq, d), lambda bh, ik, iq: (bh, jnp.where(ik == nk - 1, iq, 0), 0))

    backward = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, **plan, nq=nq, nk=nk,
                          scale=1.0 / math.sqrt(d), causal=causal,
                          q_offset=q_offset, kv_offset=kv_offset),
        grid=(bh, nk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[dq_spec, k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(qf.shape, qf.dtype),
                   jax.ShapeDtypeStruct(kf.shape, kv_dtype),
                   jax.ShapeDtypeStruct(vf.shape, kv_dtype)],
        scratch_shapes=[pltpu.VMEM((lq, d), jnp.float32),    # dq, whole head
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_DQ_RESIDENT_BYTES + _BWD_TILE_BYTES),
        interpret=interpret,
    )
    with jax.named_scope("flash_attention"):
        return backward(qf, kf, vf, gf, lse, delta)


def _fa_backward(q, k, v, out, lse, g, causal, q_offset, kv_offset,
                 block_q, block_k, interpret):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    # delta_i = sum_d o_i * do_i — the softmax-jacobian row term; a single
    # fused elementwise+reduce, no reason to put it in the kernel. Stored
    # in the same (B*H, Lq, STAT_LANES) layout as lse (Mosaic block tiling).
    delta = jnp.sum(_fold(out).astype(jnp.float32) * gf.astype(jnp.float32),
                    axis=-1)                              # (B*H, Lq)
    delta = jnp.broadcast_to(delta[..., None],
                             (*delta.shape, _STAT_LANES))
    call = functools.partial(_fa_backward_call, causal=causal,
                             kv_offset=kv_offset, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             sub=_SUB["backward"])

    # the rows of q whose float32 dq fits the resident accumulator
    bq, _ = _blocks(lq, lk, block_q, block_k)
    rows = max(_DQ_RESIDENT_BYTES // (4 * max(d, _LANES)) // bq, 1) * bq
    if lq <= rows:
        dq, dk, dv = call(qf, kf, vf, gf, lse, delta, q_offset=q_offset,
                          kv_dtype=k.dtype)
    else:
        # a longer lq: q chunks, each a fused call at its own offset; their
        # dk/dv partial sums stay float32 until they are added
        parts = [call(qf[:, r:r + rows], kf, vf, gf[:, r:r + rows],
                      lse[:, r:r + rows], delta[:, r:r + rows],
                      q_offset=q_offset + r, kv_dtype=jnp.float32)
                 for r in range(0, lq, rows)]
        dq = jnp.concatenate([p[0] for p in parts], axis=1)
        dk = sum(p[1] for p in parts).astype(k.dtype)
        dv = sum(p[2] for p in parts).astype(v.dtype)

    unfold = lambda x, l: jnp.swapaxes(x.reshape(b, h, l, d), 1, 2)
    return unfold(dq, lq), unfold(dk, lk), unfold(dv, lk)


# ---------------------------------------------------------------------------
# int8-KV flash attention (pre-quantized keys/values, decode-path variant)
# ---------------------------------------------------------------------------
#
# The decode tick is KV-bandwidth-bound once contexts grow: every generated
# token re-reads the whole cache. Storing K/V as int8 with one fp32 scale
# per (batch, position, head) row halves that HBM traffic; this kernel
# consumes the quantized layout DIRECTLY — the dequant multiply happens on
# the (bk, D) VMEM tile inside the kernel, so the fp16/fp32 K/V never exist
# in HBM at all. Forward-only by design (decode never differentiates);
# training keeps the fp kernels above.

def quantize_kv(k, v):
    """Per-row symmetric int8 quantization of a KV pair in model layout.

    ``k``/``v`` are (B, L, H, D); returns ``(k_q, k_scale, v_q, v_scale)``
    with int8 values and one fp32 scale per (b, l, h) row (amax over D) —
    the layout :func:`int8kv_flash_attention_fn` consumes, and the HBM
    format an int8 KV cache would hold. Rows are quantized by
    ``ops.quant.quantize_int8`` itself (not a copy of its math), so the
    round/clip/EPS convention can never drift from the training path's."""
    from tpu_dist.ops.quant import quantize_int8

    def one(x):
        q, scale = quantize_int8(x, (-1,))
        return q, scale[..., 0].astype(jnp.float32)
    kq, ks = one(k)
    vq, vs = one(v)
    return kq, ks, vq, vs


def _fa_fwd_int8kv_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                          acc_ref, m_ref, l_ref, *,
                          bq, bk, nk, scale, causal, q_offset, kv_offset):
    import jax.experimental.pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    q_start = q_offset + iq * bq
    k_start = kv_offset + ik * bk

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    skip, needs_mask = _causal_bounds(causal, q_start, k_start, bq, bk)

    @pl.when(jnp.logical_not(skip))
    def _step():
        # dequant on the VMEM tile: int8 rows x per-row fp32 scale — the
        # only fp copy of this KV block that ever exists
        kf = k_ref[0].astype(jnp.float32) * ks_ref[0][:, :1]     # (bk, D)
        vf = v_ref[0].astype(jnp.float32) * vs_ref[0][:, :1]
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(jnp.logical_or(jnp.logical_not(needs_mask),
                                         kpos <= qpos), s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, :1]))
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jax.lax.dot_general(
                            p, vf, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        last_live = jnp.clip((q_start + bq - 1 - kv_offset) // bk, 0, nk - 1)
    else:
        last_live = nk - 1

    @pl.when(ik == last_live)
    def _finalize():
        l_cur = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l_cur).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def int8kv_flash_attention_fn(block_q: int = 1024, block_k: int | None = None,
                              interpret: bool | None = None):
    """Returns ``attn(q, kv, causal=True, q_offset=0, kv_offset=0)`` over a
    PRE-QUANTIZED KV pack ``kv = quantize_kv(k, v)`` (int8 values + per-row
    fp32 scales): the decode-path flash variant — K/V stay int8 in HBM,
    halving the cache traffic the autoregressive tick is bound by, and the
    dequant happens per VMEM tile inside the kernel. Forward-only (decode
    never differentiates; the bwd kernels above serve training).
    ``interpret=None`` auto-selects interpreter mode off-TPU."""
    if block_k is None:
        block_k = 1024

    def attn(q, kv, *, causal: bool = True, q_offset=0, kv_offset=0):
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        kq, ks, vq, vs = kv
        use_interpret = pallas_interpret(interpret)
        b, lq, h, d = q.shape
        lk = kq.shape[1]
        bq, bk = _blocks(lq, lk, block_q, block_k)
        qf = _fold(q)
        kf, vf = _fold(kq), _fold(vq)                # (B*H, L, D) int8
        # scales to the lse/delta stat layout: (B*H, L, _STAT_LANES)
        def fold_scale(s):
            s2 = jnp.swapaxes(s, 1, 2).reshape(b * h, lk)
            return jnp.broadcast_to(s2[..., None], (b * h, lk, _STAT_LANES))
        ksf, vsf = fold_scale(ks), fold_scale(vs)
        scale = 1.0 / math.sqrt(d)
        grid = (b * h, lq // bq, lk // bk)

        out = pl.pallas_call(
            functools.partial(_fa_fwd_int8kv_kernel, bq=bq, bk=bk,
                              nk=lk // bk, scale=scale, causal=causal,
                              q_offset=q_offset, kv_offset=kv_offset),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, _STAT_LANES),
                             lambda bh, iq, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, _STAT_LANES),
                             lambda bh, iq, ik: (bh, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),        # acc
                pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
                pltpu.VMEM((bq, _LANES), jnp.float32),   # running sum
            ],
            interpret=use_interpret,
        )(qf, kf, vf, ksf, vsf)
        return jnp.swapaxes(out.reshape(b, h, lq, d), 1, 2)

    return attn


@functools.lru_cache(maxsize=None)
def flash_attention_fn(block_q: int = 1024, block_k: int | None = None,
                       interpret: bool | None = None,
                       recompute_block: int | None = None,
                       mesh=None, spec=None):
    """Returns attn(q, k, v, causal=True, q_offset=0, kv_offset=0) backed by
    the Pallas FlashAttention-2 kernels (forward AND one fused backward — it
    recomputes scores from the stashed logsumexp, it does not re-run a full
    blockwise forward).

    ``mesh`` + ``spec`` (a PartitionSpec over the (B, L, H, D) operands) are
    for a model traced under a compiler-partitioned (GSPMD) step on more
    than one device: the TPU compiler refuses to partition a Mosaic kernel
    ("cannot be automatically partitioned"), so the returned attn runs the
    kernel per shard inside ``shard_map`` — attention is independent across
    batch rows and heads, so sharding those two axes needs no collective.
    Leave both unset on one device or inside an already-manual program.

    ``block_q``/``block_k`` are the GRID's tile: upper bounds on what one
    grid step holds in VMEM, clamped to the sequence lengths (and shrunk to
    a gcd where they do not divide them, ``_blocks``). They are not the
    granularity of the causal mask: inside a tile the kernels walk
    sub-blocks (``_SUB``: 512 wide forward, 256 backward, where the tile
    allows) and skip, run bare or mask each by where the diagonal lies.
    1024 x 1024 tiles keep the grid's steps few (each step costs about
    0.35 us and a K/V fetch); the sub-block widths come from chip timings
    at B4/L2048/H16/D128 (beside ``_SUB``). The backward keeps float32 dQ
    for a whole head in VMEM
    and cuts a longer lq into chunks by the operands' shapes alone
    (``_fa_backward``).

    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    code runs in the CPU test mesh. ``recompute_block`` is a legacy alias
    for ``block_k`` (the round-2 kernel's recompute granularity); passing
    both is an error rather than a silent override (ADVICE r3).
    """
    if recompute_block is not None:
        if block_k is not None:
            raise ValueError("pass block_k or its legacy alias "
                             "recompute_block, not both")
        block_k = recompute_block
    if block_k is None:
        block_k = 1024

    pick_interpret = functools.partial(pallas_interpret, interpret)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
    def attn_core(q, k, v, causal, q_offset, kv_offset):
        out, _ = _fa_forward(q, k, v, causal, q_offset, kv_offset,
                             block_q, block_k, pick_interpret())
        return out

    def fwd(q, k, v, causal, q_offset, kv_offset):
        out, lse = _fa_forward(q, k, v, causal, q_offset, kv_offset,
                               block_q, block_k, pick_interpret())
        return out, (q, k, v, out, lse)

    def bwd(causal, q_offset, kv_offset, res, g):
        q, k, v, out, lse = res
        return _fa_backward(q, k, v, out, lse, g, causal,
                            q_offset, kv_offset, block_q, block_k,
                            pick_interpret())

    attn_core.defvjp(fwd, bwd)

    def attn(q, k, v, *, causal: bool = True, q_offset=0, kv_offset=0):
        return attn_core(q, k, v, causal, q_offset, kv_offset)

    if mesh is None or mesh.devices.size == 1:
        return attn

    from tpu_dist._compat import shard_map

    def attn_per_shard(q, k, v, *, causal: bool = True, q_offset=0,
                       kv_offset=0):
        return shard_map(
            functools.partial(attn, causal=causal, q_offset=q_offset,
                              kv_offset=kv_offset),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return attn_per_shard
