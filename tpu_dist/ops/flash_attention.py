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
  (batch*head, q_blocks, kv_blocks) with VMEM scratch accumulators carried
  across the innermost KV dimension (scores never touch HBM; O(bq*bk)
  working set at ANY sequence length), causal above-diagonal blocks skipped,
  fp32 online math, per-row logsumexp written out. Backward is two Pallas
  kernels (dq; dk+dv) that re-derive probabilities from the stashed
  logsumexp — score recompute only, not a second full forward.

Both are numerically validated against full attention (tests/test_flash.py)
and compose with the causal offsets ring attention uses.
"""

from __future__ import annotations

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


# ---------------------------------------------------------------------------
# Pallas flash attention (FlashAttention-2 schedule, forward + backward)
# ---------------------------------------------------------------------------
#
# Forward: grid (B*H, q_blocks, kv_blocks) with the KV dimension INNERMOST,
# so the VMEM scratch accumulators (acc, running max m, running sum l) carry
# across KV steps of one q block — peak memory is O(bq * bk) regardless of
# sequence length (no whole-K/V fetch, unlike the round-2 kernel). Causal
# blocks strictly above the diagonal are skipped (pl.when), saving ~half the
# FLOPs. The (bq,) logsumexp per row is written out for the backward.
#
# Backward: two Pallas kernels re-derive p = exp(s - lse) from the stashed
# statistics (FLASH-style recompute of SCORES only, never a second full
# forward): dq accumulates over KV blocks; dk/dv accumulate over q blocks.
# delta = rowsum(o * dout) is a cheap fused elementwise pass outside Pallas.

_LANES = 128      # TPU vector lane count: scratch row-stats are (bq, _LANES)
_STAT_LANES = 8   # lse/delta HBM layout: (B*H, L, 8) — Mosaic block tiling
                  # wants the last dim either 128-divisible or equal to the
                  # array's, so an 8-wide stat lane keeps blocks legal while
                  # costing 8 (not 128) floats per row


def _causal_bounds(causal, q_start, k_start, bq, bk):
    """(skip_block, needs_mask) for one (q block, kv block) pair."""
    if not causal:
        return False, False
    skip = k_start > q_start + bq - 1          # entirely above the diagonal
    needs_mask = k_start + bk - 1 > q_start    # straddles the diagonal
    return skip, needs_mask


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
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
        # inputs stay in their storage dtype (bf16 at real scales): the MXU
        # takes bf16 x bf16 -> fp32 natively; upcasting first would force
        # the ~4x-slower fp32 matmul path
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk) f32
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos <= qpos
            s = jnp.where(jnp.logical_or(jnp.logical_not(needs_mask), mask),
                          s, NEG_INF)
        m_prev = m_ref[...]                     # (bq, LANES), lanes equal
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked scores contribute ZERO even when the whole row is masked
        # (m_new == NEG_INF would make exp(s - m_new) = 1 otherwise)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, :1]))
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new
        l_ref[...] = l_new

    # finalize ONCE, at this q block's last live KV step (computable from the
    # causal geometry; nk-1 when not causal or when the diagonal lies beyond
    # the kv range) — not a per-step write-through
    if causal:
        last_live = jnp.clip((q_start + bq - 1 - kv_offset) // bk, 0, nk - 1)
    else:
        last_live = nk - 1

    @pl.when(ik == last_live)
    def _finalize():
        l_cur = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l_cur).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_ref[..., :1] + jnp.log(l_cur),
                                      (bq, _STAT_LANES))


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *,
                      bq, bk, nk, scale, causal, q_offset, kv_offset):
    import jax.experimental.pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    q_start = q_offset + iq * bq
    k_start = kv_offset + ik * bk

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    skip, needs_mask = _causal_bounds(causal, q_start, k_start, bq, bk)

    @pl.when(jnp.logical_not(skip))
    def _step():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(jnp.logical_or(jnp.logical_not(needs_mask),
                                         kpos <= qpos), s, NEG_INF)
        lse = lse_ref[0][:, :1]                 # (bq, 1)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, bk)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_live = jnp.clip((q_start + bq - 1 - kv_offset) // bk, 0, nk - 1)
    else:
        last_live = nk - 1

    @pl.when(ik == last_live)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *,
                       bq, bk, nq, scale, causal, q_offset, kv_offset):
    import jax.experimental.pallas as pl

    ik, iq = pl.program_id(1), pl.program_id(2)   # q blocks INNERMOST here
    q_start = q_offset + iq * bq
    k_start = kv_offset + ik * bk

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    skip, needs_mask = _causal_bounds(causal, q_start, k_start, bq, bk)

    @pl.when(jnp.logical_not(skip))
    def _step():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(jnp.logical_or(jnp.logical_not(needs_mask),
                                         kpos <= qpos), s, NEG_INF)
        lse = lse_ref[0][:, :1]
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g_ref.dtype), g_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bk, D)
        dp = jax.lax.dot_general(
            g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale         # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # every causal kv block's LAST live q block is the final one (later q
    # rows attend to all earlier kv), so finalize exactly once at iq == nq-1
    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


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


def _fa_forward(q, k, v, causal, q_offset, kv_offset, block_q, block_k,
                interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = _blocks(lq, lk, block_q, block_k)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    scale = 1.0 / math.sqrt(d)
    grid = (b * h, lq // bq, lk // bk)          # kv INNERMOST: scratch carries

    forward = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, bq=bq, bk=bk, nk=lk // bk,
                          scale=scale, causal=causal,
                          q_offset=q_offset, kv_offset=kv_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
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
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running sum
        ],
        interpret=interpret,
    )
    # the kernels' name in a trace, whatever the backend calls them
    with jax.named_scope("flash_attention"):
        out, lse = forward(qf, kf, vf)
    return jnp.swapaxes(out.reshape(b, h, lq, d), 1, 2), lse


def _fa_backward(q, k, v, out, lse, g, causal, q_offset, kv_offset,
                 block_q, block_k, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = _blocks(lq, lk, block_q, block_k)
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    # delta_i = sum_d o_i * do_i — the softmax-jacobian row term; a single
    # fused elementwise+reduce, no reason to put it in the kernel. Stored
    # in the same (B*H, Lq, STAT_LANES) layout as lse (Mosaic block tiling).
    delta = jnp.sum(_fold(out).astype(jnp.float32) * gf.astype(jnp.float32),
                    axis=-1)                              # (B*H, Lq)
    delta = jnp.broadcast_to(delta[..., None],
                             (*delta.shape, _STAT_LANES))

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0))
    row_spec = pl.BlockSpec((1, bq, _STAT_LANES),
                            lambda bh, iq, ik: (bh, iq, 0))

    backward_dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, bq=bq, bk=bk, nk=lk // bk,
                          scale=scale, causal=causal,
                          q_offset=q_offset, kv_offset=kv_offset),
        grid=(b * h, lq // bq, lk // bk),       # kv innermost: dq carries
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )

    # second pass: kv block fixed, q blocks innermost (dk/dv carry)
    q_spec2 = pl.BlockSpec((1, bq, d), lambda bh, ik, iq: (bh, iq, 0))
    k_spec2 = pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0))
    row_spec2 = pl.BlockSpec((1, bq, _STAT_LANES),
                             lambda bh, ik, iq: (bh, iq, 0))
    backward_dkv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, bq=bq, bk=bk, nq=lq // bq,
                          scale=scale, causal=causal,
                          q_offset=q_offset, kv_offset=kv_offset),
        grid=(b * h, lk // bk, lq // bq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope("flash_attention"):
        dq = backward_dq(qf, kf, vf, gf, lse, delta)
        dk, dv = backward_dkv(qf, kf, vf, gf, lse, delta)

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
    the Pallas FlashAttention-2 kernels (forward AND backward — the backward
    recomputes scores from the stashed logsumexp, it does not re-run a full
    blockwise forward).

    ``mesh`` + ``spec`` (a PartitionSpec over the (B, L, H, D) operands) are
    for a model traced under a compiler-partitioned (GSPMD) step on more
    than one device: the TPU compiler refuses to partition a Mosaic kernel
    ("cannot be automatically partitioned"), so the returned attn runs the
    kernel per shard inside ``shard_map`` — attention is independent across
    batch rows and heads, so sharding those two axes needs no collective.
    Leave both unset on one device or inside an already-manual program.

    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    code runs in the CPU test mesh. ``recompute_block`` is a legacy alias
    for ``block_k`` (the round-2 kernel's recompute granularity); passing
    both is an error rather than a silent override (ADVICE r3). ``block_k``
    defaults to 1024 — a round-4 on-chip sweep at B8/L2048/H16/D64 measured
    1024x1024 ~20% faster fwd+bwd than the round-3 512x512 default (blocks
    clamp to the sequence length, so short sequences are unaffected).
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
