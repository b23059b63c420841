"""Blockwise + Pallas-flash attention == full attention (values AND grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.models.transformer import full_attention, tiny_lm
from tpu_dist.ops.flash_attention import (blockwise_attention_fn,
                                          flash_attention_fn)

B, L, H, D = 2, 128, 4, 32


def _qkv(seed=0, l=L):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (B, l, H, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("blk", [32, 64, 128])
def test_blockwise_matches_full(blk):
    q, k, v = _qkv()
    ref = full_attention(q, k, v)
    out = blockwise_attention_fn(blk)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_grads_match_full():
    q, k, v = _qkv(1)

    def loss(fn, *args):
        return jnp.sum(fn(*args) ** 2)

    g_ref = jax.grad(lambda q_, k_, v_: loss(full_attention, q_, k_, v_),
                     argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(
        lambda q_, k_, v_: loss(blockwise_attention_fn(32), q_, k_, v_),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


def test_flash_forward_matches_full():
    q, k, v = _qkv(2)
    ref = full_attention(q, k, v)
    out = flash_attention_fn(block_q=64)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grads_match_full():
    q, k, v = _qkv(3)

    def loss(fn, *args):
        return jnp.sum(fn(*args) ** 2)

    g_ref = jax.grad(lambda q_, k_, v_: loss(full_attention, q_, k_, v_),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(
        lambda q_, k_, v_: loss(flash_attention_fn(block_q=64), q_, k_, v_),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


def test_offsets_respected():
    """Shifted positions mask exactly like full attention's offsets."""
    q, k, v = _qkv(4, l=64)
    ref = full_attention(q, k, v, q_offset=64, kv_offset=0)
    blk = blockwise_attention_fn(32)(q, k, v, q_offset=64, kv_offset=0)
    fl = flash_attention_fn(block_q=32)(q, k, v, q_offset=64, kv_offset=0)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_zero():
    """kv_offset > q_offset makes EVERY key future for the early queries:
    those rows must output zeros (not the unmasked mean of V, which the
    online softmax produces when masked probabilities aren't zeroed)."""
    q, k, v = _qkv(6, l=64)
    # kv block starts 64 positions AFTER the queries -> all rows fully masked
    blk = blockwise_attention_fn(32)(q, k, v, q_offset=0, kv_offset=64)
    fl = flash_attention_fn(block_q=32)(q, k, v, q_offset=0, kv_offset=64)
    np.testing.assert_allclose(np.asarray(blk), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fl), 0.0, atol=1e-6)
    # partial masking: kv_offset = q_offset + 32 -> first 32 rows masked
    blk2 = blockwise_attention_fn(32)(q, k, v, q_offset=0, kv_offset=32)
    ref = full_attention(q, k, v, q_offset=0, kv_offset=32)
    ref = jnp.nan_to_num(ref)  # full attention NaNs on all-masked rows
    np.testing.assert_allclose(np.asarray(blk2[:, :32]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(blk2[:, 32:]),
                               np.asarray(ref[:, 32:]), rtol=2e-5, atol=2e-5)


def _masked_attention(q, k, v, *, causal=True, q_offset=0, kv_offset=0):
    """full_attention in float32 whose rows with no live key give zeros (and
    zero gradients) where full_attention gives NaN."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if not causal:
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    live = (kv_offset + jnp.arange(k.shape[1])[None, :]
            <= q_offset + jnp.arange(q.shape[1])[:, None])
    w = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.where(live, w, 0.0), v)


# every path of the kernels' block program. ``sub`` is the sub-block width
# (``_SUB``, 512 and 256 on the chip) brought down so that a grid tile of 256
# holds 2 x 2 sub-blocks at lengths the interpreter walks in seconds.
#         lq   lk  d   dtype     block_q block_k sub  kwargs
WALKS = {
    # 2 x 2 tiles of 2 x 2 sub-blocks: interior, diagonal and skipped
    # sub-blocks inside one tile, and all three kinds of tile
    "tiles_of_sub_blocks_f32_d64":
        (512, 512, 64, jnp.float32, 256, 256, 128, {}),
    "tiles_of_sub_blocks_bf16_d128":
        (512, 512, 128, jnp.bfloat16, 256, 256, 128, {}),
    # one tile, 4 x 4 sub-blocks: the whole walk inside one grid step
    "one_tile_f32_d128": (512, 512, 128, jnp.float32, 512, 512, 128, {}),
    # the cells' own geometry: 1024 tiles, 512 sub-blocks, L 2048, D 128
    "cell_geometry_bf16_d128":
        (2048, 2048, 128, jnp.bfloat16, 1024, 1024, 512, {}),
    # a decode-like suffix: the queries are the last 128 of 384 positions
    "suffix_queries_f32_d64":
        (128, 384, 64, jnp.float32, 128, 128, 128, dict(q_offset=256)),
    # offsets that put the diagonal inside sub-blocks, not on their corners
    "unaligned_offsets_bf16_d64":
        (256, 512, 64, jnp.bfloat16, 256, 256, 128,
         dict(q_offset=200, kv_offset=8)),
    # keys that start after the queries: rows with no live key, dead tiles
    "dead_rows_f32_d64":
        (256, 256, 64, jnp.float32, 128, 128, 128,
         dict(q_offset=0, kv_offset=160)),
    # 768 does not divide by 512: the tile shrinks to the gcd, 256
    "gcd_shrunk_768_f32_d64": (768, 768, 64, jnp.float32, 512, 512, 128, {}),
    "gcd_shrunk_768_bf16_d128":
        (768, 768, 128, jnp.bfloat16, 512, 512, 128, {}),
    # shorter than one sub-block, and a tile that is no whole number of lanes
    "below_one_sub_block_f32_d64":
        (64, 64, 64, jnp.float32, 1024, 1024, 512, {}),
    "tile_of_96_f32_d64": (96, 96, 64, jnp.float32, 1024, 1024, 128, {}),
    # eight bands a tile: their kinds pick the body one by one (a single
    # code for the whole tile would pass 32 bits)
    "eight_bands_a_tile_f32_d64":
        (1024, 1024, 64, jnp.float32, 1024, 1024, 128, {}),
    "non_causal_f32_d64":
        (256, 512, 64, jnp.float32, 128, 256, 128, dict(causal=False)),
    "non_causal_bf16_d128":
        (512, 256, 128, jnp.bfloat16, 256, 256, 128, dict(causal=False)),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_flash_walk_matches_full(case, monkeypatch):
    """Values and all three gradients of the sub-block program against
    plain masked attention, on every path ``WALKS`` names."""
    import tpu_dist.ops.flash_attention as fa

    lq, lk, d, dtype, block_q, block_k, sub, kw = WALKS[case]
    monkeypatch.setattr(fa, "_SUB", dict(forward=sub, backward=sub))
    rng = np.random.default_rng(len(case))
    mk = lambda l: jnp.asarray(rng.normal(0, 1, (1, l, 2, d)), dtype)
    q, k, v = mk(lq), mk(lk), mk(lk)
    w = jnp.asarray(rng.normal(0, 1, (1, lq, 2, d)), jnp.float32)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a, **kw).astype(jnp.float32) * w)
    flash = flash_attention_fn(block_q=block_q, block_k=block_k)
    out, ref = flash(q, k, v, **kw), _masked_attention(q, k, v, **kw)
    g_out = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(_masked_attention), argnums=(0, 1, 2))(q, k, v)
    tol = (dict(rtol=2e-4, atol=5e-5) if dtype == jnp.float32
           else dict(rtol=3e-2, atol=3e-2))
    for a, b in zip((out, *g_out), (ref, *g_ref)):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_flash_backward_cuts_a_long_lq_into_resident_chunks(monkeypatch):
    """An lq whose float32 dQ does not fit the resident accumulator is cut
    into q chunks, each a fused call at its own offset: same gradients."""
    import tpu_dist.ops.flash_attention as fa

    q, k, v = _qkv(9, l=256)
    flash = flash_attention_fn(block_q=64, block_k=128)
    grads = lambda: jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                             argnums=(0, 1, 2))(q, k, v)
    whole = grads()
    calls = []
    real = fa._fa_backward_call
    monkeypatch.setattr(fa, "_fa_backward_call",
                        lambda *a, **kw: calls.append(kw["q_offset"])
                        or real(*a, **kw))
    monkeypatch.setattr(fa, "_DQ_RESIDENT_BYTES", 128 * 128 * 4)
    for a, b in zip(grads(), whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    assert calls == [0, 128]


def _count_by_mask(lq, lk, sq, sk, q_offset=0, kv_offset=0):
    """(skipped, unmasked, masked) sub-block pairs, from the mask itself."""
    live = (kv_offset + np.arange(lk)[None, :]
            <= q_offset + np.arange(lq)[:, None])
    tiles = live.reshape(lq // sq, sq, lk // sk, sk).swapaxes(1, 2)
    full, some = tiles.all((2, 3)), tiles.any((2, 3))
    return int((~some).sum()), int(full.sum()), int((some & ~full).sum())


@pytest.mark.parametrize("lq,lk,block,sub,kw", [
    (512, 512, 256, 128, {}),
    (256, 768, 256, 128, dict(q_offset=300, kv_offset=40)),
    (256, 256, 128, 128, dict(kv_offset=160)),
    (2048, 2048, 1024, 512, {}),
])
def test_flash_work_counts_the_walk(lq, lk, block, sub, kw, monkeypatch):
    import tpu_dist.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_SUB", dict(forward=sub, backward=2 * sub))
    work = fa.flash_work(lq, lk, 64, block, block, **kw)
    none = fa.flash_work(lq, lk, 64, block, block, causal=False)
    for direction, products in (("forward", 2), ("backward", 5)):
        got, s = work[direction], min(fa._SUB[direction], block)
        assert got["sub_block"] == (s, s)
        assert (got["skipped"], got["unmasked"], got["masked"]) == \
            _count_by_mask(lq, lk, s, s, **kw)
        ran = got["unmasked"] + got["masked"]
        assert got["flops"] == products * 2 * s * s * 64 * ran
        free = none[direction]
        assert (free["skipped"], free["masked"]) == (0, 0)


def test_flash_executes_at_most_half_again_the_counted_work():
    """At the LM training cell's shape the schedules execute no more than
    1.5 times the matrix work the benchmark's floor counts, forward and
    backward (the two-kernel program at 1024 x 1024: 1.5 and 2.625)."""
    import os
    import sys

    import tpu_dist.ops.flash_attention as fa

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.kernels import flash_attention as counted

    b, l, h, d = 4, 2048, 16, 128
    work = fa.flash_work(l, l, d, 1024, 1024)
    fwd, bwd = work["forward"], work["backward"]
    assert (fwd["skipped"], fwd["unmasked"], fwd["masked"]) == (6, 6, 4)
    assert (bwd["skipped"], bwd["unmasked"], bwd["masked"]) == (28, 28, 8)
    fwd = b * h * fwd["flops"] / counted.forward(b, l, h, d)["flops"]
    bwd = b * h * bwd["flops"] / counted.backward(b, l, h, d)["flops"]
    assert fwd == 1.25 and bwd == 1.40625
    assert (2 * fwd + 4 * bwd) / 6 <= 1.5


@pytest.mark.parametrize("fn_name", ["blockwise", "flash"])
def test_lm_forward_same_logits(fn_name):
    """The SAME TransformerLM weights produce the same logits under the
    memory-efficient attention flavors (the attn_fn plug-in contract)."""
    attn = (blockwise_attention_fn(32) if fn_name == "blockwise"
            else flash_attention_fn(block_q=32))
    kw = dict(vocab_size=64, num_layers=2, d_model=64, num_heads=4,
              max_len=L)
    lm_full = tiny_lm(**kw)
    lm_eff = tiny_lm(attn_fn=attn, **kw)
    params = lm_full.init({"params": jax.random.PRNGKey(0)},
                          jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2, L)), jnp.int32)
    ref = lm_full.apply({"params": params}, tokens, train=False)
    out = lm_eff.apply({"params": params}, tokens, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_block_k_alias_conflict_raises():
    """recompute_block is a legacy alias for block_k: passing both is an
    error, not a silent override (ADVICE r3)."""
    with pytest.raises(ValueError, match="not both"):
        flash_attention_fn(block_k=256, recompute_block=128)
    # the alias alone still works
    assert flash_attention_fn(recompute_block=128) is not None


def test_blockwise_non_divisible_length_fits_gcd():
    """Blockwise follows the flash _blocks fit rule: a kv length that is a
    multiple of 512 but not of the 1024 default shrinks to the gcd instead
    of raising (the round-4 attn_block default bump must not break it)."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 96, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 96, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 96, 2, 16)), jnp.float32)
    out = blockwise_attention_fn(64)(q, k, v)  # 96 % 64 != 0 -> gcd 32
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- int8-KV decode-path variant (round 9) --------------------------------

def test_int8kv_flash_matches_full_on_dequantized_kv():
    """The int8-KV kernel's only approximation is the KV quantization
    itself: against full attention over the DEQUANTIZED keys/values the
    outputs must agree to flash tolerance (the in-kernel per-tile dequant
    is exact), and against the fp KV the error stays at int8 scale."""
    from tpu_dist.ops.flash_attention import (int8kv_flash_attention_fn,
                                              quantize_kv)

    q, k, v = _qkv(7)
    kv = quantize_kv(k, v)
    kq, ks, vq, vs = kv
    assert kq.dtype == jnp.int8 and ks.shape == k.shape[:3]
    k_dq = kq.astype(jnp.float32) * ks[..., None]
    v_dq = vq.astype(jnp.float32) * vs[..., None]
    out = int8kv_flash_attention_fn(block_q=64, block_k=64)(q, kv)
    ref = full_attention(q, k_dq, v_dq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # int8 KV vs fp KV: bounded by the quantization step, not exact
    fp = full_attention(q, k, v)
    assert float(jnp.max(jnp.abs(out - fp))) < 0.15


def test_int8kv_flash_decode_offsets():
    """The decode shape: one new query block attending into a longer
    quantized cache via q_offset (causal against absolute positions)."""
    from tpu_dist.ops.flash_attention import (int8kv_flash_attention_fn,
                                              quantize_kv)

    q, k, v = _qkv(8)
    kv = quantize_kv(k, v)
    kq, ks, vq, vs = kv
    k_dq = kq.astype(jnp.float32) * ks[..., None]
    v_dq = vq.astype(jnp.float32) * vs[..., None]
    tail = q[:, 64:]                 # last 64 positions are the new block
    out = int8kv_flash_attention_fn(block_q=32, block_k=64)(
        tail, kv, q_offset=64)
    ref = full_attention(q, k_dq, v_dq)[:, 64:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
