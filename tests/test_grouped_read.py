"""The grouped in-place decode read of a rows-layout KV layer
(``ops.paged_attention.paged_grouped_decode_attention``, interpreted off the
TPU) against the gathered read of the same rows, over block-table pages and
over window rings; the ring's write; ``decode_read``'s rule for the layout;
and the banded window attention a window layer's prefill runs
(``ops.flash_attention.window_attention``) against a full masked softmax.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.ops import paged_attention as pa
from tpu_dist.ops.flash_attention import window_attention

KV, GROUP, D, PAGE = 2, 4, 128, 8
B, H = 3, KV * GROUP


def _arenas(rng, pages, dtype=jnp.float32):
    return [jnp.asarray(rng.normal(size=(pages, PAGE, KV * D)), dtype)
            for _ in range(2)]


@pytest.mark.parametrize("positions", [
    [0, 5, 31], [7, 100, 319], [127, 128, 129],    # a chunk is 16 pages
    [250, 8, 1]])
def test_block_table_pages_read_in_place_as_gathered(positions):
    rng = np.random.default_rng(0)
    k, v = _arenas(rng, 128)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(128)[:B * 40].reshape(B, 40),
                         jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    kw = dict(kv_heads=KV, scale=0.125)
    got = pa.paged_grouped_decode_attention(q, k, v, tables, pos, **kw)
    want = pa.grouped_gathered_attention(q, k, v, tables, pos, **kw)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # and against a softmax written out: head h reads KV head h // GROUP
    b = 1
    n = int(pos[b]) + 1
    rows = lambda a: a[tables[b]].reshape(-1, KV, D)[:n]
    for h in (0, GROUP, H - 1):
        s = rows(k)[:, h // GROUP] @ q[b, h] * 0.125
        o = jax.nn.softmax(s) @ rows(v)[:, h // GROUP]
        np.testing.assert_allclose(got[b, h], o, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("positions", [
    [0, 5, 31], [23, 24, 25],        # around the window's edge (24)
    [32, 40, 319],                   # wrapped: the ring has 32 rows
    [250, 8, 1]])
def test_a_ring_read_in_place_sees_the_window_and_nothing_else(positions):
    """A ring of 4 pages of 8 (window 24 + one page) a slot, filled by
    ``grouped_write`` position by position up to each slot's own."""
    window, ring = 24, 4
    rng = np.random.default_rng(1)
    layer = pa.PagedLayer(jnp.zeros((B * ring, PAGE, KV * D)),
                          jnp.zeros((B * ring, PAGE, KV * D)), ring=ring)
    tables = pa.ring_block_tables(jnp.arange(B), ring)
    last = max(positions)
    ks = jnp.asarray(rng.normal(size=(B, last + 1, KV * D)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(B, last + 1, KV * D)), jnp.float32)
    at = jnp.arange(last + 1)[None, :].repeat(B, 0)
    pos = jnp.asarray(positions, jnp.int32)
    layer = pa.grouped_write(layer, ks, vs, tables, at,
                             at <= pos[:, None])          # each to its own
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kw = dict(kv_heads=KV, scale=0.125, window=window)
    got = pa.paged_grouped_decode_attention(q, layer.k, layer.v, tables, pos,
                                            **kw)
    np.testing.assert_allclose(
        got, pa.grouped_gathered_attention(q, layer.k, layer.v, tables, pos,
                                           **kw), rtol=2e-5, atol=2e-6)
    for b in range(B):                # the window, from the rows as written
        lo, hi = max(0, int(pos[b]) - window + 1), int(pos[b]) + 1
        for h in (0, H - 1):
            j = h // GROUP
            s = ks[b, lo:hi].reshape(-1, KV, D)[:, j] @ q[b, h] * 0.125
            o = jax.nn.softmax(s) @ vs[b, lo:hi].reshape(-1, KV, D)[:, j]
            np.testing.assert_allclose(got[b, h], o, rtol=2e-5, atol=2e-6)


def test_a_masked_write_lands_nowhere():
    layer = pa.PagedLayer(jnp.ones((4, PAGE, 16)), jnp.ones((4, PAGE, 16)),
                          ring=2)
    tables = pa.ring_block_tables(jnp.arange(2), 2)
    new = pa.grouped_write(layer, jnp.zeros((2, 1, 16)), jnp.zeros((2, 1, 16)),
                           tables, jnp.asarray([[3], [19]]),
                           jnp.asarray([[False], [True]]))
    assert float(new.k[:2].min()) == 1.0                 # slot 0: untouched
    assert float(new.k[2:].sum()) == 2 * PAGE * 16 - 16  # slot 1: row 19 % 16
    assert float(new.k[2, 3].max()) == 0.0


@pytest.mark.parametrize("dtype,page,d,how", [
    (jnp.float32, 8, 128, "pages"), (jnp.bfloat16, 16, 128, "pages"),
    (jnp.bfloat16, 8, 128, "gathered"),      # half a bf16 sublane tile
    (jnp.float32, 4, 128, "gathered"),
    (jnp.float32, 8, 32, "gathered")])       # heads narrower than the lanes
def test_decode_read_takes_the_rows_layout_by_its_tiles(dtype, page, d, how):
    layer = pa.PagedLayer(jnp.zeros((3, page, 2 * d), dtype),
                          jnp.zeros((3, page, 2 * d), dtype))
    assert pa.decode_read(layer, 1, None, 4, d) == how
    # a wider window over block-table rows is gathered and viewed by head
    # (``paged_attend``); over a ring it is refused
    assert pa.decode_read(layer, 2, None, 4, d) == "gathered"
    with pytest.raises(NotImplementedError, match="one query a row"):
        pa.decode_read(layer.replace(ring=3), 2, None, 4, d)


def test_paged_layer_carries_its_ring_through_jit():
    layer = pa.PagedLayer(jnp.zeros((4, 8, 16)), jnp.zeros((4, 8, 16)),
                          ring=2)
    out = jax.jit(lambda l: l.replace(k=l.k + 1))(layer)
    assert out.ring == 2 and out.quant == "none" and float(out.k[0, 0, 0]) == 1


@pytest.mark.parametrize("length,window", [
    (30, 8), (8, 8), (5, 8), (64, 16), (17, 16), (16, 512)])
def test_banded_window_attention_is_the_masked_softmax(length, window):
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(size=(2, length, 3, 16)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, length, 3, 32)), jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    i = jnp.arange(length)
    live = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1), v)
    np.testing.assert_allclose(window_attention(q, k, v, window), want,
                               rtol=2e-5, atol=2e-6)
