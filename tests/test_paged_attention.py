"""The in-place decode read (ops.paged_attention.paged_decode_attention).

Three pins, all in interpret mode on the CPU at lane-wide heads (D = 128):

* the kernel against the gathered read it replaces (``masked_attention``
  over ``gather_pages``) at the lengths where its page and chunk walks turn:
  one row, a page's edges, a chunk's edges, the whole table; block tables
  scattered over the arena with trash entries past the pages; an inactive
  row (position 0, all-trash table) beside live ones;
* the dispatch rule of ``paged_attend``: which inputs lower to a program
  with the kernel in it and which keep the gathered path;
* the engine: a toy LM with ``head_dim`` 128 served through ``ServeEngine``
  gives ``engine.generate``'s greedy tokens and says so in ``stats()``.

What Mosaic takes or refuses at the cell's shapes is
``tests/test_chip_compile.py``'s; times are the chip's alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.engine.generate import generate
from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.obs import trace
from tpu_dist.ops import paged_attention as pa
from tpu_dist.parallel.mesh import SP_AXIS, make_mesh

H, D, PS = 2, 128, 16
N_PAGES, MAX_PAGES = 40, 12                      # 192 positions a row
CHUNK = pa._DECODE_CHUNK_PAGES * PS              # 128 tokens


def _arenas(dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (N_PAGES + 1, PS, H, D)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype), rng)


def _tables(lengths, rng, n_pages=N_PAGES, max_pages=MAX_PAGES, page=PS):
    """Each live row's pages drawn from all over the arena, trash entries
    past them; a row of length 1 with ``None`` pages is an inactive slot."""
    bt = np.full((len(lengths), max_pages), n_pages, np.int32)
    free = list(rng.permutation(n_pages))
    for b, n in enumerate(lengths):
        if n is not None:
            bt[b, :pa.pages_for(n, page)] = [free.pop() for _ in
                                             range(pa.pages_for(n, page))]
    return jnp.asarray(bt)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, CHUNK, CHUNK + 1,
                                    MAX_PAGES * PS])
def test_kernel_matches_the_gathered_read(length, dtype):
    k, v, rng = _arenas(dtype, seed=length)
    # the case's row between a short live row and an inactive slot
    lengths = [40, length, None]
    bt = _tables(lengths, rng)
    ln = jnp.asarray([n or 1 for n in lengths], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, 1, H, D)), dtype)
    out = pa.paged_decode_attention(q, k, v, bt, ln)
    ref = pa.masked_attention(q, pa.gather_pages(k, bt),
                              pa.gather_pages(v, bt), ln - 1)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    # one bf16 step at the outputs' magnitude (the gathered read rounds its
    # softmax weights to bf16, the kernel keeps them float32); fp32 arenas
    # differ by summation order alone
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out, ref, atol=tol, rtol=0)


def _tick_jaxpr(lq=1, d=D, quant="none", sp=False, h=H):
    """``paged_attend`` for one non-prefill step, as a jaxpr's text."""
    shape = (N_PAGES + 1, PS, h, d)
    if quant == "int8":
        layer = pa.PagedLayer(jnp.zeros(shape, jnp.int8),
                              jnp.zeros(shape, jnp.int8),
                              jnp.ones(shape[:3], jnp.float32),
                              jnp.ones(shape[:3], jnp.float32),
                              quant="int8", read="exact")
    else:
        layer = pa.PagedLayer(jnp.zeros(shape, jnp.bfloat16),
                              jnp.zeros(shape, jnp.bfloat16))
    mesh = (make_mesh((1,), (SP_AXIS,), devices=jax.devices()[:1])
            if sp else None)
    bt = jnp.full((2, MAX_PAGES), N_PAGES, jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    x = jnp.zeros((2, lq, h, d), jnp.bfloat16)

    def step(x, layer, bt, pos):
        paged = {"layer": layer, "block_tables": bt, "positions": pos,
                 "lengths": pos + lq, "sp_mesh": mesh}
        return pa.paged_attend(x, x, x, paged, prefill=False, attn_fn=None,
                               dtype=jnp.bfloat16)

    return str(jax.make_jaxpr(step)(x, layer, bt, pos))


@pytest.mark.parametrize("case,kw,kernel", [
    ("aligned_decode_tick", {}, True),
    ("eight_heads", {"h": 8}, True),
    ("verify_window", {"lq": 3}, False),
    ("int8_pages", {"quant": "int8"}, False),
    ("sp_sharded_arena", {"sp": True}, False),
    ("narrow_heads", {"d": 16}, False),
    ("heads_short_of_a_bf16_tile", {"h": 12}, False),
])
def test_dispatch_rule(case, kw, kernel):
    assert ("pallas_call" in _tick_jaxpr(**kw)) is kernel, case


def test_engine_serves_generates_tokens_through_the_kernel():
    lm = tiny_lm(vocab_size=64, num_layers=2, d_model=256, num_heads=2,
                 max_len=64)
    params = lm.init({"params": jax.random.PRNGKey(3)},
                     jnp.zeros((1, 64), jnp.int32), train=False)["params"]
    prompts = [np.array([1, 9, 17, 4, 30], np.int32),
               np.array([5], np.int32),
               np.arange(2, 21, dtype=np.int32)]          # 19: over a page
    steps = [12, 20, 14]
    refs = [np.asarray(generate(lm, params, jnp.asarray(p[None]), steps=s,
                                use_cache=True))[0]
            for p, s in zip(prompts, steps)]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=16, num_pages=12))
    seen = len(trace.ring().snapshot())
    comps = eng.run([DecodeRequest(i, p, s)
                     for i, (p, s) in enumerate(zip(prompts, steps))])
    assert len(comps) == 3
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens)
    st = eng.stats()
    assert st["read"] == "pages"
    assert st["ticks_by_read"] == {"pages": st["ticks"]}
    assert 0 < st["live_pages"] < 1
    ticks = [s for s in trace.ring().snapshot()[seen:]
             if s.name == "serve.tick"]
    assert len(ticks) == st["ticks"]
    assert all(s.attrs["read"] == "pages" and s.attrs["live_pages"] >= 1
               for s in ticks)
    # a toy model's narrow heads keep the gathered path, and say so
    small = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                    max_len=64)
    sp = small.init({"params": jax.random.PRNGKey(0)},
                    jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    assert ServeEngine(small, sp, ServeConfig(
        max_slots=1, page_size=16, num_pages=4)).stats()["read"] == "gathered"


# ---------------------------------------------------------------------------
# a ROWS layer through ``paged_attend`` (the expert model's attention layer)
# ---------------------------------------------------------------------------
#
# The same K and V go into the 4-D layout (grouped heads: the gathered read)
# and into the rows layout, both through ``paged_attend``, and every kind of
# call a served sequence makes must give the same outputs: a prefill, ticks
# at ragged positions beside slots that sit out on the trash page, and a
# prefill chunk's window. ``published_*`` is the expert cell's head shape (2
# KV heads of 128 under 32 query heads, pages of 16): the grouped kernel,
# interpreted. ``toy`` does not tile and takes the kernel's gathered twin.

ROWS_SHAPES = {
    "published_fp32": dict(kv=2, d=128, group=16, page=16,
                           dtype=jnp.float32, read="pages", tol=2e-5),
    "published_bf16": dict(kv=2, d=128, group=16, page=16,
                           dtype=jnp.bfloat16, read="pages", tol=2.0 ** -7),
    "toy": dict(kv=2, d=16, group=2, page=4, dtype=jnp.float32,
                read="gathered", tol=2e-5),
}
ROWS_PAGES, ROWS_TABLE = 96, 20     # 20 pages a slot: the kernel's chunk is 16


def _both_layouts(s):
    shape = (ROWS_PAGES + 1, s["page"], s["kv"], s["d"])
    zeros = lambda shp: jnp.zeros(shp, s["dtype"])
    return (pa.PagedLayer(zeros(shape), zeros(shape)),
            pa.PagedLayer(zeros(shape[:2] + (s["kv"] * s["d"],)),
                          zeros(shape[:2] + (s["kv"] * s["d"],))))


def _qkv(rng, s, b, lq):
    f = lambda heads: jnp.asarray(
        rng.standard_normal((b, lq, heads, s["d"])), s["dtype"])
    return f(s["kv"] * s["group"]), f(s["kv"]), f(s["kv"])


def _attend_both(layers, qkv, bt, positions, lengths, *, prefill,
                 valid=None):
    """One call over each layout; the outputs and the layers it leaves."""
    from tpu_dist.models.transformer import full_attention

    outs, new = [], []
    for layer in layers:
        paged = {"layer": layer, "block_tables": bt,
                 "positions": jnp.asarray(positions, jnp.int32),
                 "lengths": jnp.asarray(lengths, jnp.int32), "valid": valid}
        out, layer = pa.paged_attend(*qkv, paged, prefill=prefill,
                                     attn_fn=full_attention,
                                     dtype=qkv[0].dtype)
        outs.append(np.asarray(out, np.float32))
        new.append(layer)
    return outs, new


@pytest.mark.parametrize("shape", sorted(ROWS_SHAPES))
def test_rows_layer_serves_what_the_4d_layout_serves(shape):
    s = ROWS_SHAPES[shape]
    page, tol = s["page"], s["tol"]
    rng = np.random.default_rng(7)
    close = lambda got, want, what: np.testing.assert_allclose(
        got, want, atol=tol, rtol=tol, err_msg=f"{shape}: {what}")
    # three prompts: inside a page, a page's edge, past the kernel's chunk
    # (16 pages) by the time the ticks end; room for 6 ticks each
    prompts = [page // 2 + 1, 3 * page, 16 * page - 2]
    ticks = 6
    bt = _tables([n + ticks for n in prompts] + [None, None], rng,
                 ROWS_PAGES, ROWS_TABLE, page)
    layers = _both_layouts(s)
    assert pa.decode_read(layers[0], 1, None, s["group"]) == "gathered"
    assert pa.decode_read(layers[1], 1, None, s["group"],
                          s["d"]) == s["read"]

    # -- prefill, a prompt a call as the engine admits them ---------------
    for b, n in enumerate(prompts):
        bucket = pa.pages_for(n, page) * page
        qkv = _qkv(rng, s, 1, bucket)
        (a, r), layers = _attend_both(layers, qkv, bt[b:b + 1], [0], [n],
                                      prefill=True)
        close(r[:, :n], a[:, :n], f"prefill of {n}")
    # the pages hold the same rows, whichever way they lie
    view = lambda layer: np.asarray(pa.gather_pages(layer.k, bt[:3]).reshape(
        3, ROWS_TABLE * page, s["kv"], s["d"]), np.float32)
    for b, n in enumerate(prompts):
        np.testing.assert_array_equal(view(layers[1])[b, :n],
                                      view(layers[0])[b, :n])

    # -- ticks: ragged positions, two slots sitting out on the trash page --
    pos = np.asarray(prompts + [0, 0], np.int32)
    for t in range(ticks):
        qkv = _qkv(rng, s, 5, 1)
        (a, r), layers = _attend_both(layers, qkv, bt, pos, pos + 1,
                                      prefill=False)
        assert r.shape == a.shape and np.isfinite(r).all()
        close(r[:3], a[:3], f"tick {t} at {pos[:3]}")
        pos[:3] += 1

    # -- a prompt in two chunks (Lq > 1, no prefill) on a fresh slot -------
    chunk, n = 2 * page, 3 * page + 1          # the second chunk half dead
    table = jnp.asarray(np.r_[np.arange(4) + 90, np.full(
        ROWS_TABLE - 4, ROWS_PAGES)].astype(np.int32)[None])
    for start in (0, chunk):
        rows = start + jnp.arange(chunk)[None]
        qkv = _qkv(rng, s, 1, chunk)
        (a, r), layers = _attend_both(
            layers, qkv, table, [start], [start + chunk], prefill=False,
            valid=rows < n)
        live = min(n - start, chunk)
        close(r[:, :live], a[:, :live], f"chunk at {start}")


@pytest.mark.parametrize("case,shape,lq,kernel", [
    ("grouped_tick_in_place", "published_bf16", 1, True),
    ("grouped_chunk_window", "published_bf16", 8, False),
    ("toy_tick", "toy", 1, False),
])
def test_rows_dispatch_rule(case, shape, lq, kernel):
    s = ROWS_SHAPES[shape]
    rows = _both_layouts(s)[1]
    qkv = _qkv(np.random.default_rng(0), s, 2, lq)
    bt = jnp.full((2, ROWS_TABLE), ROWS_PAGES, jnp.int32)

    def step(q, k, v, layer, bt):
        pos = jnp.zeros((2,), jnp.int32)
        return pa.paged_attend(
            q, k, v, {"layer": layer, "block_tables": bt, "positions": pos,
                      "lengths": pos + lq}, prefill=False, attn_fn=None,
            dtype=q.dtype)

    text = str(jax.make_jaxpr(step)(*qkv, rows, bt))
    assert ("pallas_call" in text) is kernel, case
    assert pa.decode_read(rows, lq, None, s["group"], s["d"]) == (
        "pages" if kernel else "gathered")


def test_rows_refusals_keep_their_names():
    """A ring takes one query a row and says so; int8 rows and an sp mesh
    are refused whatever the window."""
    s = ROWS_SHAPES["published_bf16"]
    rows = _both_layouts(s)[1]
    ring = rows.replace(ring=2)
    assert pa.decode_read(ring, 1, None, s["group"], s["d"]) == "pages"
    with pytest.raises(NotImplementedError, match="one query a row"):
        pa.decode_read(ring, 2, None, s["group"], s["d"])
    mesh = make_mesh((1,), (SP_AXIS,), devices=jax.devices()[:1])
    for lq in (1, 4):
        with pytest.raises(NotImplementedError, match="no int8 pages"):
            pa.decode_read(rows.replace(quant="int8"), lq, None,
                           s["group"], s["d"])
        with pytest.raises(NotImplementedError, match="no sp mesh"):
            pa.decode_read(rows, lq, mesh, s["group"], s["d"])
