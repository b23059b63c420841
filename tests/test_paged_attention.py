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


def _tables(lengths, rng):
    """Each live row's pages drawn from all over the arena, trash entries
    past them; a row of length 1 with ``None`` pages is an inactive slot."""
    bt = np.full((len(lengths), MAX_PAGES), N_PAGES, np.int32)
    free = list(rng.permutation(N_PAGES))
    for b, n in enumerate(lengths):
        if n is not None:
            bt[b, :pa.pages_for(n, PS)] = [free.pop() for _ in
                                           range(pa.pages_for(n, PS))]
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
