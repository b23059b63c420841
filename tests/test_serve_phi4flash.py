"""The decoder-hybrid-decoder LM through ``ServeEngine``: prefill, then ticks
through window rings, ONE layer's pages that the cross layers read too,
per-slot recurrent state and the gated memory units must give, token by
token, the LOGITS of the plain reference's full forward
(``benchmarks/reference/phi4flash.py``) over the same tokens.

The logits are read as ``tests/test_serve_hybrid.py`` reads them (its
``log`` fixture and ``serve_recorded``): the sampler hands every row it is
given to the host, the dispatches say whose rows those are.

Tolerance. float32 on both sides at toy size (12 layers, a window of 8,
pages of 8, so a ring of 16 rows: a float32 page is one sublane tile, which
the in-place read's rule asks for; sequences of up to 40 tokens, more than 3
windows): a decode step recomputes nothing the full forward does not, in
another order (the in-place grouped read, interpreted, against a masked
softmax a pair; the one-step scan against ``lax.scan``): logits of size ~5
agree to 3e-4 relative + 1e-4 absolute, as the issue's 1e-4 asks of logits
of size 1. A ring that keeps a row too few or too many, a cross layer that
reads another block table or a prefill that hands the cross-decoder the
bucket's last row is off by 1e-2 to 1 (the tests break each and see it).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.reference import phi4flash as ref  # noqa: E402
from test_phi4flash_lm import TOY, engine_params, toy_model  # noqa: E402
from test_serve_hybrid import log, serve_recorded  # noqa: E402,F401
from tpu_dist.engine import serve  # noqa: E402
from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,  # noqa: E402
                                   ServeEngine)

RTOL, ATOL = 3e-4, 1e-4
WINDOW, PAGE = TOY["sliding_window"], 8


def weights(seed=1):
    return ref.make_weights(TOY, jax.random.PRNGKey(seed))


def engine(w, **cfg):
    model = toy_model()
    fields = dict(max_slots=2, page_size=PAGE, num_pages=64, max_len=64)
    return ServeEngine(model, engine_params(model, w),
                       ServeConfig(**{**fields, **cfg}))


def check_against_reference(done, rows, w, rtol=RTOL, atol=ATOL):
    programs = ref.layer_programs(TOY)
    for rid, c in done.items():
        want = np.asarray(ref.forward(
            w, jnp.asarray(c.tokens[None]), TOY, programs)[0])
        got = np.stack(rows[rid])
        assert got.shape[0] == c.n_generated
        # row t of the reference predicts token t + 1
        want = want[c.prompt_len - 1:c.prompt_len - 1 + c.n_generated]
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=f"request {rid}")


def requests(lens, new=6, seed=0, first_rid=0):
    r = np.random.default_rng(seed)
    return [DecodeRequest(rid=first_rid + i,
                          prompt=r.integers(0, 256, n).astype(np.int32),
                          max_new_tokens=new + i) for i, n in enumerate(lens)]


def test_mixed_lengths_joining_and_leaving_with_the_tick_ahead(log):
    """Prompts from under a window to over three, admitted while others
    decode, three slots, the tick one ahead of the host."""
    w = weights(seed=2)
    reqs = requests([9, 30, 6, 27, 12], new=9, seed=3)
    eng = engine(w, max_slots=3)
    done, rows = serve_recorded(
        eng, log, {0: reqs[:2], 3: reqs[2:3], 5: reqs[3:4], 6: reqs[4:]})
    assert len(done) == 5 and eng.ticks_ahead > 0
    assert eng.tick_read == "pages"              # the in-place grouped read
    check_against_reference(done, rows, w)
    spans = serve.trace.ring().snapshot()
    ticks = [sp for sp in spans if sp.name == "serve.tick"][-eng.ticks:]
    alone = [sp.attrs for sp in ticks if len(sp.attrs["rids"]) == 1]
    assert alone and all(a["window_tokens"] == min(a["live_tokens"], WINDOW)
                         for a in alone)
    assert max(sp.attrs["window_tokens"] for sp in ticks) == 3 * WINDOW
    assert max(sp.attrs["state_slots"] for sp in ticks) == 3


def test_a_steps_admissions_pipelined_over_rings_and_the_shared_layer(log):
    """Four requests of four buckets, from under a window to over three,
    in the queue before ONE step: each prefill's program is called before
    the last one's token is read, and writes its own slot's rings and
    state beside the one page layer. Every sampled row is the reference's,
    with all four admitted together and fed one a step."""
    w = weights(seed=7)
    tokens = {}
    for arrivals, ahead in (({0: requests([6, 30, 13, 40], new=5, seed=9)},
                             3),
                            ({k: [r] for k, r in enumerate(
                                requests([6, 30, 13, 40], new=5, seed=9))},
                             0)):
        del log[:]
        eng = engine(w, max_slots=4)
        done, rows = serve_recorded(eng, log, arrivals)
        assert len(done) == 4
        assert eng.stats()["prefills_ahead"] == ahead
        check_against_reference(done, rows, w)
        tokens[ahead] = {rid: c.tokens for rid, c in done.items()}
    for rid in tokens[0]:
        np.testing.assert_array_equal(tokens[0][rid], tokens[3][rid])


@pytest.mark.parametrize("prompt", [
    WINDOW - 1, WINDOW, WINDOW + 1,          # around the window's edge
    WINDOW + PAGE,                           # the ring's
    3 * WINDOW + 2])                         # and long past both
def test_prompts_around_the_windows_and_the_rings_edges(log, prompt):
    """Each decodes across a page edge or wraps its ring of 16 rows (pages
    of 8, 9 new tokens)."""
    w = weights(seed=4)
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([prompt], new=9)})
    check_against_reference(done, rows, w)


@pytest.mark.parametrize("page,read", [(PAGE, "pages"), (4, "gathered")])
def test_a_reused_slot_holds_nothing_of_its_last_occupant(log, page, read):
    """One slot, three requests in turn, the first long enough to wrap the
    ring, the next shorter than a window: the stale rows stay out. Pages of
    4 rows are no whole float32 tile, so that engine's reads gather."""
    w = weights(seed=5)
    eng = engine(w, max_slots=1, page_size=page)
    assert eng.tick_read == read
    done, rows = serve_recorded(eng, log, {0: requests([29, 5, 14], seed=6)})
    assert len(done) == 3 and eng.prefills == 3
    check_against_reference(done, rows, w)


@pytest.mark.parametrize("what", ["window_narrow", "window_wide",
                                  "cross_table", "bucket_row"])
def test_a_broken_cache_is_caught(log, monkeypatch, what):
    """The same run with one mechanism broken underneath: the window's read
    a key too narrow or too wide, the cross layers' read through a block
    table offset by a page, the cross-decoder handed the bucket's last row
    for the prompt's."""
    import tpu_dist.models.phi4flash as m

    w = weights(seed=7)
    if what in ("window_narrow", "window_wide"):
        real_read = m.grouped_read
        by = 1 if what == "window_wide" else -1
        monkeypatch.setattr(m, "grouped_read", lambda *a, window=None, **kw:
                            real_read(*a, window=window and window + by, **kw))
    elif what == "cross_table":
        real_read = m.grouped_read

        def off_by_a_page(q, layer, tables, *a, **kw):
            if not layer.ring and kw.get("window") is None:
                tables = jnp.roll(tables, 1, axis=1)
            return real_read(q, layer, tables, *a, **kw)
        monkeypatch.setattr(m, "grouped_read", off_by_a_page)
    else:
        real_take = jnp.take_along_axis
        monkeypatch.setattr(
            m.jnp, "take_along_axis",
            lambda x, at, axis: real_take(
                x, jnp.full_like(at, x.shape[axis] - 1), axis=axis))
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([21], new=8, seed=8)})
    with pytest.raises(AssertionError):
        check_against_reference(done, rows, w)


def test_prefill_runs_the_cross_decoder_on_one_row_a_prompt(log):
    """Read from the traced program (the span's ``cross_rows`` is the rows
    of logits the bucket's program returned) and from its shapes: no
    [1, bucket, V] logits exist in the prefill's jaxpr."""
    w = weights(seed=9)
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([13, 27], new=3)})
    pre = [sp for sp in serve.trace.ring().snapshot()
           if sp.name == "serve.prefill"][-2:]
    assert [sp.attrs["bucket"] for sp in pre] == [16, 32]
    assert all(sp.attrs["cross_rows"] == 1 for sp in pre)
    assert all(sp.attrs["window_layers"] == 3
               and sp.attrs["shared_readers"] == 3
               and sp.attrs["state_layers"] == 4 for sp in pre)
    model = toy_model()
    bucket, vocab = 32, TOY["vocab_size"]
    program = serve._prefill_program(model, 0.0, 0, 0.0, None)
    text = str(jax.make_jaxpr(program)(
        eng.params, eng.pool.layers(), jnp.zeros((1, 16), jnp.int32),
        jnp.int32(20), jnp.int32(0), jnp.zeros((1, bucket), jnp.int32),
        eng._rng, jnp.int32(0)))
    # products 256 wide (the fused MLP's first, the Mamba input's, the
    # cross layers' queries, the head: the toy vocabulary is 256 too): the
    # self-decoder's 8 MLPs and 4 Mamba inputs over the bucket's rows, the
    # cross-decoder's 4 MLPs, 2 query projections and the head over ONE
    made = lambda rows: len(re.findall(
        rf"f32\[1,{rows},{vocab}\] = dot_general", text))
    assert made(bucket) == 8 + 4 and made(1) == 4 + 2 + 1
    check_against_reference(done, rows, w)


@pytest.mark.parametrize("max_len", [64, 256])
def test_window_bytes_do_not_depend_on_max_len(max_len):
    """The rings are slots x (window + page) rows whatever ``max_len`` is;
    the full layer alone holds block-table pages; cross and GMU layers
    allocate nothing."""
    eng = engine(weights(), max_slots=3, max_len=max_len, num_pages=96)
    row = 2 * TOY["num_key_value_heads"] * TOY["head_dim"] * 4   # K and V
    st = eng.stats()
    assert st["window_bytes"] == 3 * 3 * (WINDOW + PAGE) * row   # 3 layers
    assert st["kv_bytes_per_token"] == row                       # ONE layer
    layers = eng.pool.layers()
    kinds = toy_model().layer_types
    for kind, layer in zip(kinds, layers):
        if kind == "window":
            assert layer.ring == WINDOW // PAGE + 1
            assert layer.k.shape == (3 * layer.ring, PAGE, row // 8)
        elif kind == "full":
            assert layer.ring == 0 and layer.k.shape == (97, PAGE, row // 8)
        elif kind == "cross":
            assert layer is None
        elif kind == "gmu":
            assert layer == {}
    assert eng.state_layers == 4 and eng.window_layers == 3
    assert eng.shared_readers == 3 and eng.window == WINDOW
    # 4 Mamba layers x 3 slots x ([16, 128] f32 + [3, 128] f32)
    assert st["state_bytes"] == 4 * 3 * (16 * 128 * 4 + 3 * 128 * 4)


@pytest.mark.parametrize("fields,mechanism", [
    (dict(prefix_cache=True), "prefix_cache over slot state and window "
     "rings.*layer that others read.*nor the window rings' rows"),
    (dict(spec_k=2), "speculative decoding.*rings' overwritten rows"),
    (dict(mesh=True), "sp-sharded pool.*slot state and window rings"),
    (dict(prefill_chunk=8), "chunked prefill.*window rings"),
])
def test_what_has_no_meaning_over_rings_and_shared_pages_is_refused_by_name(
        fields, mechanism):
    model = toy_model()
    params = engine_params(model, weights())
    kw = {}
    if fields.pop("mesh", None):
        from tpu_dist.parallel.mesh import SP_AXIS, make_mesh

        kw["mesh"] = make_mesh((2,), (SP_AXIS,), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=mechanism):
        ServeEngine(model, params, ServeConfig(
            max_slots=2, page_size=PAGE, num_pages=64, max_len=64, **fields),
            **kw)


def test_the_kv_cache_event_says_what_a_sequence_costs():
    """``window_bytes`` and ``kv_bytes_per_token`` ride the ``kv_cache``
    ledger event, and ``tools/ledger_report.py`` prints them."""
    from tools.ledger_report import decode_section
    from tpu_dist.obs.ledger import Ledger

    records = []
    model = toy_model()
    eng = ServeEngine(model, engine_params(model, weights()), ServeConfig(
        max_slots=2, page_size=PAGE, num_pages=64, max_len=64),
        ledger=Ledger(None, sinks=(records.append,)))
    assert len(eng.run(requests([5], new=3))) == 1
    kv = [r for r in records if r["event"] == "kv_cache"][-1]
    row = 2 * TOY["num_key_value_heads"] * TOY["head_dim"] * 4
    assert kv["kv_bytes_per_token"] == row
    assert kv["window_bytes"] == 3 * 2 * (WINDOW + PAGE) * row
    lines = []
    decode_section(records, out=lines.append)
    assert any("KV cache: 1.02 kB a token in pages, 98.30 kB of window rings"
               in line for line in lines), lines
