"""The ``nemotron_h`` LM through ``ServeEngine``: prefill (the chunked
Mamba-2 form, the routed layer's sorted or dense form by the bucket), then
ticks through per-slot Mamba-2 state, one layer's pages and the routed
layer's masked dense form must give, token by token, the LOGITS of the plain
reference's full forward (``benchmarks/reference/nemotron_h.py``: the
token-by-token recurrence, a loop over the held experts) over the same
tokens.

The logits are read as ``tests/test_serve_hybrid.py`` reads them (its
``log`` fixture and ``serve_recorded``): the sampler hands every row it is
given to the host, the dispatches say whose rows those are.

Tolerance. float32 on both sides at toy size (12 layers, sequences of up to
50 tokens): logits of size ~4 agree to 3e-4 relative + 1e-4 absolute, as
the issue's 1e-4 asks of logits of size 1. A state taken at the bucket's
end, a padded row that reaches an expert or a held block off by one is off
by 1e-2 to 1 (the tests break each and see it).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.reference import nemotron_h as ref  # noqa: E402
from test_nemotron_h_lm import TOY, engine_params, toy_model  # noqa: E402
from test_serve_hybrid import log, serve_recorded  # noqa: E402,F401
from tpu_dist.engine import serve  # noqa: E402
from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,  # noqa: E402
                                   ServeEngine)

RTOL, ATOL = 3e-4, 1e-4
# the toy with the attention layer's PUBLISHED head shape: 2 KV heads of 128
# under 32 query heads, which the grouped in-place read's tiling takes
WIDE = {**TOY, "num_attention_heads": 32, "head_dim": 128}
# the toy with the Mamba-2 mixers' PUBLISHED state width: 128 states fill
# the lanes, which the tick's kernel over the live rows takes
STATES = {**TOY, "ssm_state_size": 128}


def weights(seed=1, sizes=TOY):
    return ref.make_weights(sizes, jax.random.PRNGKey(seed))


def engine(w, sizes=TOY, **cfg):
    model = toy_model(num_heads=sizes["num_attention_heads"],
                      head_dim=sizes["head_dim"],
                      d_state=sizes["ssm_state_size"])
    fields = dict(max_slots=2, page_size=4, num_pages=64, max_len=64)
    return ServeEngine(model, engine_params(model, w),
                       ServeConfig(**{**fields, **cfg}))


def check_against_reference(done, rows, w, rtol=RTOL, atol=ATOL, sizes=TOY):
    programs = ref.layer_programs(sizes)
    for rid, c in done.items():
        want = np.asarray(ref.forward(
            w, jnp.asarray(c.tokens[None]), sizes, programs)[0])
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


def _ticks(eng):
    return [sp for sp in serve.trace.ring().snapshot()
            if sp.name == "serve.tick"][-eng.ticks:]


def test_mixed_lengths_joining_and_leaving_with_the_tick_ahead(log):
    """Prompts in four buckets (5, 11 and 20 tokens are shorter than
    theirs), admitted while others decode, three slots, the tick one ahead
    of the host and a step's admissions ahead of each other."""
    w = weights(seed=2)
    reqs = requests([9, 30, 5, 27, 11, 20], new=7, seed=3)
    eng = engine(w, max_slots=3)
    done, rows = serve_recorded(
        eng, log, {0: reqs[:2], 3: reqs[2:3], 5: reqs[3:5], 6: reqs[5:]})
    assert len(done) == 6 and eng.ticks_ahead > 0
    assert eng.stats()["prefills_ahead"] > 0
    check_against_reference(done, rows, w)
    ticks = _ticks(eng)
    assert max(sp.attrs["state_slots"] for sp in ticks) == 3
    # dropless: every decoding row's 3 assignments land somewhere among
    # the 16, a quarter of them here on average; never more than all
    for sp in ticks:
        a = sp.attrs
        assert (a["expert_layers"], a["experts_held"]) == (6, 4)
        assert 0 <= a["expert_rows"] <= 6 * 3 * len(a["rids"])
        assert a["experts_hit"] <= min(a["expert_rows"], 6 * 4)
    st = eng.stats()
    prefills = [sp for sp in serve.trace.ring().snapshot()
                if sp.name == "serve.prefill"][-eng.prefills:]
    # the program's own word on its routed layers' form: these buckets and
    # the tick's rows all take the masked dense one
    assert {sp.attrs["grouped_calls"] for sp in ticks + prefills} == {0}
    assert st["expert_rows"] == sum(sp.attrs["expert_rows"]
                                    for sp in ticks + prefills)
    assert 0 < st["experts_hit_mean"] <= 4


def test_a_padded_row_that_reaches_an_expert_is_caught(log, monkeypatch):
    """The same kind of run with the routed layer told that every row of
    the bucket is live: the counters then count the padding (the outputs of
    the dead rows are never read, so the logits still agree)."""
    import tpu_dist.models.nemotron_h as m

    w = weights(seed=4)
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([11], new=3)})
    check_against_reference(done, rows, w)
    last_prefill = lambda: [sp for sp in serve.trace.ring().snapshot()
                            if sp.name == "serve.prefill"][-1]
    sound = last_prefill().attrs["expert_rows"]

    real = m.LatentExperts.__call__

    def all_rows_live(self, h, live):
        return real(self, h, jnp.full_like(live, h.shape[1]))

    monkeypatch.setattr(m.LatentExperts, "__call__", all_rows_live)
    del log[:]
    for p in (serve._prefill_program, serve._tick_program):
        p.cache_clear()
    eng = engine(w, max_slots=1)
    serve_recorded(eng, log, {0: requests([11], new=3)})
    padded = last_prefill().attrs["expert_rows"]
    assert padded > sound            # 16 rows of the bucket against 11


def test_state_taken_at_the_buckets_end_is_caught(log, monkeypatch):
    import tpu_dist.models.nemotron_h as m

    real = m.Mamba2Mixer.__call__

    def at_bucket_end(self, h, paged):
        if paged is not None and h.shape[1] > 1:
            paged = {**paged, "live": jnp.full_like(paged["live"], h.shape[1])}
        return real(self, h, paged)

    monkeypatch.setattr(m.Mamba2Mixer, "__call__", at_bucket_end)
    w = weights()
    done, rows = serve_recorded(engine(w, max_slots=1), log,
                                {0: requests([11])})
    with pytest.raises(AssertionError):
        check_against_reference(done, rows, w)


def test_a_reused_slot_holds_nothing_of_its_last_occupant(log):
    w = weights(seed=4)
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([13, 7, 10], seed=5)})
    assert len(done) == 3 and eng.prefills == 3
    assert float(jnp.abs(eng.pool.layers()[0]["ssm"]).max()) > 0
    check_against_reference(done, rows, w)


def test_the_tick_walks_the_live_rows_where_the_states_fill_the_lanes(
        log, monkeypatch):
    """At the published 128 states the tick updates the live rows' state in
    place with ``ssd_step_live`` (interpreted here), one call a Mamba-2
    layer: two slots that five requests join, leave and reuse give the
    reference's logits, and the greedy tokens of the same requests through
    the plain form (the shape rule answering 0, as it does for the toy's 16
    states)."""
    from tpu_dist.ops import ssd

    def arrivals():
        return {0: requests([9, 30], new=7, seed=22),
                3: requests([5], new=4, seed=23, first_rid=2),
                6: requests([27, 11], new=6, seed=24, first_rid=3)}

    calls, real = [], ssd.ssd_step_live
    monkeypatch.setattr(ssd, "ssd_step_live", lambda *a, **kw: (
        calls.append(a[-1]), real(*a, **kw))[1])
    w = weights(seed=21, sizes=STATES)
    eng = engine(w, STATES)
    done, rows = serve_recorded(eng, log, arrivals())
    assert len(done) == 5 and eng.ticks_ahead > 0
    # traced once: the four mixers, each the whole of its 8 heads a step
    assert calls == [8] * STATES["hybrid_override_pattern"].count("M")
    check_against_reference(done, rows, w, sizes=STATES)
    assert min(sp.attrs["state_slots"] for sp in _ticks(eng)) == 1

    monkeypatch.setattr(ssd, "head_tile", lambda s, groups: 0)
    del log[:], calls[:]
    serve._tick_program.cache_clear()
    plain, _ = serve_recorded(engine(w, STATES), log, arrivals())
    assert not calls
    for rid, c in done.items():
        np.testing.assert_array_equal(c.tokens, plain[rid].tokens)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_carries_the_state_from_chunk_to_chunk(log, chunk):
    """The chunked matrix form honours a carried-in state, so a prompt in
    chunks of 8 or 16 (the Mamba-2 chunk is 8) gives the reference's
    logits; the attention layer's chunks read the gathered pages."""
    w = weights(seed=8)
    eng = engine(w, prefill_chunk=chunk)
    done, rows = serve_recorded(eng, log,
                                {0: requests([29, 6], new=4, seed=9),
                                 2: requests([37], new=4, seed=10,
                                             first_rid=2)})
    assert len(done) == 3 and eng.chunk_ticks > 0
    check_against_reference(done, rows, w)


@pytest.mark.parametrize("sizes,page,chunk,read", [
    (WIDE, 8, 0, "pages"), (TOY, 4, 0, "gathered"), (WIDE, 8, 16, "pages")],
    ids=["published_heads", "toy_heads", "published_heads_chunked"])
def test_the_tick_reads_the_rows_in_place_where_a_head_fills_the_lanes(
        log, sizes, page, chunk, read):
    """The attention layers' pages lie in rows, and the tick reads them
    through ``paged_attend``: the grouped kernel (interpreted here) at the
    published head shape, its gathered twin at the toy's, each saying so in
    every tick's span; a prompt in chunks reads the same rows gathered. All
    give the reference's logits."""
    w = weights(seed=15, sizes=sizes)
    eng = engine(w, sizes, max_slots=3, page_size=page, prefill_chunk=chunk)
    assert eng.tick_read == read
    assert all(layer.k.ndim == 3 for layer in eng.pool.page_layers())
    done, rows = serve_recorded(
        eng, log, {0: requests([9, 21], new=5, seed=16),
                   2: requests([14, 30], new=5, seed=17, first_rid=2)})
    assert len(done) == 4 and (eng.chunk_ticks > 0) == (chunk > 0)
    check_against_reference(done, rows, w, sizes=sizes)
    st = eng.stats()
    assert st["read"] == read and st["ticks_by_read"] == {read: st["ticks"]}
    assert all(sp.attrs["read"] == read for sp in _ticks(eng))


def test_int8_pages_are_refused_by_name():
    """The one capability the rows layout gives up: this model's pages in
    int8 (0.13 GB of the cell's 11, so nothing was to be saved). The pool
    builds no int8 rows and says so, as for the phi-4 model."""
    with pytest.raises(NotImplementedError,
                       match="rows-layout KV layer in an sp-sharded or "
                       "int8 pool"):
        engine(weights(), kv_quant="int8")


def test_the_pools_bytes_are_what_the_layout_says():
    """``M`` layers hold slots x ([heads, channels, states] float32 + three
    rows of the convolution's channels), ``E`` layers nothing, and the two
    ``*`` layers pages, a token's two KV heads side by side in a row."""
    eng = engine(weights(), max_slots=3)
    st = eng.stats()
    state = 8 * 16 * 16 * 4 + 3 * (8 * 16 + 2 * 2 * 16) * 4
    assert st["state_bytes"] == 4 * 3 * state
    assert st["state_bytes_per_slot"] == 4 * state
    assert eng.state_layers == 4 and (eng.expert_layers,
                                      eng.experts_held) == (6, 4)
    kinds = [type(l).__name__ for l in eng.pool.layers()]
    assert kinds == ["dict", "dict", "dict", "dict", "PagedLayer", "dict",
                     "dict", "dict", "dict", "dict", "PagedLayer", "dict"]
    assert [len(l) for l in eng.pool.layers() if isinstance(l, dict)] \
        == [2, 0, 2, 0, 0, 2, 0, 2, 0, 0]
    assert eng.pool.page_layers()[0].k.shape == (65, 4, 2 * 16)  # KV heads
    assert eng.pool.layers()[0]["ssm"].shape == (3, 8, 16, 16)
    assert st["kv_bytes_per_token"] == 2 * 2 * (2 * 16 * 4)
    assert st["expert_rows"] == 0 and st["experts_hit_mean"] is None


def test_int8_wo_builds_and_serves():
    """The engine's own weight-only int8: the experts, the shared expert,
    the latent projections and the mixers' projections int8 in HBM, the
    router float32; it serves, and not the float32 tokens' logits."""
    w = weights(seed=11)
    eng = engine(w, quant="int8_wo")
    moe = eng.params["layer1"]["moe"]
    assert moe["w_in"].dtype == jnp.int8
    assert moe["gate"]["kernel"].dtype == jnp.float32
    assert eng.params["layer0"]["mamba"]["out_proj"]["kernel"].dtype \
        == jnp.int8
    done = eng.run(requests([12, 7, 21], seed=12))
    assert len(done) == 3
    assert all(c.n_generated == 6 + i for i, c in
               enumerate(sorted(done, key=lambda c: c.rid)))
    assert eng.stats()["expert_rows"] > 0


def test_a_model_without_experts_returns_no_counters():
    """The other served models' programs hand back what they did: no
    counter, no attribute."""
    from test_hybrid_lm import engine_params as hybrid_params
    from test_hybrid_lm import toy_model as hybrid_model
    from test_serve_hybrid import weights as hybrid_weights

    model = hybrid_model()
    eng = ServeEngine(model, hybrid_params(model, hybrid_weights()),
                      ServeConfig(max_slots=2, page_size=4, num_pages=64,
                                  max_len=64))
    assert (eng.expert_layers, eng.experts_held) == (0, 0)
    eng.run(requests([9, 5], new=3))
    assert all(f.counts is None for f in eng._flights)
    tick = [sp for sp in serve.trace.ring().snapshot()
            if sp.name == "serve.tick"][-1]
    assert "expert_rows" not in tick.attrs
    assert eng.stats()["experts_hit_mean"] is None
    program = serve._tick_program(eng.model, 0.0, 0, 0.0, None)
    out = jax.eval_shape(program, eng.params, eng.pool.layers(), *eng._dev,
                         eng._rng)
    assert len(out) == 3


def test_a_model_with_no_cache_layout_is_refused_by_what_it_lacks():
    from tpu_dist.models.moe import MoETransformerLM

    model = MoETransformerLM(vocab_size=64, num_layers=1, d_model=32,
                             num_heads=2, num_experts=2, max_len=32)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    with pytest.raises(NotImplementedError,
                       match="MoETransformerLM has no cache_layout"
                       ".*capacity-factor"):
        ServeEngine(model, params, ServeConfig(max_len=32))


def test_the_counters_reach_the_ledger_and_its_report():
    """``expert_rows``, ``experts_hit_mean`` and ``state_bytes_per_slot`` on
    the ``kv_cache`` event, as ``stats()`` has them, and the two lines
    ``tools/ledger_report.py`` prints from them."""
    from tools.ledger_report import decode_section
    from tpu_dist.obs.ledger import Ledger

    cap = []
    model = toy_model()
    eng = ServeEngine(model, engine_params(model, weights(seed=13)),
                      ServeConfig(max_slots=2, page_size=4, num_pages=64,
                                  max_len=64),
                      ledger=Ledger(None, sinks=(cap.append,)))
    eng.run(requests([10, 6], new=4, seed=14))
    kv = [r for r in cap if r["event"] == "kv_cache"][-1]
    st = eng.stats()
    assert kv["expert_rows"] == st["expert_rows"] > 0
    assert kv["experts_hit_mean"] == st["experts_hit_mean"] > 0
    assert kv["state_bytes_per_slot"] == st["state_bytes_per_slot"] > 0
    lines = []
    decode_section(cap, out=lines.append)
    assert any("experts:" in ln and f"{st['expert_rows']} assignments" in ln
               and "held experts hit a routed layer and tick" in ln
               for ln in lines), lines
    assert any("slot state:" in ln and "a slot)" in ln for ln in lines), lines
