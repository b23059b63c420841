"""The hybrid LM through ``ServeEngine``: prefill, then ticks through pages
(the attention layers) and per-slot recurrent state (the Mamba layers) must
give, token by token, the LOGITS of the plain reference's full forward
(``benchmarks/reference/jamba.py``) over the same tokens.

How the logits are read: the engine's sampler (``serve._sample``) is
wrapped to hand every logits row it is given to the host, and the engine's
prefill, chunk and tick DISPATCHES to say whose rows those are. The device
runs the programs in the order they were dispatched (the tick runs one
ahead of the host's read), so the k-th sampling dispatch owns the k-th
logged batch of rows.

Tolerance. float32 on both sides at toy size: a decode step recomputes
nothing the full forward does not, but in another order (one-step scan
against ``lax.scan``, gathered pages against a masked softmax, XLA:CPU
matmuls against ``highest``), through 8 layers and up to 50 positions:
logits of size ~1 agree to 3e-4 relative + 1e-4 absolute. State taken at a
bucket's end instead of the prompt's length, a leaked state or a tick that
moves a parked slot's state is off by 1e-2 to 1 (the tests break each and
see it).
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

from benchmarks.reference import jamba as ref  # noqa: E402
from test_hybrid_lm import TOY, engine_params, toy_model  # noqa: E402
from tpu_dist.engine import serve  # noqa: E402
from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,  # noqa: E402
                                   ServeEngine)

RTOL, ATOL = 3e-4, 1e-4
PROGRAMS = (serve._prefill_program, serve._tick_program,
            serve._chunk_prefill_program, serve._sample_first_program)


@pytest.fixture
def log(monkeypatch):
    """Every logits row the engine samples from, in order, between the
    markers the wrapped engine methods leave."""
    events = []
    real = serve._sample

    def recording(logits, temperature, rng, top_k=0, top_p=0.0):
        jax.debug.callback(lambda x: events.append(np.asarray(x)), logits)
        return real(logits, temperature, rng, top_k, top_p)

    for p in PROGRAMS:                 # traced with the real sampler before
        p.cache_clear()
    monkeypatch.setattr(serve, "_sample", recording)
    yield events
    for p in PROGRAMS:
        p.cache_clear()


def weights(seed=1, quantized=False):
    w = ref.make_weights(TOY, jax.random.PRNGKey(seed))
    if quantized:
        # what int8_wo serves: every projection's kernel through int8 with
        # a scale an output channel; the reference gets the same values
        from tpu_dist.ops.quant import dequantize, quantize_int8

        w = {k: dequantize(*quantize_int8(v, (0,)), jnp.float32)
             if v.ndim == 2 and k.split(".")[-1] not in (
                 "tok_emb", "conv_w", "A_log") else v for k, v in w.items()}
    return w


def engine(w, **cfg):
    model = toy_model()
    fields = dict(max_slots=2, page_size=4, num_pages=64, max_len=64)
    eng = ServeEngine(model, engine_params(model, w),
                      ServeConfig(**{**fields, **cfg}))
    return eng


def serve_recorded(eng, log, arrivals):
    """Drive ``eng`` step by step, submitting ``arrivals[step]`` before
    that step; returns ({rid: Completion}, {rid: [logits row a generated
    token]})."""
    rows = {}
    marks = []          # one entry a dispatch that samples: [(row, rid)]

    def wrap(name, mark):
        real = getattr(eng, name)

        def wrapped(*a, **kw):
            out = real(*a, **kw)
            owners = mark(out, *a, **kw)
            if owners is not None:
                marks.append(owners)
            return out

        setattr(eng, name, wrapped)

    # a step's prefills are issued one ahead of the read (PR 39): the
    # program's call, not the landing, is what the device runs in order
    wrap("_issue_prefill", lambda out, adm, send: [(0, adm.req.rid)])
    # a chunk that is not a prompt's last samples nothing (returns None)
    wrap("_dispatch_chunk",
         lambda tok, i, s: None if tok is None else [(0, s.req.rid)])
    wrap("_dispatch_tick",
         lambda _, active: [(i, s.req.rid) for i, s in active])
    done, step = {}, 0
    while arrivals or eng.queue or any(s is not None for s in eng.slots):
        for req in arrivals.pop(step, ()):
            assert eng.submit(req)
        for c in eng.step():
            done[c.rid] = c
        step += 1
        assert step < 500
    assert not eng._flights
    jax.effects_barrier()
    assert len(marks) == len(log)
    for owners, logged in zip(marks, log):
        for row, rid in owners:
            rows.setdefault(rid, []).append(logged[row])
    return done, rows


def check_against_reference(done, rows, w, rtol=RTOL, atol=ATOL):
    programs = ref.layer_programs(TOY)
    worst = 0.0
    for rid, c in done.items():
        want = np.asarray(ref.forward(
            w, jnp.asarray(c.tokens[None]), TOY, programs)[0])
        got = np.stack(rows[rid])
        assert got.shape[0] == c.n_generated
        # row t of the reference predicts token t + 1
        want = want[c.prompt_len - 1:c.prompt_len - 1 + c.n_generated]
        worst = max(worst, float(np.abs(got - want).max()))
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=f"request {rid}")
    return worst


def requests(lens, new=6, seed=0, first_rid=0):
    r = np.random.default_rng(seed)
    return [DecodeRequest(rid=first_rid + i,
                          prompt=r.integers(0, 256, n).astype(np.int32),
                          max_new_tokens=new + i) for i, n in enumerate(lens)]


def test_prompts_shorter_than_their_bucket(log):
    """Lengths 5, 11 and 20 in the 8, 16 and 32 buckets (16 and 32 run the
    scan kernel, interpreted): the state is the LAST LIVE token's."""
    w = weights()
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([5, 11, 20])})
    assert len(done) == 3
    check_against_reference(done, rows, w)
    assert eng.stats()["state_writes"] == 3


def test_state_taken_at_the_buckets_end_is_caught(log, monkeypatch):
    """The same run with the state write broken underneath: the scan and
    the convolution told the bucket's length, not the prompt's."""
    import tpu_dist.models.hybrid as hybrid

    real = hybrid.MambaMixer.__call__

    def at_bucket_end(self, h, paged):
        if paged is not None and h.shape[1] > 1:
            paged = {**paged, "live": jnp.full_like(paged["live"], h.shape[1])}
        return real(self, h, paged)

    monkeypatch.setattr(hybrid.MambaMixer, "__call__", at_bucket_end)
    w = weights()
    done, rows = serve_recorded(engine(w, max_slots=1), log,
                                {0: requests([11])})
    with pytest.raises(AssertionError):
        check_against_reference(done, rows, w)


def test_requests_admitted_while_others_decode(log):
    w = weights(seed=2)
    reqs = requests([9, 14, 6, 21, 12], new=5, seed=3)
    eng = engine(w, max_slots=3)
    done, rows = serve_recorded(
        eng, log, {0: reqs[:2], 3: reqs[2:3], 5: reqs[3:4], 6: reqs[4:]})
    assert len(done) == 5
    check_against_reference(done, rows, w)
    ticks = [sp for sp in serve.trace.ring().snapshot()
             if sp.name == "serve.tick"][-eng.ticks:]
    assert max(sp.attrs["state_slots"] for sp in ticks) == 3
    assert all(sp.attrs["state_slots"] == len(sp.attrs["rids"])
               for sp in ticks)


def test_a_steps_admissions_pipelined_over_slot_state(log):
    """Four requests of four buckets (8, 16, 32, 64) in the queue before
    ONE step: each prefill's program is called before the last one's token
    is read and writes its own slot's state. Every sampled row is the
    reference's, with all four admitted together and fed one a step."""
    w = weights(seed=6)
    tokens = {}
    for arrivals, ahead in (({0: requests([5, 11, 20, 40], new=4, seed=7)},
                             3),
                            ({k: [r] for k, r in enumerate(
                                requests([5, 11, 20, 40], new=4, seed=7))},
                             0)):
        del log[:]
        eng = engine(w, max_slots=4)
        done, rows = serve_recorded(eng, log, arrivals)
        assert len(done) == 4
        assert eng.stats()["prefills_ahead"] == ahead
        assert eng.stats()["state_writes"] == 4
        check_against_reference(done, rows, w)
        tokens[ahead] = {rid: c.tokens for rid, c in done.items()}
    for rid in tokens[0]:
        np.testing.assert_array_equal(tokens[0][rid], tokens[3][rid])


def test_a_reused_slot_holds_nothing_of_its_last_occupant(log):
    """One slot, three requests in turn: each admission's prefill starts
    from zero state and overwrites the slot's rows."""
    w = weights(seed=4)
    eng = engine(w, max_slots=1)
    done, rows = serve_recorded(eng, log, {0: requests([13, 7, 10], seed=5)})
    assert len(done) == 3 and eng.prefills == 3
    state = eng.pool.layers()[0]
    assert float(jnp.abs(state["ssm"]).max()) > 0      # the rows are used
    check_against_reference(done, rows, w)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_carries_the_state_from_chunk_to_chunk(log, chunk):
    """A prompt of 37 in chunks (the last one partly padding), admitted
    while another request decodes: the tick runs between the chunks and
    must leave the parked slot's state alone."""
    w = weights(seed=6)
    short, long_ = requests([6], new=12, seed=7), requests(
        [37], new=5, seed=8, first_rid=1)
    eng = engine(w, prefill_chunk=chunk)
    done, rows = serve_recorded(eng, log, {0: short, 2: long_})
    assert eng.chunk_ticks == -(-37 // chunk)
    assert eng.ticks > eng.chunk_ticks            # ticks ran between chunks
    check_against_reference(done, rows, w)


def test_a_tick_that_moves_a_parked_slots_state_is_caught(log, monkeypatch):
    """The hold broken underneath: the tick's mixer told every slot is
    live, so the slot parked between its chunks is stepped on token 0."""
    import tpu_dist.models.hybrid as hybrid

    real = hybrid.MambaMixer.__call__

    def all_live(self, h, paged):
        if paged is not None and paged.get("slots") is None:
            paged = {**paged, "live": jnp.ones_like(paged["live"])}
        return real(self, h, paged)

    monkeypatch.setattr(hybrid.MambaMixer, "__call__", all_live)
    w = weights(seed=6)
    short, long_ = requests([6], new=12, seed=7), requests(
        [37], new=5, seed=8, first_rid=1)
    done, rows = serve_recorded(engine(w, prefill_chunk=8), log,
                                {0: short, 2: long_})
    with pytest.raises(AssertionError):
        check_against_reference(done, rows, w)


def test_int8_weight_only_serving_agrees_with_the_reference_on_its_weights(log):
    w = weights(seed=9, quantized=True)
    raw = weights(seed=9)
    model = toy_model()
    eng = ServeEngine(model, engine_params(model, raw), ServeConfig(
        max_slots=2, page_size=4, num_pages=64, max_len=64, quant="int8_wo"))
    done, rows = serve_recorded(eng, log, {0: requests([10, 18], seed=10)})
    # the same tolerance: the engine dequantizes the very values the
    # reference is handed
    check_against_reference(done, rows, w)
    with pytest.raises(AssertionError):             # and int8 moved them
        check_against_reference(done, rows, raw)


def test_int8_kv_pages_serve_the_attention_layers(log):
    """``kv_quant="int8"`` applies to the page layers only; the slot state
    stays float32. Two attention layers' K and V at 8 bits move a logit by
    up to a few 1e-2."""
    w = weights(seed=11)
    eng = engine(w, kv_quant="int8")
    assert eng.pool.page_layers()[0].k.dtype == jnp.int8
    assert eng.pool.layers()[0]["ssm"].dtype == jnp.float32
    done, rows = serve_recorded(eng, log, {0: requests([12, 7], seed=12)})
    worst = check_against_reference(done, rows, w, rtol=0.0, atol=0.1)
    assert worst > ATOL


def _nemotron_h():
    """The Mamba-2 / latent-expert model's toy size and its parameters."""
    import test_nemotron_h_lm as nh

    model = nh.toy_model()
    return model, nh.engine_params(model, nh.ref.make_weights(
        nh.TOY, jax.random.PRNGKey(1)))


@pytest.mark.parametrize("fields,mechanism", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_k=2), "speculative"),
    (dict(mesh=True), "sp-sharded"),
    # Mamba-2 state is slot state too, and expert layers keep nothing
    (dict(prefix_cache=True, nemotron_h=True),
     "prefix_cache over slot state.*Mamba-1 or Mamba-2 layer's"),
    (dict(spec_k=2, nemotron_h=True),
     "speculative decoding.*Mamba-2's a head"),
    (dict(mesh=True, nemotron_h=True), "sp-sharded pool.*slot state"),
])
def test_what_has_no_meaning_over_slot_state_is_refused_by_name(fields,
                                                                mechanism):
    fields = dict(fields)
    if fields.pop("nemotron_h", None):
        model, params = _nemotron_h()
    else:
        model = toy_model()
        params = engine_params(model, weights())
    kw = {}
    if fields.pop("mesh", None):
        from tpu_dist.parallel.mesh import SP_AXIS, make_mesh

        kw["mesh"] = make_mesh((2,), (SP_AXIS,), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=mechanism):
        ServeEngine(model, params, ServeConfig(
            max_slots=2, page_size=4, num_pages=64, max_len=64, **fields),
            **kw)


def test_slot_state_is_allocated_once_and_counted():
    eng = engine(weights(), max_slots=3)
    st = eng.stats()
    # 6 Mamba layers x 3 slots x ([16, 128] f32 + [3, 128] f32)
    assert st["state_bytes"] == 6 * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert st["state_writes"] == 0 and eng.state_layers == 6
    assert eng.tick_read == "gathered"           # grouped heads: 2 under 4
    kinds = [type(l).__name__ for l in eng.pool.layers()]
    assert kinds == ["dict", "dict", "PagedLayer", "dict"] * 2
    assert eng.pool.page_layers()[0].k.shape == (65, 4, 2, 16)   # KV heads


@pytest.mark.parametrize("dtype,d_model,heads,read", [
    (jnp.float32, 32, 4, "gathered"),      # the toy models of test_serve.py
    (jnp.float32, 256, 2, "pages"),        # lane-wide heads: the kernel
    (jnp.bfloat16, 256, 2, "pages"),
])
def test_a_transformer_engine_builds_the_pool_it_had(dtype, d_model, heads,
                                                     read):
    """``TransformerLM.cache_layout`` answers pages of every head in every
    layer, so the engine's pool, its tick's read and its counters are what
    they were before the engine asked."""
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.models.transformer import tiny_lm

    model = tiny_lm(vocab_size=64, num_layers=3, d_model=d_model,
                    num_heads=heads, max_len=64, dtype=dtype)
    assert model.cache_layout() == (("pages", heads, d_model // heads, 1),) * 3
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    eng = ServeEngine(model, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=16, max_len=64))
    was = PagedKVPool(3, 16, 8, heads, d_model // heads, dtype=dtype)
    assert len(eng.pool.layers()) == 3
    for got, want in zip(eng.pool.layers(), was.layers()):
        assert (got.k.shape, got.k.dtype, got.quant, got.read) == (
            want.k.shape, want.k.dtype, want.quant, want.read)
    assert eng.tick_read == read
    assert eng.state_layers == 0 and eng.stats()["state_bytes"] == 0
    done = eng.run([DecodeRequest(rid=0, prompt=np.arange(5, dtype=np.int32),
                                  max_new_tokens=3)])
    assert len(done) == 1 and eng.stats()["state_writes"] == 0
    tick = [sp for sp in serve.trace.ring().snapshot()
            if sp.name == "serve.tick"][-1]
    assert tick.attrs["state_slots"] == 0


@pytest.mark.parametrize("slots", [1, 2])
def test_a_slot_that_ended_on_eos_leaves_nothing_to_its_next_occupant(slots):
    """The tick runs one ahead of the host, so a slot that ends on
    ``eos_id`` has been stepped once more when the host sees it: that token
    is dropped, the state row it moved is overwritten by the next prefill
    into the slot, and every completion is still the plain decode's."""
    from test_serve import plain_greedy

    # a tied head over full-size embeddings echoes the last token for ever;
    # smaller embeddings let the layers move the answer mid-way
    w = {k: 0.1 * v if k.endswith("tok_emb") else v
         for k, v in weights(seed=13).items()}
    model = toy_model()
    params = engine_params(model, w)
    fwd = jax.jit(lambda p, x: model.apply({"params": p}, x))
    reqs = requests([9, 12, 7, 10], new=6, seed=14)
    free = plain_greedy(fwd, params, reqs[0].prompt, 6, 64)
    eos = int(free[reqs[0].prompt.size + 2])
    refs = {r.rid: plain_greedy(fwd, params, r.prompt, r.max_new_tokens, 64,
                                eos) for r in reqs}
    eng = ServeEngine(model, params, ServeConfig(
        max_slots=slots, page_size=4, num_pages=64, max_len=64, eos_id=eos))
    done = {c.rid: c for c in eng.run(reqs)}
    assert sorted(done) == [0, 1, 2, 3]
    for rid, c in done.items():
        np.testing.assert_array_equal(refs[rid], c.tokens, str(rid))
    ended = [r for r in reqs if refs[r.rid][-1] == eos
             and 1 < len(refs[r.rid]) - r.prompt.size < r.max_new_tokens]
    assert ended and eng.stats()["overrun_tokens"] == len(ended)
    assert eng.pool.pages_free == eng.pool.num_pages and not eng._flights
