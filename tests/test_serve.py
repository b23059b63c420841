"""Continuous-batching serve engine + paged KV cache (engine.serve/kv_cache).

The pins that matter:
* greedy decode through the paged path is BIT-IDENTICAL to the contiguous
  flax-cache `generate` (fp32, bf16, int8_wo weights) — the paged cache is
  an allocator change, never a model change;
* mixed-length sequences fit a pool the contiguous per-slot allocator
  provably cannot (the fragmentation win paged caches exist for);
* the continuous schedule's completions per tick and occupancy over one
  request set are pinned (deterministic: both numbers are schedule math,
  not wall clocks);
* a forced overload sheds new work through SLO-aware admission control,
  emitting `slo` + rejection events that reach the flight recorder and the
  Prometheus gauges through the NORMAL sink fan-out (zero new plumbing);
* speculative decoding (round 16) emits BITWISE the non-speculative greedy
  stream for ANY draft — a perfect draft multiplies tokens/tick, a
  hostile draft degrades to >=1 token/tick, never to wrong tokens;
* copy-on-write prefix caching (round 16) maps repeated prompts onto
  shared refcounted pages at bit-identical output, forking only the one
  divergent frontier page — and the refcount discipline is pinned
  (double-free raises, sharing never inflates the footprint).
"""

import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.engine.generate import generate
from tpu_dist.engine.kv_cache import PagedKVPool
from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.obs.ledger import Ledger, read_ledger
from tpu_dist.parallel.mesh import SP_AXIS, make_mesh

V, L = 64, 32


def _lm_and_params(seed=0, **kw):
    lm = tiny_lm(vocab_size=V, num_layers=2, d_model=64, num_heads=4,
                 max_len=L, **kw)
    params = lm.init({"params": jax.random.PRNGKey(seed)},
                     jnp.zeros((1, L), jnp.int32), train=False)["params"]
    return lm, params


# ---------------------------------------------------------------- pool
def test_pool_alloc_free_and_high_water():
    pool = PagedKVPool(1, num_pages=8, page_size=4,
                       num_heads=2, head_dim=8)
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert len(a) == 3 and len(b) == 4 and not (set(a) & set(b))
    assert pool.alloc(2) is None          # 1 page left: all-or-nothing
    assert pool.pages_free == 1
    pool.free(a)
    assert pool.pages_free == 4
    assert pool.high_water_used == 7      # the peak, not the current
    # the trash page exists beyond the allocatable range
    assert pool.layers()[0].k.shape[0] == 9
    assert pool.pages_needed(9) == 3


def test_pool_validates_flash_needs_int8():
    with pytest.raises(ValueError, match="flash"):
        PagedKVPool(1, 8, 4, 2, 8, read="flash")


# ------------------------------------------------- bit-identity pins
def _assert_serve_matches_generate(lm, params, quant="none", n_reqs=2):
    """Per-request generate (the contiguous cache) vs one serve run over
    requests of MIXED prompt lengths — every token bitwise equal.
    ``n_reqs=1`` is the budget-lean variant for the dtype/quant twins
    (one reference program instead of two; the mixed-length coverage
    rides the fp32 run)."""
    prompts = [np.array([1, 9, 17], np.int32),
               np.array([5], np.int32)][:n_reqs]
    steps = [10, 12][:n_reqs]
    refs = [np.asarray(generate(lm, params, jnp.asarray(p[None]), steps=s,
                                use_cache=True, quant=quant))[0]
            for p, s in zip(prompts, steps)]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=16, quant=quant))
    comps = eng.run([DecodeRequest(i, p, s)
                     for i, (p, s) in enumerate(zip(prompts, steps))])
    assert len(comps) == n_reqs
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens)


def test_paged_greedy_bit_identical_to_generate():
    lm, params = _lm_and_params(seed=4)
    _assert_serve_matches_generate(lm, params)


# bfloat16 is the dtype every serving cell runs
def test_paged_greedy_bit_identical_bf16():
    lm, params = _lm_and_params(seed=5, dtype=jnp.bfloat16)
    _assert_serve_matches_generate(lm, params, n_reqs=1)


# int8_wo is the weight form every lower-precision control runs
def test_paged_greedy_bit_identical_int8_wo():
    lm, params = _lm_and_params(seed=6)
    _assert_serve_matches_generate(lm, params, quant="int8_wo", n_reqs=1)


@pytest.mark.parametrize("max_len", [24, 64, 100, 2048, 4096])
def test_default_buckets_cover_every_legal_prompt(max_len):
    """The prefill ladder ascends without repeats and ends in ``max_len``,
    so the longest prompt ``submit()`` lets in has a bucket; at the two
    toy sizes an engine serves such a prompt, through the ladder's last
    bucket, to the contiguous cache's tokens."""
    from tpu_dist.engine.serve import _default_buckets

    ladder = _default_buckets(max_len)
    assert list(ladder) == sorted(set(ladder)) and ladder[-1] == max_len
    assert all(b & (b - 1) == 0 for b in ladder[:-1])     # powers of two
    if max_len > 64:
        return
    lm = tiny_lm(vocab_size=V, num_layers=1, d_model=32, num_heads=2,
                 max_len=max_len)
    params = lm.init({"params": jax.random.PRNGKey(8)},
                     jnp.zeros((1, max_len), jnp.int32), train=False)["params"]
    new = 2
    prompt = ((np.arange(max_len - 1 - new, dtype=np.int32) * 7 + 3) % V)
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=8, num_pages=8))
    assert eng.buckets == ladder and prompt.size > ladder[-2]
    comps = eng.run([DecodeRequest(0, prompt, new)])
    assert eng.rejected == 0 and ("prefill", max_len) in eng._dispatched
    np.testing.assert_array_equal(
        _greedy_refs(lm, params, [prompt], [new])[0], comps[0].tokens)
    assert eng.pool.pages_free == eng.pool.num_pages


def test_paged_sampling_is_deterministic_given_rng():
    lm, params = _lm_and_params(seed=7)
    reqs = lambda: [DecodeRequest(0, np.array([3, 1, 4], np.int32), 8)]
    cfg = ServeConfig(max_slots=1, page_size=8, num_pages=8,
                      temperature=0.9)
    a = ServeEngine(lm, params, cfg,
                    rng=jax.random.PRNGKey(11)).run(reqs())[0]
    b = ServeEngine(lm, params, cfg,
                    rng=jax.random.PRNGKey(11)).run(reqs())[0]
    c = ServeEngine(lm, params, cfg,
                    rng=jax.random.PRNGKey(12)).run(reqs())[0]
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert int(a.tokens.max()) < V and int(a.tokens.min()) >= 0
    assert not np.array_equal(a.tokens, c.tokens)


# ------------------------------------------------- int8 KV pages
def test_int8_kv_exact_and_flash_kernel_agree():
    """The gathered-int8 exact read (dequant + fp attention) and the
    Pallas length-masked kernel decode the SAME tokens — the kernel is a
    bandwidth optimization of the identical math (interpret mode off-TPU,
    like every Pallas test in this suite)."""
    lm, params = _lm_and_params(seed=8)
    req = lambda: [DecodeRequest(0, np.array([1, 9, 17, 25], np.int32), 10)]
    outs = {}
    for read in ("exact", "flash"):
        eng = ServeEngine(lm, params, ServeConfig(
            max_slots=1, page_size=8, num_pages=8, kv_quant="int8",
            attn_read=read))
        outs[read] = eng.run(req())[0].tokens
        assert int(outs[read].max()) < V
    np.testing.assert_array_equal(outs["exact"], outs["flash"])


# ------------------------------------------------- fragmentation pin
def test_mixed_lengths_fit_where_contiguous_cannot():
    """4 concurrent sequences with totals {32, 12, 8, 8} need 15 pages of
    4; a contiguous max_len-per-slot allocator would preallocate 32. A
    20-page pool therefore fits the paged layout and provably not the
    contiguous one — and the run completes with every sequence resident
    at once."""
    lm, params = _lm_and_params(seed=9)
    pool_pages = 20
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=4, page_size=4, num_pages=pool_pages))
    assert eng.pool.contiguous_pages_needed(4, L) > pool_pages
    reqs = [DecodeRequest(0, np.arange(16, dtype=np.int32) % V, 16),
            DecodeRequest(1, np.array([7, 8, 9, 10], np.int32), 8),
            DecodeRequest(2, np.array([1, 2], np.int32), 6),
            DecodeRequest(3, np.array([3, 4], np.int32), 6)]
    comps = eng.run(reqs)
    assert len(comps) == 4
    assert {c.rid for c in comps} == {0, 1, 2, 3}
    # all four were admitted before any finished (truly concurrent)
    assert eng.pool.high_water_used == 8 + 3 + 2 + 2
    assert eng.pool.pages_free == pool_pages  # everything reclaimed


# ------------------------------------------------- perf pin
def test_continuous_batching_schedule_pin():
    """Twelve requests through four slots, every slot refilled the step
    after its sequence ends: 116 tokens, 12 of them the prefills' own, so
    104 slot-ticks. The schedule packs them into 32 ticks (26 is the
    least four slots could take) at an occupancy of 104 / (4 x 32); both
    numbers are pure schedule arithmetic, deterministic on any machine."""
    lm, params = _lm_and_params(seed=10)
    rng = np.random.default_rng(0)
    reqs = [DecodeRequest(
        i, rng.integers(0, V, (int(rng.integers(2, 8)),)).astype(np.int32),
        int(rng.integers(2, 20))) for i in range(12)]
    assert sum(r.max_new_tokens for r in reqs) == 116
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=4, page_size=8, num_pages=64))
    comps = eng.run(reqs)
    assert len(comps) == 12
    assert eng.ticks == 32
    assert eng.occupancy == 104 / (4 * 32) == 0.8125


# ------------------------------------------------- admission + overload
def test_admission_rejects_impossible_requests():
    lm, params = _lm_and_params(seed=11)
    led_records = []
    ledger = Ledger(None, sinks=(led_records.append,))
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=4), ledger=ledger)
    # prompt + max_new beyond max_len
    assert not eng.submit(DecodeRequest(0, np.arange(30, dtype=np.int32),
                                        30))
    # needs more pages than the whole pool (but within max_len)
    assert not eng.submit(DecodeRequest(1, np.arange(20, dtype=np.int32),
                                        8))
    reasons = [r.get("reason") for r in led_records
               if r["event"] == "admit"]
    assert reasons == ["too_long", "exceeds_pool"]
    assert eng.rejected == 2


@pytest.mark.parametrize("prompt_len, new, pages, reason", [
    (0, 4, 8, "too_long"),               # nothing to continue
    (4, 0, 8, "too_long"),               # nothing to generate
    (L - 3, 4, 8, "too_long"),           # one token past max_len
    (L - 4, 4, 8, None),                 # max_len exactly
    (17, 4, 5, "exceeds_pool"),          # six pages of four, five held
    (16, 4, 5, None),                    # the whole pool, to the page
], ids=["empty_prompt", "zero_new", "past_max_len", "at_max_len",
        "past_pool", "at_pool"])
def test_submit_checks_a_requests_geometry_at_the_door(prompt_len, new,
                                                       pages, reason):
    """``submit()`` refuses what no slot could ever serve, with the reason
    on the ``admit`` event, and queues the largest request that one can:
    the bounds are ``max_len`` and the pool, both inclusive."""
    lm, params = _lm_and_params(seed=11)
    led_records = []
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=pages),
        ledger=Ledger(None, sinks=(led_records.append,)))
    took = eng.submit(DecodeRequest(0, np.arange(prompt_len, dtype=np.int32),
                                    new))
    admit, = [r for r in led_records if r["event"] == "admit"]
    assert took == admit["accepted"] == (reason is None)
    assert admit.get("reason") == reason
    assert (eng.rejected, len(eng.queue)) == ((0, 1) if took else (1, 0))


def test_overload_sheds_emits_slo_and_fires_flightrec(tmp_path):
    """Queue overload: the wait EMA breaches the SLO floor -> one `slo`
    event (which auto-triggers the flight recorder through the existing
    sink fan-out), shedding rejects new submits with `slo_shedding`, and
    the serving gauges land in the metrics registry — all through the
    standard ledger plumbing, zero serve-specific wiring."""
    from tpu_dist.obs.flightrec import FlightRecorder
    from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink

    lm, params = _lm_and_params(seed=12)
    path = str(tmp_path / "serve.jsonl")
    ledger = Ledger(path)
    reg = MetricsRegistry()
    ledger.add_sink(metrics_ledger_sink(reg))
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=ledger,
                        trace_steps=0)
    ledger.add_sink(fr.sink)
    # a virtual clock that leaps 1s per reading: every queued request
    # accumulates huge waits, so the EMA breaches the 0.5s floor as soon
    # as min_samples admissions have happened
    clock = itertools.count()
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=8, queue_depth_max=3,
        slo_queue_wait_s=0.5, slo_min_samples=1),
        ledger=ledger, now_fn=lambda: float(next(clock)))
    reqs = [DecodeRequest(i, np.array([1, 2, 3], np.int32), 4)
            for i in range(10)]
    accepted = [eng.submit(r) for r in reqs]
    assert not all(accepted)              # queue cap rejected some
    # step until the wait EMA breaches and shedding engages, then a fresh
    # submit is rejected for the SLO (not the queue cap)
    for _ in range(50):
        eng.step()
        if eng.shedding:
            break
    assert eng.shedding
    assert not eng.submit(DecodeRequest(99, np.array([1], np.int32), 2))
    # drain; idle decay then re-arms the breach (hysteresis downswing) —
    # a transient overload must not shed forever
    for _ in range(200):
        eng.step()
        if not eng.shedding and not eng.queue \
                and not any(s is not None for s in eng.slots):
            break
    assert not eng.shedding
    assert eng.submit(DecodeRequest(100, np.array([1], np.int32), 2))
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    ledger.close()
    recs = read_ledger(path)
    events = [r["event"] for r in recs]
    assert "slo" in events
    rejected = [r for r in recs if r["event"] == "admit"
                and not r["accepted"]]
    assert {r.get("reason") for r in rejected} >= {"queue_full",
                                                   "slo_shedding"}
    diags = [r for r in recs if r["event"] == "diagnosis"]
    assert diags and diags[0]["reason"] == "slo"
    assert os.path.isdir(diags[0]["bundle"])
    # the scrape carries the serving series
    scrape = reg.render()
    assert "tpu_dist_serve_queue_depth" in scrape
    assert "tpu_dist_kv_pages_free" in scrape
    assert reg.read_value("tpu_dist_serve_rejected_total") >= 2
    assert reg.read_value("tpu_dist_serve_requests_total") >= 1


# ------------------------------------------------- obs + report
def test_request_events_render_in_ledger_report(tmp_path):
    lm, params = _lm_and_params(seed=13)
    path = str(tmp_path / "serve.jsonl")
    ledger = Ledger(path)
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=16, kv_event_every=1),
        ledger=ledger)
    comps = eng.run([DecodeRequest(i, np.array([1 + i, 5, 9], np.int32), 6)
                     for i in range(4)])
    ledger.close()
    assert len(comps) == 4
    recs = read_ledger(path)
    reqs = [r for r in recs if r["event"] == "request"]
    assert len(reqs) == 4
    for r in reqs:
        assert r["finish_ts"] >= r["first_token_ts"] >= r["admit_ts"]
        assert r["tokens"] == 6
    from tools.ledger_report import summarize

    summary = summarize(recs, out=lambda s: None)
    srv = summary["decode"]["serving"]
    assert srv["completed"] == 4 and srv["rejected"] == 0
    assert srv["queue_wait_s"]["p50"] is not None
    assert srv["ttft_s"]["p99"] >= srv["ttft_s"]["p50"]
    assert 0 < srv["occupancy"] <= 1


# ------------------------------------------------- quantize memo (bugfix)
def test_quantize_for_decode_lru_survives_alternating_trees():
    """The round-9 memo held ONE entry keyed on leaf identities: a server
    alternating two live base trees re-quantized on every call. The LRU
    keyed per (treedef, mode, leaves) must quantize each tree once."""
    import tpu_dist.ops.quant as quant_mod
    from tpu_dist.engine.generate import _quantize_for_decode

    lm, params_a = _lm_and_params(seed=14)
    _, params_b = _lm_and_params(seed=15)
    calls = []
    orig = quant_mod.wo_quantize_params
    quant_mod.wo_quantize_params = lambda p: (calls.append(1), orig(p))[1]
    try:
        for _ in range(3):
            _quantize_for_decode(lm, params_a, "int8_wo")
            _quantize_for_decode(lm, params_b, "int8_wo")
    finally:
        quant_mod.wo_quantize_params = orig
    assert len(calls) == 2, f"expected one quantization per tree, " \
                            f"got {len(calls)}"


# ------------------------------------------------- graceful drain (round 13)
def test_drain_finishes_inflight_sheds_queue_and_frees_pages():
    """Graceful preemption drain: in-flight sequences run to completion
    (their pages were paid for), queued requests are rejected with a
    `shed` admission record, the pool ends fully free, and a `run_end`
    lands — a drained server, not a mid-tick corpse."""
    lm, params = _lm_and_params(seed=13)
    led_records = []
    ledger = Ledger(None, sinks=(led_records.append,))
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=32), ledger=ledger)
    reqs = [DecodeRequest(i, np.array([1, 2, 3], np.int32), 6)
            for i in range(6)]
    for r in reqs:
        assert eng.submit(r)
    eng.step()  # two slots prefilled + one decode tick; four still queued
    inflight = {s.req.rid for s in eng.slots if s is not None}
    assert len(inflight) == 2 and len(eng.queue) == 4
    comps = eng.drain(reason="sigterm")
    # the two in-flight sequences finished their full generation
    assert {c.rid for c in comps} == inflight
    assert all(c.n_generated == 6 for c in comps)
    # the queue was shed with per-request admission records
    shed = [r for r in led_records if r["event"] == "admit"
            and r.get("reason") == "shed"]
    assert len(shed) == 4
    assert eng.pool.pages_free == eng.pool.num_pages  # everything reclaimed
    ends = [r for r in led_records if r["event"] == "run_end"]
    assert len(ends) == 1 and ends[0]["status"] == "preempted"
    assert ends[0]["shed"] == 4 and ends[0]["completed"] == 2
    scales = [r for r in led_records if r["event"] == "scale"]
    assert [s["action"] for s in scales] == ["drain"]
    # draining is sticky: new submits shed, a second drain is a no-op
    assert not eng.submit(DecodeRequest(99, np.array([1], np.int32), 2))
    assert eng.drain() == []
    assert sum(1 for r in led_records if r["event"] == "run_end") == 1


# ------------------------------------- speculative decoding (round 16)
def _greedy_refs(lm, params, prompts, steps, quant="none"):
    return [np.asarray(generate(lm, params, jnp.asarray(p[None]), steps=s,
                                use_cache=True, quant=quant))[0]
            for p, s in zip(prompts, steps)]


def test_spec_decode_greedy_bit_identical_to_generate():
    """Self-speculation (draft == base) with k=3 over mixed-length
    requests: every emitted stream is BITWISE the non-speculative greedy
    decode — speculation is a throughput optimization, never a model
    change — and an always-agreeing draft clears >1 token per slot-tick,
    finishing in strictly fewer ticks than one-token-per-tick decode."""
    lm, params = _lm_and_params(seed=16)
    prompts = [np.array([1, 9, 17], np.int32), np.array([5], np.int32)]
    steps = [10, 12]
    refs = _greedy_refs(lm, params, prompts, steps)
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=16, spec_k=3))
    comps = eng.run([DecodeRequest(i, p, s)
                     for i, (p, s) in enumerate(zip(prompts, steps))])
    assert len(comps) == 2
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens)
    assert eng.accepted_per_tick > 1.0
    assert eng.ticks < max(steps)         # sublinear in emitted tokens


@pytest.mark.slow  # tier-1 budget (PR 20): quantized twin of test_spec_decode_greedy_bit_identical_to_generate (in-budget); the int8_wo path itself stays pinned by test_int8_kv_exact_and_flash_kernel_agree
def test_spec_decode_bit_identical_int8_wo():
    """The quantized twin: the draft rides the same int8_wo tree through
    the memoized quantize path; the verified stream stays bitwise the
    quantized ``generate``."""
    lm, params = _lm_and_params(seed=17)
    prompts = [np.array([2, 11, 23], np.int32)]
    refs = _greedy_refs(lm, params, prompts, [10], quant="int8_wo")
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=8, num_pages=16, quant="int8_wo",
        spec_k=2))
    comps = eng.run([DecodeRequest(0, prompts[0], 10)])
    np.testing.assert_array_equal(refs[0], comps[0].tokens)
    assert eng.accepted_per_tick > 1.0


def test_spec_reject_storm_still_progresses_bit_identical():
    """A deliberately wrong draft (same architecture, different random
    init) rejects nearly every proposal. The emission rule still commits
    the base model's own greedy correction every tick — >=1 token per
    slot-tick, output bitwise the non-speculative stream. A bad draft
    costs throughput, never correctness."""
    lm, params = _lm_and_params(seed=18)
    _, draft_params = _lm_and_params(seed=99)  # same shape, wrong weights
    prompts = [np.array([1, 2, 3], np.int32)]
    refs = _greedy_refs(lm, params, prompts, [10])
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=8, num_pages=16, spec_k=3),
        draft_model=lm, draft_params=draft_params)
    comps = eng.run([DecodeRequest(0, prompts[0], 10)])
    np.testing.assert_array_equal(refs[0], comps[0].tokens)
    assert eng.accepted_per_tick >= 1.0   # the progress floor
    assert eng.accepted_per_tick < 3.0    # the storm actually rejected


def test_spec_guards_reject_bad_configs():
    lm, params = _lm_and_params(seed=19)
    small = tiny_lm(vocab_size=32, num_layers=1, d_model=32, num_heads=2,
                    max_len=L)
    small_params = small.init({"params": jax.random.PRNGKey(0)},
                              jnp.zeros((1, L), jnp.int32),
                              train=False)["params"]
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(lm, params, ServeConfig(spec_k=2),
                    draft_model=small, draft_params=small_params)
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(lm, params, ServeConfig(),
                    draft_model=lm, draft_params=params)
    with pytest.raises(ValueError, match="temperature"):
        ServeEngine(lm, params, ServeConfig(spec_k=2, temperature=0.5))


# ------------------------------------- CoW prefix caching (round 16)
def test_prefix_cache_cow_bit_identical_and_saves_pages():
    """Three requests with the SAME 18-token prompt (page_size 4: four
    full pages + a 2-token frontier) under ``prefix_cache``: outputs are
    bitwise the uncached greedy stream, the 2nd/3rd admission map the
    hot prompt onto shared pages, and each forks exactly ONE page — the
    frontier it is about to overwrite. Fresh allocations drop from 18
    (3x6 unshared) to 10, the pinned sublinear footprint."""
    lm, params = _lm_and_params(seed=20)
    prompt = ((np.arange(18, dtype=np.int32) * 5 + 3) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [6])[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=3, page_size=4, num_pages=32, prefix_cache=True))
    comps = eng.run([DecodeRequest(i, prompt, 6) for i in range(3)])
    assert len(comps) == 3
    for c in comps:
        np.testing.assert_array_equal(ref, c.tokens)
    pool = eng.pool
    assert pool.alloc_total == 6 + 2 + 2   # vs 18 without sharing
    assert pool.cow_copies == 2            # one frontier fork per sharer
    assert pool.prefix_hits == 10          # 5 prompt pages x 2 sharers
    assert eng.prefix_hit_rate == pytest.approx(10 / 15)
    assert eng.stats()["pages_per_request"] == pytest.approx(10 / 3)
    assert pool.pages_free == pool.num_pages   # cached pages still count


def test_spec_and_prefix_cache_compose_bit_identical():
    """Both round-16 features on at once (the serving configuration the
    bench publishes): shared-prefix admissions + speculative ticks still
    produce the exact non-speculative, uncached token streams."""
    lm, params = _lm_and_params(seed=21)
    prompt = ((np.arange(9, dtype=np.int32) * 7 + 1) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [8])[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=32, spec_k=2,
        prefix_cache=True))
    comps = eng.run([DecodeRequest(i, prompt, 8) for i in range(2)])
    assert len(comps) == 2
    for c in comps:
        np.testing.assert_array_equal(ref, c.tokens)
    assert eng.accepted_per_tick > 1.0
    assert eng.pool.prefix_hits > 0


# ------------------------------------- pool refcounts + heap (round 16)
def test_pool_refcount_double_free_and_leak_pins():
    """The CoW refcount discipline: double-free raises (a silently
    recycled page corrupts another sequence's cache), a shared page
    survives its first holder's release, and a full share/release cycle
    leaks nothing — high_water_used stays at the unshared peak because
    sharing never inflates the physical footprint."""
    pool = PagedKVPool(1, num_pages=8, page_size=4,
                       num_heads=2, head_dim=8)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError, match="double-free"):
        pool.free(a)
    prompt = np.arange(8, dtype=np.int32)     # two full pages
    b = pool.alloc(2)
    pool.register_prefix(prompt, b)
    m = pool.share_prefix(prompt)
    assert m.full == 2 and not m.partial and m.pages == b
    assert pool.shared_pages == 2
    pool.free(b)                  # first holder out: pages stay live
    assert pool.shared_pages == 0 and pool.pages_used == 2
    pool.free(m.pages)            # last ref: parked as reclaimable cache
    assert pool.pages_used == 0
    assert pool.pages_free == pool.num_pages      # no leak
    assert pool.high_water_used == 2              # sharing added nothing
    with pytest.raises(ValueError, match="double-free"):
        pool.free(m.pages)


def test_pool_heap_grants_lowest_index_first():
    """Round 16 swapped the free list's O(n log n) full-sort-per-free
    for a heap; the observable grant order is pinned unchanged —
    lowest index first, whatever order pages came back in."""
    pool = PagedKVPool(1, num_pages=8, page_size=4,
                       num_heads=2, head_dim=8)
    assert pool.alloc(6) == [0, 1, 2, 3, 4, 5]
    pool.free([4, 1, 3])
    assert pool.alloc(3) == [1, 3, 4]
    pool.free([5, 0, 2])
    assert pool.alloc(4) == [0, 2, 5, 6]


def test_sigterm_routes_run_into_drain():
    """The preemption signal itself: install_sigterm_drain() turns
    SIGTERM into a flag, run() finishes the tick and drains instead of
    dying mid-tick (engine/serve.py round-11 behavior)."""
    import os
    import signal as _signal

    lm, params = _lm_and_params(seed=14)
    led_records = []
    ledger = Ledger(None, sinks=(led_records.append,))
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=16), ledger=ledger)
    uninstall = eng.install_sigterm_drain()
    try:
        for i in range(4):
            assert eng.submit(DecodeRequest(i, np.array([1, 2], np.int32),
                                            4))
        eng.step()  # slot 0 in flight
        os.kill(os.getpid(), _signal.SIGTERM)  # the scheduler's notice
        comps = eng.run()  # would have processed all 4 without the signal
    finally:
        uninstall()
    # only the in-flight request finished; the rest were shed
    assert {c.rid for c in comps} == {0}
    shed = [r for r in led_records if r["event"] == "admit"
            and r.get("reason") == "shed"]
    assert len(shed) == 3
    assert eng.pool.pages_free == eng.pool.num_pages
    assert [r["status"] for r in led_records
            if r["event"] == "run_end"] == ["preempted"]
    # the handler was restored by uninstall
    assert _signal.getsignal(_signal.SIGTERM) not in (None,)


# ------------------- long-context serving plane (round 19)
def _sp_mesh(n):
    return make_mesh((n,), (SP_AXIS,), devices=jax.devices()[:n])


def test_chunked_prefill_bit_identical_fp32():
    """Chunked prefill (prefill_chunk=8) over MIXED prompt lengths emits
    token-for-token the monolithic greedy stream: each chunk writes its
    rows through the same per-row-position write mask the decode tick
    uses and re-reads the earlier chunks' pages, so splitting the prompt
    changes scheduling, never bits."""
    lm, params = _lm_and_params(seed=22)
    prompts = [((np.arange(13, dtype=np.int32) * 5 + 2) % V),
               ((np.arange(18, dtype=np.int32) * 3 + 7) % V)]
    refs = _greedy_refs(lm, params, prompts, [6, 6])
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=32, prefill_chunk=8))
    comps = eng.run([DecodeRequest(i, p, 6) for i, p in enumerate(prompts)])
    assert len(comps) == 2
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens)
    # ceil(13/8) + ceil(18/8) chunk dispatches, one per iteration
    assert eng.chunk_ticks == 2 + 3
    assert eng.chunks_pending == 0
    assert eng.pool.pages_free == eng.pool.num_pages


def test_chunked_prefill_bit_identical_int8_wo():
    """Quant twin of the chunked pin: int8 weight-only serving (the
    deployment quant) chunks to the same tokens as its monolithic self.
    (int8 KV pages are the documented exception — chunked re-READS
    quantized rows monolithic prefill never quantizes.)"""
    lm, params = _lm_and_params(seed=23)
    prompt = ((np.arange(11, dtype=np.int32) * 7 + 1) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [5], quant="int8_wo")[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=16, prefill_chunk=4,
        quant="int8_wo"))
    comps = eng.run([DecodeRequest(0, prompt, 5)])
    np.testing.assert_array_equal(ref, comps[0].tokens)
    assert eng.chunk_ticks == 3


def test_chunked_prefill_interleaves_with_decode():
    """The scheduling contract itself: while a long prompt chunks in, the
    already-decoding request keeps emitting one token per iteration — the
    chunk rides the SAME scheduler step as the decode tick, it never
    stalls the stream. Deterministic: pure schedule math, no clocks."""
    lm, params = _lm_and_params(seed=24)
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=32, prefill_chunk=4))
    assert eng.submit(DecodeRequest(0, np.array([1, 2, 3], np.int32), 12))
    eng.step()                       # short admitted + first token
    short = eng.slots[0]
    assert short is not None and short.generated >= 1
    long_prompt = ((np.arange(17, dtype=np.int32) * 5 + 3) % V)
    assert eng.submit(DecodeRequest(1, long_prompt, 4))
    gen_before, ticks_before = short.generated, eng.ticks
    eng.step()                       # long admitted; chunk 1 + decode tick
    assert eng.chunk_ticks == 1
    assert eng.ticks == ticks_before + 1          # decode never skipped
    assert short.generated == gen_before + 1
    assert eng.chunks_pending == 4                # ceil(17/4) - 1 to go
    eng.run()                                     # drain both
    assert eng.completed == 2


def test_sp_prefill_bit_identical_fp32():
    """Sequence-parallel prefill over a 2-device sp mesh (ring attention
    inside shard_map, each device scattering K/V into ITS local pages)
    emits token-for-token the single-device stream — and a short prompt
    below the threshold rides the monolithic program over the SAME
    sharded pool (the flat block-table translation is exact either way)."""
    lm, params = _lm_and_params(seed=25)
    prompts = [((np.arange(12, dtype=np.int32) * 5 + 2) % V),
               np.array([5, 9], np.int32)]
    refs = _greedy_refs(lm, params, prompts, [6, 6])
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=8, sp_prefill_threshold=9),
        mesh=_sp_mesh(2))
    comps = eng.run([DecodeRequest(i, p, 6) for i, p in enumerate(prompts)])
    assert len(comps) == 2
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens)
    assert eng.sp_prefills == 1          # only the 12-token prompt
    assert eng.pool.sharded_devices == 2
    assert eng.pool.pages_free == eng.pool.num_pages


def test_sp_prefill_bit_identical_int8_wo():
    """Quant twin of the sp pin: int8 weight-only + sp-sharded prefill
    still matches single-device int8_wo greedy bit-for-bit."""
    lm, params = _lm_and_params(seed=26)
    prompt = ((np.arange(14, dtype=np.int32) * 3 + 5) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [5], quant="int8_wo")[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=8, num_pages=8, sp_prefill_threshold=9,
        quant="int8_wo"), mesh=_sp_mesh(2))
    comps = eng.run([DecodeRequest(0, prompt, 5)])
    np.testing.assert_array_equal(ref, comps[0].tokens)
    assert eng.sp_prefills == 1


def test_sp_context_exceeds_single_device_page_budget():
    """The capacity headline: a 4-device sp pool serves a context LONGER
    than any one device's page budget (23 tokens vs 8 per device), with
    tokens bitwise the unsharded stream — KV capacity scales with the
    mesh, which is what the sharded pool exists for. Eviction then
    returns every striped page to its owner's heap (second admit runs
    on a fully reclaimed pool)."""
    lm, params = _lm_and_params(seed=27)
    prompt = ((np.arange(17, dtype=np.int32) * 5 + 1) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [6])[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=8, sp_prefill_threshold=9),
        mesh=_sp_mesh(4))
    budget = eng.pool.pages_per_device * eng.cfg.page_size
    assert prompt.size + 6 > budget      # the context one device can't hold
    for _ in range(2):                   # second wave = reclaim proof
        comps = eng.run([DecodeRequest(0, prompt, 6)])
        np.testing.assert_array_equal(ref, comps[0].tokens)
        assert eng.pool.pages_free == eng.pool.num_pages
    assert eng.sp_prefills == 2


def test_sp_and_chunked_guards():
    """Config guards: sp needs a mesh with the 'sp' axis and an sp-bucket-
    divisible max_len; speculative decoding over a sharded pool is the
    named residue and refuses loudly instead of corrupting pages."""
    lm, params = _lm_and_params(seed=28)
    with pytest.raises(ValueError, match="mesh"):
        ServeEngine(lm, params, ServeConfig(sp_prefill_threshold=8))
    with pytest.raises(ValueError, match="sp"):
        ServeEngine(lm, params, ServeConfig(),
                    mesh=make_mesh((2,), ("data",),
                                   devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(lm, params, ServeConfig(
            sp_prefill_threshold=8, page_size=4, max_len=28),
            mesh=_sp_mesh(4))
    with pytest.raises(NotImplementedError, match="speculative"):
        ServeEngine(lm, params, ServeConfig(spec_k=2), mesh=_sp_mesh(2))


def test_chunked_prefix_cache_compose_bit_identical():
    """Chunked prefill + CoW prefix caching: a LATER identical prompt
    maps onto the first one's pages — registered only at the FINAL chunk
    (a partial prompt must never be shareable, so two concurrent chunked
    admits of the same prompt correctly miss) — and both streams stay
    bitwise the uncached monolithic greedy."""
    lm, params = _lm_and_params(seed=29)
    prompt = ((np.arange(13, dtype=np.int32) * 5 + 3) % V).astype(np.int32)
    ref = _greedy_refs(lm, params, [prompt], [6])[0]
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=32, prefill_chunk=4,
        prefix_cache=True))
    for _ in range(2):
        comps = eng.run([DecodeRequest(0, prompt, 6)])
        np.testing.assert_array_equal(ref, comps[0].tokens)
    assert eng.pool.prefix_hits > 0      # second admit rode shared pages
    assert eng.pool.cow_copies == 1
    assert eng.chunk_ticks >= 4          # both admissions chunked


def test_kv_cache_event_carries_serving_plane_fields(tmp_path):
    """The ledger contract the report + DL006 fixtures lean on: every
    kv_cache event now carries sharded_devices and chunks_pending (and
    the cumulative chunk_ticks for the occupancy trend) — mid-chunking
    snapshots show a nonzero backlog, the final one shows it drained."""
    lm, params = _lm_and_params(seed=30)
    path = tmp_path / "ledger.jsonl"
    ledger = Ledger(str(path))
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=1, page_size=4, num_pages=32, prefill_chunk=4,
        kv_event_every=1), ledger=ledger)
    prompt = ((np.arange(17, dtype=np.int32) * 3 + 2) % V)
    eng.submit(DecodeRequest(0, prompt, 4))
    depths = []
    while eng.queue or any(s is not None for s in eng.slots):
        depths.append(eng.chunks_pending)
        eng.step()
    eng._emit_kv_cache()
    ledger.close()
    kv = [r for r in read_ledger(str(path)) if r["event"] == "kv_cache"]
    assert kv, "no kv_cache events"
    for r in kv:
        assert r["sharded_devices"] == 1
        assert "chunks_pending" in r and "chunk_ticks" in r
        assert r["ticks_ahead"] <= r["tick"] and r["overrun_tokens"] == 0
    assert max(depths) > 0               # backlog was visible mid-flight
    assert kv[-1]["chunks_pending"] == 0
    assert kv[-1]["chunk_ticks"] == 5    # ceil(17/4)


# ------------------------------------------------------ program spans
@pytest.fixture(scope="module")
def span_lm():
    return _lm_and_params(seed=31)


_SPAN_MODES = {
    "plain": dict(max_slots=2, page_size=8, num_pages=16),
    "traced": dict(max_slots=2, page_size=8, num_pages=16),   # + a ledger
    "chunked": dict(max_slots=2, page_size=4, num_pages=32, prefill_chunk=8),
    "speculative": dict(max_slots=2, page_size=8, num_pages=16, spec_k=3),
}


@pytest.mark.parametrize("mode", list(_SPAN_MODES))
def test_step_spans_nest_and_every_token_has_a_time(span_lm, mode):
    """One ``serve.step`` a ``step()``, its phases as children that lie
    inside it, a request's spans under one identifier, and per token one
    engine-clock time that lies inside the span that produced it: all on
    the engine's (here virtual, strictly increasing) clock."""
    from tpu_dist.obs import trace

    lm, params = span_lm
    clock = itertools.count()
    ledger = Ledger(None) if mode == "traced" else None
    eng = ServeEngine(lm, params, ServeConfig(**_SPAN_MODES[mode]),
                      ledger=ledger, now_fn=lambda: float(next(clock)))
    prompts = [((np.arange(13, dtype=np.int32) * 5 + 2) % V),
               ((np.arange(18, dtype=np.int32) * 3 + 7) % V)]
    with trace.ring().span("mark") as mark:
        pass
    for i, p in enumerate(prompts):
        assert eng.submit(DecodeRequest(i, p, 6))
    comps, n_steps = [], 0
    while eng.queue or any(s is not None for s in eng.slots):
        comps += eng.step()
        n_steps += 1
    # a full garbage collection that lands in the run is a span too, on
    # the real clock (tests/test_trace_gc.py): not this test's
    spans = [sp for sp in trace.ring().snapshot()
             if sp.sid > mark.sid and sp.name != "host.gc"]
    by_sid = {sp.sid: sp for sp in spans}
    kids = {}
    for sp in spans:                      # ring order: by closing time
        kids.setdefault(sp.parent, []).append(sp)
    names = lambda sid: [k.name for k in sorted(kids.get(sid, ()),
                                                key=lambda k: k.start)]
    steps = [sp for sp in spans if sp.name == "serve.step"]
    assert len(steps) == n_steps and all(s.parent is None for s in steps)
    assert [s.attrs["tick"] for s in steps] == sorted(
        s.attrs["tick"] for s in steps)
    chunked = mode == "chunked"
    for st in steps:
        assert set(st.attrs) == {"tick", "n_active", "queue_depth"}
        got = names(st.sid)
        assert got[:2] == ["serve.evict", "serve.admit"]
        assert got[2:] in ([], ["serve.tick"],
                           *([["serve.prefill"],
                              ["serve.prefill", "serve.tick"]]
                             if chunked else []))
    for sp in spans:                      # children lie inside parents
        if sp.parent is not None:
            par = by_sid[sp.parent]
            assert par.start < sp.start < sp.end < par.end, (sp, par)
    prefills = [sp for sp in spans if sp.name == "serve.prefill"]
    for pf in prefills:
        assert by_sid[pf.parent].name == ("serve.step" if chunked
                                          else "serve.admit")
        assert {"rid", "trace_id", "prompt_len", "bucket",
                "shared_len"} <= set(pf.attrs)
        want = (eng.tracer.trace_id(pf.attrs["rid"]) if mode == "traced"
                else pf.attrs["rid"])
        assert pf.attrs["trace_id"] == want
        assert names(pf.sid) in (["prefill.dispatch", "prefill.behind",
                                  "prefill.wait"],
                                 *([["prefill.dispatch"]] if chunked else []))
        # a prefill that was waited for says when its program was issued
        # and how long the tick in flight held it back: its child's length
        waited = names(pf.sid)[-1] == "prefill.wait"
        assert ({"issued", "behind_s"} <= set(pf.attrs)) == waited
        if waited:
            send, behind, _ = sorted(kids[pf.sid], key=lambda k: k.start)
            if pf.attrs["ahead"]:
                # called inside the prefill before it: the device can
                # have begun it when that one had landed, not earlier
                assert pf.attrs["issued"] == pf.start
            else:
                assert send.start < pf.attrs["issued"] < send.end
            assert pf.attrs["behind_s"] == behind.end - behind.start
    # both requests are in the queue at the first step: the second
    # monolithic prefill is called ahead of the first one's read
    assert [pf.attrs["ahead"] for pf in prefills
            if "behind_s" in pf.attrs] == (
        [0, 0] if chunked else [0, 1])
    assert eng.stats()["prefills_ahead"] == (0 if chunked else 1)
    assert len(prefills) == (2 + 3 if chunked else 2)   # ceil(13/8)+ceil(18/8)
    assert sum(sp.attrs["n"] for sp in spans
               if sp.name == "serve.admit") == 2
    assert sum(sp.attrs["n"] for sp in spans
               if sp.name == "serve.evict") == 2
    ticks = [sp for sp in spans if sp.name == "serve.tick"]
    assert len(ticks) == eng.ticks
    for tk in ticks:
        assert names(tk.sid) == ["tick.build", "tick.dispatch", "tick.wait",
                                 "tick.emit"]
        assert set(tk.attrs["rids"]) <= {0, 1}
        assert ("trace_ids" in tk.attrs) == (mode == "traced")
    # a program's first dispatch, and only that one, says it compiles
    first = [sp.attrs["first_call"] for sp in spans
             if sp.name == "tick.dispatch"]
    assert first == [True] + [False] * (len(ticks) - 1)
    # per-token times
    assert sorted(c.rid for c in comps) == [0, 1]
    for c in comps:
        assert c.token_ts.dtype == np.float64
        assert len(c.token_ts) == c.n_generated == 6
        assert c.first_token_ts == c.token_ts[0]
        assert c.finish_ts == c.token_ts[-1]
        assert np.all(np.diff(c.token_ts) >= 0)
        last_pf = max((pf for pf in prefills if pf.attrs["rid"] == c.rid),
                      key=lambda pf: pf.start)
        assert last_pf.start < c.token_ts[0] < last_pf.end
        mine = [tk for tk in ticks if c.rid in tk.attrs["rids"]]
        for t in c.token_ts[1:]:
            assert any(tk.start < t < tk.end for tk in mine)
        if mode != "speculative":           # one token a tick
            assert len(mine) == c.n_generated - 1
            assert len(set(c.token_ts)) == c.n_generated


# ------------------- a prefill apart from the tick it queues behind (PR 38)
def _staggered(eng, arrivals):
    """Step ``eng``, submitting ``arrivals[step]`` before that step; the
    completions by rid, and the run's spans."""
    from tpu_dist.obs import trace

    with trace.ring().span("mark") as mark:
        pass
    arrivals, done, step = dict(arrivals), {}, 0
    while arrivals or eng.queue or any(s is not None for s in eng.slots):
        for req in arrivals.pop(step, ()):
            assert eng.submit(req)
        for c in eng.step():
            done[c.rid] = c
        step += 1
        assert step < 200
    return done, [sp for sp in trace.ring().snapshot()
                  if sp.sid > mark.sid and sp.name != "host.gc"]


def _late_burst(eng, reqs):
    """Feed ``eng`` the first request alone and the rest together once it
    is mid-decode with a tick in flight: ONE step admits as many as there
    are free slots, each but the first called ahead of a read. The
    completions by rid."""
    assert eng.submit(reqs[0])
    done = {c.rid: c for _ in range(2) for c in eng.step()}
    assert eng._flights and not done
    for r in reqs[1:]:
        assert eng.submit(r)
    took = min(len(reqs) - 1, eng.slots.count(None))
    done.update((c.rid, c) for c in eng.step())
    assert eng.prefills == 1 + took > 2
    assert eng.stats()["prefills_ahead"] == took - 1
    done.update((c.rid, c) for c in eng.run())
    return done


def test_prefill_behind_blocks_on_the_tick_in_flight_and_on_nothing_else(
        span_lm, monkeypatch):
    """``prefill.behind`` sits between the dispatch and the wait whether a
    tick is in flight or not; with one, it blocks on that tick's tokens
    (the newest flight's), with none it makes no runtime call at all."""
    from tpu_dist.engine import serve

    lm, params = span_lm
    eng = ServeEngine(lm, params, ServeConfig(max_slots=2, page_size=8,
                                              num_pages=16))
    blocked = []
    real = jax.block_until_ready

    def recording(x):
        blocked.append((x, [f.nxt for f in eng._flights]))
        return real(x)

    monkeypatch.setattr(serve.jax, "block_until_ready", recording)
    p = (np.arange(9, dtype=np.int32) * 5 + 2) % V
    done, spans = _staggered(eng, {0: [DecodeRequest(0, p, 8)],
                                   3: [DecodeRequest(1, p[:5], 4)]})
    assert sorted(done) == [0, 1]
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent, []).append(sp)
    prefills = sorted((sp for sp in spans if sp.name == "serve.prefill"),
                      key=lambda sp: sp.start)
    assert [pf.attrs["rid"] for pf in prefills] == [0, 1]
    for pf in prefills:
        assert [k.name for k in sorted(kids[pf.sid], key=lambda k: k.start)
                ] == ["prefill.dispatch", "prefill.behind", "prefill.wait"]
        behind = next(k for k in kids[pf.sid] if k.name == "prefill.behind")
        assert pf.attrs["behind_s"] == behind.end - behind.start >= 0.0
        assert pf.start <= pf.attrs["issued"] <= behind.start
    # request 0 met an idle engine; request 1 the tick dispatched a pass
    # earlier, the only one in flight, and waited for that one
    assert len(blocked) == 1
    waited_for, in_flight = blocked[0]
    assert len(in_flight) == 1 and waited_for is in_flight[0]


@pytest.mark.parametrize("mode", ["plain", "chunked"])
def test_a_request_counts_the_other_requests_prefills_it_stood_behind(
        span_lm, mode):
    """``Completion.behind_prefill_s`` is the sum of the own times,
    (end - ``issued``) - ``behind_s``, of the OTHER requests' prefills that
    ended between its first and its last token, and never holds its own:
    exact on a virtual clock that moves by one a read."""
    lm, params = span_lm
    clock = itertools.count()
    eng = ServeEngine(lm, params, ServeConfig(**_SPAN_MODES[mode]),
                      now_fn=lambda: float(next(clock)))
    r = np.random.default_rng(38)
    reqs = [DecodeRequest(i, r.integers(0, V, (n,)).astype(np.int32), m)
            for i, (n, m) in enumerate([(8, 22), (5, 3), (18, 9), (7, 1),
                                        (9, 6)])]
    done, spans = _staggered(eng, {0: reqs[:1], 2: reqs[1:2], 5: reqs[2:4],
                                   9: reqs[4:]})
    assert sorted(done) == [0, 1, 2, 3, 4]
    waited = [sp for sp in spans if sp.name == "serve.prefill"
              and "behind_s" in sp.attrs]
    assert sorted(sp.attrs["rid"] for sp in waited) == [0, 1, 2, 3, 4]
    own = {sp.attrs["rid"]: sp.end - sp.attrs["issued"]
           - sp.attrs["behind_s"] for sp in waited}
    assert all(v > 0 for v in own.values())
    ended = {sp.attrs["rid"]: sp.end for sp in waited}
    assert eng.stats()["prefill_own_s"] == sum(own.values())
    for c in done.values():
        others = [rid for rid in own if rid != c.rid
                  and c.first_token_ts < ended[rid] < c.finish_ts]
        assert c.behind_prefill_s == sum(own[rid] for rid in others), c.rid
        assert c.behind_gc_s >= 0.0
    # request 0 decodes through every later admission; a request that ends
    # on its first token stood behind nothing
    assert done[0].finish_ts > max(ended.values())
    assert done[0].behind_prefill_s == sum(own.values()) - own[0]
    assert done[3].n_generated == 1 and done[3].behind_prefill_s == 0.0


# ------------------------------------ the tick one ahead of the host (PR 27)
def plain_greedy(fwd, params, prompt, steps, width, eos=None):
    """A plain step-by-step decode: one full forward over everything so far
    for every token (no cache, no pages, no engine), greedy; stops on
    ``eos`` as the engine does, the end token kept."""
    toks = [int(t) for t in prompt]
    for _ in range(steps):
        x = np.zeros((1, width), np.int32)
        x[0, :len(toks)] = toks
        toks.append(int(np.argmax(np.asarray(
            fwd(params, jnp.asarray(x)))[0, len(toks) - 1])))
        if toks[-1] == eos:
            break
    return np.asarray(toks, np.int32)


def _mixed_requests(seed, n=5):
    r = np.random.default_rng(seed)
    return [DecodeRequest(i, r.integers(0, V, (int(r.integers(2, 12)),))
                          .astype(np.int32), int(r.integers(3, 14)))
            for i in range(n)]


def _hybrid_case():
    from test_hybrid_lm import TOY, engine_params, toy_model

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.reference import jamba as ref

    model = toy_model()
    params = engine_params(model, ref.make_weights(TOY,
                                                   jax.random.PRNGKey(3)))
    r = np.random.default_rng(3)
    reqs = [DecodeRequest(i, r.integers(0, 256, (n,)).astype(np.int32), new)
            for i, (n, new) in enumerate([(9, 4), (14, 6), (6, 3)])]
    return (model, params, jax.jit(lambda p, x: model.apply({"params": p}, x)),
            64, dict(max_slots=2, page_size=4, num_pages=64, max_len=64), reqs)


def _gpt2_case(seed, cfg, reqs=None):
    lm, params = _lm_and_params(seed=seed)
    return (lm, params,
            jax.jit(lambda p, x: lm.apply({"params": p}, x, train=False)), L,
            {**dict(max_slots=2, page_size=4, num_pages=32), **cfg},
            reqs if reqs is not None else _mixed_requests(seed))


_HOT = ((np.arange(18, dtype=np.int32) * 5 + 3) % V).astype(np.int32)
_AHEAD_CASES = {
    # five requests of mixed lengths through two slots: slots are left and
    # refilled while the tick runs ahead
    "gpt2": lambda: _gpt2_case(40, {}),
    # per-slot recurrent state beside the pages
    "hybrid": _hybrid_case,
    # the same prompt three times: the second and third read a shared
    # frontier page whose fork is pending at their first decode write
    "prefix_cow": lambda: _gpt2_case(20, dict(prefix_cache=True, max_slots=3),
                                     [DecodeRequest(i, _HOT, 6)
                                      for i in range(3)]),
    # a 17-token prompt chunks in over five steps while the other slot
    # decodes: the parked slot sits those ticks out
    "chunked": lambda: _gpt2_case(24, dict(prefill_chunk=4), [
        DecodeRequest(0, np.array([1, 2, 3], np.int32), 12),
        DecodeRequest(1, ((np.arange(17, dtype=np.int32) * 5 + 3) % V), 4),
        DecodeRequest(2, np.array([7, 8], np.int32), 5)]),
    # int8 pages move a logit by a few 1e-2: on these weights no greedy
    # choice is that close, so the tokens are the plain decode's
    "int8_kv": lambda: _gpt2_case(42, dict(kv_quant="int8")),
    # one request alone for two steps, then four arrive together: three
    # are admitted in ONE step while the first is mid-decode, its tick in
    # flight (fed by _late_burst)
    "late_burst": lambda: _gpt2_case(40, dict(max_slots=4), [
        DecodeRequest(0, np.array([5, 6, 7], np.int32), 12),
        *_mixed_requests(40)[1:]]),
    # ends the host cannot foresee: see the test
    "eos": lambda: _gpt2_case(40, {}),
}


@pytest.mark.parametrize("case", list(_AHEAD_CASES))
def test_engine_one_tick_ahead_serves_the_plain_decodes_tokens(case):
    """The engine dispatches tick n + 1 before it has read tick n; whatever
    rides the tick, every completion is, token for token, a plain
    step-by-step greedy decode of its own prompt. Under ``eos_id`` (a token
    the model emits mid-answer) the tick in flight has computed one more
    token for the slot that ended: it is absent from the completion, counted
    in ``overrun_tokens``, and the slot's pages are freed once."""
    model, params, fwd, width, cfg, reqs = _AHEAD_CASES[case]()
    eos = None
    if case == "eos":
        free = plain_greedy(fwd, params, reqs[0].prompt,
                            reqs[0].max_new_tokens, width)
        eos = int(free[reqs[0].prompt.size + 2])     # its third token
    refs = {r.rid: plain_greedy(fwd, params, r.prompt, r.max_new_tokens,
                                width, eos) for r in reqs}
    eng = ServeEngine(model, params, ServeConfig(**cfg, eos_id=eos))
    pages0 = eng.pool.pages_free
    comps = (list(_late_burst(eng, reqs).values()) if case == "late_burst"
             else eng.run(reqs))
    assert sorted(c.rid for c in comps) == [r.rid for r in reqs]
    for c in comps:
        np.testing.assert_array_equal(refs[c.rid], c.tokens, str(c.rid))
        assert c.n_generated == len(c.token_ts) == len(c.tokens) - c.prompt_len
    st = eng.stats()
    assert not eng._flights                      # nothing left unread
    assert eng.pool.pages_free == pages0         # a double free raises
    assert st["ticks_ahead"] > 0
    # one dropped token for every request that ended on eos in a tick, short
    # of its budget (an end on the prefill's own token is seen before any
    # tick, an end at the budget was foreseen)
    cut = [r for r in reqs if refs[r.rid][-1] == eos
           and 1 < len(refs[r.rid]) - r.prompt.size < r.max_new_tokens]
    assert st["overrun_tokens"] == len(cut) and bool(cut) == (case == "eos")
    if case == "prefix_cow":
        assert eng.pool.cow_copies == 2
    if case == "chunked":
        assert eng.chunk_ticks == 5 and eng.ticks > 5


def test_tick_spans_say_which_ticks_ran_ahead_and_tokens_are_stamped_held():
    """On the span ring, with an injected clock: the first tick after an
    idle spell is dispatched with nothing unread (``ahead`` 0), every other
    one while the tick before it is still in flight (``ahead`` 1, also
    through an admission into the busy engine); tick n + 1 is dispatched
    before tick n's ``tick.wait`` ends; a token's time is taken after the
    ``tick.wait`` that fetched it; ``drain()`` leaves no tick unread."""
    from tpu_dist.obs import trace

    lm, params = _lm_and_params(seed=31)
    clock = itertools.count()
    now = lambda: float(next(clock))
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=8, num_pages=16), now_fn=now)
    dispatched = []
    real = eng._dispatch_tick

    def recording(active):
        dispatched.append(now())
        return real(active)

    eng._dispatch_tick = recording
    with trace.ring().span("mark") as mark:
        pass
    comps = []
    assert eng.submit(DecodeRequest(0, np.array([1, 9, 17], np.int32), 9))
    for _ in range(4):
        comps += eng.step()
    assert len(eng._flights) == 1                # one tick ahead, no more
    assert eng.submit(DecodeRequest(1, np.array([5, 6], np.int32), 4))
    comps += eng.run()                           # both to their end: idle
    assert not eng._flights
    assert eng.submit(DecodeRequest(2, np.array([3], np.int32), 6))
    comps += eng.step()
    comps += eng.drain()
    assert not eng._flights and all(s is None for s in eng.slots)
    assert sorted(c.rid for c in comps) == [0, 1, 2]

    spans = [sp for sp in trace.ring().snapshot() if sp.sid > mark.sid]
    ticks = [sp for sp in spans if sp.name == "serve.tick"]
    waits = {sp.parent: sp for sp in spans if sp.name == "tick.wait"}
    sends = {sp.parent: sp for sp in spans if sp.name == "tick.dispatch"}
    assert len(ticks) == eng.ticks == len(dispatched)
    # two idle spells: request 0's first tick and request 2's
    first_of_2 = next(k for k, tk in enumerate(ticks)
                      if tk.attrs["rids"] == [2])
    assert [tk.attrs["ahead"] for tk in ticks] == [
        int(k not in (0, first_of_2)) for k in range(len(ticks))]
    assert eng.stats()["ticks_ahead"] == len(ticks) - 2
    assert eng.stats()["overrun_tokens"] == 0
    # tick n + 1 goes out before tick n has been read
    for n, tk in enumerate(ticks[:-1]):
        if ticks[n + 1].attrs["ahead"]:
            assert dispatched[n + 1] < waits[tk.sid].end
            assert sends[tk.sid].start < dispatched[n + 1] < sends[tk.sid].end
    # a token is stamped once the host holds it
    for c in comps:
        mine = [tk for tk in ticks if c.rid in tk.attrs["rids"]]
        assert len(mine) == c.n_generated - 1
        for t, tk in zip(c.token_ts[1:], mine):
            assert waits[tk.sid].end <= t <= tk.end
        assert np.all(np.diff(c.token_ts) > 0)


# ------------------------- a step's admissions as a pipeline (PR 39)
class _OneAtATime(ServeEngine):
    """The loop before PR 39: every admission's first token is read before
    the next admission is planned, so nothing is ever issued ahead."""

    def _prefill(self, adm, plans):
        assert super()._prefill(adm, iter(())) is None
        return next(plans, None)


def _bucket_requests(seed, lens=(3, 20, 9, 13, 6), vocab=V):
    """One request a length, of different prefill buckets (8, 16, 32)."""
    r = np.random.default_rng(seed)
    return [DecodeRequest(i, r.integers(0, vocab, (n,)).astype(np.int32),
                          4 + i) for i, n in enumerate(lens)]


def _one_a_step(eng, reqs):
    """Feed ``eng`` one request a step: nothing can be issued ahead."""
    done, _ = _staggered(eng, {k: [r] for k, r in enumerate(reqs)})
    assert eng.stats()["prefills_ahead"] == 0
    return done


_PIPELINE_CASES = {
    "plain": dict(),
    # requests 3 and 4 repeat request 1's prompt: admitted in the same step,
    # they must still find its pages
    "prefix_cache": dict(prefix_cache=True),
    "speculative": dict(spec_k=3),
    # the comparison engine takes the burst while a slot is mid-decode
    "late_burst": dict(),
}


@pytest.mark.parametrize("case", list(_PIPELINE_CASES))
def test_a_steps_admissions_pipelined_serve_the_same_tokens(case):
    """Five requests of different buckets in the queue before ONE step:
    prefill k + 1 is called before prefill k's token is read, and every
    completion is a plain greedy decode's, the same engine's fed one
    request a step (``late_burst``: one, then four together into the busy
    engine), and the one-at-a-time loop's."""
    lm, params = _lm_and_params(seed=39)
    fwd = jax.jit(lambda p, x: lm.apply({"params": p}, x, train=False))
    cfg = dict(max_slots=5, page_size=4, num_pages=64,
               **_PIPELINE_CASES[case])
    reqs = _bucket_requests(39)
    if case == "prefix_cache":
        for k in (3, 4):
            reqs[k] = DecodeRequest(k, reqs[1].prompt, reqs[k].max_new_tokens)
    eng = ServeEngine(lm, params, ServeConfig(**cfg))
    pages0 = eng.pool.pages_free
    for r in reqs:
        assert eng.submit(r)
    eng.step()
    assert not eng.queue and eng.prefills == 5        # ONE step took all
    assert eng.stats()["prefills_ahead"] == 4
    done = {c.rid: c for c in eng.run()}
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert eng.pool.pages_free == pages0
    apart = (_late_burst if case == "late_burst" else _one_a_step)(
        ServeEngine(lm, params, ServeConfig(**cfg)), reqs)
    sync = _OneAtATime(lm, params, ServeConfig(**cfg))
    synced = {c.rid: c for c in sync.run(reqs)}
    assert sync.stats()["prefills_ahead"] == 0
    for r in reqs:
        want = plain_greedy(fwd, params, r.prompt, r.max_new_tokens, L)
        for got in (done, apart, synced):
            np.testing.assert_array_equal(want, got[r.rid].tokens,
                                          f"{case} {r.rid}")
    if case == "prefix_cache":
        # each repeat shares every full page of the 20-token prompt
        full = reqs[1].prompt.size // 4
        assert eng.shared_prompt_pages == sync.shared_prompt_pages
        assert eng.shared_prompt_pages >= 2 * full > 0
        assert eng.pool.cow_copies == sync.pool.cow_copies


def test_sampled_tokens_pipelined_are_the_one_at_a_time_loops():
    """``temperature > 0``: the random key rides through the prefills in
    admission order and then through the ticks, as in the loop that reads
    each first token before it plans the next admission, so the sampled
    tokens are that loop's. (Fed one request a step the ticks come between
    the prefills and the key's path is another: nothing to compare.)"""
    lm, params = _lm_and_params(seed=39)
    cfg = ServeConfig(max_slots=5, page_size=4, num_pages=64,
                      temperature=0.9, top_k=8)
    reqs = _bucket_requests(7)
    runs = []
    for cls in (ServeEngine, _OneAtATime):
        eng = cls(lm, params, cfg, rng=jax.random.PRNGKey(5))
        runs.append({c.rid: c.tokens for c in eng.run(reqs)})
        assert eng.stats()["prefills_ahead"] == (4 if cls is ServeEngine
                                                 else 0)
    for r in reqs:
        np.testing.assert_array_equal(runs[0][r.rid], runs[1][r.rid])
    # and they are sampled: not the greedy stream
    greedy = {c.rid: c.tokens for c in ServeEngine(
        lm, params, ServeConfig(max_slots=5, page_size=4,
                                num_pages=64)).run(reqs)}
    assert any(not np.array_equal(greedy[r.rid], runs[0][r.rid])
               for r in reqs)


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_pool_pressure_on_the_third_admission_lands_the_first_two(
        prefix_cache):
    """Pages for two of five requests: the third's allocation fails while
    the second's prefill is out ahead; the first two land, the rest stay
    queued in order, no page is leaked (with ``prefix_cache`` the third
    repeats the first's prompt: its share is handed back), and the backlog
    is served as slots and pages come free."""
    lm, params = _lm_and_params(seed=39)
    reqs = [DecodeRequest(i, ((np.arange(9, dtype=np.int32) * (3 + 2 * (
        i % 2 if prefix_cache else i)) + 1) % V), 3) for i in range(5)]
    need = 3                                   # cdiv(9 + 3, 4) pages each
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=5, page_size=4, num_pages=2 * need,
        prefix_cache=prefix_cache))
    for r in reqs:
        assert eng.submit(r)
    eng.step()
    assert eng.prefills == 2 and eng.stats()["prefills_ahead"] == 1
    assert [r.rid for r, _ in eng.queue] == [2, 3, 4]
    landed = [s for s in eng.slots if s is not None]
    assert len(landed) == 2 and all(s.generated >= 1 for s in landed)
    st = eng.pool.stats()
    assert st["pages_free"] == 0 and st["shared_pages"] == 0
    done = {c.rid: c for c in eng.run()}
    assert sorted(done) == [0, 1, 2, 3, 4] and not eng.queue
    st = eng.pool.stats()
    assert st["pages_free"] == 2 * need and st["shared_pages"] == 0
    fwd = jax.jit(lambda p, x: lm.apply({"params": p}, x, train=False))
    for r in reqs:
        np.testing.assert_array_equal(
            plain_greedy(fwd, params, r.prompt, 3, L), done[r.rid].tokens)


def _recorded_prefills(monkeypatch):
    """Patch the prefill program and ``jax.device_get`` to log ("call", k)
    when prefill k's program is called and ("read", k) when ITS first
    token is fetched; returns the log."""
    from tpu_dist.engine import serve

    events, order, keep = [], {}, []
    real_program, real_get = serve._prefill_program, jax.device_get

    def program_for(*a):
        program = real_program(*a)

        def call(*args):
            out = program(*args)
            keep.append(out[0])               # ids stay unique while kept
            order[id(out[0])] = len(order)
            events.append(("call", order[id(out[0])]))
            return out

        call.head_rows = program.head_rows
        return call

    def get(x):
        if id(x) in order:
            events.append(("read", order[id(x)]))
        return real_get(x)

    monkeypatch.setattr(serve, "_prefill_program", program_for)
    monkeypatch.setattr(serve.jax, "device_get", get)
    return events


def test_prefill_k_plus_1_is_called_before_token_k_is_read(span_lm,
                                                           monkeypatch):
    """Order and depth of one step's admissions, on a virtual clock that
    moves by one a read: program k + 1 is called before token k is read,
    tokens are read in admission order, never more than one program is out
    beyond the one being read, ``_admit`` returns with none unread; the
    spans say which ran ahead, and their own times tile the step."""
    lm, params = span_lm
    events = _recorded_prefills(monkeypatch)
    clock = itertools.count()
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=5, page_size=4, num_pages=64),
        now_fn=lambda: float(next(clock)))
    real_admit, unread_after = eng._admit, []

    def admit():
        n = real_admit()
        unread_after.append(sum(e == "call" for e, _ in events)
                            - sum(e == "read" for e, _ in events))
        return n

    eng._admit = admit
    reqs = _bucket_requests(11)
    # the first request meets an idle engine; the other four one step later
    # a busy one with a tick in flight, together
    done, spans = _staggered(eng, {0: reqs[:1], 2: reqs[1:]})
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert events == [("call", 0), ("read", 0),
                      ("call", 1), ("call", 2), ("read", 1),
                      ("call", 3), ("read", 2),
                      ("call", 4), ("read", 3), ("read", 4)]
    out = 0
    for what, _ in events:
        out += 1 if what == "call" else -1
        assert 0 <= out <= 2
    assert unread_after and not any(unread_after)
    prefills = sorted((sp for sp in spans if sp.name == "serve.prefill"),
                      key=lambda sp: sp.start)
    assert [pf.attrs["rid"] for pf in prefills] == [0, 1, 2, 3, 4]
    assert [pf.attrs["ahead"] for pf in prefills] == [0, 0, 1, 1, 1]
    st = eng.stats()
    assert st["prefills_ahead"] == sum(pf.attrs["ahead"] for pf in prefills)
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent, []).append(sp)
    for before, pf in zip(prefills, prefills[1:]):
        send, behind, wait = sorted(kids[pf.sid], key=lambda k: k.start)
        assert [send.name, behind.name, wait.name] == [
            "prefill.dispatch", "prefill.behind", "prefill.wait"]
        assert pf.attrs["behind_s"] == behind.end - behind.start
        if pf.attrs["ahead"]:
            assert before.end < pf.attrs["issued"] == pf.start
        else:
            assert send.start < pf.attrs["issued"] < send.end
    # own time ends with the span; one step's do not overlap and add up
    own = [(pf.end - (pf.end - pf.attrs["issued"] - pf.attrs["behind_s"]),
            pf.end) for pf in prefills]
    assert all(a < b for a, b in own)
    assert all(own[k][1] <= own[k + 1][0] for k in range(len(own) - 1))
    assert st["prefill_own_s"] == sum(b - a for a, b in own)
    # request 0 decodes through the burst and stands behind all four; each
    # of the burst's stands behind those admitted after it
    for c in done.values():
        later = [b - a for (a, b), pf in zip(own, prefills)
                 if pf.attrs["rid"] > c.rid and pf.end < c.finish_ts]
        assert c.behind_prefill_s == sum(later), c.rid


def test_no_prefill_runs_ahead_when_requests_arrive_one_a_step(span_lm):
    lm, params = span_lm
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=5, page_size=4, num_pages=64))
    done, spans = _staggered(eng, {k: [r] for k, r in enumerate(
        _bucket_requests(12))})
    assert len(done) == 5 and eng.stats()["prefills_ahead"] == 0
    assert [sp.attrs["ahead"] for sp in spans
            if sp.name == "serve.prefill"] == [0] * 5
