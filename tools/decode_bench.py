#!/usr/bin/env python
"""Decode (generation) throughput: KV-cache vs full-recompute, on-chip.

The training side has tokens/sec + MFU north stars; this is
the inference twin — tokens/sec and per-token latency for
tpu_dist.engine.generate at an LM-bench-class geometry. The KV-cache path
embeds ONE token per tick and attends over the cache (O(L*d) per token);
the full-recompute path re-runs the whole prefix every tick (O(L^2*d)) —
this tool puts the factor between them on record.

``--requests N`` additionally runs N sequential warm KV-cache calls as
individual *requests* and reports per-request latency percentiles
(p50/p99) plus request tok/s in the headline JSON — the first
scrape-able serving SLO. With ``--ledger`` (or ``BENCH_LEDGER``) each
request lands as one ``decode`` ledger event, so
``tools/ledger_report.py`` renders the same percentiles in its decode
section.

``--trace N`` switches to REQUEST-TRACE REPLAY through the
continuous-batching engine (engine.serve + the paged KV cache): N
requests with seeded Poisson arrivals and mixed prompt/output lengths
stream through the scheduler, and the SAME trace then replays through
static batching (drain refill) at equal slot capacity. The headline JSON
gains a ``serving`` block — completed requests/s (wall AND per-tick, the
deterministic twin), TTFT and per-output-token latency p50/p99, batch
occupancy, and the static baseline — making throughput-UNDER-LOAD the
recorded metric; ``tools/bench_track.py`` gates on it like ``data_s``.
Arrivals are scheduled in TICK units from a seeded rng, so the schedule
(and the per-tick numbers) are machine-speed-independent. ``--spec-k``
runs the trace through the speculative tick (``accepted_per_tick`` joins
the block), and ``--prefix-tenants``/``--prefix-len`` give requests
shared per-tenant system prompts with CoW prefix caching on — plus a
cache-off baseline replay, so the ``pages_per_request`` drop is on
record (``prefix_hit_rate`` says why).

Usage:
    python tools/decode_bench.py                         # both paths
    python tools/decode_bench.py --steps 512 --batch 16
    python tools/decode_bench.py --requests 16 --ledger dec.jsonl
    python tools/decode_bench.py --trace 64 --serve-slots 8
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pctl_ms(xs, q):
    """Nearest-rank percentile of a list of seconds, in ms — THE repo
    percentile (tools/ledger_report._pctl), ms-scaled, so the bench and
    the report can never disagree on rank convention."""
    from tools.ledger_report import _pctl

    v = _pctl(sorted(xs), q)
    return None if v is None else round(v * 1e3, 3)


def _drive_trace(eng, arrivals, prompts, outs):
    """Replay one arrival schedule through a ServeEngine: requests are
    submitted when the WALL tick (loop iteration) reaches their arrival
    tick — idle iterations cost nothing, so the schedule stays
    deterministic whatever the machine speed. Returns (completions,
    elapsed_wall_s)."""
    import time as _t

    from tpu_dist.engine.serve import DecodeRequest

    n = len(prompts)
    i = 0
    wall_tick = 0
    comps = []
    t0 = _t.perf_counter()
    while i < n or eng.queue or any(s is not None for s in eng.slots):
        while i < n and arrivals[i] <= wall_tick:
            eng.submit(DecodeRequest(i, prompts[i], int(outs[i])))
            i += 1
        comps.extend(eng.step())
        wall_tick += 1
        if wall_tick > 1_000_000:
            raise RuntimeError("trace replay did not drain")
    return comps, _t.perf_counter() - t0


class _VirtualClock:
    """Deterministic engine clock for the long-context replay: one unit
    is one TOKEN-EQUIVALENT of scheduler-step cost. Each iteration costs
    ``tick_floor`` (the decode dispatch everyone pays) plus however many
    prefill tokens that iteration actually pushed (the engine's
    ``prefill_token_work`` delta) — so a monolithic 16k admit shows up as
    one enormous inter-token gap for every concurrently-decoding request,
    while chunked prefill amortizes the same work into
    ``prefill_chunk``-sized bumps. The TPOT-interference number is then
    pure cost-model arithmetic: machine-independent, warm-up-free, and
    assertable in CI (the wall-clock twin would be noise on shared
    runners)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _drive_longcontext(eng, clock, reqs, floor):
    """Replay a long-context trace under the virtual cost-model clock:
    arrivals are in scheduler-ITERATION units, and the clock advances by
    floor + this iteration's prefill-token work after every step."""
    from tpu_dist.engine.serve import DecodeRequest

    n = len(reqs)
    i = 0
    it = 0
    comps = []
    while i < n or eng.queue or any(s is not None for s in eng.slots):
        while i < n and reqs[i]["arrival"] <= it:
            eng.submit(DecodeRequest(i, reqs[i]["prompt"],
                                     int(reqs[i]["out_len"])))
            i += 1
        work0 = eng.prefill_token_work
        comps.extend(eng.step())
        clock.t += floor + (eng.prefill_token_work - work0)
        it += 1
        if it > 1_000_000:
            raise RuntimeError("long-context replay did not drain")
    return comps


def replay_long_context(args, model, params, trace=None):
    """--long-context / --prompt-len-dist: the mixed-traffic tail-latency
    benchmark. A trace whose prompt lengths span orders of magnitude
    (tools/traces/longcontext_mix.json ships a 16k admit among short
    interactive requests) replays through chunked prefill under the
    virtual cost-model clock, and the SAME trace with the long prompts
    REMOVED replays as the interference baseline. The headline gains:

    * ``ttft_long_p99``   — TTFT p99 of the long (>= long_threshold)
      requests, in virtual token-equivalents: the price of admitting a
      book-length prompt at all;
    * ``tpot_interference_pct`` — how much the SHORT requests' TPOT p99
      degrades when the long prompts are in flight, vs the no-long
      baseline. Chunked prefill's whole claim is that this stays bounded
      by chunk/tick_floor instead of exploding by prompt_len/tick_floor
      (``--long-monolithic`` puts the unchunked contrast on record);
    * ``sp_capacity``     — with ``--sp-capacity N``: a context longer
      than ONE device's page budget served end-to-end on an N-device CPU
      sp submesh (the sharded-pool existence proof, geometry-tiny).

    ``tools/bench_track.py`` gates the first two like ``data_s``
    (abstaining on pre-long-context history)."""
    import numpy as np

    from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,
                                       ServeEngine)

    if trace is None and args.long_context:
        with open(args.long_context) as f:
            trace = json.load(f)
    if trace is None:
        # --prompt-len-dist "LEN:WEIGHT,LEN:WEIGHT,...": draw the trace's
        # prompt lengths from the weighted mixture, everything else from
        # the standard seeded Poisson machinery
        pairs = [p.split(":") for p in args.prompt_len_dist.split(",")]
        lens = np.array([int(l) for l, _ in pairs])
        weights = np.array([float(w) for _, w in pairs], dtype=float)
        weights = weights / weights.sum()
        count = args.trace or 32
        rng = np.random.default_rng(args.trace_seed)
        gaps = rng.exponential(1.0 / max(args.arrival_rate, 1e-9), count)
        arrivals = np.floor(np.cumsum(gaps)).astype(int)
        plens = rng.choice(lens, size=count, p=weights)
        outs = rng.integers(args.min_out, args.max_out + 1, count)
        trace = {"seed": args.trace_seed, "tick_floor": args.tick_floor,
                 "long_threshold": args.long_threshold,
                 "requests": [
                     {"arrival": int(a), "prompt_len": int(p),
                      "out_len": int(o)}
                     for a, p, o in zip(arrivals, plens, outs)]}
    floor = trace["tick_floor"]
    thr = trace["long_threshold"]
    rng = np.random.default_rng(trace["seed"])
    reqs = [dict(r) for r in trace["requests"]]
    for r in reqs:
        # token content drawn in trace order from the trace seed: the
        # replay is bit-reproducible from the JSON alone
        r["prompt"] = rng.integers(0, args.vocab_size,
                                   (r["prompt_len"],)).astype(np.int32)
    max_total = max(r["prompt_len"] + r["out_len"] for r in reqs)
    pages_per_seq = -(-max_total // args.page_size)
    num_pages = args.num_pages or args.serve_slots * pages_per_seq

    def run(subset, chunk):
        clock = _VirtualClock()
        eng = ServeEngine(model, params, ServeConfig(
            max_slots=args.serve_slots, page_size=args.page_size,
            num_pages=num_pages, max_len=max_total,
            quant=args.serve_quant, kv_quant=args.kv_quant,
            prefill_chunk=chunk), now_fn=clock)
        comps = _drive_longcontext(eng, clock, subset, floor)
        return comps, eng

    def _p99(xs):
        from tools.ledger_report import _pctl

        v = _pctl(sorted(xs), 99)
        return None if v is None else round(v, 3)

    def short_tpots(comps, subset):
        return [(c.finish_ts - c.first_token_ts) / (c.n_generated - 1)
                for c in comps if c.n_generated > 1
                and subset[c.rid]["prompt_len"] < thr]

    chunk = args.prefill_chunk
    comps, eng = run(reqs, chunk)
    ttft_long = [c.ttft_s for c in comps
                 if reqs[c.rid]["prompt_len"] >= thr]
    tpot_mixed = _p99(short_tpots(comps, reqs))
    shorts_only = [r for r in reqs if r["prompt_len"] < thr]
    base_comps, _ = run(shorts_only, chunk)
    tpot_base = _p99(short_tpots(base_comps, shorts_only))
    interference = (None if not tpot_base or tpot_mixed is None
                    else round((tpot_mixed - tpot_base) / tpot_base * 100,
                               2))
    serving = {
        "mode": "long_context",
        "requests": len(reqs),
        "long_requests": len(reqs) - len(shorts_only),
        "completed": len(comps),
        "ticks": eng.ticks, "chunk_ticks": eng.chunk_ticks,
        "requests_per_tick": round(len(comps) / max(eng.ticks, 1), 4),
        "prefill_token_work": eng.prefill_token_work,
        "prefill_chunk": chunk, "tick_floor": floor,
        "long_threshold": thr,
        "trace_seed": trace["seed"],
        "slots": args.serve_slots, "page_size": args.page_size,
        "num_pages": num_pages, "kv_quant": args.kv_quant,
        "occupancy": round(eng.occupancy, 4),
        # virtual token-equivalent units throughout (see _VirtualClock)
        "ttft_long_p99": _p99(ttft_long),
        "tpot_short_p99": tpot_mixed,
        "tpot_baseline_p99": tpot_base,
        "tpot_interference_pct": interference,
    }
    print(f"serve[long-context]: {len(comps)}/{len(reqs)} completed "
          f"({serving['long_requests']} long >= {thr} tok) in {eng.ticks} "
          f"ticks + {eng.chunk_ticks} chunk ticks; TTFT-long p99 "
          f"{serving['ttft_long_p99']}, short-TPOT interference "
          f"{interference}% (chunk {chunk}, floor {floor})",
          file=sys.stderr)
    if getattr(args, "long_monolithic", False):
        # the unchunked contrast: same trace, prefill_chunk=0 — the
        # full-prompt stall lands in every concurrent short's TPOT
        mono_comps, mono_eng = run(reqs, 0)
        mono_p99 = _p99(short_tpots(mono_comps, reqs))
        serving["monolithic"] = {
            "tpot_short_p99": mono_p99,
            "tpot_interference_pct": (
                None if not tpot_base or mono_p99 is None
                else round((mono_p99 - tpot_base) / tpot_base * 100, 2)),
            "ticks": mono_eng.ticks,
        }
        print(f"serve[long-context]: monolithic contrast interference "
              f"{serving['monolithic']['tpot_interference_pct']}%",
              file=sys.stderr)
    serving["sp_capacity"] = None
    if args.sp_capacity > 0:
        import jax

        from tpu_dist.parallel.mesh import SP_AXIS, make_mesh

        n = args.sp_capacity
        if len(jax.devices()) < n:
            print(f"serve[long-context]: sp capacity proof skipped "
                  f"({len(jax.devices())} devices < {n}; set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={n})",
                  file=sys.stderr)
        else:
            # geometry-tiny existence proof: per-device budget 2 pages of
            # 4 tokens, context > that budget, bit-served on the submesh
            ps = 4
            mesh = make_mesh((n,), (SP_AXIS,),
                             devices=jax.devices()[:n])
            eng_sp = ServeEngine(model, params, ServeConfig(
                max_slots=1, page_size=ps, num_pages=2 * n,
                max_len=8 * n, quant=args.serve_quant,
                sp_prefill_threshold=ps + 1), mesh=mesh)
            plen, out_len = 5 * n + 1, n + 2
            sp_prompt = np.random.default_rng(trace["seed"]).integers(
                0, args.vocab_size, (plen,)).astype(np.int32)
            sp_comps = eng_sp.run([DecodeRequest(0, sp_prompt, out_len)])
            budget = eng_sp.pool.pages_per_device * ps
            serving["sp_capacity"] = {
                "devices": n, "page_size": ps,
                "pages_per_device": eng_sp.pool.pages_per_device,
                "device_token_budget": budget,
                "context_tokens": plen + out_len,
                "exceeds_single_device": plen + out_len > budget,
                "completed": len(sp_comps),
                "sp_prefills": eng_sp.sp_prefills,
            }
            print(f"serve[long-context]: sp capacity — "
                  f"{plen + out_len}-token context on {n} devices of "
                  f"{budget}-token budget each "
                  f"({len(sp_comps)} completed)", file=sys.stderr)
    return serving


def replay_serving_trace(args, model, params, ledger=None):
    """--trace: the throughput-under-load benchmark. One seeded trace
    (Poisson arrivals in tick units, mixed prompt/output lengths) replays
    through continuous batching AND through static drain-batching at equal
    slot capacity; the returned dict is the headline's ``serving`` block.
    A warm pass (full replay, discarded) pays the prefill-bucket and tick
    compiles so both timed modes run warm.

    ``--prefix-tenants T`` prepends one of T fixed per-tenant system
    prompts (``--prefix-len`` tokens, seeded) to every request — the
    shared-prefix traffic shape real multi-tenant serving has — and
    enables copy-on-write prefix caching; a third replay with the cache
    OFF becomes the ``no_prefix_cache`` baseline, so the
    ``pages_per_request`` drop is measured, not asserted. ``--spec-k``
    runs the speculative tick (self-speculation: the base drafts for
    itself) and publishes ``accepted_per_tick``. Both knobs only shape
    the seeded schedule deterministically — per-tick numbers stay
    machine-independent."""
    import numpy as np

    from tools.request_report import (requests_summary, slowest_traces,
                                      waterfall_lines)
    from tpu_dist.engine.serve import ServeConfig, ServeEngine
    from tpu_dist.obs import reqtrace
    from tpu_dist.obs.ledger import Ledger

    rng = np.random.default_rng(args.trace_seed)
    gaps = rng.exponential(1.0 / max(args.arrival_rate, 1e-9), args.trace)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    prompts = [rng.integers(0, args.vocab_size,
                            (int(rng.integers(args.min_prompt,
                                              args.max_prompt + 1)),)
                            ).astype(np.int32)
               for _ in range(args.trace)]
    outs = rng.integers(args.min_out, args.max_out + 1, args.trace)
    prefix_on = args.prefix_tenants > 0
    prefix_len = args.prefix_len if prefix_on else 0
    if prefix_on:
        # per-tenant system prompts, drawn AFTER the base trace so the
        # pre-existing schedule (and its tracked numbers) is unchanged
        # when the knob is off
        tenants = [rng.integers(0, args.vocab_size,
                                (args.prefix_len,)).astype(np.int32)
                   for _ in range(args.prefix_tenants)]
        tenant_of = rng.integers(0, args.prefix_tenants, args.trace)
        prompts = [np.concatenate([tenants[tenant_of[j]], prompts[j]])
                   for j in range(args.trace)]
    max_total = prefix_len + args.max_prompt + args.max_out
    pages_per_seq = -(-max_total // args.page_size)
    num_pages = args.num_pages or args.serve_slots * pages_per_seq

    def make(refill, led=None, prefix_cache=prefix_on):
        return ServeEngine(model, params, ServeConfig(
            max_slots=args.serve_slots, page_size=args.page_size,
            num_pages=num_pages, max_len=max_total,
            quant=args.serve_quant, kv_quant=args.kv_quant,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, refill=refill, spec_k=args.spec_k,
            prefix_cache=prefix_cache,
            kv_event_every=32), ledger=led)

    _drive_trace(make("continuous"), arrivals, prompts, outs)  # warm
    # the continuous (headline) mode always runs with a span-capturing
    # ledger: the engine's per-request spans (obs.reqtrace) feed the
    # tail_attribution block and --waterfalls without needing --ledger
    span_cap = []
    cont_led = ledger if ledger is not None else Ledger(None)
    cont_led.add_sink(span_cap.append)
    results = {}
    modes = [("continuous", True), ("drain", True)]
    if prefix_on:
        # the CoW baseline: same trace, same scheduler, cache off — the
        # pages_per_request delta is the prefix cache's whole claim
        modes.append(("no_prefix_cache", False))
    for refill, prefix_cache in modes:
        eng = make("continuous" if refill == "no_prefix_cache" else refill,
                   led=cont_led if refill == "continuous" else None,
                   prefix_cache=prefix_cache)
        comps, elapsed = _drive_trace(eng, arrivals, prompts, outs)
        ttft = [c.ttft_s for c in comps]
        tpot = [(c.finish_ts - c.first_token_ts) / (c.n_generated - 1)
                for c in comps if c.n_generated > 1]
        waits = [c.queue_wait_s for c in comps]
        toks = sum(c.n_generated for c in comps)
        apt = eng.accepted_per_tick
        results[refill] = {
            "completed": len(comps), "rejected": eng.rejected,
            "ticks": eng.ticks,
            "requests_per_tick": (round(len(comps) / eng.ticks, 4)
                                  if eng.ticks else None),
            "requests_per_sec": (round(len(comps) / elapsed, 2)
                                 if elapsed else None),
            "tokens_per_sec": (round(toks / elapsed, 1)
                               if elapsed else None),
            "occupancy": round(eng.occupancy, 4),
            # per-active-slot tokens per tick: identically 1.0 for the
            # plain tick, > 1.0 once speculative acceptance lands
            "accepted_per_tick": (round(apt, 4) if apt is not None
                                  else (1.0 if eng.ticks else None)),
            # fresh pages granted per completed request — the number the
            # prefix cache exists to shrink
            "pages_per_request": (round(eng.pool.alloc_total / len(comps),
                                        4) if comps else None),
            "prefix_hit_rate": (round(eng.prefix_hit_rate, 4)
                                if eng.prefix_hit_rate is not None
                                else None),
            "cow_copies": eng.pool.cow_copies,
            "ttft_ms": {"p50": _pctl_ms(ttft, 50),
                        "p99": _pctl_ms(ttft, 99)},
            "tpot_ms": {"p50": _pctl_ms(tpot, 50),
                        "p99": _pctl_ms(tpot, 99)},
            "queue_wait_ms": {"p50": _pctl_ms(waits, 50),
                              "p99": _pctl_ms(waits, 99)},
        }
        print(f"serve[{refill}]: {len(comps)}/{args.trace} completed in "
              f"{eng.ticks} ticks ({results[refill]['requests_per_tick']} "
              f"req/tick, {results[refill]['requests_per_sec']} req/s, "
              f"{results[refill]['accepted_per_tick']} accepted/tick, "
              f"{results[refill]['pages_per_request']} pages/req), "
              f"occupancy {eng.occupancy * 100:.0f}%, TTFT p50 "
              f"{results[refill]['ttft_ms']['p50']}ms", file=sys.stderr)
    serving = dict(results["continuous"])
    serving["requests"] = args.trace
    serving["slots"] = args.serve_slots
    serving["page_size"] = args.page_size
    serving["num_pages"] = num_pages
    serving["kv_quant"] = args.kv_quant
    serving["arrival_rate"] = args.arrival_rate
    serving["trace_seed"] = args.trace_seed
    serving["spec_k"] = args.spec_k
    serving["prefix_tenants"] = args.prefix_tenants
    serving["prefix_len"] = prefix_len
    serving["static"] = results["drain"]
    if prefix_on:
        serving["no_prefix_cache"] = results["no_prefix_cache"]
    # the request-observatory view of the continuous replay: the captured
    # span stream is the same record shape tools/request_report.py reads
    # off a ledger, so the headline carries per-request attribution
    # (bench_track gates coverage) and --waterfalls renders the slowest
    # requests' span trees
    summary = requests_summary(span_cap)
    ta = summary.get("tail_attribution")
    serving["tail_attribution"] = ta
    if ta:
        print(f"serve[traces]: {summary['completed_requests']} request "
              f"trace(s), coverage {ta['coverage']}, sum-check "
              f"{'OK' if ta['sum_check']['ok'] else 'FAILED'} "
              f"(max residue {ta['sum_check']['max_residue_s']:.6g}s)",
              file=sys.stderr)
    n_falls = getattr(args, "waterfalls", 0)
    if n_falls > 0:
        traces = reqtrace.traces(span_cap)
        slow = slowest_traces(traces, n_falls)
        print(f"serve[traces]: {len(slow)} slowest request waterfall(s):",
              file=sys.stderr)
        for tr in slow:
            for line in waterfall_lines(tr):
                print("  " + line, file=sys.stderr)
    return serving


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=384)
    ap.add_argument("--vocab-size", type=int, default=32000)
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--num-layers", type=int, default=8)
    ap.add_argument("--num-heads", type=int, default=8)
    ap.add_argument("--precision", default="bf16", choices=["fp32", "bf16"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--tp", type=int, default=0,
                    help="decode over a ('model',) mesh of this many devices "
                         "(Megatron head/vocab sharding + heads-sharded KV "
                         "cache; engine.generate mesh path). 0 = no mesh. "
                         "The decode tick is weight-bandwidth-bound, so TP "
                         "cuts ms/token ~linearly when devices exist.")
    ap.add_argument("--dp", type=int, default=0,
                    help="decode over a ('data',) mesh: batch-sharded")
    ap.add_argument("--num-experts", type=int, default=0,
                    help="bench the MoE LM (cached decode via the shared "
                         "attend_maybe_cached) instead of the dense one")
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--skip-full", action="store_true",
                    help="skip the O(L^2) full-recompute reference "
                         "(slow at long totals)")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--requests", type=int, default=8,
                    help="sequential warm kv-cache calls timed as "
                         "individual requests for the latency percentiles "
                         "(0 = skip the per-request section)")
    ap.add_argument("--ledger", default=os.environ.get("BENCH_LEDGER", ""),
                    help="JSONL run ledger: one 'decode' event per request "
                         "(tools/ledger_report.py renders p50/p99 from it)")
    ap.add_argument("--trace", type=int, default=0,
                    help="request-trace replay through the continuous-"
                         "batching engine (engine.serve): this many "
                         "requests with seeded Poisson arrivals and mixed "
                         "lengths, plus a static-batching baseline at "
                         "equal capacity; adds the 'serving' block to the "
                         "headline JSON (0 = off)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--waterfalls", type=int, default=0,
                    help="after the trace replay, print this many slowest "
                         "request waterfalls (span trees from "
                         "obs.reqtrace) to stderr (0 = off)")
    ap.add_argument("--arrival-rate", type=float, default=1.0,
                    help="mean request arrivals per decode tick (Poisson)")
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--min-out", type=int, default=4)
    ap.add_argument("--max-out", type=int, default=64)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding for the trace replay: this "
                         "many greedy draft tokens per tick "
                         "(self-speculation; 0 = plain decode). Greedy "
                         "output is token-identical either way — only "
                         "accepted_per_tick moves")
    ap.add_argument("--prefix-tenants", type=int, default=0,
                    help="shared-prefix traffic for the trace replay: "
                         "each request gets one of this many fixed "
                         "per-tenant system prompts prepended, and "
                         "copy-on-write prefix caching turns on (plus a "
                         "cache-off baseline replay). 0 = off")
    ap.add_argument("--prefix-len", type=int, default=32,
                    help="tokens per tenant system prompt "
                         "(with --prefix-tenants)")
    ap.add_argument("--serve-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged KV pool size (0 = auto: slots x pages for "
                         "the worst-case sequence)")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="page arenas int8+scales (the PR 9 quantize_kv "
                         "layout) instead of the model dtype")
    ap.add_argument("--serve-quant", default="none",
                    choices=["none", "int8", "int8_wo"],
                    help="weight quant for the serving engine "
                         "(engine.generate._quantize_for_decode)")
    ap.add_argument("--long-context", default="",
                    help="path to a long-context trace JSON (e.g. "
                         "tools/traces/longcontext_mix.json): mixed "
                         "short/long traffic replayed through chunked "
                         "prefill under the virtual cost-model clock; "
                         "adds serving.ttft_long_p99 and "
                         "serving.tpot_interference_pct to the headline "
                         "(replaces the one-shot decode sections)")
    ap.add_argument("--prompt-len-dist", default="",
                    help="generate the long-context trace instead of "
                         "loading one: 'LEN:WEIGHT,LEN:WEIGHT,...' "
                         "weighted prompt-length mixture (--trace N "
                         "requests, --trace-seed, --arrival-rate, "
                         "--min-out/--max-out as usual)")
    ap.add_argument("--prefill-chunk", type=int, default=128,
                    help="chunk size for the long-context replay "
                         "(ServeConfig.prefill_chunk; 0 = monolithic)")
    ap.add_argument("--long-threshold", type=int, default=1024,
                    help="prompts at least this long count as 'long' for "
                         "ttft_long_p99 / the interference baseline "
                         "(--prompt-len-dist mode; trace files carry "
                         "their own)")
    ap.add_argument("--tick-floor", type=int, default=1024,
                    help="virtual cost of one scheduler step before "
                         "prefill work, in token-equivalents "
                         "(--prompt-len-dist mode; trace files carry "
                         "their own)")
    ap.add_argument("--long-monolithic", action="store_true",
                    help="also replay the long-context trace with "
                         "prefill_chunk=0 and report the contrast "
                         "interference (slow at 16k prompts: one "
                         "prompt-sized forward)")
    ap.add_argument("--sp-capacity", type=int, default=0,
                    help="with the long-context replay: prove a context "
                         "longer than one device's page budget serves on "
                         "an N-device cpu sp submesh (geometry-tiny; "
                         "needs XLA_FLAGS host_platform_device_count)")
    args = ap.parse_args()

    import jax

    from tpu_dist.runtime import enable_compile_cache
    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.engine.generate import generate
    from tpu_dist.models.transformer import TransformerLM

    lc_trace = None
    if args.long_context:
        with open(args.long_context) as f:
            lc_trace = json.load(f)
    long_mode = lc_trace is not None or bool(args.prompt_len_dist)

    total = args.prompt_len + args.steps
    # the pos_emb table must cover the longest sequence either mode runs:
    # the one-shot geometry AND the trace replay's worst case
    max_len = max(total, (args.max_prompt + args.max_out
                          + (args.prefix_len if args.prefix_tenants else 0))
                  if args.trace else 0)
    if long_mode:
        if lc_trace is not None:
            lc_max = max(r["prompt_len"] + r["out_len"]
                         for r in lc_trace["requests"])
        else:
            lens = [int(p.split(":")[0])
                    for p in args.prompt_len_dist.split(",")]
            lc_max = max(lens) + args.max_out
        max_len = max(max_len, lc_max, 8 * args.sp_capacity)
    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    if args.num_experts:
        from tpu_dist.models.moe import MoETransformerLM
        if args.trace:
            raise SystemExit("--trace serves the dense TransformerLM "
                             "(engine.serve has no MoE scheduling story "
                             "yet, ROADMAP item 4)")
        model = MoETransformerLM(
            vocab_size=args.vocab_size, num_layers=args.num_layers,
            d_model=args.d_model, num_heads=args.num_heads, max_len=max_len,
            num_experts=args.num_experts,
            capacity_factor=args.capacity_factor, dtype=dtype)
    else:
        model = TransformerLM(
            vocab_size=args.vocab_size, num_layers=args.num_layers,
            d_model=args.d_model, num_heads=args.num_heads, max_len=max_len,
            dtype=dtype)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 16), np.int32), train=False)["params"]
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, args.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    mesh = None
    if args.tp or args.dp:
        from tpu_dist.parallel.mesh import make_mesh
        if args.dp and args.batch % args.dp:
            # generate() would silently fall back to a replicated buffer
            # and the JSON would claim a dp run that never happened
            raise SystemExit(f"--dp {args.dp} needs --batch divisible by it "
                             f"(got {args.batch})")
        if args.tp and args.dp:
            mesh = make_mesh((args.dp, args.tp), ("data", "model"),
                             devices=jax.devices()[:args.dp * args.tp])
        elif args.tp:
            mesh = make_mesh((args.tp,), ("model",),
                             devices=jax.devices()[:args.tp])
        else:
            mesh = make_mesh((args.dp,), ("data",),
                             devices=jax.devices()[:args.dp])

    def timed(use_cache):
        # the timed region ends in a device_get of the tokens: they cannot
        # reach the host before the program has finished, and the readback
        # is (B, total) i32 — microseconds.
        # ticks: the cache path runs ONE batched prefill forward + steps-1
        # one-token ticks; the full path runs exactly `steps` full forwards.
        ticks = args.steps
        out = generate(model, params, prompt, args.steps,
                       temperature=args.temperature, use_cache=use_cache,
                       top_k=args.top_k, top_p=args.top_p, mesh=mesh)
        jax.device_get(out)                             # compile + warm
        best = float("inf")
        for _ in range(args.trials):
            t0 = time.perf_counter()
            out = generate(model, params, prompt, args.steps,
                           temperature=args.temperature, use_cache=use_cache,
                           top_k=args.top_k, top_p=args.top_p, mesh=mesh)
            jax.device_get(out)
            best = min(best, time.perf_counter() - t0)
        toks = args.batch * args.steps
        return toks / best, best / ticks * 1e3, out

    ledger = None
    if args.ledger:
        from tpu_dist.obs.ledger import Ledger
        ledger = Ledger(args.ledger)
        ledger.emit("run_start", kind="decode_bench",
                    config={k: v for k, v in vars(args).items()
                            if not callable(v)},
                    mesh=({"tp": args.tp, "dp": args.dp}
                          if args.tp or args.dp else None),
                    devices=sorted({d.device_kind
                                    for d in jax.local_devices()}),
                    process_count=jax.process_count())

    cache_rate = None
    full_rate = None
    if not long_mode:
        cache_rate, cache_ms, out_c = timed(True)
        print(f"kv-cache decode: {cache_rate:,.0f} generated-tok/s incl. "
              f"batched prefill ({cache_ms:.2f} ms/generated token, "
              f"batch {args.batch}, {args.num_layers}L/d{args.d_model}, "
              f"prompt {args.prompt_len}, total {total})", file=sys.stderr)
    if not long_mode and not args.skip_full:
        full_rate, full_ms, out_f = timed(False)
        print(f"full-recompute decode: {full_rate:,.0f} tok/s "
              f"({full_ms:.2f} ms/token-tick)", file=sys.stderr)
        if args.temperature == 0.0:
            # with RANDOM weights the 32k-way logits are near-ties, so
            # bf16 rounding differences between the two attention orders
            # can break argmax differently and the sequences diverge —
            # exact equality on trained/tiny models is pinned by
            # tests/test_generate.py; this line is informational
            same = bool(jnp.array_equal(out_c, out_f))
            print(f"greedy outputs identical: {same} "
                  f"(random-weight near-ties; see tests/test_generate.py "
                  f"for the exact-equality contract)", file=sys.stderr)

    # -- per-request serving latency (the first scrape-able serving SLO):
    # N sequential warm kv-cache calls, each timed as one request; the
    # nearest-rank percentiles match tools/ledger_report.decode_section
    latency = None
    req_tok_s = None
    if not long_mode and args.requests > 0:
        lat = []
        for _ in range(args.requests):
            t0 = time.perf_counter()
            out_r = generate(model, params, prompt, args.steps,
                             temperature=args.temperature, use_cache=True,
                             top_k=args.top_k, top_p=args.top_p, mesh=mesh,
                             ledger=ledger)
            jax.device_get(out_r)  # the request's completion barrier
            lat.append(time.perf_counter() - t0)
        lat.sort()
        pick = lambda q: lat[min(int(round(q / 100.0 * (len(lat) - 1))),
                                 len(lat) - 1)]
        latency = {"p50_ms": round(pick(50) * 1e3, 3),
                   "p99_ms": round(pick(99) * 1e3, 3)}
        req_tok_s = round(args.batch * args.steps * len(lat) / sum(lat), 1)
        print(f"requests: {len(lat)} sequential kv-cache calls, "
              f"{req_tok_s:,.0f} tok/s; latency p50 {latency['p50_ms']:.1f}"
              f"ms / p99 {latency['p99_ms']:.1f}ms", file=sys.stderr)
    # -- request-trace replay (continuous batching vs static, engine.serve)
    serving = None
    if long_mode:
        serving = replay_long_context(args, model, params, trace=lc_trace)
    elif args.trace > 0:
        serving = replay_serving_trace(args, model, params, ledger=ledger)

    if ledger is not None:
        ledger.emit("run_end", steps=args.requests,
                    seconds=round(sum(lat), 3) if latency else 0.0)
        ledger.close()

    print(json.dumps({
        # long-context replays publish their own metric name so the
        # virtual-clock numbers never gate the wall-clock tok/s line
        "metric": ("lm_longcontext_serving" if long_mode
                   else "lm_decode_tokens_per_sec"),
        "kv_cache": round(cache_rate, 1) if cache_rate is not None else None,
        "full_recompute": (round(full_rate, 1)
                           if full_rate is not None else None),
        "batch": args.batch, "prompt_len": args.prompt_len,
        "steps": args.steps, "layers": args.num_layers,
        "d_model": args.d_model, "vocab": args.vocab_size,
        "precision": args.precision,
        "temperature": args.temperature, "top_k": args.top_k,
        "top_p": args.top_p, "tp": args.tp, "dp": args.dp,
        "num_experts": args.num_experts,
        "requests": args.requests or None,
        "latency_ms": latency,
        "request_tokens_per_sec": req_tok_s,
        "serving": serving,
        "ledger": args.ledger or None,
    }))


if __name__ == "__main__":
    main()
