"""Request observatory (round 17): obs.reqtrace span ids, the span
pipeline through engine.serve, and the reading side
(tools/request_report) over a canned two-host fixture.

The pins that matter:

* span ids are DETERMINISTIC and host-independent: two hosts that never
  exchanged a byte mint the same trace_id for the same (namespace, rid),
  so cross-host stitching is id equality, no coordination;
* the canned fixture (tests/fixtures/reqtrace: rid 4 completed on host
  0; rid 5 shed on host 0 under drain, re-admitted and completed on host
  1) reproduces EXACT attribution numbers — per-request queue/prefill/
  decode seconds, residue 0, coverage 1.0 — and stitches rid 5 into ONE
  trace spanning both hosts;
* every ``slo`` breach resolves to >= 1 concrete exemplar trace, worst
  offender first (the shed request outranks the completed one);
* the report is byte-deterministic: same ledger bytes -> same report
  bytes, twice (scripts/lint.sh gates on the same invariant, jax-free);
* the LIVE engine (engine.serve under a virtual clock) emits spans that
  tile admit->finish: queue+prefill meet at first token, decode windows
  meet at finish, so the sum-check holds with residue ~ 0 by
  construction, and a drain shed emits the orphan ``shed`` span.
"""

import itertools
import json
import os
import subprocess
import sys

import pytest

from tpu_dist.obs import reqtrace
from tpu_dist.obs.ledger import Ledger
from tpu_dist.sim.fleet import FleetLedger
from tools.request_report import (requests_summary, slowest_traces,
                                  waterfall_lines)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(ROOT, "tests", "fixtures", "reqtrace")


# ------------------------------------------------------------- span ids
def test_trace_id_is_host_independent_and_deterministic():
    a = reqtrace.trace_id("ci", 5)
    b = reqtrace.trace_id("ci", 5)
    assert a == b and len(a) == 16
    assert reqtrace.trace_id("ci", 4) != a          # rid separates
    assert reqtrace.trace_id("prod", 5) != a        # namespace separates


def test_root_and_child_ids_separate_attempts_and_names():
    tid = reqtrace.trace_id("ci", 5)
    r0 = reqtrace.root_span_id(tid, "ci-h0", 0)
    r1 = reqtrace.root_span_id(tid, "ci-h1", 0)
    assert r0 != r1                                  # per host-attempt view
    assert reqtrace.root_span_id(tid, "ci-h0", 1) != r0
    k0 = reqtrace.child_span_id(r0, "decode", 0)
    k1 = reqtrace.child_span_id(r0, "decode", 1)
    assert k0 != k1 and k0 != reqtrace.child_span_id(r0, "queue", 0)


def test_tracer_advances_per_name_counters_and_stamps_attrs():
    cap = []
    led = Ledger(None, sinks=(cap.append,))
    tr = reqtrace.RequestTracer(led, job_id="j", attempt=2, host=3,
                                trace_ns="ns")
    tid, root, parent = tr.root_ids(7)
    assert tid == reqtrace.trace_id("ns", 7) and parent is None
    _, s0, p0 = tr.ids(7, "decode")
    _, s1, p1 = tr.ids(7, "decode")
    assert p0 == p1 == root                          # children hang off root
    assert s0 == reqtrace.child_span_id(root, "decode", 0)
    assert s1 == reqtrace.child_span_id(root, "decode", 1)
    assert tr.attrs() == {"job_id": "j", "attempt": 2, "host": 3}
    # standalone serving: no host stamp
    assert "host" not in reqtrace.RequestTracer(led, job_id="j").attrs()


# -------------------------------------------- the canned two-host fixture
def _fixture_records():
    return FleetLedger.discover(FIX).merged()


def test_fixture_stitches_rid5_into_one_cross_host_trace():
    traces = reqtrace.traces(_fixture_records())
    assert len(traces) == 2
    t4 = traces[reqtrace.trace_id("ci", 4)]
    t5 = traces[reqtrace.trace_id("ci", 5)]
    assert t4["hosts"] == [0] and t4["rid"] == 4
    # ONE trace for rid 5: the shed attempt on host 0 and the completed
    # re-admission on host 1 share the id two processes derived alone
    assert t5["hosts"] == [0, 1] and t5["rid"] == 5
    assert [r["job_id"] for r in t5["roots"]] == ["ci-h1"]
    names = sorted(s["name"] for s in t5["spans"])
    assert names == ["cow_fork", "decode", "prefill", "prefix_hit",
                     "queue", "readmit", "request", "shed"]
    # the tree: every completed-side child hangs off host 1's root
    kids = reqtrace.children_of(t5)
    root = t5["roots"][0]["span_id"]
    assert {s["name"] for s in kids[root]} == {
        "queue", "prefill", "decode", "readmit", "prefix_hit", "cow_fork"}
    # walk() yields the root first, then its children one level down
    depths = {s["name"]: d for d, s in reqtrace.walk(t5)}
    assert depths["request"] == 0 and depths["decode"] == 1


def test_fixture_attribution_numbers_exact():
    summary = requests_summary(_fixture_records())
    assert summary["traces"] == 2
    assert summary["completed_requests"] == 2
    assert summary["cross_host_traces"] == 1
    assert summary["sheds"] == 1 and summary["readmits"] == 1
    r4, r5 = summary["per_request"]
    assert (r4["rid"], r4["latency_s"], r4["queue_s"], r4["prefill_s"],
            r4["decode_s"], r4["residue_s"]) == (4, 1.0, 0.2, 0.3, 0.5, 0.0)
    assert r4["tpot_s"] == 0.0625 and r4["sum_check_ok"]
    assert (r5["rid"], r5["latency_s"], r5["queue_s"], r5["prefill_s"],
            r5["decode_s"], r5["residue_s"]) == (5, 2.0, 0.3, 0.6, 1.1, 0.0)
    assert r5["tpot_s"] == 0.06875 and r5["sum_check_ok"]
    ta = summary["tail_attribution"]
    assert ta["coverage"] == 1.0
    assert ta["sum_check"] == {"ok": True, "requests": 2, "failed": [],
                               "max_residue_s": 0.0, "tolerance_s": 1e-4}
    assert ta["shares"]["queue"]["seconds"] == 0.5
    assert ta["shares"]["prefill"]["seconds"] == 0.9
    assert ta["shares"]["decode"]["seconds"] == 1.6
    assert ta["shares"]["residue"]["seconds"] == 0.0
    # the percentile IS a concrete request: p50 TTFT names rid 4's split,
    # p99 names rid 5's
    assert ta["ttft"]["p50"]["rid"] == 4
    assert ta["ttft"]["p50"]["queue_s"] == 0.2
    assert ta["ttft"]["p99"]["rid"] == 5
    assert ta["ttft"]["p99"]["prefill_s"] == 0.6


def test_fixture_every_slo_breach_has_exemplars_worst_first():
    records = _fixture_records()
    summary = requests_summary(records)
    assert len(summary["slo_exemplars"]) == 1
    breach = summary["slo_exemplars"][0]
    assert breach["kind"] == "queue_wait" and breach["host"] == 0
    assert len(breach["exemplars"]) >= 1
    # worst offender first: the 1.4s shed outranks the 0.2s completion
    assert [e["kind"] for e in breach["exemplars"]] == ["shed", "request"]
    assert breach["exemplars"][0]["rid"] == 5
    assert breach["exemplars"][0]["score_s"] == 1.4


def test_fixture_report_is_byte_deterministic():
    def build():
        records = FleetLedger.discover(FIX).merged()
        summary = requests_summary(records)
        lines = []
        from tools.request_report import render
        render(summary, records, out=lines.append, waterfalls=5)
        return json.dumps(summary, default=str), "\n".join(lines)

    assert build() == build()


def test_fixture_waterfall_shows_cross_host_story():
    traces = reqtrace.traces(_fixture_records())
    slow = slowest_traces(traces, 2)
    assert [t["rid"] for t in slow] == [5, 4]        # slowest first
    lines = "\n".join(waterfall_lines(slow[0]))
    assert "hosts=[0,1]" in lines
    assert "no root: attempt never completed it" in lines  # host 0's shed
    assert "ticks=16 tokens=16" in lines             # the decode window


# ------------------------------------------------ reading-side plumbing
def test_ledger_report_renders_requests_section():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "ledger_report.py"),
         os.path.join(FIX, "host1", "run.jsonl"), "--json"],
        capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    req = json.loads(proc.stdout)["requests"]
    assert req["traces"] == 1 and req["completed_requests"] == 1
    assert req["tail_attribution"]["coverage"] == 1.0


def test_trace_merge_gives_each_request_its_own_lane(tmp_path):
    out = str(tmp_path / "trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_merge.py"),
         os.path.join(FIX, "host1", "run.jsonl"), "-o", out,
         "--no-discover"], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    lanes = [e for e in events if e["ph"] == "M"
             and e["args"].get("name") == "request r5"]
    assert len(lanes) == 1
    spans = [e for e in events if e["ph"] == "X"
             and e["tid"] == lanes[0]["tid"]]
    assert {e["name"] for e in spans} >= {"queue", "prefill", "decode",
                                          "request"}
    dec = next(e for e in spans if e["name"] == "decode")
    assert dec["dur"] == pytest.approx(1.1e6)        # engine seconds -> us
    assert dec["args"]["trace_id"] == reqtrace.trace_id("ci", 5)


def test_metrics_sink_observes_request_ttft_histogram():
    from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink

    reg = MetricsRegistry()
    sink = metrics_ledger_sink(reg)
    # only root spans carry ttft_s; child spans must not observe
    sink({"event": "span", "name": "decode", "rid": 1, "ts": 1.0})
    sink({"event": "span", "name": "request", "rid": 1, "ttft_s": 0.5,
          "ts": 1.0})
    text = reg.render()
    assert "tpu_dist_request_ttft_seconds_count 1" in text
    assert "tpu_dist_request_ttft_seconds_sum 0.5" in text


# ------------------------------------------- the live engine (jax, tiny)
def test_serve_spans_tile_admit_to_finish_and_drain_sheds():
    """The whole writing side at once, no fixture: a tiny engine under a
    virtual clock completes requests (queue+prefill+decode spans tile
    admit->finish exactly — residue 0, coverage 1.0) and a drain sheds
    the queued stragglers as orphan ``shed`` spans."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,
                                       ServeEngine)
    from tpu_dist.models.transformer import tiny_lm

    L = 32
    lm = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                 max_len=L)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, L), jnp.int32), train=False)["params"]
    cap = []
    led = Ledger(None, sinks=(cap.append,))
    clock = itertools.count()
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=16, trace_window_ticks=4),
        ledger=led, now_fn=lambda: float(next(clock)))
    for i in range(3):
        assert eng.submit(DecodeRequest(i, np.array([1, 2, 3], np.int32),
                                        6))
    for _ in range(100):
        eng.step()
        if eng.completed == 3 and not eng.queue:
            break
    assert eng.completed == 3
    # one more queued request, then drain: it must shed with a span
    assert eng.submit(DecodeRequest(9, np.array([1], np.int32), 4))
    eng.drain(reason="sigterm")
    summary = requests_summary(cap)
    assert summary["completed_requests"] == 3
    ta = summary["tail_attribution"]
    assert ta["sum_check"]["ok"], ta["sum_check"]
    assert ta["coverage"] == 1.0
    assert summary["sheds"] == 1
    shed = next(s for t in reqtrace.traces(cap).values()
                for s in t["spans"] if s["name"] == "shed")
    assert shed["rid"] == 9 and shed["reason"] == "shed"
    # decode windows tile first token -> finish with shared boundaries
    for tr in reqtrace.traces(cap).values():
        decs = sorted((s for s in tr["spans"] if s["name"] == "decode"),
                      key=lambda s: s["start"])
        for a, b in zip(decs, decs[1:]):
            assert a["end"] == b["start"]


# ------------------------------- per-request stall counters (PR 38)
def _row(rid, tokens, decode_s, prefill, pauses):
    return {"rid": rid, "tokens": tokens, "decode_s": decode_s,
            "behind_prefill_s": prefill, "behind_gc_s": pauses}


def test_decode_split_adds_up_and_names_the_slow_twentieth():
    from tools.request_report import decode_split

    # twenty requests of 11 tokens: gaps of 10 ms, but one stood behind two
    # admissions (30 ms) and one inside a 120 ms collection
    rows = [_row(i, 11, 0.100, 0.0, 0.0) for i in range(18)]
    rows += [_row(18, 11, 0.130, 0.030, 0.0), _row(19, 11, 0.220, 0.0, 0.120)]
    got = decode_split(rows)
    assert got["all"] == {
        "requests": 20, "mean_gap_s": round(2.15 / 200, 6),
        "decode_s": 2.15, "ticks_s": 2.0, "behind_prefill_s": 0.03,
        "behind_gc_s": 0.12}
    # nearest rank of 20 at 95%: the 19th, so the two slowest are the tail
    assert got["p95_mean_gap_s"] == 0.013
    assert got["at_or_above_p95"] == {
        "requests": 2, "mean_gap_s": 0.0175, "decode_s": 0.35,
        "ticks_s": 0.2, "behind_prefill_s": 0.03, "behind_gc_s": 0.12}
    # one-token requests have no gap; a ledger without the counters, no split
    assert decode_split([_row(0, 1, 0.0, 0.0, 0.0)]) is None
    assert decode_split([_row(0, 9, 0.1, None, None)]) is None


def test_fixture_without_the_counters_has_no_decode_split():
    recs = FleetLedger.discover(FIX).merged()
    ta = requests_summary(recs)["tail_attribution"]
    assert ta["decode_split"] is None
    assert all(r["behind_prefill_s"] is None
               for r in requests_summary(recs)["per_request"])


def test_live_engine_counters_reach_the_ledger_and_both_reports():
    """``behind_prefill_s``/``behind_gc_s`` ride the ``request`` event and
    the reqtrace root span, ``prefill_own_s``/``gc_pause_s`` the
    ``kv_cache`` event; ``request_report`` splits the decode time by them
    and ``ledger_report`` prints the engine's totals."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tools.ledger_report import decode_section
    from tools.request_report import render
    from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,
                                       ServeEngine)
    from tpu_dist.models.transformer import tiny_lm

    lm = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                 max_len=32)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    cap = []
    clock = itertools.count()
    eng = ServeEngine(lm, params, ServeConfig(
        max_slots=2, page_size=4, num_pages=16, trace_window_ticks=4),
        ledger=Ledger(None, sinks=(cap.append,)),
        now_fn=lambda: float(next(clock)))
    eng.submit(DecodeRequest(0, np.array([1, 2, 3], np.int32), 12))
    for _ in range(3):
        eng.step()
    eng.submit(DecodeRequest(1, np.array([4, 5], np.int32), 5))
    done = {c.rid: c for c in eng.run()}
    assert done[0].behind_prefill_s > 0 == done[1].behind_prefill_s
    events = {r["rid"]: r for r in cap if r["event"] == "request"}
    roots = {r["rid"]: r for r in cap
             if r["event"] == "span" and r["name"] == "request"}
    for rid, c in done.items():
        for rec in (events[rid], roots[rid]):
            assert rec["behind_prefill_s"] == c.behind_prefill_s
            assert rec["behind_gc_s"] == round(c.behind_gc_s, 6)
    kv = [r for r in cap if r["event"] == "kv_cache"][-1]
    st = eng.stats()
    assert kv["prefill_own_s"] == st["prefill_own_s"] > 0
    assert 0.0 <= kv["gc_pause_s"] <= st["gc_pause_s"]
    split = requests_summary(cap)["tail_attribution"]["decode_split"]
    assert split["all"]["requests"] == 2
    assert split["all"]["behind_prefill_s"] == done[0].behind_prefill_s
    assert split["all"]["decode_s"] == pytest.approx(sum(
        c.finish_ts - c.first_token_ts for c in done.values()))
    lines = []
    render(requests_summary(cap), cap, out=lines.append, waterfalls=0)
    assert any("by what held the next token back" in ln for ln in lines)
    assert any(ln.lstrip().startswith("all requests (2): mean gap")
               and "behind admissions" in ln and "in collections" in ln
               for ln in lines), lines
    lines = []
    decode_section(cap, out=lines.append)
    assert any("KV cache:" in ln and "admissions held the decoding slots"
               in ln and "garbage collections" in ln for ln in lines), lines
    # one request a step: none of its prefills ran ahead, and the line says
    assert kv["prefills_ahead"] == st["prefills_ahead"] == 0
    assert any("KV cache:" in ln and "(0 prefills issued ahead of the last "
               "one's read)" in ln for ln in lines), lines
