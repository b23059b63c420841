"""Goodput accounting, run lineage, and progress SLOs (obs.goodput).

The no-jax half hand-writes multi-attempt fixture ledgers with
deterministic timestamps and pins EXACT category expectations — 2
attempts, a torn trailing line, attempt 1 missing its run_end (the
SIGKILL signature) — through the accumulator, job stitching,
ledger_report's goodput/decode sections, and trace_merge's 2-attempt
lanes. The jax half is the acceptance smoke: a 2-attempt CPU LM run
(attempt 1 crashes mid-run, attempt 2 resumes from its checkpoint) whose
stitched goodput categories sum to ~100% of wall-clock including the
restart gap, and a forced progress-SLO breach that emits an `slo` event
and auto-triggers a flight-recorder bundle through the ledger-sink path.
"""

import json
import os

import pytest

from tpu_dist.obs.goodput import (GoodputAccumulator, GoodputMonitor,
                                  accounting, attempt_path,
                                  discover_attempt_paths, job_accounting,
                                  next_attempt_index, split_attempts)
from tpu_dist.obs.ledger import Ledger, read_ledger

# ---------------------------------------------------------------- lineage


def test_attempt_path_naming():
    assert attempt_path("run.jsonl", 0) == "run.jsonl"
    assert attempt_path("run.jsonl", 2) == "run.a2.jsonl"
    assert attempt_path("", 3) == ""


def test_next_attempt_index_and_discovery(tmp_path):
    base = str(tmp_path / "run.jsonl")
    assert next_attempt_index(base) == 0          # nothing on disk yet
    open(base, "w").close()
    assert next_attempt_index(base) == 1          # bare file taken
    open(str(tmp_path / "run.a1.jsonl"), "w").close()
    open(str(tmp_path / "run.a3.jsonl"), "w").close()
    assert next_attempt_index(base) == 4          # holes don't confuse it
    # discovery finds the whole family in attempt order, from ANY member
    fam = [base, str(tmp_path / "run.a1.jsonl"),
           str(tmp_path / "run.a3.jsonl")]
    assert discover_attempt_paths(base) == fam
    assert discover_attempt_paths(fam[2]) == fam
    # .pN process siblings are NOT attempts
    open(str(tmp_path / "run.p1.jsonl"), "w").close()
    assert discover_attempt_paths(base) == fam


def test_next_attempt_index_probes_own_process_files(tmp_path):
    """The shared-FS race guard: process 0 creating the bare ledger first
    must NOT make a later-starting process 1 of the SAME attempt
    self-assign attempt 1 — each process probes only its own files."""
    base = str(tmp_path / "run.jsonl")
    open(base, "w").close()                     # process 0, attempt 0, live
    assert next_attempt_index(base, process_index=1) == 0   # p1 joins a0
    open(str(tmp_path / "run.p1.jsonl"), "w").close()
    assert next_attempt_index(base, process_index=1) == 1   # p1 restarted
    open(str(tmp_path / "run.a1.p1.jsonl"), "w").close()
    assert next_attempt_index(base, process_index=1) == 2
    # process 0 meanwhile counts only its own lineage
    assert next_attempt_index(base, process_index=0) == 1


# ------------------------------------------------- fixture ledgers (no jax)
# Deterministic timestamps; category math pinned EXACTLY below.

def _attempt0_records():
    """Killed mid-run: no run_end; a torn line follows on disk."""
    return [
        {"event": "run_start", "ts": 100.0, "pid": 0, "kind": "lm",
         "config": {}, "mesh": None, "devices": ["cpu"],
         "process_count": 1, "job_id": "run", "attempt": 0},
        # startup: run_start -> compile gap (3.0s)
        {"event": "compile", "ts": 103.0, "pid": 0, "program": "train_step",
         "seconds": 2.5},
        # the warm record charges NOTHING: the compile event above already
        # covers its span via the run_start->compile gap (only streams
        # with no compile event fall back to charging warm spans)
        {"event": "step", "ts": 104.0, "pid": 0, "step": 0, "loss": 2.0,
         "throughput": 900.0, "unit": "tok/s", "data_s": 0.4,
         "dispatch_s": 0.1, "device_s": 0.1, "comm_s": None, "mfu": 0.1,
         "steps_in_dispatch": 1, "warm": True},
        # hot: data 0.5 / dispatch 0.3 / device 1.0 across 2 opt steps
        {"event": "step", "ts": 106.0, "pid": 0, "step": 2, "loss": 1.5,
         "throughput": 1000.0, "unit": "tok/s", "data_s": 0.5,
         "dispatch_s": 0.3, "device_s": 1.0, "comm_s": None, "mfu": 0.2,
         "steps_in_dispatch": 2},
        # a health skip moves that record's per-step device share
        # (1.0 / 2 = 0.5s) from goodput to 'skipped'
        {"event": "health", "ts": 106.1, "pid": 0, "step": 2,
         "kind": "nonfinite", "policy": "skip", "action": "skip",
         "value": 1.0},
        {"event": "step", "ts": 108.0, "pid": 0, "step": 4, "loss": 1.2,
         "throughput": 1100.0, "unit": "tok/s", "data_s": 0.2,
         "dispatch_s": 0.1, "device_s": 0.9, "comm_s": None, "mfu": 0.2,
         "steps_in_dispatch": 2},
    ]


def _attempt1_records():
    """The restarted attempt: completes, with exact eval/ckpt seconds and
    a watchdog stall whose wait resurfaces in the next record's device_s."""
    return [
        {"event": "run_start", "ts": 120.0, "pid": 0, "kind": "lm",
         "config": {}, "mesh": None, "devices": ["cpu"],
         "process_count": 1, "job_id": "run", "attempt": 1},
        {"event": "compile", "ts": 121.0, "pid": 0,
         "program": "train_step"},
        {"event": "step", "ts": 121.5, "pid": 0, "step": 4, "loss": 1.2,
         "throughput": 900.0, "unit": "tok/s", "data_s": 0.2,
         "dispatch_s": 0.1, "device_s": 0.2, "comm_s": None, "mfu": 0.1,
         "steps_in_dispatch": 1, "warm": True},
        {"event": "step", "ts": 124.0, "pid": 0, "step": 8, "loss": 1.0,
         "throughput": 1200.0, "unit": "tok/s", "data_s": 0.5,
         "dispatch_s": 0.5, "device_s": 2.0, "comm_s": None, "mfu": 0.25,
         "steps_in_dispatch": 4},
        # stall: 1.5s badput, deducted from the NEXT record's device_s
        {"event": "stall", "ts": 125.0, "pid": 0, "idle_s": 1.5,
         "threshold_s": 1.0, "stacks": "..."},
        {"event": "step", "ts": 127.0, "pid": 0, "step": 12, "loss": 0.9,
         "throughput": 1100.0, "unit": "tok/s", "data_s": 0.3,
         "dispatch_s": 0.2, "device_s": 2.0, "comm_s": None, "mfu": 0.22,
         "steps_in_dispatch": 4},
        # exact durations stamped by the engines since this round
        {"event": "eval", "ts": 128.0, "pid": 0, "epoch": 0, "loss": 0.8,
         "seconds": 0.8},
        {"event": "ckpt", "ts": 128.5, "pid": 0, "epoch": 1, "path": "ck",
         "is_best": True, "seconds": 0.2},
        {"event": "run_end", "ts": 129.0, "pid": 0, "steps": 9,
         "seconds": 9.0, "status": "ok"},
    ]


def _write_jsonl(path, records, torn=False):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        if torn:
            f.write('{"event": "step", "ts": 999.0, "pid": 0, "loss"')
    return records


@pytest.fixture
def job_dir(tmp_path):
    base = str(tmp_path / "run.jsonl")
    _write_jsonl(base, _attempt0_records(), torn=True)
    _write_jsonl(str(tmp_path / "run.a1.jsonl"), _attempt1_records())
    return tmp_path


def test_attempt0_accounting_exact():
    part = accounting(_attempt0_records())
    # wall: 100 -> 108 (no run_end: last event stands in)
    assert part["wall_s"] == pytest.approx(8.0)
    cats = part["categories"]
    # run_start -> compile gap; the warm record's span is inside it (the
    # record is just EMITTED later, at the drain), so it adds nothing
    assert cats["startup"] == pytest.approx(3.0)
    assert cats["data_wait"] == pytest.approx(0.7)
    assert cats["dispatch"] == pytest.approx(0.4)
    assert cats["skipped"] == pytest.approx(0.5)         # 1.0 / 2 steps
    assert part["goodput_s"] == pytest.approx(1.9 - 0.5)  # device - skip
    assert cats["idle"] == pytest.approx(8.0 - 3.0 - 0.7 - 0.4 - 0.5 - 1.4)
    # the partition is exhaustive: categories + goodput == wall
    assert sum(cats.values()) + part["goodput_s"] == pytest.approx(8.0)
    assert part["overrun_s"] == 0.0 and part["status"] is None
    assert part["opt_steps"] == 4


def test_attempt1_accounting_exact_stall_and_seconds():
    part = accounting(_attempt1_records())
    assert part["wall_s"] == pytest.approx(9.0)
    cats = part["categories"]
    assert cats["startup"] == pytest.approx(1.0)  # warm span inside the gap
    assert cats["stall"] == pytest.approx(1.5)
    # the stall's wait resurfaced in the 127.0 record's device_s: its
    # contribution drops to 0.5, so goodput = 2.0 + 0.5
    assert part["goodput_s"] == pytest.approx(2.5)
    assert cats["eval"] == pytest.approx(0.8)   # exact field, not the gap
    assert cats["ckpt"] == pytest.approx(0.2)
    assert sum(cats.values()) + part["goodput_s"] == pytest.approx(9.0)
    assert part["status"] == "ok"


def test_job_accounting_stitches_attempts_with_restart_gap(job_dir):
    base = str(job_dir / "run.jsonl")
    records = []
    for p in discover_attempt_paths(base):
        records.extend(read_ledger(p, strict=False))  # torn line skipped
    attempts = split_attempts(records)
    assert len(attempts) == 2
    gp = job_accounting(attempts)
    # stitched wall 100 -> 129; gap 108 -> 120 charged as restart badput
    assert gp["wall_s"] == pytest.approx(29.0)
    assert gp["categories"]["restart_gap"] == pytest.approx(12.0)
    assert gp["goodput_s"] == pytest.approx(1.4 + 2.5)
    assert gp["ratio"] == pytest.approx(3.9 / 29.0, abs=1e-6)
    assert sum(gp["categories"].values()) + gp["goodput_s"] == \
        pytest.approx(29.0)
    a0, a1 = gp["attempts"]
    assert a0["status"] is None          # killed: no run_end on disk
    assert a1["status"] == "ok" and a1["restart_gap_s"] == pytest.approx(12)


def test_lost_intermediate_attempt_keeps_stamped_ordinals(tmp_path):
    """run.a1.jsonl lost: the survivors must keep their STAMPED attempt
    numbers (0 and 2) in both the report and the trace lanes — never be
    renumbered by list position."""
    from tools.trace_merge import main as merge_main

    base = str(tmp_path / "run.jsonl")
    _write_jsonl(base, _attempt0_records())
    a2 = [dict(r) for r in _attempt1_records()]
    a2[0]["attempt"] = 2
    _write_jsonl(str(tmp_path / "run.a2.jsonl"), a2)
    records = []
    for p in discover_attempt_paths(base):
        records.extend(read_ledger(p, strict=False))
    gp = job_accounting(split_attempts(records))
    assert [a["attempt"] for a in gp["attempts"]] == [0, 2]
    out = str(tmp_path / "trace.json")
    assert merge_main([base, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    assert {e["pid"] for e in trace["traceEvents"]} == {0, 200}


def test_ledger_report_goodput_section_and_cli_discovery(job_dir, capsys):
    from tools.ledger_report import main as report_main, summarize

    base = str(job_dir / "run.jsonl")
    records = []
    for p in discover_attempt_paths(base):
        records.extend(read_ledger(p, strict=False))
    lines = []
    summary = summarize(records, out=lines.append)
    gp = summary["goodput"]
    assert gp["categories"]["restart_gap"] == pytest.approx(12.0)
    txt = "\n".join(lines)
    assert "goodput (2 attempt(s), stitched wall 29.0s)" in txt
    assert "restart gap" in txt and "health-skipped" in txt
    assert "MISSING run_end" in txt
    # the CLI auto-discovers the .a1 sibling from the bare path
    assert report_main([base]) == 0
    out = capsys.readouterr().out
    assert "stitching 2 attempt ledgers" in out
    assert "restart gap" in out
    # --json carries the same dict
    assert report_main([base, "--json"]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["goodput"]["categories"]["restart_gap"] == pytest.approx(12.0)


def test_ledger_report_decode_section(tmp_path, capsys):
    """Per-request serving latency from decode events (engine.generate
    with a ledger): nearest-rank p50/p99 + tok/s."""
    from tools.ledger_report import summarize

    recs = [{"event": "decode", "ts": 10.0 + i, "pid": 0, "tokens": 100,
             "seconds": 0.1 * (i + 1), "throughput": 100 / (0.1 * (i + 1))}
            for i in range(10)]
    lines = []
    summary = summarize(recs, out=lines.append)
    d = summary["decode"]
    assert d["requests"] == 10 and d["tokens"] == 1000
    assert d["latency_s"]["p50"] == pytest.approx(0.5)   # nearest-rank
    assert d["latency_s"]["p99"] == pytest.approx(1.0)
    assert d["tokens_per_sec"] == pytest.approx(1000 / 5.5, rel=1e-3)
    assert any("latency p50" in ln for ln in lines)


def test_ledger_report_serving_chunk_and_sharded_fields(capsys):
    """Round 19 ledger half: the serving section renders chunk-prefill
    occupancy (cumulative chunk_ticks over tick, first->last windows), the
    chunk-queue depth gauge (max backlog / drained-or-not last), and the
    sp-sharded pool's device count — all from the periodic kv_cache
    snapshots the engine already emits (no-jax: pure dict arithmetic)."""
    from tools.ledger_report import summarize

    reqs = [{"event": "request", "ts": 1.0 + i, "rid": i, "tokens": 8,
             "queue_wait_s": 0.01, "ttft_s": 0.02} for i in range(3)]
    kv = [{"event": "kv_cache", "ts": 2.0, "tick": 10, "chunk_ticks": 8,
           "chunks_pending": 6, "sharded_devices": 4, "active_seqs": 3,
           "slots": 4, "pages_free": 10},
          {"event": "kv_cache", "ts": 3.0, "tick": 20, "chunk_ticks": 12,
           "chunks_pending": 2, "sharded_devices": 4, "active_seqs": 2,
           "slots": 4, "pages_free": 12},
          {"event": "kv_cache", "ts": 4.0, "tick": 30, "chunk_ticks": 12,
           "chunks_pending": 0, "sharded_devices": 4, "active_seqs": 1,
           "slots": 4, "pages_free": 20}]
    lines = []
    summary = summarize(reqs + kv, out=lines.append)
    srv = summary["decode"]["serving"]
    co = srv["chunk_occupancy"]
    assert co["overall"] == pytest.approx(12 / 30)
    assert co["first"] == pytest.approx(0.8)      # 8 chunks / 10 steps
    assert co["last"] == pytest.approx(0.0)       # backlog drained
    assert srv["chunks_pending_max"] == 6
    assert srv["chunks_pending_last"] == 0
    assert srv["sharded_devices"] == 4
    txt = "\n".join(lines)
    assert "chunked prefill: 40% of steps ran a chunk" in txt
    assert "queue depth max 6, last 0" in txt
    assert "sp-sharded KV pool: 4 devices" in txt
    # unsharded single-device runs stay silent (no sp line, no chunk line
    # when the counters never moved)
    kv1 = [dict(k, sharded_devices=1, chunk_ticks=0) for k in kv]
    lines = []
    summary = summarize(reqs + kv1, out=lines.append)
    srv = summary["decode"]["serving"]
    assert srv["sharded_devices"] == 1
    txt = "\n".join(lines)
    assert "sp-sharded" not in txt
    assert "chunked prefill" not in txt


def test_trace_merge_two_attempt_lanes(job_dir):
    """The 2-attempt lane check: each attempt renders its own lane group,
    attempt 1 offset by its true wall distance, restart gap drawn."""
    from tools.trace_merge import main as merge_main

    base = str(job_dir / "run.jsonl")
    out = str(job_dir / "trace.json")
    assert merge_main([base, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    assert trace["otherData"]["attempts"] == 2
    ev = trace["traceEvents"]
    assert {e["pid"] for e in ev} == {0, 100}     # one lane per attempt
    names = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names[0].startswith("attempt 0") and \
        names[100].startswith("attempt 1")
    # attempt 1's clock is offset by its real distance from attempt 0's
    # run_start (20s), so its own events never sit at t~0
    a1_ts = [e["ts"] for e in ev if e["pid"] == 100 and "ts" in e
             and e["name"] != "restart gap"]
    assert min(a1_ts) >= 20e6 - 1
    (gap,) = [e for e in ev if e["name"] == "restart gap"]
    assert gap["dur"] == pytest.approx(12e6)
    assert gap["ts"] == pytest.approx(8e6)        # starts at attempt 0 end


# -------------------------------------------------- live monitor (no jax)

def _emit_step(led, step, **kw):
    # spans far smaller than the emit cadence, so the live partition's
    # itemization can never exceed the (tiny) wall between real emits
    rec = dict(step=step, loss=1.0, throughput=kw.pop("throughput", 1000.0),
               unit="tok/s", data_s=1e-6, dispatch_s=1e-6, device_s=1e-6,
               comm_s=None, mfu=0.1, steps_in_dispatch=1, **kw)
    return led.emit("step", **rec)  # ledger-schema: forward


def test_monitor_periodic_and_final_goodput_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    mon = GoodputMonitor(led, every_s=0.0)  # final-only cadence
    led.add_sink(mon.sink)
    led.emit("run_start", kind="t", config={}, mesh=None, devices=["cpu"],
             process_count=1)
    for i in range(3):
        _emit_step(led, i)
    assert mon.emit_goodput(final=True) is not None
    led.close()
    recs = read_ledger(path)  # schema-valid round trip
    (gp,) = [r for r in recs if r["event"] == "goodput"]
    assert gp["final"] is True and 0.0 <= gp["ratio"] <= 1.0
    assert set(gp["categories"]) >= {"startup", "data_wait", "idle"}
    assert gp["slo_breaches"] == 0


def test_monitor_slo_breach_hysteresis_and_flightrec_autotrigger(tmp_path):
    """A breach emits ONE slo event per episode, and the event reaches the
    flight recorder through the ledger-sink fan-out — a diagnosis bundle
    with reason='slo' and zero new plumbing."""
    from tpu_dist.obs.flightrec import FlightRecorder

    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    rec = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led,
                         trace_steps=0)
    led.add_sink(rec.sink)
    # floor no run can meet -> breach as soon as the EMA arms
    mon = GoodputMonitor(led, every_s=0.0, slo_throughput=1e12,
                         unit="tok/s", min_records=2)
    led.add_sink(mon.sink)
    led.emit("run_start", kind="t", config={}, mesh=None, devices=["cpu"],
             process_count=1)
    for i in range(5):
        _emit_step(led, i)
    led.close()
    recs = read_ledger(path)
    slos = [r for r in recs if r["event"] == "slo"]
    assert len(slos) == 1                     # hysteresis: one per episode
    assert slos[0]["kind"] == "throughput" and slos[0]["floor"] == 1e12
    assert mon.breaches == 1
    diags = [r for r in recs if r["event"] == "diagnosis"]
    assert [d["reason"] for d in diags] == ["slo"]
    bundle = diags[0]["bundle"]
    assert os.path.isdir(bundle)
    with open(os.path.join(bundle, "manifest.json")) as f:
        assert json.load(f)["reason"] == "slo"


def test_monitor_steps_rate_ignores_eval_ckpt_boundaries(tmp_path):
    """An epoch boundary (eval + ckpt) legitimately pauses step
    completions; the first step after it must NOT read as a steps/min
    collapse and fire a spurious breach on a healthy run."""
    import time

    led = Ledger(str(tmp_path / "r.jsonl"))
    # floor 1000/min = one step per 60ms: back-to-back emits (µs apart)
    # clear it by orders of magnitude; the 0.3s boundary gap alone would
    # read as 200/min and breach — unless the boundary resets the sample
    mon = GoodputMonitor(led, every_s=0.0, slo_steps_per_min=1000.0,
                         min_records=1, alpha=1.0)  # EMA = last sample
    led.add_sink(mon.sink)
    led.emit("run_start", kind="t", config={}, mesh=None, devices=["cpu"],
             process_count=1)
    _emit_step(led, 0)
    _emit_step(led, 1)  # fast back-to-back: rate far above the floor
    assert mon.breaches == 0
    led.emit("eval", epoch=0, loss=1.0)
    led.emit("ckpt", epoch=1, path="ck", is_best=True)
    time.sleep(0.3)  # a "slow" boundary gap; dt alone would breach
    _emit_step(led, 2)  # first post-boundary step: no steps/min sample
    _emit_step(led, 3)  # and the next dt is steady again
    assert mon.breaches == 0
    led.close()


def test_monitor_recovery_rearms_breach(tmp_path):
    led = Ledger(str(tmp_path / "r.jsonl"))
    mon = GoodputMonitor(led, every_s=0.0, slo_throughput=500.0,
                         min_records=2, alpha=1.0)  # EMA = last sample
    led.add_sink(mon.sink)
    led.emit("run_start", kind="t", config={}, mesh=None, devices=["cpu"],
             process_count=1)
    for thr in (1000.0, 100.0, 100.0, 1000.0, 100.0):
        _emit_step(led, 0, throughput=thr)
    led.close()
    assert mon.breaches == 2  # breach, recover, breach again


def test_metrics_sink_goodput_gauges_and_slo_counter():
    from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink

    reg = MetricsRegistry()
    sink = metrics_ledger_sink(reg)
    text = reg.render()
    # pre-registered at zero: absence and zero are different answers
    assert "tpu_dist_goodput_ratio 0" in text
    assert 'tpu_dist_slo_breaches_total{kind="steps_per_min"} 0' in text
    assert 'tpu_dist_badput_seconds{category="restart_gap"} 0' in text
    assert "tpu_dist_last_step_age_s -1" in text
    sink({"event": "goodput", "ts": 1.0, "wall_s": 10.0, "goodput_s": 4.0,
          "ratio": 0.4, "categories": {"startup": 3.0, "idle": 3.0}})
    sink({"event": "slo", "ts": 1.1, "step": 3, "kind": "throughput",
          "value": 10.0, "floor": 100.0})
    text = reg.render()
    assert "tpu_dist_goodput_ratio 0.4" in text
    assert 'tpu_dist_badput_seconds{category="startup"} 3' in text
    assert 'tpu_dist_slo_breaches_total{kind="throughput"} 1' in text


def test_healthz_reports_last_step_age(tmp_path):
    """The progress-aware /healthz satellite: the body carries
    last_step_age_s (computed at read time, no registry render); /livez
    stays a bare liveness probe."""
    import urllib.request

    from tpu_dist.obs.metrics import (MetricsRegistry, metrics_ledger_sink,
                                      serve_metrics)

    reg = MetricsRegistry()
    sink = metrics_ledger_sink(reg)
    srv = serve_metrics(reg, port=0, host="127.0.0.1")
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            assert r.read().decode() == "ok last_step_age_s=-1.000\n"
        import time

        sink({"event": "step", "ts": time.time(), "step": 0, "loss": 1.0,
              "throughput": 1.0, "unit": "t", "data_s": 0, "dispatch_s": 0,
              "device_s": 0, "comm_s": None, "mfu": None})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            body = r.read().decode()
        age = float(body.split("last_step_age_s=")[1])
        assert 0.0 <= age < 60.0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/livez", timeout=5) as r:
            assert r.read().decode() == "ok\n"
    finally:
        srv.close()


# ------------------------------------------ ACCEPTANCE: 2-attempt LM smoke

def test_two_attempt_lm_smoke_goodput_slo_flightrec(tmp_path):
    """ISSUE 7 acceptance: attempt 1 dies mid-run, attempt 2 resumes from
    its checkpoint under attempt=-1 auto-lineage; ledger_report renders a
    goodput section whose categories sum to ~100% of the stitched wall
    including the restart gap, and a forced progress-SLO breach emits an
    `slo` event that auto-triggers a flightrec bundle."""
    import dataclasses

    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    path = str(tmp_path / "run.jsonl")
    ck = str(tmp_path / "ck")
    cfg = LMConfig(epochs=2, batch_size=8, seq_len=32, vocab_size=64,
                   num_layers=1, d_model=32, num_heads=2, synth_tokens=2048,
                   print_freq=4, seed=0, ledger_path=path,
                   checkpoint_dir=ck, flightrec_trace_steps=0,
                   goodput_every_s=0.0)
    tr1 = LMTrainer(cfg)
    assert tr1.obs.attempt == 0 and tr1.obs.job_id == "run"
    real_validate = tr1.validate

    def dies_in_epoch_1(epoch=0):
        if epoch >= 1:  # epoch 0 completes (ckpt lands), epoch 1 dies
            raise RuntimeError("preempted")
        return real_validate(epoch)

    tr1.validate = dies_in_epoch_1
    with pytest.raises(RuntimeError, match="preempted"):
        tr1.fit()
    assert os.path.exists(path)

    # attempt 2: auto-lineage picks .a1, resumes from the epoch-0 ckpt,
    # and a floor no CPU run can meet forces the SLO breach
    cfg2 = dataclasses.replace(
        cfg, attempt=-1, resume=os.path.join(ck, "lm-checkpoint.msgpack"),
        slo_steps_per_min=1e9)
    tr2 = LMTrainer(cfg2)
    assert tr2.obs.attempt == 1
    tr2.fit()
    a1 = str(tmp_path / "run.a1.jsonl")
    assert os.path.exists(a1)

    from tools.ledger_report import summarize

    records = read_ledger(path, strict=False) + read_ledger(a1,
                                                            strict=False)
    lines = []
    summary = summarize(records, out=lines.append)
    gp = summary["goodput"]
    # categories + goodput sum to ~100% of the stitched wall-clock,
    # restart gap included (idle absorbs residue; only double-attribution
    # could break the sum, and it must not have happened here)
    total = sum(gp["categories"].values()) + gp["goodput_s"]
    assert total == pytest.approx(gp["wall_s"], rel=0.01)
    assert gp["overrun_s"] == 0.0
    assert gp["categories"]["restart_gap"] > 0
    assert gp["goodput_s"] > 0 and gp["categories"]["startup"] > 0
    assert len(gp["attempts"]) == 2
    assert gp["attempts"][0]["status"] == "crashed"
    assert gp["attempts"][1]["status"] == "ok"
    txt = "\n".join(lines)
    assert "goodput (2 attempt(s)" in txt and "restart gap" in txt
    # each attempt emitted its final partition event
    finals = [r for r in records if r["event"] == "goodput"
              and r.get("final")]
    assert len(finals) == 2
    # the forced breach: slo event -> flightrec bundle, via the sink path
    slos = [r for r in records if r["event"] == "slo"]
    assert slos and slos[0]["kind"] == "steps_per_min"
    diags = [r for r in records if r["event"] == "diagnosis"
             and r["reason"] == "slo"]
    assert diags and os.path.isdir(diags[0]["bundle"])
    assert gp["slo_breaches"] == len(slos)
    # run lineage stamped in run_start
    starts = [r for r in records if r["event"] == "run_start"]
    assert [s["attempt"] for s in starts] == [0, 1]
    assert all(s["job_id"] == "run" for s in starts)
    assert starts[1]["resumed_from"] == cfg2.resume
