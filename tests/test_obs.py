"""Observability subsystem (round 6): ledger / tracer / skew / watchdog.

Covers: schema round-trip for every declared event type, tracer span
nesting + accumulation, watchdog firing on an injected stall (and staying
silent on a healthy loop) WITHOUT killing the run, the skew monitor's
straggler math (single-process inline; 2 real processes via
mp_obs_worker), both engines' CPU smoke runs producing
fully-populated step records, the epoch-CSV-as-sink parity, and the static
schema checker as a plain test (tier-1 schema-drift tripwire)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from tpu_dist.obs import (EVENT_SCHEMA, EpochCsvSink, Ledger, ProgressSink,
                          SkewMonitor, StepTracer, Watchdog, trace,
                          per_process_path, phase_totals, read_ledger)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- ledger
def _required_stub(event):
    """A value for every required field of ``event`` (None is legal)."""
    return {k: None for k in EVENT_SCHEMA[event]}


def test_ledger_schema_roundtrip_every_event(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    for event in EVENT_SCHEMA:
        led.emit(event, **_required_stub(event))  # ledger-schema: forward
    led.close()
    recs = read_ledger(path)  # validates: declared event + required fields
    assert [r["event"] for r in recs] == list(EVENT_SCHEMA)
    for r in recs:
        assert r["ts"] > 0 and r["pid"] == 0


def test_ledger_run_start_captures_config_and_mesh(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    led.emit("run_start", kind="test", config={"lr": 0.1, "arch": "lenet"},
             mesh={"data": 4, "model": 2}, devices=["cpu"], process_count=1)
    led.close()
    (rec,) = read_ledger(path)
    assert rec["config"]["arch"] == "lenet"
    assert rec["mesh"] == {"data": 4, "model": 2}


def test_ledger_rejects_undeclared_event_and_missing_fields(tmp_path):
    led = Ledger(str(tmp_path / "x.jsonl"))
    with pytest.raises(ValueError, match="undeclared"):
        led.emit("not_an_event", foo=1)  # ledger-schema: forward
    with pytest.raises(ValueError, match="missing required"):
        led.emit("step", step=0)  # ledger-schema: forward
    led.close()


def test_ledger_pathless_sink_only_and_thread_safe():
    seen = []
    led = Ledger(None)
    led.add_sink(seen.append)

    def spam():
        for i in range(50):
            led.emit("hbm", bytes_in_use=i)

    threads = [threading.Thread(target=spam) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(seen) == 200
    assert led.last["event"] == "hbm"
    led.close()


def test_per_process_path():
    assert per_process_path("run.jsonl", 0) == "run.jsonl"
    assert per_process_path("run.jsonl", 3) == "run.p3.jsonl"
    assert per_process_path("/a/b/tele.csv", 1) == "/a/b/tele.p1.csv"
    assert per_process_path("", 2) == ""


def test_epoch_csv_sink_renders_legacy_row(tmp_path):
    """The cookbook-parity CSV row is a VIEW of the ledger's epoch event:
    [wall_start, seconds, rate, hbm] — identical to what the loops wrote
    inline through round 5."""
    import csv

    path = str(tmp_path / "ep.csv")
    led = Ledger(None)
    led.add_sink(EpochCsvSink(path))
    led.emit("epoch", epoch=0, start_ts=123.5, seconds=7.25,
             throughput=1234.56, unit="img/s", loss=0.5, hbm_bytes=999)
    led.emit("epoch", epoch=1, start_ts=130.75, seconds=6.0,
             throughput=2000.0, unit="img/s", loss=0.4, hbm_bytes=None)
    led.close()
    rows = list(csv.reader(open(path)))
    assert rows == [["123.5", "7.25", "1234.6", "999"],
                    ["130.75", "6.0", "2000.0", ""]]


def test_all_none_records_render_without_crashing(tmp_path):
    """The schema pins PRESENCE, not non-nullness — every renderer
    (ProgressSink, ledger_report.summarize) must survive records whose
    required fields are all None (e.g. a backend with no counters)."""
    path = str(tmp_path / "n.jsonl")
    led = Ledger(path)
    for event in EVENT_SCHEMA:
        led.emit(event, **_required_stub(event))  # ledger-schema: forward
    led.close()
    recs = read_ledger(path)
    sink = ProgressSink(printer=lambda s: None)
    for r in recs:
        sink(r)
    from tools.ledger_report import summarize

    summarize(recs, out=lambda s: None)


def test_watchdog_beat_derives_interdrain_durations():
    """beat() (the loops' drain-point signal) self-derives durations: the
    first beat only arms, later beats append the inter-beat gap, and a
    beat after pause() re-arms without polluting the median with the
    eval-phase gap."""
    wd = Watchdog(factor=2.0, min_timeout_s=0.01, poll_s=5.0)
    wd.beat()  # arms only
    assert len(wd._durations) == 0
    time.sleep(0.05)
    wd.beat()
    assert len(wd._durations) == 1 and wd._durations[0] >= 0.04
    wd.pause()
    time.sleep(0.1)  # an eval-sized gap that must NOT enter the median
    wd.beat()
    assert len(wd._durations) == 1  # re-armed, no new duration
    time.sleep(0.03)
    wd.beat()
    assert len(wd._durations) == 2 and wd._durations[-1] < 0.09
    wd.stop()


def test_progress_sink_renders_step_line():
    lines = []
    sink = ProgressSink(printer=lines.append)
    sink({"event": "step", "step": 3, "loss": 1.25, "throughput": 1000.0,
          "unit": "tok/s", "mfu": 0.5, "data_s": 0.1, "dispatch_s": 0.2,
          "device_s": 0.3})
    assert "step 3" in lines[0] and "MFU 50.0%" in lines[0]
    assert "1,000 tok/s" in lines[0]


# ---------------------------------------------------------------- tracer
def test_tracer_span_nesting_and_accumulation():
    tr = StepTracer(prefix="train.")
    with tr.span("data"):
        time.sleep(0.02)
        with tr.span("decode"):
            time.sleep(0.02)
    with tr.span("data"):  # accumulates into the same key
        time.sleep(0.01)
    ph = tr.pop()
    assert set(ph) == {"data", "data/decode"}
    # parent includes the child (wall-clock truth), second span adds on
    assert ph["data"] >= ph["data/decode"] >= 0.02
    assert ph["data"] >= 0.03
    # pop() reset
    assert tr.pop() == {}
    # the same spans are in the process-wide ring, under the prefix, and
    # the sums are the ring's own lengths
    # (a full garbage collection landing here is a span of its own)
    mine = [sp for sp in trace.ring().tail(8) if sp.name != "host.gc"][-3:]
    assert [sp.name for sp in mine] == ["train.decode", "train.data",
                                        "train.data"]
    assert ph["data"] == pytest.approx(
        sum(sp.end - sp.start for sp in mine if sp.name == "train.data"))
    assert not hasattr(tr, "add") and not hasattr(tr, "phases")


def test_ring_keeps_parents_and_attrs_and_is_bounded():
    r = trace.SpanRing(size=8)
    clock = iter(range(100))
    now = lambda: float(next(clock))
    with r.span("serve.step", now=now, tick=7) as step:
        assert [sp.name for sp in r.open_stack()] == ["serve.step"]
        with r.span("serve.evict", now=now) as ev:
            ev.attrs["n"] = 2          # what the phase found out
        with r.span("serve.tick", now=now, rids=[3, 4]):
            with r.span("tick.wait", now=now):
                pass
    evict, wait, tick, top = r.snapshot()   # a child closes before its parent
    assert (evict.name, evict.attrs) == ("serve.evict", {"n": 2})
    assert top.parent is None and top.attrs == {"tick": 7}
    assert evict.parent == tick.parent == top.sid == step.sid
    assert wait.parent == tick.sid and tick.attrs == {"rids": [3, 4]}
    for child, parent in ((evict, top), (wait, tick), (tick, top)):
        assert parent.start <= child.start <= child.end <= parent.end
    assert r.open_stack() == []
    for i in range(20):                      # bounded: the oldest fall out
        with r.span("x", now=now, i=i):
            pass
    assert len(r.snapshot()) == 8
    assert [sp.attrs["i"] for sp in r.snapshot()] == list(range(12, 20))


def test_ring_dump_is_jsonl_and_timed_iter_spans_the_wait(tmp_path):
    tr = StepTracer(prefix="t.")
    assert list(tr.timed_iter("data", iter([1, 2, 3]))) == [1, 2, 3]
    assert "data" in tr.pop()
    path = str(tmp_path / "spans.jsonl")
    n = trace.ring().dump(path)
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == n and rows[-1]["name"] == "t.data"
    assert {"sid", "name", "start", "end", "parent"} <= set(rows[-1])


def test_every_span_is_in_a_profiler_sessions_trace(tmp_path):
    """No switch: a session that somebody else started (here the test)
    holds the program's spans, with the ring's own ids."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.ring().span("serve.step", tick=1) as outer:
            with StepTracer(prefix="train.").span("dispatch") as inner:
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    seen = {ev.name: dict(ev.stats).get("sid")
            for plane in ProfileData.from_file(pb).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("tpu_dist:")}
    assert seen == {"tpu_dist:serve.step": outer.sid,
                    "tpu_dist:train.dispatch": inner.sid}


# -------------------------------------------------------------- watchdog
def test_watchdog_fires_on_stall_without_killing_run(tmp_path):
    import io

    path = str(tmp_path / "wd.jsonl")
    led = Ledger(path)
    err = io.StringIO()
    wd = Watchdog(factor=2.0, ledger=led, min_timeout_s=0.05, poll_s=0.02,
                  stream=err)
    for _ in range(5):
        wd.step_done(0.02)
    time.sleep(0.5)  # the injected stall: no step completes
    assert wd.stall_count == 1  # fired ONCE per stall, not per poll
    dump = err.getvalue()
    assert "NO STEP COMPLETED" in dump
    assert "tpu-dist-watchdog" not in dump.split("--- thread")[0]
    assert "--- thread" in dump  # stack dump includes thread frames
    # the run is NOT killed: stepping resumes and re-arms cleanly
    wd.step_done(0.02)
    time.sleep(0.1)
    assert wd.stall_count == 2  # a second stall fires again after re-arm
    wd.stop()
    led.close()
    stalls = [r for r in read_ledger(path) if r["event"] == "stall"]
    assert len(stalls) == 2
    assert stalls[0]["idle_s"] >= 0.05
    assert "--- thread" in stalls[0]["stacks"]


def test_watchdog_silent_on_healthy_loop_and_when_paused(tmp_path):
    led = Ledger(str(tmp_path / "wd2.jsonl"))
    wd = Watchdog(factor=2.0, ledger=led, min_timeout_s=0.05, poll_s=0.02)
    for _ in range(20):  # healthy cadence well under the threshold
        wd.step_done(0.01)
        time.sleep(0.01)
    assert wd.stall_count == 0
    wd.pause()  # eval/ckpt phase: no steps complete, by design
    time.sleep(0.3)
    assert wd.stall_count == 0
    wd.stop()
    led.close()
    assert not [r for r in read_ledger(led.path) if r["event"] == "stall"]


def _stalling_loop(tmp_path, span_name, **attrs):
    """A loop that beats a few times and then sits inside one span for
    ten times the watchdog's (shortened) threshold, with a flight recorder
    on the ledger as the engines wire it."""
    import io

    from tpu_dist.obs import FlightRecorder

    led = Ledger(str(tmp_path / "wd.jsonl"))
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led, trace_steps=2)
    led.add_sink(fr.sink)
    err = io.StringIO()
    wd = Watchdog(factor=2.0, ledger=led, min_timeout_s=0.05, poll_s=0.01,
                  stream=err)
    tr = StepTracer(prefix="train.")
    for _ in range(5):
        wd.step_done(0.005)
    with tr.span(span_name, **attrs):
        deadline = time.monotonic() + 5.0
        while (wd.stall_count + wd.compile_waits == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)            # polls; no real seconds are slept
        time.sleep(0.05)                # a second threshold: still one note
    wd.stop()
    led.close()
    return wd, fr, err.getvalue(), read_ledger(led.path)


def test_watchdog_reads_a_long_first_dispatch_as_a_compilation(tmp_path):
    wd, fr, err, recs = _stalling_loop(tmp_path, "dispatch", step=0,
                                       first_call=True)
    assert (wd.compile_waits, wd.stall_count) == (1, 0)
    assert err.count("a compilation, not a stall") == 1
    assert "train.dispatch" in err and "NO STEP COMPLETED" not in err
    assert not [r for r in recs if r["event"] in ("stall", "diagnosis")]
    assert fr.bundles == [] and fr._trace is None    # no window armed


@pytest.mark.parametrize("span_name,attrs", [
    ("wait", {}),                                    # the drain's device_get
    ("dispatch", {"step": 9, "first_call": False}),  # a compiled program
])
def test_watchdog_names_the_open_span_of_a_real_stall(tmp_path, span_name,
                                                      attrs):
    wd, fr, err, recs = _stalling_loop(tmp_path, span_name, **attrs)
    assert (wd.compile_waits, wd.stall_count) == (0, 1)
    head = err.split("--- thread")[0]       # before the thread stacks
    assert "NO STEP COMPLETED" in head
    assert "open spans (outermost first):" in head and "last spans:" in head
    assert f"train.{span_name} [" in head.split("last spans:")[0]
    (stall,) = [r for r in recs if r["event"] == "stall"]
    assert stall["open_span"] == f"train.{span_name}"
    assert len(fr.bundles) == 1             # the stall armed the recorder
    tail = [json.loads(line) for line in
            open(os.path.join(fr.bundles[0], "spans_tail.jsonl"))]
    assert any(r.get("open") and r["name"] == f"train.{span_name}"
               for r in tail)


# ------------------------------------------------------------------ skew
def test_skew_monitor_single_process(tmp_path):
    led = Ledger(str(tmp_path / "skew.jsonl"))
    mon = SkewMonitor(every=3, ledger=led)
    assert mon.record(0, 0.01) is None  # not at the boundary yet
    assert mon.record(1, 0.01) is None
    stats = mon.record(2, 0.02, data_s=0.005)
    assert stats is not None
    assert stats["straggler"] == 0 and stats["n_procs"] == 1
    assert stats["spread_s"] == 0.0
    assert stats["p50_s"] == pytest.approx(np.mean([0.01, 0.01, 0.02]))
    led.close()
    (rec,) = [r for r in read_ledger(led.path) if r["event"] == "skew"]
    assert rec["step"] == 2 and rec["straggler"] == 0


def test_skew_monitor_two_real_processes(tmp_path):
    """Straggler detection over an actual process boundary: process 1
    reports 3x step times; every process's allgathered stats must agree
    that process 1 is the straggler (reuses the mp_worker spawn pattern)."""
    from test_multiprocess import run_workers  # tests/ is on sys.path

    worker = os.path.join(ROOT, "tests", "mp_obs_worker.py")
    outdir = run_workers(str(tmp_path), "skew", nprocs=2, local_devices=2,
                         worker=worker)
    for rank in (0, 1):
        with open(os.path.join(outdir, f"skew-result-{rank}.json")) as f:
            res = json.load(f)
        assert res["process_count"] == 2
        assert res["stats"]["n_procs"] == 2
        assert res["stats"]["straggler"] == 1  # the injected slow process
        assert res["stats"]["spread_s"] == pytest.approx(0.020, abs=1e-6)
    # each process wrote its OWN ledger file (.pN suffix for non-main)
    assert os.path.exists(os.path.join(outdir, "skew.jsonl"))
    assert os.path.exists(os.path.join(outdir, "skew.p1.jsonl"))
    # acceptance: the two REAL per-process ledgers merge into one valid
    # Chrome trace with a lane per process
    from tools.trace_merge import merge_ledgers

    trace = json.loads(json.dumps(
        merge_ledgers([os.path.join(outdir, "skew.jsonl"),
                       os.path.join(outdir, "skew.p1.jsonl")])))
    assert trace["otherData"]["processes"] == 2
    assert {e["pid"] for e in trace["traceEvents"]} == {0, 1}


# -------------------------------------------------- engine smoke (CPU)
def _assert_step_records_complete(recs, unit):
    steps = [r for r in recs if r["event"] == "step"]
    assert steps, "no step events in ledger"
    for r in steps:
        for k in ("data_s", "dispatch_s", "device_s", "mfu", "throughput",
                  "loss"):
            assert r[k] is not None, (k, r)
        # the fused health probes (obs.health) ride every step record
        for k in ("grad_norm", "nonfinite_count", "update_norm"):
            assert r[k] is not None, (k, r)
        assert r["nonfinite_count"] == 0  # a healthy smoke run
        assert r["unit"] == unit
    assert phase_totals(steps)["dispatch_s"] > 0
    return steps


def _assert_run_shape(recs):
    events = [r["event"] for r in recs]
    assert events[0] == "run_start" and events[-1] == "run_end"
    assert "compile" in events and "epoch" in events and "eval" in events
    # cost attribution rides the compile probe (obs.attr): buckets with
    # real flops, matmul (or attention) among them
    (cm,) = [r for r in recs if r["event"] == "cost_model"]
    assert cm["total_flops"] > 0 and cm["buckets"]
    assert any(c in cm["buckets"] for c in ("matmul", "attention"))
    run = recs[0]
    assert run["config"] and run["devices"] and run["mesh"]
    # crash-safe shutdown: a clean run stamps status=ok, and the registry
    # snapshot lands just before run_end
    assert recs[-1]["status"] == "ok"
    assert events[-2] == "metrics_snapshot"
    assert recs[-2]["metrics"]["tpu_dist_steps_total"]


def test_image_engine_ledger_smoke(tmp_path):
    """Acceptance: a CPU run of the image engine with ledger_path set
    yields step records with non-null phase breakdown, MFU and throughput,
    and tools/ledger_report renders the file."""
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine.loop import Trainer

    path = str(tmp_path / "img.jsonl")
    cfg = TrainConfig(arch="lenet", dataset="synthetic", epochs=1,
                      batch_size=16, workers=1, print_freq=2, seed=0,
                      synth_train_size=64, synth_val_size=32,
                      checkpoint_dir=str(tmp_path / "ck"),
                      ledger_path=path, log_csv=str(tmp_path / "ep.csv"),
                      skew_every=2)
    with trace.ring().span("mark") as mark:   # the ring is the process's
        pass
    Trainer(cfg).fit()
    recs = read_ledger(path)
    _assert_run_shape(recs)
    _assert_step_records_complete(recs, "img/s")
    assert [r for r in recs if r["event"] == "skew"]
    assert [r for r in recs if r["event"] == "ckpt"]
    # run_end left the program's spans beside the ledger: the drain's wait
    # and emit under their epoch, each dispatch with its step
    from tpu_dist.obs import SPANS_SUFFIX

    mine = [sp for sp in map(json.loads, open(path + SPANS_SUFFIX))
            if sp["sid"] > mark.sid]
    assert {"train.epoch", "train.data", "train.dispatch", "train.wait",
            "train.emit"} <= {sp["name"] for sp in mine}
    steps = {r["step"] for r in recs if r["event"] == "step"}
    assert {sp["step"] for sp in mine
            if sp["name"] == "train.dispatch"} == steps
    assert [sp["first_call"] for sp in mine
            if sp["name"] == "train.dispatch"][:2] == [True, False]
    # the legacy CSV rendered as a sink, same values as the epoch event
    import csv

    (ep,) = [r for r in recs if r["event"] == "epoch"]
    (row,) = list(csv.reader(open(tmp_path / "ep.csv")))
    assert float(row[0]) == pytest.approx(ep["start_ts"])
    assert float(row[2]) == pytest.approx(round(ep["throughput"], 1))
    # the report tool renders it
    from tools.ledger_report import summarize

    lines = []
    counts = summarize(recs, out=lines.append)
    assert counts["steps"] > 0 and counts["epochs"] == 1
    assert any("phase time share" in ln for ln in lines)


def test_run_start_carries_the_build_and_the_report_prints_it(tmp_path):
    """``Trainer.fit()``'s run_start record carries what the constructor
    cost (``build_s``: the train.build span and its parts) and how many
    backend compilations it made; tools/ledger_report prints both from the
    recorded ledger, and the spans land beside it."""
    from tools.ledger_report import summarize
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine.loop import Trainer
    from tpu_dist.obs import SPANS_SUFFIX

    path = str(tmp_path / "img.jsonl")
    tr = Trainer(TrainConfig(
        arch="lenet", dataset="synthetic-mnist", epochs=1, batch_size=16,
        print_freq=100, seed=0, synth_train_size=64, synth_val_size=32,
        steps_per_dispatch=2, checkpoint_dir=str(tmp_path / "ck"),
        ledger_path=path))
    tr.fit()
    recs = read_ledger(path)
    (start,) = [r for r in recs if r["event"] == "run_start"]
    assert start["build_s"] == tr.build_info["build_s"]
    assert list(start["build_s"]) == ["total", "data", "init", "place"]
    assert 1 <= start["build_compiles"] == tr.build_info["build_compiles"]
    lines = []
    summary = summarize(recs, out=lines.append)
    (line,) = [ln for ln in lines if ln.startswith("build: ")]
    assert f"{start['build_compiles']} backend compilations" in line
    assert all(f"{part} " in line for part in ("data", "init", "place"))
    assert summary["run"]["build_s"] == start["build_s"]
    assert summary["run"]["build_compiles"] == start["build_compiles"]
    names = [json.loads(ln)["name"] for ln in open(path + SPANS_SUFFIX)]
    assert {"train.build", "build.data", "build.init",
            "build.place"} <= set(names)
    # a ledger recorded before the constructor was measured still renders
    for r in recs:
        r.pop("build_s", None), r.pop("build_compiles", None)
    lines = []
    summarize(recs, out=lines.append)
    assert not [ln for ln in lines if ln.startswith("build: ")]


def test_lm_engine_ledger_smoke(tmp_path):
    """Acceptance twin for the LM engine, windowed (K>1) path included —
    plus the live-metrics acceptance: a curl-equivalent scrape of the
    Prometheus endpoint DURING the run returns parseable text carrying
    step throughput, MFU, and the stall/health-trip counters."""
    import socket
    import urllib.request

    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    path = str(tmp_path / "lm.jsonl")
    tr = None
    for _ in range(5):  # free-port probe is TOCTOU; retry on the rare race
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cfg = LMConfig(epochs=1, batch_size=8, seq_len=32, vocab_size=64,
                       num_layers=1, d_model=32, num_heads=2,
                       synth_tokens=4096, print_freq=4, seed=0,
                       steps_per_dispatch=3, ledger_path=path,
                       metrics_port=port)
        tr = LMTrainer(cfg)
        if tr.obs.metrics_server is not None:
            break
        os.remove(path)  # the lost race left a stale ledger; start clean
    assert tr.obs.metrics_server is not None
    scraped = {}

    def scrape_mid_run(rec):
        # the epoch event lands mid-run (before run_end closes the
        # endpoint): scrape exactly then, deterministically
        if rec.get("event") == "epoch" and "text" not in scraped:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
                scraped["text"] = r.read().decode()

    tr.obs.ledger.add_sink(scrape_mid_run)
    tr.fit()
    from test_metrics import assert_prometheus_parseable

    text = scraped["text"]
    assert_prometheus_parseable(text)
    assert "tpu_dist_steps_total" in text and "tpu_dist_mfu" in text
    assert 'tpu_dist_step_throughput{unit="tok/s"}' in text
    assert "tpu_dist_stalls_total 0" in text
    assert 'tpu_dist_health_trips_total{kind="nonfinite"} 0' in text
    recs = read_ledger(path)
    _assert_run_shape(recs)
    steps = _assert_step_records_complete(recs, "tok/s")
    # the windowed path records K-step dispatches
    assert max(r["steps_in_dispatch"] for r in steps) == 3
    (ep,) = [r for r in recs if r["event"] == "epoch"]
    assert ep["unit"] == "tok/s" and ep["ppl"] > 0


def test_crash_safe_run_end_stamps_status(tmp_path):
    """The crash-shutdown satellite: an unhandled exception inside the
    loop reaches run_end through fit()'s finally with status='crashed'
    and a truncated traceback — and the line-buffered JSONL means every
    prior event already survived on disk."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    path = str(tmp_path / "crash.jsonl")
    cfg = LMConfig(epochs=1, batch_size=8, seq_len=32, vocab_size=64,
                   num_layers=1, d_model=32, num_heads=2, synth_tokens=2048,
                   print_freq=2, seed=0, ledger_path=path)
    tr = LMTrainer(cfg)

    def boom(epoch=0):
        raise RuntimeError("injected mid-run crash")

    tr.validate = boom  # dies after the train epoch, inside fit()
    with pytest.raises(RuntimeError, match="injected"):
        tr.fit()
    recs = read_ledger(path)
    (end,) = [r for r in recs if r["event"] == "run_end"]
    assert end["status"] == "crashed"
    assert "injected mid-run crash" in end["error"]
    assert [r for r in recs if r["event"] == "step"]  # prior events intact
    # the guard disarmed cleanly (compare the underlying functions — a
    # bound method is a fresh object per attribute access, so `is`
    # against tr.obs._excepthook would be vacuous)
    import signal as _signal
    import sys as _sys

    from tpu_dist.obs import RunObs

    assert tr.obs._prev_excepthook is None
    assert getattr(_sys.excepthook, "__func__", None) \
        is not RunObs._excepthook
    assert getattr(_signal.getsignal(_signal.SIGTERM), "__func__", None) \
        is not RunObs._on_sigterm


def test_generate_ledger_decode_event(tmp_path):
    import jax.numpy as jnp

    from tpu_dist.engine.generate import generate
    from tpu_dist.models.transformer import tiny_lm

    model = tiny_lm(vocab_size=32, num_layers=1, d_model=16, num_heads=2,
                    max_len=16)
    import jax

    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 16), jnp.int32),
                        train=False)["params"]
    led = Ledger(str(tmp_path / "gen.jsonl"))
    prompt = jnp.zeros((2, 4), jnp.int32)
    out = generate(model, params, prompt, steps=5, ledger=led)
    led.close()
    assert out.shape == (2, 9)
    (rec,) = [r for r in read_ledger(led.path) if r["event"] == "decode"]
    assert rec["tokens"] == 10 and rec["throughput"] > 0
    assert rec["dispatch_s"] >= 0 and rec["device_s"] >= 0


# ------------------------------------------------------- static checker
def test_check_ledger_schema_tree_is_clean():
    """Tier-1 tripwire: every ledger.emit call site in the tree names a
    declared event and passes its required fields (AST walk, no jax)."""
    from tools.check_ledger_schema import check_tree, load_schema

    assert load_schema() == EVENT_SCHEMA  # AST extraction == runtime dict
    assert check_tree() == []


def test_check_ledger_schema_catches_drift(tmp_path):
    """The checker actually rejects: undeclared events, computed event
    names, and required fields hidden in a **splat."""
    from tools.check_ledger_schema import check_file, load_schema

    bad = tmp_path / "bad.py"
    bad.write_text(
        "ledger.emit('no_such_event', x=1)\n"
        "ledger.emit(name, step=1)\n"
        "ledger.emit('step', **fields)\n"
        "self.obs.ledger.emit('ckpt', epoch=1, path='p', is_best=False)\n")
    out = check_file(str(bad), load_schema(), "bad.py")
    assert len(out) == 3  # the last line is conformant
    assert any("undeclared" in v for v in out)
    assert any("literal" in v for v in out)
    assert any("missing required" in v for v in out)
