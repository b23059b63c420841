"""Flight recorder (obs.flightrec): triggered forensic bundles.

Covers: a direct trigger writing a complete bundle (manifest + stacks +
ledger tail) and its ``diagnosis`` ledger event; the ledger-sink
auto-triggers (stall event, health event, skew-straggler spike — and
benign skew staying silent); cooldown/cap rate limiting; bundle-root
derivation from the ledger path; SIGUSR1 through RunObs; and the
acceptance test — an induced stall in a CPU LM engine smoke producing a
bundle with a valid manifest, a ``diagnosis`` event, and a captured
jax.profiler trace window of the steps after the trigger.
"""

import json
import os
import signal
import time

import pytest

from tpu_dist.obs import FlightRecorder, Ledger, read_ledger
from tpu_dist.obs.flightrec import SKEW_SPREAD_MIN_S


def _manifest(bundle):
    with open(os.path.join(bundle, "manifest.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- unit-ish
def test_trigger_writes_bundle_and_diagnosis_event(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led,
                        trace_steps=0)
    led.add_sink(fr.sink)
    for i in range(5):  # ring content leading up to the trigger
        led.emit("hbm", bytes_in_use=i)
    led.emit("step", step=7, loss=1.0, throughput=10.0, unit="tok/s",
             data_s=0.0, dispatch_s=0.0, device_s=0.0, comm_s=None,
             mfu=None)
    bundle = fr.trigger("manual", note="operator asked")
    assert bundle and os.path.isdir(bundle)
    m = _manifest(bundle)
    assert m["reason"] == "manual" and m["note"] == "operator asked"
    assert m["step"] == 7  # last step record seen by the ring
    assert m["trace"]["status"] == "disabled"
    assert "stacks.txt" in m["files"] and "events_tail.jsonl" in m["files"]
    stacks = open(os.path.join(bundle, "stacks.txt")).read()
    assert "--- thread" in stacks
    tail = [json.loads(ln) for ln in
            open(os.path.join(bundle, "events_tail.jsonl"))]
    assert [r["event"] for r in tail].count("hbm") == 5
    assert tail[-1]["event"] == "step"
    led.close()
    (diag,) = [r for r in read_ledger(path) if r["event"] == "diagnosis"]
    assert diag["reason"] == "manual" and diag["bundle"] == bundle
    assert diag["step"] == 7 and diag["trace"] == "disabled"


def _step(led, i):
    led.emit("step", step=i, loss=1.0, throughput=10.0, unit="tok/s",
             data_s=0.0, dispatch_s=0.0, device_s=0.0, comm_s=None, mfu=None)


def test_bundle_carries_the_span_rings_tail(tmp_path):
    from tpu_dist.obs import trace

    fr = FlightRecorder(dir=str(tmp_path / "fr"), trace_steps=0)
    with trace.ring().span("train.epoch", epoch=3):
        with trace.ring().span("train.wait"):
            pass
        bundle = fr.trigger("manual")
    assert "spans_tail.jsonl" in _manifest(bundle)["files"]
    rows = [json.loads(ln) for ln in
            open(os.path.join(bundle, "spans_tail.jsonl"))]
    closed = [r for r in rows if not r.get("open")]
    assert closed[-1]["name"] == "train.wait"
    (still_open,) = [r for r in rows if r.get("open")]
    assert still_open["name"] == "train.epoch" and still_open["epoch"] == 3
    assert closed[-1]["parent"] == still_open["sid"]


def test_a_profiler_session_already_running_is_skipped_not_fatal(tmp_path):
    """Somebody else (a benchmark's --trace 1, a user) holds the global
    profiler when the recorder's window comes due: it records
    ``trace_skipped`` and goes on, and the other session is untouched."""
    import jax

    led = Ledger(str(tmp_path / "run.jsonl"))
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led, trace_steps=2)
    led.add_sink(fr.sink)
    jax.profiler.start_trace(str(tmp_path / "theirs"))
    try:
        bundle = fr.trigger("manual")
        assert _manifest(bundle)["trace"]["status"] == "armed"
        for i in range(3):
            _step(led, i)               # the window would start here
        m = _manifest(bundle)
        assert m["trace"]["status"] == "trace_skipped", m["trace"]
        assert "already" in m["trace"]["why"].lower()
        assert fr._trace is None        # nothing left armed or active
    finally:
        jax.profiler.stop_trace()       # theirs: still running, stops clean
    assert os.path.isdir(tmp_path / "theirs" / "plugins")
    # and the recorder is whole: the next trigger's window is its own
    fr.cooldown_s = 0.0
    bundle2 = fr.trigger("manual")
    for i in range(3, 7):
        _step(led, i)
    assert _manifest(bundle2)["trace"]["status"] == "captured"
    led.close()


def test_sink_auto_triggers_on_stall_health_and_skew_spike(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led,
                        trace_steps=0, cooldown_s=0.0)
    led.add_sink(fr.sink)
    led.emit("stall", idle_s=9.0, threshold_s=1.0, stacks="...")
    led.emit("health", step=3, kind="nonfinite", policy="record",
             action="record", value=1.0)
    # benign skew: small spread — must NOT trigger
    led.emit("skew", step=10, p50_s=0.01, p99_s=0.012, spread_s=0.002,
             straggler=0)
    # straggler spike: spread over both bounds
    led.emit("skew", step=20, p50_s=0.05,
             p99_s=SKEW_SPREAD_MIN_S, spread_s=SKEW_SPREAD_MIN_S + 0.1,
             straggler=1)
    led.close()
    assert [os.path.basename(b).split("-")[1] for b in fr.bundles] == \
        ["stall", "health", "skew"]
    diags = [r for r in read_ledger(path) if r["event"] == "diagnosis"]
    assert [d["reason"] for d in diags] == ["stall", "health", "skew"]
    assert "straggler 1" in diags[-1]["note"]


def test_cooldown_and_bundle_cap_rate_limit(tmp_path):
    led = Ledger(None)
    fr = FlightRecorder(dir=str(tmp_path / "fr"), ledger=led,
                        trace_steps=0, cooldown_s=60.0)
    assert fr.trigger("manual") is not None
    assert fr.trigger("manual") is None  # inside the cooldown
    fr2 = FlightRecorder(dir=str(tmp_path / "fr2"), ledger=led,
                         trace_steps=0, cooldown_s=0.0, max_bundles=2)
    assert fr2.trigger("a") and fr2.trigger("b")
    assert fr2.trigger("c") is None  # capped
    led.close()


def test_bundle_root_derives_from_ledger_path(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = Ledger(path)
    fr = FlightRecorder(ledger=led, trace_steps=0)
    bundle = fr.trigger("manual")
    assert bundle.startswith(path + ".flightrec")
    led.close()
    # pathless ledger: a temp root still captures the bundle
    fr2 = FlightRecorder(ledger=Ledger(None), trace_steps=0)
    b2 = fr2.trigger("manual")
    assert b2 and os.path.isfile(os.path.join(b2, "manifest.json"))
    import shutil

    shutil.rmtree(fr2._dir, ignore_errors=True)


# ------------------------------------------------------------ with jax
def test_sigusr1_captures_bundle_through_runobs(tmp_path):
    """kill -USR1 <pid> is the operator-initiated trigger: RunObs arms
    the handler at run_start, restores the previous one at run_end."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.obs import RunObs

    prev = signal.getsignal(signal.SIGUSR1)
    path = str(tmp_path / "run.jsonl")
    cfg = LMConfig(ledger_path=path, flightrec_trace_steps=0,
                   flightrec_dir=str(tmp_path / "fr"))
    obs = RunObs("lm", cfg, None, unit="tok/s")
    obs.run_start()
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)  # handler runs on the main thread imminently
    finally:
        obs.run_end()
    assert signal.getsignal(signal.SIGUSR1) == prev  # restored
    recs = read_ledger(path)
    (diag,) = [r for r in recs if r["event"] == "diagnosis"]
    assert diag["reason"] == "sigusr1"
    assert os.path.isfile(os.path.join(diag["bundle"], "manifest.json"))


def _run_stalling_lm(tmp_path, trace_steps: int):
    """A tiny CPU LM run with one injected mid-epoch stall: the watchdog
    fires, its ledger event auto-triggers the flight recorder."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    path = str(tmp_path / "lm.jsonl")
    cfg = LMConfig(epochs=1, batch_size=8, seq_len=32, vocab_size=64,
                   num_layers=1, d_model=32, num_heads=2,
                   synth_tokens=2304, print_freq=1, seed=0,
                   ledger_path=path, watchdog_factor=4.0,
                   flightrec_trace_steps=trace_steps,
                   flightrec_dir=str(tmp_path / "fr"))
    tr = LMTrainer(cfg)
    # shrink the watchdog's floor/poll so the injected stall fires fast
    # (production floor is 5s — too slow for tier-1)
    tr.obs.watchdog.min_timeout_s = 0.25
    tr.obs.watchdog.poll_s = 0.05
    orig_step, calls = tr.train_step, {"n": 0}

    def stalling_step(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 5:  # mid-epoch, after the median is established
            time.sleep(1.2)
        return orig_step(*a, **kw)

    tr.train_step = stalling_step
    tr.fit()
    return read_ledger(path)


def test_induced_stall_in_lm_engine_produces_bundle(tmp_path):
    """ACCEPTANCE: an induced stall in a CPU engine smoke produces a
    flight-recorder bundle with a valid manifest and a ``diagnosis``
    ledger event, and ledger_report renders the diagnosis section.
    trace_steps=0 here: the profiler's one-time ~20s init belongs behind
    the slow marker (test_stall_profiler_window_captured)."""
    recs = _run_stalling_lm(tmp_path, trace_steps=0)
    assert [r for r in recs if r["event"] == "stall"], "watchdog never fired"
    diags = [r for r in recs if r["event"] == "diagnosis"]
    assert diags and diags[0]["reason"] == "stall"
    bundle = diags[0]["bundle"]
    m = _manifest(bundle)
    assert m["reason"] == "stall" and "stacks.txt" in m["files"]
    assert m["step"] is not None
    assert m["trace"]["status"] == "disabled"
    # events_tail holds the run-up to the stall
    tail = [json.loads(ln) for ln in
            open(os.path.join(bundle, "events_tail.jsonl"))]
    assert any(r["event"] == "step" for r in tail)
    # the report tool surfaces the bundle
    from tools.ledger_report import summarize

    lines = []
    summary = summarize(recs, out=lines.append)
    assert summary["diagnosis"] == len(diags)
    assert any("DIAGNOSIS BUNDLES" in ln for ln in lines)
    assert any(bundle in ln for ln in lines)


@pytest.mark.slow
def test_stall_profiler_window_captured(tmp_path):
    """Full-size twin: the profiler window armed at the trigger captures
    the next step records into <bundle>/trace (slow: jax.profiler's
    first start_trace pays a ~20s one-time init on this backend)."""
    recs = _run_stalling_lm(tmp_path, trace_steps=2)
    diags = [r for r in recs if r["event"] == "diagnosis"]
    assert diags and diags[0]["reason"] == "stall"
    m = _manifest(diags[0]["bundle"])
    assert m["trace"]["status"] == "captured", m["trace"]
    trace_dir = os.path.join(diags[0]["bundle"], "trace")
    assert os.path.isdir(trace_dir) and any(os.scandir(trace_dir))
