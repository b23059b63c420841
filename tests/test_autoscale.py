"""Closed autoscaling loop (round 20): the observability plane drives
capacity, with an auditable decision ledger — no jax anywhere but the
acceptance run's worker children.

The pins that matter:

* the policy grammar refuses garbage and the checked-in exemplar
  (``scripts/autoscale_policy.json``) round-trips;
* the capacity monitor's signals fold deterministically from ledger
  records, scale-up attribution is the FIRST tripped signal in the
  canonical order, and scale-down needs sustained calm (hysteresis) with
  a breach-free window — pressure at max capacity resets the streak;
* ``replay_decisions`` over the canned fixture is byte-deterministic
  with exact decision pins (the same property ``scripts/lint.sh`` gates);
* every consumer speaks the events: the ledger schema, the Prometheus
  series, the trace_merge decision markers, ledger_report's decision
  section, the fleet stitcher's decision<->scale<->applied join, and
  the fleet run's own reaction lag (``headline.json``);
* the ACCEPTANCE scenario (``scripts/fleet_autoscale.json``: 3 hosts,
  one parked standby, a diurnal curve with an overload burst) runs end
  to end and — read from ``tools/fleet_report.py --json`` — shows
  hosts-live following traffic, every scale action paired 1:1 with a
  decision, zero shed requests lost, and the post-rescale plan hash
  matching a byte-deterministic re-run of the tuner at the new world
  size.
"""

import json
import os
import subprocess
import sys

import pytest

from tpu_dist.obs.autoscale import (CALM_SIGNAL, SIGNAL_NAMES,
                                    AutoscalePolicy, CapacityMonitor,
                                    LedgerTailer, emit_decision,
                                    replay_decisions)
from tpu_dist.obs.ledger import Ledger, read_ledger
from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink
from tpu_dist.sim.fleet import FleetLedger
from tpu_dist.sim.scenario import (RID_STRIDE, load_scenario,
                                   parse_scenario)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLICY = os.path.join(ROOT, "scripts", "autoscale_policy.json")
SCENARIO = os.path.join(ROOT, "scripts", "fleet_autoscale.json")
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "autoscale",
                       "ledger.jsonl")


# ---------------------------------------------------------------------------
# policy grammar

def _policy_doc(**over):
    doc = {"min_hosts": 1, "max_hosts": 3,
           "up": {"step": 1, "cooldown_ticks": 4,
                  "signals": {"queue_wait_ema_s": 0.1}},
           "down": {"step": 1, "cooldown_ticks": 4, "stable_ticks": 2,
                    "signals": {"queue_wait_ema_s": 0.05}}}
    doc.update(over)
    return doc


def test_policy_validation_refuses_garbage():
    with pytest.raises(ValueError, match="missing required key"):
        AutoscalePolicy.from_doc({"max_hosts": 2})
    with pytest.raises(ValueError, match="unknown signal"):
        AutoscalePolicy.from_doc(_policy_doc(
            up={"signals": {"vibes": 1.0}}))
    with pytest.raises(ValueError, match="step must be >= 1"):
        AutoscalePolicy.from_doc(_policy_doc(
            up={"step": 0, "signals": {"queue_wait_ema_s": 0.1}}))
    with pytest.raises(ValueError, match="max_hosts must be >="):
        AutoscalePolicy.from_doc(_policy_doc(min_hosts=5))
    with pytest.raises(ValueError, match="ema_alpha"):
        AutoscalePolicy.from_doc(_policy_doc(ema_alpha=0.0))
    with pytest.raises(ValueError, match="at least one trip"):
        AutoscalePolicy.from_doc(_policy_doc(up={"signals": {}}))
    # down-side signals without hysteresis would flap: refused
    with pytest.raises(ValueError, match="hysteresis is required"):
        AutoscalePolicy.from_doc(_policy_doc(
            down={"signals": {"queue_wait_ema_s": 0.05}}))


def test_checked_in_policy_loads_and_roundtrips():
    pol = AutoscalePolicy.load(POLICY)
    assert pol.min_hosts == 2 and pol.max_hosts == 3
    assert pol.up.signals and pol.down.signals
    assert pol.down.stable_ticks >= 1
    # the hysteresis band is real: every signal configured on both sides
    # trips up strictly ABOVE where it reads calm (no dead-zone overlap)
    for name, calm in pol.down.signals.items():
        trip = pol.up.signals.get(name)
        if trip is not None:
            assert trip > calm, (name, trip, calm)
    assert AutoscalePolicy.from_doc(pol.to_doc()).to_doc() == pol.to_doc()


# ---------------------------------------------------------------------------
# signal folding

def test_monitor_folds_signals_from_ledger_records():
    pol = AutoscalePolicy.from_doc(_policy_doc(ema_alpha=0.25))
    mon = CapacityMonitor(pol, hosts_live=1)
    assert all(mon.signal_value(n) is None or n == "slo_breaches_window"
               for n in SIGNAL_NAMES)
    mon.observe({"event": "request", "queue_wait_s": 0.2})
    assert mon.signal_value("queue_wait_ema_s") == pytest.approx(0.2)
    mon.observe({"event": "request", "queue_wait_s": 0.0})
    assert mon.signal_value("queue_wait_ema_s") == pytest.approx(0.15)
    mon.observe({"event": "admit", "queue_depth": 8})
    assert mon.signal_value("queue_depth_ema") == pytest.approx(8.0)
    mon.observe({"event": "kv_cache", "pages_free": 3, "pages_used": 13})
    assert mon.signal_value("free_page_frac") == pytest.approx(3 / 16)
    mon.observe({"event": "goodput", "ratio": 0.4})
    assert mon.signal_value("goodput_ratio") == pytest.approx(0.4)
    mon.observe({"event": "fleet", "tick": 5, "goodput_ratio": 0.5})
    assert mon.signal_value("goodput_ratio") == pytest.approx(0.5)
    assert mon.tick == 5
    # the slo window slides with the replay clock
    mon.observe({"event": "slo", "kind": "queue_wait"})
    assert mon.signal_value("slo_breaches_window") == 1.0
    mon.observe({"event": "fleet", "tick": 5 + pol.window_ticks + 1})
    assert mon.signal_value("slo_breaches_window") == 0.0
    # a sustained step-time regression pushes the changepoint ratio > 1
    for wall in (0.1,) * 8 + (0.3,) * 8:
        mon.observe({"event": "step", "data_s": 0.0, "dispatch_s": 0.0,
                     "device_s": wall, "steps_in_dispatch": 1})
    assert mon.signal_value("step_time_ratio") > 1.0
    mon.observe({"event": "diagnosis", "bundle": "bundles/b0"})
    with pytest.raises(ValueError, match="unknown autoscale signal"):
        mon.signal_value("vibes")
    dec = mon.evaluate(tick=40, hosts_live=1)
    assert dec is not None and dec["bundle"] == "bundles/b0"


# ---------------------------------------------------------------------------
# policy evaluation: attribution order, cooldown, hysteresis

def test_scale_up_attributes_first_tripped_signal_in_canonical_order():
    pol = AutoscalePolicy.from_doc(_policy_doc(
        up={"step": 1, "cooldown_ticks": 10,
            "signals": {"queue_depth_ema": 5.0,
                        "slo_breaches_window": 1.0}}))
    mon = CapacityMonitor(pol, hosts_live=1)
    mon.observe({"event": "admit", "queue_depth": 9})   # trips depth
    mon.observe({"event": "slo", "kind": "x"})          # trips slo too
    dec = mon.evaluate(tick=3)
    # slo_breaches_window precedes queue_depth_ema in SIGNALS: it names
    # the decision even though both tripped
    assert dec["signal"] == "slo_breaches_window"
    assert (dec["decision"], dec["direction"]) == ("d0", "up")
    assert (dec["hosts_from"], dec["target_hosts"]) == (1, 2)
    assert dec["tick"] == 3 and dec["threshold"] == 1.0
    # cooldown blocks an immediate repeat; expiry re-arms it
    assert mon.evaluate(tick=4) is None
    dec2 = mon.evaluate(tick=13)
    assert (dec2["decision"], dec2["target_hosts"]) == ("d1", 3)
    # at max capacity pressure produces NO decision
    assert mon.evaluate(tick=30) is None
    assert [d["decision"] for d in mon.decisions] == ["d0", "d1"]


def test_scale_down_needs_sustained_calm_and_breach_free_window():
    pol = AutoscalePolicy.from_doc(_policy_doc(
        min_hosts=1, max_hosts=2,
        up={"step": 1, "cooldown_ticks": 0,
            "signals": {"queue_wait_ema_s": 0.1}},
        down={"step": 1, "cooldown_ticks": 0, "stable_ticks": 3,
              "signals": {"queue_wait_ema_s": 0.05}}))
    mon = CapacityMonitor(pol, hosts_live=2)
    mon.observe({"event": "request", "queue_wait_s": 0.2})
    # tripped at max: no up decision, and the calm streak must not accrue
    assert mon.evaluate(tick=0) is None
    # cool the EMA below the calm threshold
    for _ in range(12):
        mon.observe({"event": "request", "queue_wait_s": 0.0})
    assert mon.signal_value("queue_wait_ema_s") < 0.05
    assert mon.evaluate(tick=10) is None     # calm starts counting here
    assert mon.evaluate(tick=12) is None     # held 2 < stable_ticks 3
    dec = mon.evaluate(tick=13)              # held 3 >= 3: fire
    assert (dec["direction"], dec["signal"]) == ("down", CALM_SIGNAL)
    assert (dec["hosts_from"], dec["target_hosts"]) == (2, 1)
    assert dec["value"] == 3.0 and dec["threshold"] == 3.0
    # at min capacity a further down never fires
    for t in (14, 20, 30):
        assert mon.evaluate(tick=t) is None
    # an SLO breach inside the window resets the streak entirely
    mon2 = CapacityMonitor(pol, hosts_live=2)
    for _ in range(12):
        mon2.observe({"event": "request", "queue_wait_s": 0.0})
    assert mon2.evaluate(tick=10) is None
    mon2.observe({"event": "slo", "kind": "x"})
    assert mon2.evaluate(tick=13) is None    # breach in window: no down
    assert mon2.evaluate(tick=10 + pol.window_ticks + 3) is None  # restart
    assert mon2.evaluate(
        tick=10 + pol.window_ticks + 6)["direction"] == "down"


# ---------------------------------------------------------------------------
# replay determinism: the canned fixture, same pins as scripts/lint.sh

def test_replay_decisions_fixture_is_byte_deterministic():
    with open(FIXTURE) as f:
        records = [json.loads(line) for line in f]

    def replay():
        return replay_decisions(records, AutoscalePolicy.load(POLICY),
                                hosts0=2)

    d1, d2 = replay(), replay()
    assert json.dumps(d1) == json.dumps(d2)
    assert [(d["decision"], d["direction"], d["signal"]) for d in d1] == \
        [("d0", "up", "slo_breaches_window"), ("d1", "down", CALM_SIGNAL)]
    assert d1[0]["tick"] == 14 and d1[1]["tick"] == 64
    assert (d1[0]["hosts_from"], d1[0]["target_hosts"]) == (2, 3)
    assert (d1[1]["hosts_from"], d1[1]["target_hosts"]) == (3, 2)


# ---------------------------------------------------------------------------
# the events: schema round-trip, Prometheus series

def test_emit_decision_and_applied_roundtrip_the_ledger_schema(tmp_path):
    led = Ledger(str(tmp_path / "fleet.jsonl"))
    pol = AutoscalePolicy.from_doc(_policy_doc())
    mon = CapacityMonitor(pol, hosts_live=1)
    mon.observe({"event": "request", "queue_wait_s": 0.5})
    dec = mon.evaluate(tick=7)
    emit_decision(led, dec)
    led.emit("applied", decision=dec["decision"], action="expand",
             processes=2, epoch=1, plan_hash="abc123def456", devices=4)
    led.close()
    recs = read_ledger(str(tmp_path / "fleet.jsonl"))
    assert [r["event"] for r in recs] == ["scale_decision", "applied"]
    sd = recs[0]
    for k in ("decision", "direction", "hosts_from", "target_hosts",
              "signal", "value", "threshold", "window_ticks", "bundle"):
        assert sd[k] == dec[k], k
    assert sd["tick"] == 7                       # the extra rides along
    assert recs[1]["plan_hash"] == "abc123def456"
    # the schema refuses an unattributed decision
    led2 = Ledger(str(tmp_path / "bad.jsonl"))
    with pytest.raises(ValueError, match="missing required"):
        led2.emit("scale_decision", direction="up")


def test_autoscale_metrics_series():
    reg = MetricsRegistry()
    sink = metrics_ledger_sink(reg)
    text = reg.render()
    # pre-registered: a steady fleet still scrapes explicit zeros
    assert 'tpu_dist_autoscale_decisions_total{direction="up"} 0' in text
    assert 'tpu_dist_autoscale_decisions_total{direction="down"} 0' in text
    assert "tpu_dist_autoscale_target_hosts 0" in text
    sink({"event": "scale_decision", "decision": "d0", "direction": "up",
          "hosts_from": 2, "target_hosts": 3, "signal": "queue_wait_ema_s",
          "value": 0.2, "threshold": 0.1, "window_ticks": 16,
          "bundle": None})
    sink({"event": "scale_decision", "decision": "d1", "direction": "down",
          "hosts_from": 3, "target_hosts": 2, "signal": CALM_SIGNAL,
          "value": 24.0, "threshold": 24.0, "window_ticks": 16,
          "bundle": None})
    text = reg.render()
    assert 'tpu_dist_autoscale_decisions_total{direction="up"} 1' in text
    assert 'tpu_dist_autoscale_decisions_total{direction="down"} 1' in text
    assert "tpu_dist_autoscale_target_hosts 2" in text


# ---------------------------------------------------------------------------
# the tailer: incremental, torn-line-safe

def test_ledger_tailer_holds_back_torn_lines(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tail = LedgerTailer()
    assert tail.poll([path]) == []               # missing file: no error
    with open(path, "w") as f:
        f.write(json.dumps({"event": "step", "step": 0}) + "\n")
        f.write("not json at all\n")
        f.write('{"event": "step", "st')         # torn mid-write
    recs = tail.poll([path])
    assert [r.get("step") for r in recs] == [0]  # corrupt skipped, torn held
    with open(path, "a") as f:
        f.write('ep": 1}\n')                     # the torn line completes
    assert [r.get("step") for r in tail.poll([path])] == [1]
    assert tail.poll([path]) == []               # nothing new


# ---------------------------------------------------------------------------
# trace_merge: decision + applied markers on the supervisor lane

def _emit_line(f, **rec):
    f.write(json.dumps(rec) + "\n")


def _attempt_ledger(path, t0):
    with open(path, "w") as f:
        _emit_line(f, event="run_start", ts=t0, pid=0, kind="fleet_sim",
                   config={}, mesh=None, devices=["cpu"], process_count=1,
                   attempt=0)
        _emit_line(f, event="step", ts=t0 + 1.0, pid=0, step=0, loss=None,
                   throughput=10.0, unit="tok/s", data_s=0.0,
                   dispatch_s=0.1, device_s=0.4, comm_s=None, mfu=None)
        _emit_line(f, event="run_end", ts=t0 + 2.0, pid=0, steps=1,
                   seconds=2.0, status="ok")


def test_trace_merge_renders_decision_markers(tmp_path):
    base = str(tmp_path / "run.jsonl")
    _attempt_ledger(base, 1000.0)
    with open(str(tmp_path / "run.sup.jsonl"), "w") as f:
        _emit_line(f, event="scale_decision", ts=1000.5, pid=0,
                   decision="d0", direction="up", hosts_from=2,
                   target_hosts=3, signal="queue_depth_ema", value=7.5,
                   threshold=6.0, window_ticks=16, bundle=None, tick=40)
        _emit_line(f, event="scale", ts=1001.0, pid=0, action="expand",
                   processes=3, epoch=1, world_from=2, decision="d0")
        _emit_line(f, event="applied", ts=1001.5, pid=0, decision="d0",
                   action="expand", processes=3, epoch=1,
                   plan_hash="abc123def456", devices=6)
    sys.path.insert(0, ROOT)
    from tools.trace_merge import main as tm_main

    out = str(tmp_path / "trace.json")
    assert tm_main([base, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    # the existing scale pin is untouched; decisions count separately
    assert trace["otherData"]["scale_events"] == 1
    assert trace["otherData"]["autoscale_events"] == 2
    marks = {e["name"]: e for e in trace["traceEvents"]
             if e.get("ph") == "i"}
    assert "scale:expand" in marks
    assert marks["scale:expand"]["args"]["decision"] == "d0"
    assert marks["decision:up"]["args"]["signal"] == "queue_depth_ema"
    assert marks["decision:up"]["args"]["target_hosts"] == 3
    assert marks["applied:expand"]["args"]["plan_hash"] == "abc123def456"
    # wall order on the one supervisor lane: decision -> scale -> applied
    order = sorted(("decision:up", "scale:expand", "applied:expand"),
                   key=lambda n: marks[n]["ts"])
    assert list(order) == ["decision:up", "scale:expand", "applied:expand"]


# ---------------------------------------------------------------------------
# ledger_report: the decision section

def test_ledger_report_decisions_section():
    sys.path.insert(0, ROOT)
    from tools.ledger_report import decisions_section

    assert decisions_section([{"event": "step", "ts": 1.0}],
                             out=lambda s: None) is None
    records = [
        {"event": "run_start", "ts": 100.0},
        {"event": "scale_decision", "ts": 101.0, "decision": "d0",
         "direction": "up", "hosts_from": 2, "target_hosts": 3,
         "signal": "queue_wait_ema_s", "value": 0.2, "threshold": 0.1,
         "window_ticks": 16, "bundle": "bundles/b1"},
        {"event": "applied", "ts": 102.0, "decision": "d0",
         "action": "expand", "processes": 3, "epoch": 1,
         "plan_hash": "abc123def456"},
    ]
    lines = []
    rows = decisions_section(records, out=lines.append)
    assert len(rows) == 2
    assert rows[0]["decision"] == "d0" and rows[1]["plan_hash"] == \
        "abc123def456"
    text = "\n".join(lines)
    assert "1 decision(s), 1 applied" in text
    assert "d0: up 2 -> 3 host(s)" in text
    assert "bundle bundles/b1" in text
    assert "expand -> 3 process(es) epoch 1" in text


# ---------------------------------------------------------------------------
# the fleet stitcher's decision<->scale<->applied join (hand-built)

def test_fleet_ledger_autoscale_join(tmp_path):
    t0 = 1000.0
    h0 = os.path.join(str(tmp_path), "host0")
    os.makedirs(h0)
    _attempt_ledger(os.path.join(h0, "run.jsonl"), t0)
    with open(os.path.join(h0, "run.sup.jsonl"), "w") as f:
        # d0 paired with its expand + applied (with a plan hash)
        _emit_line(f, event="scale", ts=t0 + 6.0, pid=0, action="expand",
                   processes=3, epoch=1, world_from=2, decision="d0")
        _emit_line(f, event="applied", ts=t0 + 6.5, pid=0, decision="d0",
                   action="expand", processes=3, epoch=1,
                   plan_hash="abc123def456")
        # d1 paired but its retune failed (plan_hash None)
        _emit_line(f, event="scale", ts=t0 + 12.0, pid=0, action="shrink",
                   processes=2, epoch=2, world_from=3, decision="d1")
        _emit_line(f, event="applied", ts=t0 + 12.5, pid=0, decision="d1",
                   action="shrink", processes=2, epoch=2, plan_hash=None)
        # a drain is per-host mechanics: decision-less is FINE
        _emit_line(f, event="scale", ts=t0 + 11.0, pid=0, action="drain",
                   processes=1, epoch=2)
        # an unattributed capacity change is the audit failure
        _emit_line(f, event="scale", ts=t0 + 15.0, pid=0, action="expand",
                   processes=3, epoch=3, world_from=2)
    with open(os.path.join(str(tmp_path), "fleet.jsonl"), "w") as f:
        _emit_line(f, event="scenario", ts=t0, pid=0, name="hand", seed=1,
                   hosts=3, ticks=10, tick_s=0.02)
        _emit_line(f, event="scale_decision", ts=t0 + 5.0, pid=0,
                   decision="d0", direction="up", hosts_from=2,
                   target_hosts=3, signal="queue_depth_ema", value=7.0,
                   threshold=6.0, window_ticks=16, bundle=None, tick=40)
        _emit_line(f, event="scale_decision", ts=t0 + 11.5, pid=0,
                   decision="d1", direction="down", hosts_from=3,
                   target_hosts=2, signal=CALM_SIGNAL, value=24.0,
                   threshold=24.0, window_ticks=16, bundle=None, tick=170)
        _emit_line(f, event="fleet", ts=t0 + 1.0, pid=0, hosts_live=2,
                   goodput_ratio=None, slo_breaches=None, tick=0)
    fleet = FleetLedger.discover(str(tmp_path), warn=lambda m: None)
    auto = fleet.autoscale()
    assert auto is not None
    assert [r["decision"] for r in auto["decisions"]] == ["d0", "d1"]
    d0, d1 = auto["decisions"]
    assert d0["scale_events"] == 1 and d0["lag_s"] == pytest.approx(1.0)
    assert d0["applied"]["plan_hash"] == "abc123def456"
    assert d0["tick"] == 40 and d0["direction"] == "up"
    assert d1["applied"]["plan_hash"] is None
    assert auto["paired"] == 2
    assert auto["applied_with_plan_hash"] == 1
    # only the decision-less EXPAND counts — the drain never needs one
    assert auto["unattributed_scales"] == 1
    assert auto["shed_lost"] == 0
    report = fleet.report()
    assert report["autoscale"]["paired"] == 2
    # the hosts-live timeline carries the fleet tick for lag math
    assert report["hosts_live"][0]["tick"] == 0
    json.dumps(report)      # --json contract: serializable as-is
    # a decision-free fleet reports no autoscale section at all
    assert FleetLedger({0: []}, []).autoscale() is None


# ---------------------------------------------------------------------------
# the supervisor's applied follow-up: retune at the new world size

def test_supervisor_retune_stamps_applied_with_reproducible_hash(tmp_path):
    from tpu_dist.parallel.consensus import MeshView
    from tpu_dist.parallel.supervisor import Supervisor
    from tpu_dist.plan.tune import tune

    plan_dir = str(tmp_path / "plans")
    sup = Supervisor([sys.executable, "-c", "pass"],
                     ledger=str(tmp_path / "run.jsonl"),
                     retune={"device_kind": "TPU v5 lite",
                             "devices_per_host": 2, "plan_dir": plan_dir})
    view = MeshView(epoch=1, hosts=(0, 1, 2), planned=3)
    sup._maybe_retune(view, "expand", "d0")
    recs = read_ledger(str(tmp_path / "run.sup.jsonl"))
    assert [r["event"] for r in recs] == ["applied"]
    app = recs[0]
    assert app["decision"] == "d0" and app["action"] == "expand"
    assert app["processes"] == 3 and app["epoch"] == 1
    assert app["devices"] == 6
    assert app["plan_hash"]
    # the audit contract: a fresh tune at the same world size reproduces
    # the stamped hash byte-for-byte
    _, results = tune(device_kinds=["TPU v5 lite"],
                      workload={"devices": 6})
    assert results["TPU v5 lite"]["best"]["hash"] == app["plan_hash"]
    # and the plan file landed beside the run, named by epoch
    with open(os.path.join(plan_dir, "plan_epoch1.json")) as f:
        assert app["plan_hash"] in f.read()


# ---------------------------------------------------------------------------
# headline.json: the reaction lag (burst onset to the first up decision)

def _burst(tick):
    return {"type": "burst", "tick": tick, "ticks": 4, "rate": 2.0}


def _decided(tick, direction):
    return {"tick": tick, "direction": direction}


@pytest.mark.parametrize("events, decisions, lag", [
    # a run without a burst has nothing to react to
    ([{"type": "kill", "tick": 5}], [_decided(9, "up")], None),
    # a burst the policy never answered
    ([_burst(10)], [_decided(30, "down")], None),
    ([_burst(10)], [_decided(17, "up")], 7),
    # the FIRST burst's onset and the FIRST up decision, whatever the
    # order the scenario lists them in and whatever came between
    ([_burst(40), {"type": "kill", "tick": 2}, _burst(12)],
     [_decided(8, "down"), _decided(15, "up"), _decided(44, "up")], 3),
    ([_burst(10)], [_decided(10, "up")], 0),
], ids=["no_burst", "no_up", "up_after_burst", "first_of_several",
        "same_tick"])
def test_autoscale_lag_ticks(events, decisions, lag):
    from tpu_dist.sim.runner import autoscale_lag_ticks

    assert autoscale_lag_ticks(events, decisions) == lag


# ---------------------------------------------------------------------------
# scenario grammar: the autoscale block

def test_scenario_autoscale_block_validation():
    def _doc(**auto):
        return {"name": "t", "seed": 3, "hosts": 3, "ticks": 40,
                "traffic": {"base_rate": 0.2}, "autoscale": auto}

    with pytest.raises(ValueError, match="needs a 'policy'"):
        parse_scenario(_doc(policy=""))
    with pytest.raises(ValueError, match="out of range"):
        parse_scenario(_doc(policy="p.json", standby_hosts=[7]))
    with pytest.raises(ValueError, match="cannot be standby"):
        parse_scenario(_doc(policy="p.json", standby_hosts=[0]))
    with pytest.raises(ValueError, match="duplicate"):
        parse_scenario(_doc(policy="p.json", standby_hosts=[2, 2]))
    sc = load_scenario(SCENARIO)
    assert sc.standby_hosts() == [2]
    assert sc.autoscale["policy"] == "scripts/autoscale_policy.json"
    # the burst that drives the acceptance scale-up is on the schedule
    assert any(ev["type"] == "burst" for ev in sc.events)


# ---------------------------------------------------------------------------
# ACCEPTANCE: the checked-in autoscale scenario end to end (CPU workers)

def test_fleet_autoscale_scenario_acceptance(tmp_path):
    """ISSUE 20 acceptance: 3 virtual hosts under
    ``scripts/fleet_autoscale.json`` — host 2 parked standby, a diurnal
    sinusoid with an overload burst at tick 40 — and every assertion read
    from ``tools/fleet_report.py --json``:

    * hosts-live FOLLOWS traffic: the first decision is a scale-up no
      later than the pinned lag after the burst (capacity peaks at 3),
      then a scale-down after sustained calm (back to 2);
    * the audit pairing: every capacity change carries a decision id
      (``unattributed_scales == 0``) and every decision produced exactly
      one scale event (``paired == decisions``);
    * zero shed requests lost: drained hosts hand their queue to a
      survivor, which re-admits under ``readmit`` spans in the SAME
      trace;
    * the applied follow-up's plan hash equals a byte-deterministic
      fresh run of the tuner at the new world size;
    * goodput holds above the pinned floor despite two rescales.

    Decision TICKS are wall-timing dependent: loaded workers run behind
    the schedule under compile pressure, and their queue wait can trip
    the up decision BEFORE the burst's own tick. So the test pins what
    does not depend on wall time — the order and shape of the decisions,
    the signals, an UPPER bound on the lag, the pairing and the plan
    hashes — and no lower bound; the exact-replay pins live in the lint
    gate's fixture, not here.
    """
    from tpu_dist.plan.tune import tune
    from tpu_dist.sim.runner import FleetSim

    out_dir = str(tmp_path / "fleet")
    sc = load_scenario(SCENARIO)
    burst0 = min(ev["tick"] for ev in sc.events if ev["type"] == "burst")
    report_inline = FleetSim(SCENARIO, out_dir).run()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_report.py"),
         out_dir, "--json"], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)

    # -- the decision ledger: up under the burst, down after calm -------
    auto = report["autoscale"]
    assert auto is not None
    rows = auto["decisions"]
    ups = [r for r in rows if r["direction"] == "up"]
    downs = [r for r in rows if r["direction"] == "down"]
    assert ups and downs, rows
    assert rows[0]["direction"] == "up"
    # reaction lag: the first up decision lands no later than the pinned
    # window after burst onset (the burst lasts 24 ticks; 64 bounds
    # compile skew)
    assert ups[0]["tick"] <= burst0 + 64, ups[0]
    assert (ups[0]["hosts_from"], ups[0]["target_hosts"]) == (2, 3)
    assert ups[0]["signal"] in SIGNAL_NAMES
    assert downs[0]["signal"] == CALM_SIGNAL
    assert (downs[0]["hosts_from"], downs[0]["target_hosts"]) == (3, 2)
    assert downs[0]["tick"] > ups[0]["tick"]

    # -- the audit pairing: no capacity change without a decision -------
    assert auto["paired"] == len(rows)
    assert auto["unattributed_scales"] == 0
    assert auto["applied_with_plan_hash"] == len(rows)
    for r in rows:
        assert r["scale_events"] == 1, r
        assert r["lag_s"] is not None and r["lag_s"] >= 0
        assert r["applied"]["decision"] == r["decision"]

    # -- the elasticity story mirrors the decisions, stamped -----------
    membership = [e for e in report["elasticity"]
                  if e["action"] in ("shrink", "expand")]
    assert [e["action"] for e in membership] == ["expand", "shrink"]
    assert membership[0]["decision"] == ups[0]["decision"]
    assert membership[0]["processes"] == 3
    assert membership[1]["decision"] == downs[0]["decision"]
    assert membership[1]["processes"] == 2
    # hosts-live follows: starts at 2 (standby parked), peaks at 3
    live = [s["hosts_live"] for s in report["hosts_live"]
            if s["hosts_live"] is not None]
    assert live[0] == 2 and max(live) == 3

    # -- zero shed requests lost: handoff + readmit close every trace --
    assert auto["shed_lost"] == 0
    traces = report["traces"]
    readmitted = [t for t in traces.values() if t["readmits"]]
    assert readmitted, "rescales never exercised the readmit path"
    for t in readmitted:
        assert t["completed"], t
    # queued-then-shed requests re-admit under the SAME trace: the shed
    # and readmit spans bind two attempts into one story
    shed_traces = [t for t in traces.values() if t["sheds"]]
    assert shed_traces, "the rescale drains left no queued work"
    for t in shed_traces:
        assert t["readmits"] > 0 and t["completed"], t
    # and the drained standby's undone arrivals really crossed hosts:
    # requests PLANNED for its rid band completed on a survivor
    drained = sc.standby_hosts()[0]
    crossed = [t for t in readmitted
               if t["rid"] // RID_STRIDE == drained]
    assert crossed, readmitted
    for t in crossed:
        assert drained not in t["hosts"], t

    # -- the applied plan hash reproduces under a fresh tune -----------
    worker_devices = sc.worker_devices
    for r in rows:
        app = r["applied"]
        _, results = tune(device_kinds=["TPU v5 lite"],
                          workload={"devices":
                                    app["processes"] * worker_devices})
        assert results["TPU v5 lite"]["best"]["hash"] == \
            app["plan_hash"], r
        plan_path = os.path.join(out_dir, "plans",
                                 f"plan_epoch{app['epoch']}.json")
        assert os.path.exists(plan_path), plan_path

    # -- goodput holds above the floor; the headline carries the loop --
    assert report["fleet"]["goodput_ratio"] >= 0.05
    assert report["slo_breaches"] <= 12
    with open(os.path.join(out_dir, "headline.json")) as f:
        headline = json.load(f)
    assert headline["fleet"]["autoscale_decisions"] == len(rows)
    lag = headline["fleet"]["autoscale_lag_ticks"]
    assert lag is not None and lag <= 64
    assert report_inline["autoscale"]["paired"] == auto["paired"]
