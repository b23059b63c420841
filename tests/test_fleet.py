"""Fleet observatory (round 14): scenario schedules, the FleetLedger
stitcher, fleet metrics/trace/report plumbing, and the CI acceptance
scenario.

The pins that matter:

* the scenario compile is DETERMINISTIC: same schedule + seed -> byte-
  identical admitted-request and injected-fault sequences, with exact
  event counts for the checked-in ``scripts/fleet_ci.json`` (no jax);
* the fleet stitcher tolerates a torn/partial per-host ledger and its
  goodput categories + goodput account for ~100% of aggregate wall;
* the ACCEPTANCE scenario (3 virtual hosts, one preemption wave with a
  host return through the real consensus path, diurnal Poisson serve
  traffic, a slow host, an overload burst) runs on CPU and — read
  entirely from ``tools/fleet_report.py --json`` — shows restart classes
  matching the schedule EXACTLY, the goodput sum-check at ~100%, and an
  SLO-breach count inside the pinned bounded range.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tpu_dist.obs import faults
from tpu_dist.obs.goodput import fleet_accounting, load_job_records
from tpu_dist.obs.ledger import Ledger, read_ledger
from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink
from tpu_dist.sim.fleet import FleetLedger
from tpu_dist.sim.scenario import (RID_STRIDE, Scenario,
                                   compile_host_plans,
                                   expected_restart_classes, load_scenario,
                                   parse_scenario)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CI_SCENARIO = os.path.join(ROOT, "scripts", "fleet_ci.json")


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults._reset_for_tests()
    yield
    faults._reset_for_tests()


# ---------------------------------------------------------------------------
# scenario grammar + deterministic compile (no jax)

def _doc(**over):
    doc = {"name": "t", "seed": 3, "hosts": 2, "ticks": 40,
           "traffic": {"base_rate": 0.2}}
    doc.update(over)
    return doc


def test_scenario_validation_refuses_garbage():
    with pytest.raises(ValueError, match="missing required key"):
        parse_scenario({"name": "x"})
    with pytest.raises(ValueError, match="unknown event type"):
        parse_scenario(_doc(events=[{"type": "meteor", "tick": 1}]))
    with pytest.raises(ValueError, match="hosts list"):
        parse_scenario(_doc(events=[{"type": "crash", "tick": 1,
                                     "hosts": [9]}]))
    with pytest.raises(ValueError, match="consensus host"):
        parse_scenario(_doc(events=[{"type": "preempt", "tick": 1,
                                     "hosts": [0]}]))
    with pytest.raises(ValueError, match="return_tick"):
        parse_scenario(_doc(events=[{"type": "preempt", "tick": 30,
                                     "hosts": [1], "return_tick": 10}]))
    with pytest.raises(ValueError, match="prompt range"):
        parse_scenario(_doc(traffic={"tenants": [
            {"name": "bad", "prompt": [9, 2]}]}))
    with pytest.raises(ValueError, match="exceeds"):
        parse_scenario(_doc(model={"max_len": 8},
                            traffic={"tenants": [
                                {"name": "big", "prompt": [6, 8],
                                 "out": [4, 6]}]}))


def test_scenario_roundtrips_through_doc_form():
    sc = load_scenario(CI_SCENARIO)
    sc2 = parse_scenario(sc.to_doc())
    assert sc2 == sc


def test_diurnal_rate_peaks_bursts_and_clamps():
    sc = parse_scenario(_doc(
        traffic={"base_rate": 0.2, "amplitude": 1.5, "period": 40},
        events=[{"type": "burst", "tick": 5, "ticks": 3, "rate": 2.0}]))
    assert sc.rate(10, 0) > 0.2              # sin peak at period/4
    assert sc.rate(30, 0) == 0.0             # deep trough clamps at zero
    assert sc.rate(5, 0) == pytest.approx(sc.rate(4, 0) + 2.0, abs=0.2)
    assert sc.rate(8, 0) < 2.0               # burst window closed


def test_compile_is_deterministic_with_exact_ci_counts():
    """THE determinism pin: the checked-in CI scenario compiles to the
    same arrivals/faults/actions every time, with exact counts."""
    sc = load_scenario(CI_SCENARIO)
    p1, a1 = compile_host_plans(sc)
    p2, a2 = compile_host_plans(sc)
    key = lambda plans: [(x.tick, x.rid, x.tenant, x.prompt_len, x.out_len)
                         for h in sorted(plans) for x in plans[h].arrivals]
    assert key(p1) == key(p2)
    assert a1 == a2
    # exact per-host admitted-request counts for seed 7 (any change to
    # the schedule, the sampler, or the seed shows up HERE, not in a
    # flaky acceptance run)
    assert [len(p1[h].arrivals) for h in range(3)] == [65, 56, 49]
    assert p1[1].faults == "preempt_sigterm@step=56,attempt=0"
    assert p1[0].faults == "" and p1[2].faults == ""
    assert p1[2].skew == 1.5
    assert [(a.tick, a.action, a.host) for a in a1] == \
        [(56, "leave", 1), (120, "register", 1)]
    # rids are fleet-unique by namespace
    rids = [x.rid for h in p1 for x in p1[h].arrivals]
    assert len(set(rids)) == len(rids)
    assert all(x.rid // RID_STRIDE == h for h in p1
               for x in p1[h].arrivals)


def test_compile_seed_changes_the_schedule():
    sc = load_scenario(CI_SCENARIO)
    other = parse_scenario({**sc.to_doc(), "seed": sc.seed + 1})
    p1, _ = compile_host_plans(sc)
    p2, _ = compile_host_plans(other)
    assert [(x.tick, x.prompt_len) for x in p1[0].arrivals] != \
        [(x.tick, x.prompt_len) for x in p2[0].arrivals]


def test_expected_restart_classes_follow_the_schedule():
    sc = load_scenario(CI_SCENARIO)
    assert expected_restart_classes(sc) == {
        # consensus host: one rescale per membership change (leave+return)
        0: ["preemption_snapshotted", "preemption_snapshotted", "clean"],
        1: ["preemption_snapshotted", "clean"],   # the wave target
        2: ["clean"]}                             # the slow host
    # a hang predicts "crash" in record mode (no watchdog in the serve
    # worker: the SIGKILLed attempt leaves neither run_end nor stall)
    crashy = parse_scenario(_doc(events=[
        {"type": "crash", "tick": 5, "hosts": [1]},
        {"type": "hang", "tick": 20, "hosts": [1]}]))
    assert expected_restart_classes(crashy)[1] == \
        ["crash", "crash", "clean"]


def test_fault_specs_use_the_standard_grammar():
    sc = parse_scenario(_doc(events=[
        {"type": "crash", "tick": 7, "hosts": [1]},
        {"type": "hang", "tick": 9, "hosts": [1], "secs": 5}]))
    plans, _ = compile_host_plans(sc)
    plan = faults.FaultPlan.parse(plans[1].faults)  # must parse cleanly
    assert plan.sites() == {"hard_exit", "hang"}
    # the k-th disruption is gated on attempt k: the restarted worker
    # (attempt 1) must still be able to fire the second scheduled fault
    assert plans[1].faults == \
        "hard_exit@step=7,attempt=0;hang@step=9,attempt=1,secs=5"


# ---------------------------------------------------------------------------
# the fleet stitcher over hand-built ledgers (no jax)

def _emit_line(f, **rec):
    f.write(json.dumps(rec) + "\n")


def _host_ledger(path, t0, *, attempt=0, steps=2, status="ok",
                 tenant="chat", slo=0, scale=None, torn=False):
    """One attempt ledger: run_start -> compile -> step(s) -> serving
    events -> run_end, with optional slo/scale events and a torn tail."""
    with open(path, "w") as f:
        _emit_line(f, event="run_start", ts=t0, pid=0, kind="fleet_sim",
                   config={}, mesh=None, devices=["cpu"], process_count=1,
                   attempt=attempt)
        _emit_line(f, event="compile", ts=t0 + 1.0, pid=0, program="serve")
        for i in range(steps):
            _emit_line(f, event="step", ts=t0 + 1.5 + i, pid=0, step=i,
                       loss=None, throughput=10.0, unit="tok/s",
                       data_s=0.0, dispatch_s=0.1, device_s=0.4,
                       comm_s=None, mfu=None)
        _emit_line(f, event="request", ts=t0 + 1.6, pid=0, rid=1, tokens=4,
                   queue_wait_s=0.05, admit_ts=0.0, first_token_ts=0.1,
                   finish_ts=0.4, tenant=tenant, ttft_s=0.1)
        for i in range(slo):
            _emit_line(f, event="slo", ts=t0 + 2.0 + i, pid=0, step=i,
                       kind="queue_wait", value=0.9, floor=0.5)
        if scale:
            _emit_line(f, event="scale", ts=t0 + 2.5, pid=0, **scale)
        if torn:
            f.write('{"event": "step", "ts": ')   # the killed writer
        else:
            _emit_line(f, event="run_end", ts=t0 + 1.5 + steps, pid=0,
                       steps=steps, seconds=1.5 + steps, status=status)


def _build_fleet_dir(root):
    t0 = 1000.0
    h0 = os.path.join(root, "host0")
    h1 = os.path.join(root, "host1")
    os.makedirs(h0)
    os.makedirs(h1)
    # host 0: preempted attempt 0 + clean attempt 1 + a sup sibling
    _host_ledger(os.path.join(h0, "run.jsonl"), t0, status="preempted",
                 tenant="chat", slo=1)
    _host_ledger(os.path.join(h0, "run.a1.jsonl"), t0 + 10.0, attempt=1,
                 tenant="chat")
    with open(os.path.join(h0, "run.sup.jsonl"), "w") as f:
        _emit_line(f, event="scale", ts=t0 + 6.0, pid=0, action="shrink",
                   processes=1, epoch=1, world_from=2)
        _emit_line(f, event="scale", ts=t0 + 9.0, pid=0, action="expand",
                   processes=2, epoch=2, world_from=1)
    # host 1: one attempt whose writer died mid-line (torn tail, no
    # run_end) — the stitcher must tolerate AND classify it
    _host_ledger(os.path.join(h1, "run.jsonl"), t0 + 0.5, tenant="batch",
                 torn=True)
    with open(os.path.join(root, "fleet.jsonl"), "w") as f:
        _emit_line(f, event="scenario", ts=t0, pid=0, name="hand", seed=1,
                   hosts=2, ticks=10, tick_s=0.02)
        _emit_line(f, event="fleet", ts=t0 + 1.0, pid=0, hosts_live=2,
                   goodput_ratio=None, slo_breaches=None)
        _emit_line(f, event="fleet", ts=t0 + 20.0, pid=0, hosts_live=0,
                   goodput_ratio=0.4, slo_breaches=1, final=True)
    return root


def test_fleet_stitcher_tolerates_torn_ledger_and_sums_to_wall(tmp_path):
    fleet = FleetLedger.discover(_build_fleet_dir(str(tmp_path)),
                                 warn=lambda m: None)
    assert sorted(fleet.hosts) == [0, 1]
    # host 1's torn trailing line was dropped, the good records kept
    assert any(r["event"] == "request" for r in fleet.hosts[1])
    report = fleet.report()
    acct = report["fleet"]
    assert acct["hosts"] == 2
    # THE invariant: goodput + categories account for the aggregate wall
    explained = acct["goodput_s"] + sum(acct["categories"].values())
    assert explained == pytest.approx(acct["aggregate_wall_s"], rel=1e-6)
    assert acct["sum_check"] == pytest.approx(1.0, abs=1e-6)
    # host 0's two attempts stitched with their restart gap
    assert acct["per_host"][0]["attempts"] == 2
    assert acct["categories"]["restart_gap"] > 0
    assert report["restart_classes"] == {
        "0": ["preemption_snapshotted", "clean"], "1": ["crash"]}
    assert report["restart_histogram"] == {
        "preemption_snapshotted": 1, "clean": 1, "crash": 1}
    assert report["slo_breaches"] == 1
    # elasticity: the sup sibling's scale events, host-stamped, in order
    assert [(e["host"], e["action"]) for e in report["elasticity"]] == \
        [(0, "shrink"), (0, "expand")]
    assert report["elasticity"][0]["t_rel"] == pytest.approx(6.0)
    # per-tenant percentiles from the request events
    assert set(report["per_tenant"]) == {"chat", "batch"}
    assert report["per_tenant"]["chat"]["requests"] == 2
    assert report["per_tenant"]["chat"]["queue_wait_s"]["p50"] == \
        pytest.approx(0.05)
    assert report["scenario"]["name"] == "hand"
    assert [s["hosts_live"] for s in report["hosts_live"]] == [2, 0]
    json.dumps(report)  # the --json contract: serializable as-is


def test_fleet_report_cli_renders_and_jsons(tmp_path):
    root = _build_fleet_dir(str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_report.py"),
         root, "--json"], capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["restart_histogram"]["crash"] == 1
    human = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_report.py"),
         root], capture_output=True, text=True, cwd=ROOT)
    assert "fleet goodput ratio" in human.stdout
    assert "restarts: histogram" in human.stdout
    assert "per-tenant serving" in human.stdout


def test_load_job_records_appends_sup_sibling(tmp_path):
    base = str(tmp_path / "run.jsonl")
    _host_ledger(base, 1000.0)
    with open(str(tmp_path / "run.sup.jsonl"), "w") as f:
        _emit_line(f, event="scale", ts=990.0, pid=0, action="shrink",
                   processes=1, epoch=1)
    records = load_job_records(base)
    # appended AFTER the attempt stream despite the earlier ts: a scale
    # event must never split a pseudo-attempt into the goodput math
    assert records[-1]["event"] == "scale"
    assert [r["event"] for r in records[:2]] == ["run_start", "compile"]
    assert load_job_records(base, discover=False)[-1]["event"] == "run_end"


def test_fleet_accounting_aggregates_and_abstains():
    assert fleet_accounting({}) is None
    j = {"wall_s": 10.0, "goodput_s": 4.0, "ratio": 0.4,
         "categories": {"startup": 2.0, "idle": 4.0}, "overrun_s": 0.0,
         "opt_steps": 7, "attempts": [{}]}
    agg = fleet_accounting({0: j, 1: j})
    assert agg["aggregate_wall_s"] == 20.0
    assert agg["goodput_ratio"] == pytest.approx(0.4)
    assert agg["sum_check"] == pytest.approx(1.0)
    assert agg["opt_steps"] == 14


# ---------------------------------------------------------------------------
# fleet Prometheus series (obs.metrics) — no jax

def test_fleet_metrics_series_and_breach_delta():
    reg = MetricsRegistry()
    sink = metrics_ledger_sink(reg)
    text = reg.render()
    for name in ("tpu_dist_fleet_goodput_ratio",
                 "tpu_dist_fleet_hosts_live",
                 "tpu_dist_fleet_slo_breaches_total"):
        assert f"{name} 0" in text    # pre-registered at zero
    sink({"event": "fleet", "hosts_live": 3, "goodput_ratio": None,
          "slo_breaches": 4})
    sink({"event": "fleet", "hosts_live": 0, "goodput_ratio": 0.31,
          "slo_breaches": 6})
    text = reg.render()
    assert "tpu_dist_fleet_hosts_live 0" in text
    assert "tpu_dist_fleet_goodput_ratio 0.31" in text
    # the counter moved by the DELTAS of the cumulative event values
    assert "tpu_dist_fleet_slo_breaches_total 6" in text
    sink({"event": "fleet", "hosts_live": 0, "goodput_ratio": 0.31,
          "slo_breaches": 6})   # repeat: no double count
    assert "tpu_dist_fleet_slo_breaches_total 6" in reg.render()


# ---------------------------------------------------------------------------
# trace_merge: the supervisor scale-event marker lane — no jax

def test_trace_merge_renders_sup_scale_lane(tmp_path):
    base = str(tmp_path / "run.jsonl")
    _host_ledger(base, 1000.0)
    with open(str(tmp_path / "run.sup.jsonl"), "w") as f:
        _emit_line(f, event="scale", ts=1002.0, pid=0, action="shrink",
                   processes=2, epoch=1, world_from=3)
        _emit_line(f, event="scale", ts=1004.0, pid=0, action="expand",
                   processes=3, epoch=2, world_from=2)
    sys.path.insert(0, ROOT)
    from tools.trace_merge import main as tm_main

    out = str(tmp_path / "trace.json")
    assert tm_main([base, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    assert trace["otherData"]["scale_events"] == 2
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "supervisor" in lanes
    marks = [e for e in trace["traceEvents"]
             if e.get("name", "").startswith("scale:")]
    assert [m["name"] for m in marks] == ["scale:shrink", "scale:expand"]
    assert marks[0]["ts"] == pytest.approx(2.0 * 1e6)  # job clock (µs)
    assert marks[0]["args"]["world_from"] == 3


# ---------------------------------------------------------------------------
# supervisor scenario hooks (jax-free fake child)

_SLEEPY_CHILD = r"""
import json, sys, time
path = sys.argv[sys.argv.index("--ledger-path") + 1]
with open(path, "a") as f:
    f.write(json.dumps({"event": "run_start", "ts": time.time(),
                        "kind": "fake", "config": {}, "mesh": None,
                        "devices": [], "process_count": 1}) + "\n")
time.sleep(60)
"""


def test_supervisor_request_stop_tears_down_and_reports_stopped(tmp_path):
    from tpu_dist.parallel.supervisor import RestartPolicy, Supervisor

    script = tmp_path / "child.py"
    script.write_text(_SLEEPY_CHILD)
    seen = []
    sup = Supervisor(
        [sys.executable, str(script)], ledger=str(tmp_path / "run.jsonl"),
        policy=RestartPolicy(max_restarts=3, backoff_base_s=0.01,
                             stall_timeout_s=60.0,
                             preempt_deadline_s=2.0),
        poll_s=0.05, on_attempt=seen.append)
    threading.Timer(1.0, sup.request_stop).start()
    t0 = time.monotonic()
    res = sup.run()
    assert time.monotonic() - t0 < 30.0
    assert res.status == "stopped" and not res.ok
    assert len(res.attempts) == 1
    # the on_attempt hook observed the classified attempt
    assert [a.attempt for a in seen] == [0]
    assert seen[0].failure_class == res.attempts[0].failure_class


def test_fleet_hold_gates_hosts_on_scheduled_membership(tmp_path):
    """The fleet clock gates both ways: ``hold.tick`` sits one publish
    window past the next scheduled action, stays there until the
    consensus supervisor has RESOLVED the change (hosts pace by the wall
    clock, the control plane gets the CPU that is left: without the hold
    a starved box lets the consensus host run its trace out before the
    rescale reaches it), and goes when nothing is scheduled. A capacity
    decision's change holds nobody."""
    from types import SimpleNamespace

    from tpu_dist.sim.runner import FleetSim
    from tpu_dist.sim.worker import WINDOW_TICKS, _read_hold

    sim = FleetSim(CI_SCENARIO, str(tmp_path))
    hold = os.path.join(str(tmp_path), "hold.tick")
    peers = {h: SimpleNamespace(register=lambda: None, leave=lambda: None)
             for h in range(3)}
    sim._members = {0, 1, 2}
    csup = SimpleNamespace(mesh_view=SimpleNamespace(hosts=(0, 1, 2)))
    sim._sups[sim.sc.consensus_host] = csup
    pending = list(sim.actions)
    assert [(a.tick, a.action) for a in pending] == \
        [(56, "leave"), (120, "register")]

    sim._publish_hold(pending, True)
    assert _read_hold(hold) == 56 + WINDOW_TICKS
    # the leave fires; the supervisor has not polled the new view yet
    act = pending.pop(0)
    sim._set_member(peers, act.host, False, act.tick)
    sim._publish_hold(pending, True)
    assert _read_hold(hold) == 56 + WINDOW_TICKS
    csup.mesh_view = SimpleNamespace(hosts=(0, 2))      # resolved
    sim._publish_hold(pending, True)
    assert _read_hold(hold) == 120 + WINDOW_TICKS
    act = pending.pop(0)
    sim._set_member(peers, act.host, True, act.tick)
    sim._publish_hold(pending, True)
    assert _read_hold(hold) == 120 + WINDOW_TICKS       # unresolved
    # a consensus host that is gone can resolve nothing: never wait on it
    sim._publish_hold(pending, False)
    assert _read_hold(hold) is None and not os.path.exists(hold)
    # an autoscale decision's membership change is no scheduled action
    sim._set_member(peers, 2, False)
    sim._publish_hold(pending, True)
    assert _read_hold(hold) is None


# ---------------------------------------------------------------------------
# ACCEPTANCE: the checked-in CI scenario end to end (CPU, real workers)

def test_fleet_ci_scenario_acceptance(tmp_path):
    """ISSUE 14 acceptance: 3 virtual hosts under scripts/fleet_ci.json —
    diurnal Poisson serve traffic, one preemption wave on host 1 with a
    host return through the real consensus path (shrink -> expand, rescale
    relaunches), a 1.5x slow host, an overload burst — and every assertion
    read from ``tools/fleet_report.py --json``:

    * stitched fleet goodput categories + goodput sum to ~100% of the
      aggregate wall;
    * per-host restart classes match the schedule's own prediction
      EXACTLY (consensus host: two rescale snapshots then clean; wave
      host: preemption_snapshotted then clean; slow host: clean);
    * the SLO-breach count lands in the pinned bounded range (the burst
      guarantees at least one; hysteresis re-arms bound the tail).
    """
    from tpu_dist.sim.runner import FleetSim

    out_dir = str(tmp_path / "fleet")
    sc = load_scenario(CI_SCENARIO)
    report_inline = FleetSim(CI_SCENARIO, out_dir).run()
    # the CI contract reads the report tool's --json, not runner internals
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_report.py"),
         out_dir, "--json"], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)

    # -- goodput sums to aggregate wall ---------------------------------
    acct = report["fleet"]
    assert acct["hosts"] == 3
    assert acct["sum_check"] == pytest.approx(1.0, abs=0.02)
    explained = acct["goodput_s"] + sum(acct["categories"].values())
    assert explained == pytest.approx(acct["aggregate_wall_s"], rel=0.02)
    assert acct["goodput_s"] > 0 and acct["goodput_ratio"] > 0
    # the wave host restarted: its crash->restart gap is on the books
    assert acct["categories"]["restart_gap"] > 0

    # -- restart classes match the schedule EXACTLY ---------------------
    want = {str(h): cls
            for h, cls in expected_restart_classes(sc).items()}
    assert report["restart_classes"] == want
    assert report["restart_histogram"] == {
        "preemption_snapshotted": 3, "clean": 3}

    # -- SLO breaches in the pinned bounded range -----------------------
    assert 1 <= report["slo_breaches"] <= 12

    # -- the elasticity story: shrink at the wave, expand at the return -
    consensus_scales = [e for e in report["elasticity"]
                        if e["host"] == 0 and e["action"] in
                        ("shrink", "expand")]
    assert [e["action"] for e in consensus_scales] == ["shrink", "expand"]
    assert consensus_scales[0]["processes"] == 2
    assert consensus_scales[1]["processes"] == 3
    # every preempted/rescaled worker drained gracefully
    assert any(e["action"] == "drain" and e["host"] == 1
               for e in report["elasticity"])

    # -- serving evidence: both tenants served, on every surviving host -
    assert set(report["per_tenant"]) == {"chat", "batch"}
    for t in report["per_tenant"].values():
        assert t["requests"] > 0
        assert t["queue_wait_s"]["p50"] is not None
    assert report["serving"]["completed"] > 0

    # -- request observatory: attribution sums, breaches have evidence --
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "request_report.py"),
         out_dir, "--json"], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    req = json.loads(proc.stdout)
    assert req["completed_requests"] > 0
    ta = req["tail_attribution"]
    # the per-request sum-check: queue + prefill + decode + residue is an
    # identity against measured latency, and residue stays inside the
    # rounding tolerance for EVERY completed request
    assert ta["sum_check"]["ok"], ta["sum_check"]
    for row in req["per_request"]:
        assert row["latency_s"] == pytest.approx(
            row["queue_s"] + row["prefill_s"] + row["decode_s"]
            + row["residue_s"], abs=1e-6)
    # every slo breach resolves to >= 1 concrete exemplar trace — a
    # breach that points at nothing is a report bug, not a gap
    assert len(req["slo_exemplars"]) == report["slo_breaches"]
    for breach in req["slo_exemplars"]:
        assert len(breach["exemplars"]) >= 1, breach
    # the fleet report stitched the same traces the request report read
    assert len(report["traces"]) == req["traces"] > 0

    # -- the runner's own artifacts -------------------------------------
    assert report_inline["restart_classes"] == report["restart_classes"]
    assert report_inline["supervisors"]["0"]["status"] == "clean"
    with open(os.path.join(out_dir, "headline.json")) as f:
        headline = json.load(f)
    assert headline["fleet"]["goodput_ratio"] == acct["goodput_ratio"]
    # the fleet ledger's final rollup matches (and fed the fleet gauges)
    fleet_events = [r for r in read_ledger(
        os.path.join(out_dir, "fleet.jsonl"), strict=False)
        if r["event"] == "fleet" and r.get("final")]
    assert fleet_events[-1]["goodput_ratio"] == acct["goodput_ratio"]
    assert fleet_events[-1]["slo_breaches"] == report["slo_breaches"]
