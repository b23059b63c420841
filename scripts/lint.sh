#!/bin/bash
# Static-analysis gate: distlint over the FULL acceptance surface —
# tpu_dist, tools (the linter lints itself), tests, scripts.
# Stdlib-only (no jax, no devices), so this runs anywhere — pre-commit,
# CI, a laptop. The run also writes distlint.sarif (SARIF 2.1.0) as a CI
# code-scanning artifact. Exit code gates on ERROR-tier findings only:
# warn-tier rules (DL102/DL103) report without failing the build, and
# suppressions require written reasons by design (--debt below keeps the
# inventory honest).
set -euo pipefail
cd "$(dirname "$0")/.."

# One run does all three jobs: the error-tier gate, the SARIF artifact,
# and the advisory suppression-debt inventory (--with-debt reuses the
# same lint result — no second full sweep of the call graph).
python -m tools.distlint --sarif-out distlint.sarif --with-debt "$@"

# Supervisor-policy gate (round 10) + consensus-policy gate (round 13),
# jax-free BY CONSTRUCTION: the elastic supervisor AND its cross-host
# consensus must keep working on a bare login/CI host (no jax installed),
# so this pass hard-blocks jax imports and runs the restart classification,
# backoff math, degraded-shrink, fault-spec grammar, dense renumbering and
# shrink->re-expand membership cycle as units. A stray `import jax`
# creeping into parallel.supervisor / parallel.consensus / obs.faults /
# the lazy parallel __init__ fails HERE, before any pod notices.
python - <<'EOF'
import builtins, signal, tempfile

_real = builtins.__import__
def _guard(name, *a, **k):
    if name == "jax" or name.startswith("jax."):
        raise ImportError(f"supervisor policy gate: jax import blocked ({name})")
    return _real(name, *a, **k)
builtins.__import__ = _guard

from tpu_dist.obs.faults import FaultPlan
from tpu_dist.parallel.supervisor import (PREEMPT_SNAPSHOT_RC, RestartPolicy,
                                          classify_attempt, compute_backoff,
                                          degraded_env)
from tpu_dist.supervise import build_parser

pol = RestartPolicy(backoff_base_s=1.0, backoff_max_s=8.0)
assert [compute_backoff(n, pol) for n in (0, 1, 2, 3, 9)] == \
    [0.0, 1.0, 2.0, 4.0, 8.0]
# per-host jitter: deterministic, decorrelated, bounded
waits = [compute_backoff(3, pol, host_id=h) for h in range(4)]
assert len(set(waits)) == 4
assert all(4.0 <= w <= 4.0 * (1 + pol.backoff_jitter) for w in waits)
assert waits == [compute_backoff(3, pol, host_id=h) for h in range(4)]
end = {"event": "run_end", "status": "crashed",
       "error": "HealthError: val_loss spike"}
assert classify_attempt([end], 1) == "health_halt"
assert classify_attempt([], -signal.SIGTERM) == "preemption"
assert classify_attempt([], PREEMPT_SNAPSHOT_RC) == "preemption_snapshotted"
assert classify_attempt(
    [{"event": "run_end", "status": "preempted"}], None) == \
    "preemption_snapshotted"
assert classify_attempt([], 1, stderr_tail="rendezvous failed") == "rendezvous"
assert classify_attempt([{"event": "stall"}], -9, True) == "stall"
assert classify_attempt([], 13) == "crash"
env, n = degraded_env({"TPU_DIST_NUM_PROCESSES": "4"})
assert n == 3 and env["TPU_DIST_DEGRADED"] == "1"
plan = FaultPlan.parse("hard_exit@step=10,attempt=0;rendezvous_fail@times=2;"
                       "preempt_deadline@step=5;host_return@nth=2")
assert plan.sites() == {"hard_exit", "rendezvous_fail", "preempt_deadline",
                        "host_return"}
build_parser().parse_args(["--ledger", "x.jsonl", "--", "true"])

# consensus-policy gate: one full shrink -> renumber -> re-expand cycle on
# real files, no jax anywhere on the import path
from tpu_dist.parallel.consensus import ConsensusDir, consensus_env

with tempfile.TemporaryDirectory() as d:
    now = [1000.0]
    hosts = [ConsensusDir(d, h, planned=3, lease_s=5.0,
                          now=lambda: now[0]) for h in range(3)]
    for c in hosts:
        c.register()
    view = hosts[0].resolve()
    assert view.epoch == 0 and view.hosts == (0, 1, 2)
    hosts[1].leave()                       # mid-numbered host loss
    view = hosts[2].resolve()
    assert view.epoch == 1 and view.hosts == (0, 2) and view.degraded
    assert view.process_id(2) == 1         # the id hole is CLOSED
    cenv = consensus_env({}, view, 2)
    assert cenv["TPU_DIST_PROCESS_ID"] == "1"
    assert cenv["TPU_DIST_DEGRADED"] == "1"
    hosts[1].register()                    # the lost host returns
    view = hosts[0].resolve()
    assert view.epoch == 2 and view.hosts == (0, 2, 1)  # survivors first
    assert not view.degraded and view.process_id(1) == 2

# fleet-scenario gate (round 14): the schedule grammar + deterministic
# compiler and the fleet stitcher must import and run jax-free — the CI
# scenario is validated and its compile double-checked for determinism
from tpu_dist.sim.scenario import (compile_host_plans, expected_restart_classes,
                                   load_scenario)
from tpu_dist.sim.fleet import FleetLedger

sc = load_scenario("scripts/fleet_ci.json")
p1, a1 = compile_host_plans(sc)
p2, a2 = compile_host_plans(sc)
assert ([ (x.tick, x.rid, x.tenant, x.prompt_len, x.out_len)
          for h in sorted(p1) for x in p1[h].arrivals ] ==
        [ (x.tick, x.rid, x.tenant, x.prompt_len, x.out_len)
          for h in sorted(p2) for x in p2[h].arrivals ]) and a1 == a2
assert {h: p.faults for h, p in p1.items() if p.faults}  # >= 1 fault wave
classes = expected_restart_classes(sc)
assert all(cls[-1] == "clean" for cls in classes.values())
assert FleetLedger({0: []}).hosts == {0: []}
print("supervisor + consensus + fleet-scenario policy gates: OK (no jax)")
EOF

# Plan-IR + auto-tuner gate (round 15), jax-free BY CONSTRUCTION: the
# step-plan IR and the tuner must import and run on a bare login/CI host
# (tools/tune.py's whole point), and the tuner's output must be
# DETERMINISTIC — the config knob, bench tags and ledger stamps all key
# on the plan hash, so two identical searches must emit byte-identical
# plan JSON. A stray `import jax` creeping into plan.ir / plan.tune
# fails HERE.
python - <<'EOF'
import builtins, json

_real = builtins.__import__
def _guard(name, *a, **k):
    if name == "jax" or name.startswith("jax."):
        raise ImportError(f"plan gate: jax import blocked ({name})")
    return _real(name, *a, **k)
builtins.__import__ = _guard

from tpu_dist.plan.ir import (Plan, PlanError, apply_plan_to_config,
                              load_plan_file, plan_for_device, plan_hash)
from tpu_dist.plan.tune import tune

# IR round-trip + hash determinism + validation
p = Plan(engine="lm", quant="int8", grad_bucket_mb=25.0, sync="explicit",
         window="indexed", steps_per_dispatch=16,
         quant_block=(256, 128, 0)).validate()
assert Plan.from_json(p.to_json()) == p
assert plan_hash(p) == plan_hash(Plan.from_json(p.to_json()))
for bad in (dict(quant="int4"), dict(tp_impl="ring"),
            dict(grad_bucket_mb=25.0), dict(quant_block=(100, 128, 0))):
    try:
        Plan(engine="lm", **bad).validate()
    except PlanError:
        pass
    else:
        raise AssertionError(f"accepted invalid plan {bad}")

# the canned-measurement search, twice: byte-identical plan JSON
text1, res1 = tune(measurement_files=["scripts/tune_ci.json"])
text2, res2 = tune(measurement_files=["scripts/tune_ci.json"])
assert text1 == text2, "tuner output is not deterministic"
best = res1["TPU v5 lite"]["best"]
assert best["measured"], "the canned trial must win (measured refinement)"
doc = json.loads(text1)
assert doc["plans"]["TPU v5 lite"]["hash"] == best["hash"]
# the emitted file round-trips through the config knob's loader
import os, tempfile
fd, tmp = tempfile.mkstemp(suffix=".json"); os.close(fd)
try:
    with open(tmp, "w") as f:
        f.write(text1)
    sel = plan_for_device(load_plan_file(tmp), "TPU v5 lite")
    assert plan_hash(sel) == best["hash"]
finally:
    os.unlink(tmp)
print("plan IR + tuner gate: OK (no jax, deterministic)")
EOF

# Request-observatory gate (round 17), jax-free BY CONSTRUCTION: the
# span model (obs.reqtrace) and the reading side (tools/request_report)
# must run on a bare login/CI host, and the report must be DETERMINISTIC
# — same ledger bytes, same report bytes. Built twice from the canned
# two-host fixture (rid 5 shed on host 0, re-admitted on host 1) with
# fresh loads, then the invariants the fixture encodes are asserted: one
# cross-host trace, coverage 1.0 with the sum-check green, and every slo
# breach holding >= 1 exemplar. A stray `import jax` creeping into
# obs.reqtrace / sim.fleet / the report tool fails HERE.
python - <<'EOF'
import builtins, json

_real = builtins.__import__
def _guard(name, *a, **k):
    if name == "jax" or name.startswith("jax."):
        raise ImportError(f"reqtrace gate: jax import blocked ({name})")
    return _real(name, *a, **k)
builtins.__import__ = _guard

from tools.request_report import render, requests_summary
from tpu_dist.sim.fleet import FleetLedger

FIX = "tests/fixtures/reqtrace"

def build():
    records = FleetLedger.discover(FIX).merged()
    summary = requests_summary(records)
    lines = []
    render(summary, records, out=lines.append, waterfalls=5)
    return summary, json.dumps(summary, default=str) + "\n".join(lines)

summary, text1 = build()
_, text2 = build()
assert text1 == text2, "request report is not deterministic"
assert summary["cross_host_traces"] == 1, summary
ta = summary["tail_attribution"]
assert ta["coverage"] == 1.0 and ta["sum_check"]["ok"], ta
assert summary["slo_exemplars"], "fixture breach lost"
assert all(b["exemplars"] for b in summary["slo_exemplars"]), \
    "a breach resolved to no exemplar"
print("reqtrace gate: OK (no jax, deterministic)")
EOF

# Autoscaling gate (round 20), jax-free BY CONSTRUCTION: the capacity
# monitor closes the observability->capacity loop, so its policy grammar,
# the checked-in acceptance scenario, and the decision replay must all
# run on a bare login/CI host — and the replay must be DETERMINISTIC
# (decision ids, attribution, ordering), because the fleet report and
# the supervisor's applied follow-ups all key on the decision id. The
# canned fixture is built twice from fresh loads and must produce
# byte-identical decisions, pinned to the [up, down] pair it encodes.
python - <<'EOF'
import builtins, json

_real = builtins.__import__
def _guard(name, *a, **k):
    if name == "jax" or name.startswith("jax."):
        raise ImportError(f"autoscale gate: jax import blocked ({name})")
    return _real(name, *a, **k)
builtins.__import__ = _guard

from tpu_dist.obs.autoscale import AutoscalePolicy, replay_decisions
from tpu_dist.sim.scenario import compile_host_plans, load_scenario

pol = AutoscalePolicy.load("scripts/autoscale_policy.json")
assert pol.min_hosts == 2 and pol.max_hosts == 3, pol
assert pol.down.stable_ticks >= 1, "down-side hysteresis lost"

# the acceptance scenario parses and compiles deterministically with its
# autoscale block (standby host parked, policy by repo-relative path)
sc = load_scenario("scripts/fleet_autoscale.json")
assert sc.standby_hosts() == [2], sc.autoscale
p1, a1 = compile_host_plans(sc)
p2, a2 = compile_host_plans(sc)
assert ([(x.tick, x.rid, x.tenant, x.prompt_len, x.out_len)
         for h in sorted(p1) for x in p1[h].arrivals] ==
        [(x.tick, x.rid, x.tenant, x.prompt_len, x.out_len)
         for h in sorted(p2) for x in p2[h].arrivals]) and a1 == a2

def replay():
    with open("tests/fixtures/autoscale/ledger.jsonl") as f:
        recs = [json.loads(line) for line in f]
    return replay_decisions(
        recs, AutoscalePolicy.load("scripts/autoscale_policy.json"),
        hosts0=2)

d1, d2 = replay(), replay()
assert json.dumps(d1) == json.dumps(d2), \
    "decision replay is not deterministic"
assert [(d["decision"], d["direction"], d["signal"]) for d in d1] == \
    [("d0", "up", "slo_breaches_window"), ("d1", "down", "calm_ticks")], d1
assert d1[0]["tick"] == 14 and d1[1]["tick"] == 64, d1
print("autoscale gate: OK (no jax, deterministic)")
EOF

# Program-audit gate (round 18): proglint over every plan in the tuner's
# canned-CI candidate space (scripts/tune_ci.json names the device kind).
# Unlike the gates above this one NEEDS jax — it traces real programs —
# so it is guarded on availability instead of blocking the import: a
# bare login host still runs every other gate. Abstract tracing only
# (eval_shape-class work, CPU, nothing executes); run TWICE because the
# canonical report is a CI artifact and artifact diffing needs it
# byte-deterministic. Publishes proglint.json + proglint.sarif next to
# distlint.sarif.
if python -c "import jax" >/dev/null 2>&1; then
python - <<'EOF'
import json

import jax

jax.config.update("jax_platforms", "cpu")
from tpu_dist._compat import set_cpu_device_count

set_cpu_device_count(8)
from tpu_dist.analysis.proglint import Finding, audit_tune_space, to_sarif

with open("scripts/tune_ci.json") as f:
    json.load(f)   # the canned space must exist and parse

r1 = audit_tune_space()
r2 = audit_tune_space()
text = json.dumps(r1, indent=1, sort_keys=True) + "\n"
assert text == json.dumps(r2, indent=1, sort_keys=True) + "\n", \
    "proglint report is not byte-deterministic"
assert r1["unwaivered"] == 0, \
    "unwaivered program-audit findings:\n" + "\n".join(
        Finding(**d).render() for d in r1["findings"] if not d["waived"])
with open("proglint.json", "w") as f:
    f.write(text)
with open("proglint.sarif", "w") as f:
    json.dump(to_sarif([Finding(**d) for d in r1["findings"]]), f,
              indent=2, sort_keys=True)
    f.write("\n")
print(f"proglint gate: OK ({r1['plans']} plan(s) -> {r1['programs']} "
      f"program(s), {r1['unwaivered']} unwaivered, deterministic)")
EOF
else
    echo "proglint gate: SKIPPED (no jax on this host; program tracing needs it)"
fi

# Advisory tier-1 budget creep warning (never fails the gate): conftest
# writes each full-suite run's wall time + top-20 durations to
# /tmp/tier1_durations.json (TPU_DIST_TIER1_DURATIONS overrides); the
# suite dies at the 870s timeout, so a wall beyond 700s deserves eyes on
# the top offenders BEFORE the timeout rediscovers it the hard way.
python - <<'EOF' || true
import json, os
path = os.environ.get("TPU_DIST_TIER1_DURATIONS", "/tmp/tier1_durations.json")
try:
    with open(path) as f:
        d = json.load(f)
except Exception:
    raise SystemExit(0)  # no recorded run on this machine — nothing to say
wall = d.get("wall_s") or 0
if wall > 700:
    print(f"WARNING: last tier-1 run took {wall:.0f}s of the 870s budget "
          f"({d.get('tests', '?')} tests; advisory only). Top offenders:")
    for t in (d.get("top") or [])[:8]:
        print(f"  {t.get('s', 0):7.1f}s  {t.get('nodeid', '?')}")
    print("  -> slow-mark new heavy tests (pyproject 'slow' marker) or "
          "shrink the biggest ones above.")
EOF
