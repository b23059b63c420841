"""FleetSim: drive one scenario across N supervised virtual hosts.

The driver is jax-free by the same construction as the supervisor it
composes (only the worker children import jax): per host it runs one
:class:`~tpu_dist.parallel.supervisor.Supervisor` (in a thread) around
``python -m tpu_dist.sim.worker``, exports that host's compiled fault
spec as ``TPU_DIST_FAULTS``, and gives the scenario's ``consensus_host``
a real :class:`~tpu_dist.parallel.consensus.ConsensusDir` — so a
preemption wave's ``leave`` and the later ``register`` (the host return)
drive the PR 12 membership path for real: epoch bump, mid-attempt
SIGTERM, rescale relaunch, ``shrink``/``expand`` scale events in the
``.sup.jsonl`` sibling.

Scheduling is on the **fleet clock**: consensus actions fire when every
live (not scheduled-down, not finished) host's published tick
(``<ledger>.tick`` sidecar) has reached the action's tick — tick gating
both orders the actions deterministically w.r.t. the traffic and proves
the gated hosts are actually serving (a host mid-restart holds the clock
until it resumes). The gate holds the other way too: hosts pace their
ticks by the wall clock, this loop and the consensus supervisor's poll run
on whatever CPU is left, so ``hold.tick`` names the tick no host may pass
until the next scheduled action has fired and the consensus supervisor has
resolved every scheduled membership change
(:meth:`FleetSim._publish_hold`) — on a
starved box a host would otherwise run its trace out before the rescale
scheduled inside it arrives. A wall deadline backstops a wedged fleet.

Outputs under ``out_dir``::

    scenario.json   # the normalized schedule (self-contained artifact)
    fleet.jsonl     # the runner's own ledger: scenario + fleet events
    host<N>/        # each host's attempt ledgers + .sup sibling + sidecars
    report.json     # the stitched FleetLedger report
    headline.json   # the run's own summary: fleet.goodput_ratio, the lag

``python -m tpu_dist.sim.runner --scenario scripts/fleet_ci.json --out
/tmp/fleet`` is the CLI; ``tools/fleet_report.py`` renders the result.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from tpu_dist.obs.autoscale import (AutoscalePolicy, CapacityMonitor,
                                    LedgerTailer, emit_decision)
from tpu_dist.obs.ledger import Ledger
from tpu_dist.obs.metrics import MetricsRegistry, metrics_ledger_sink
from tpu_dist.parallel.consensus import ConsensusDir
from tpu_dist.parallel.supervisor import RestartPolicy, Supervisor
from tpu_dist.sim.scenario import (Scenario, compile_host_plans,
                                   load_scenario)
from tpu_dist.sim.worker import WINDOW_TICKS


_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _scrubbed_env(extra: Dict[str, str]) -> Dict[str, str]:
    """A child env with no inherited TPU_DIST/XLA state (the test
    harness's own knobs must not leak into the simulated hosts — its
    cheap-compile switch included: the scenarios' tick margins were tuned
    against hosts that compile at the normal cost).
    ``python -m tpu_dist.sim.worker`` must resolve from any cwd, so the
    package root rides PYTHONPATH."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_DIST")
           and k not in ("XLA_FLAGS", "JAX_DISABLE_MOST_OPTIMIZATIONS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(extra)
    return env


class FleetSim:
    """One scenario run (see module docstring). ``scenario`` may be a
    path or a parsed :class:`~tpu_dist.sim.scenario.Scenario`."""

    def __init__(self, scenario, out_dir: str, *,
                 python: str = sys.executable,
                 stall_timeout_s: float = 300.0,
                 max_restarts: int = 6):
        self.sc: Scenario = (scenario if isinstance(scenario, Scenario)
                             else load_scenario(scenario))
        self.out = out_dir
        self.python = python
        self.stall_timeout_s = stall_timeout_s
        self.max_restarts = max_restarts
        self.plans, self.actions = compile_host_plans(self.sc)
        self.results: Dict[int, object] = {}
        self._sups: Dict[int, Supervisor] = {}
        self._breaches = 0
        # autoscaling (round 20, obs.autoscale): standby hosts start
        # parked; the CapacityMonitor, fed by tailing every host ledger,
        # decides when they join (and when elastic hosts leave again)
        auto = self.sc.autoscale or {}
        pol = auto.get("policy")
        if isinstance(pol, str) and not os.path.exists(pol):
            # checked-in scenarios name their policy repo-relative
            # (scripts/autoscale_policy.json) — resolve it from anywhere
            pol = os.path.join(_REPO_ROOT, pol)
        self.policy: Optional[AutoscalePolicy] = (
            None if pol is None else
            AutoscalePolicy.from_doc(pol) if isinstance(pol, dict)
            else AutoscalePolicy.load(pol))
        self.standby = set(self.sc.standby_hosts())
        self.decisions: List[dict] = []
        # the hold (module docstring): the membership this runner has
        # asked for, and the tick of a change the consensus supervisor
        # has not resolved yet
        self._members: set = set()
        self._unresolved_tick: Optional[int] = None
        self._hold: Optional[int] = None    # what hold.tick says now

    # -- wiring -----------------------------------------------------------
    def _host_dir(self, h: int) -> str:
        return os.path.join(self.out, f"host{h}")

    def _ledger_path(self, h: int) -> str:
        return os.path.join(self._host_dir(h), "run.jsonl")

    def _build_supervisor(self, h: int, cdir: str,
                          scenario_path: str) -> Supervisor:
        plan = self.plans[h]
        sc = self.sc
        env = _scrubbed_env({
            "TPU_DIST_NUM_PROCESSES": str(sc.hosts),
            "TPU_DIST_PROCESS_ID": str(h),
            **({"TPU_DIST_FAULTS": plan.faults} if plan.faults else {}),
        })
        # a preempted-with-return host must stay genuinely absent until
        # its return tick: the first restart's backoff covers the gap
        holdoff = plan.restart_holdoff_ticks * sc.tick_s * plan.skew
        policy = RestartPolicy(
            max_restarts=self.max_restarts,
            backoff_base_s=max(holdoff, 0.2),
            backoff_max_s=max(holdoff * 2, 30.0),
            stall_timeout_s=self.stall_timeout_s,
            # the sim's SIGTERM faults are the schedule, not host loss
            shrink_on_host_loss=False)
        consensus = (ConsensusDir(cdir, h, planned=self._planned(),
                                  lease_s=3600.0)
                     if h == sc.consensus_host else None)
        # with a policy configured, the consensus host re-tunes the plan
        # deterministically at every new world size (the PR 15
        # retune-on-rescale residue) and stamps its hash into the
        # decision's `applied` follow-up event
        retune = None
        if consensus is not None and self.policy is not None:
            retune = {"device_kind": "TPU v5 lite",
                      "devices_per_host": max(sc.worker_devices, 1),
                      "plan_dir": os.path.join(self.out, "plans")}
        return Supervisor(
            [self.python, "-m", "tpu_dist.sim.worker",
             "--scenario", scenario_path, "--host", str(h)],
            ledger=self._ledger_path(h), policy=policy, env=env,
            poll_s=0.1, consensus=consensus, consensus_poll_s=0.25,
            retune=retune)

    def _planned(self) -> int:
        """The baseline (planned) world size: standby hosts are extra
        elastic capacity ABOVE plan, so the consensus host's first
        resolve at the parked-standby world must not read as a shrink."""
        return self.sc.hosts - (len(self.standby) if self.policy else 0)

    def _read_tick(self, h: int) -> int:
        try:
            with open(self._ledger_path(h) + ".tick") as f:
                return int(f.read().strip() or 0)
        except (OSError, ValueError):
            return 0

    def _set_member(self, peers, h: int, present: bool,
                    hold_tick: Optional[int] = None) -> None:
        """One membership change through the consensus dir. A SCHEDULED
        change (``hold_tick``, its tick) goes on the books for the hold:
        its place in the trace is part of the schedule. A capacity
        decision's change does not: it lands when the monitor says, and
        the hosts it rescales should meet it under the load that caused
        it, not after a pause that let their queues drain."""
        if present:
            peers[h].register()
            self._members.add(h)
        else:
            peers[h].leave()
            self._members.discard(h)
        if hold_tick is not None:
            self._unresolved_tick = hold_tick

    def _publish_hold(self, pending: list, consensus_alive: bool) -> None:
        """Write (or clear) ``hold.tick``: one publish window past the
        earlier of the next scheduled action and a membership change the
        consensus supervisor has not resolved — a host reaches the action's
        tick, publishes it, and waits there (sim.worker)."""
        csup = self._sups.get(self.sc.consensus_host)
        view = csup.mesh_view if csup is not None else None
        if not consensus_alive or (
                view is not None and set(view.hosts) == self._members):
            self._unresolved_tick = None
        ticks = [t for t in (self._unresolved_tick,
                             pending[0].tick if pending else None)
                 if t is not None]
        hold = min(ticks) + WINDOW_TICKS if ticks else None
        if hold == self._hold:
            return
        path = os.path.join(self.out, "hold.tick")
        try:
            if hold is None:
                os.remove(path)
            else:
                with open(path + ".tmp", "w") as f:
                    f.write(f"{hold}\n")
                os.replace(path + ".tmp", path)
            self._hold = hold
        except OSError:
            pass    # retried next poll

    # -- the autoscaling loop (round 20, obs.autoscale) -------------------
    def _autoscale_step(self, monitor: CapacityMonitor,
                        tailer: LedgerTailer, clock: int, live: list,
                        peers: Dict[int, ConsensusDir], parked: set,
                        elastic: set, gone: set, down: set,
                        fleet_ledger: Ledger, start_host) -> None:
        """Feed the monitor from every host's growing ledgers, evaluate
        the policy at the fleet clock, and EXECUTE any decision through
        the machinery that already owns capacity: consensus membership
        (register a parked standby / leave an elastic host) whose epoch
        bump the consensus-host supervisor turns into the shrink/expand
        rescale — stamped with the decision id for the 1:1 pairing."""
        sc = self.sc
        paths = sorted(glob.glob(os.path.join(
            glob.escape(self.out), "host*", "run*.jsonl")))
        for rec in tailer.poll(paths):
            monitor.observe(rec)
        # capacity is what decisions CONTROL (parked standby out, removed
        # hosts out) — not thread liveness: a host finishing its trace is
        # not a scale-down, and must not re-open headroom under the max
        capacity = sc.hosts - len(parked) - len(gone)
        dec = monitor.evaluate(tick=clock, hosts_live=capacity)
        if dec is None:
            return
        emit_decision(fleet_ledger, dec)
        self.decisions.append(dec)
        n = dec["target_hosts"] - dec["hosts_from"]
        csup = self._sups.get(sc.consensus_host)
        if dec["direction"] == "up":
            for h in sorted(parked)[:max(n, 0)]:
                # seed a FRESH cursor at the fleet clock: the new host
                # serves from now on (pre-start arrivals were never
                # admitted anywhere) and publishes its tick immediately
                # so the fleet clock never snaps back to zero
                base = self._ledger_path(h)
                with open(base + ".cursor.json", "w") as f:
                    json.dump({"tick": clock, "done": [], "fresh": True}, f)
                with open(base + ".tick", "w") as f:
                    f.write(f"{clock}\n")
                if csup is not None:
                    csup.autoscale_decision = dec["decision"]
                self._set_member(peers, h, True)
                parked.discard(h)
                elastic.add(h)
                start_host(h)
        else:
            cands = sorted((h for h in elastic
                            if h in live and h != sc.consensus_host),
                           reverse=True)
            for h in cands[:max(-n, 0)]:
                if csup is not None:
                    csup.autoscale_decision = dec["decision"]
                self._set_member(peers, h, False)
                down.add(h)      # the clock must not wait on it
                gone.add(h)      # permanently out: sheds hand off
                elastic.discard(h)
                sup = self._sups.get(h)
                if sup is not None:
                    sup.request_stop()

    def _handoff_step(self, gone: set, handoff_done: set,
                      live: list) -> None:
        """Once a permanently-removed host's drain cursor lands (it
        carries the `shed` descriptors), append them to the lowest
        surviving host's handoff sidecar — the worker re-admits each at
        its scheduled tick under a `readmit` span, so no shed request is
        lost and the request stays one trace across hosts."""
        for h in sorted(gone - handoff_done):
            cursor = self._ledger_path(h) + ".cursor.json"
            try:
                with open(cursor) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            if "shed" not in doc:
                continue        # not drained yet — retry next poll
            handoff_done.add(h)
            shed = [e for e in (doc.get("shed") or ())
                    if isinstance(e, dict) and e.get("rid") is not None]
            survivors = [s for s in live if s != h]
            if not shed or not survivors:
                continue
            dst = self._ledger_path(min(survivors)) + ".handoff.jsonl"
            try:
                with open(dst, "a") as f:
                    for e in shed:
                        f.write(json.dumps({**e, "from_host": h}) + "\n")
            except OSError:
                pass    # the report will show the loss — never crash

    # -- the run ----------------------------------------------------------
    def run(self, timeout_s: Optional[float] = None) -> dict:
        sc = self.sc
        os.makedirs(self.out, exist_ok=True)
        for h in range(sc.hosts):
            os.makedirs(self._host_dir(h), exist_ok=True)
        scenario_path = os.path.join(self.out, "scenario.json")
        with open(scenario_path, "w") as f:
            json.dump(sc.to_doc(), f, indent=1)
        if timeout_s is None:
            # paced trace + a compile/restart allowance per expected launch
            launches = sc.hosts + sum(
                len(p.expected_classes) - 1 for p in self.plans.values())
            timeout_s = sc.wall_estimate_s() * 4 + 90.0 * launches + 120.0

        fleet_ledger = Ledger(os.path.join(self.out, "fleet.jsonl"))
        registry = MetricsRegistry()
        fleet_ledger.add_sink(metrics_ledger_sink(registry))
        fleet_ledger.emit("scenario", name=sc.name, seed=sc.seed,
                          hosts=sc.hosts, ticks=sc.ticks,
                          tick_s=sc.tick_s, consensus_host=sc.consensus_host,
                          events=[dict(ev) for ev in sc.events])

        cdir = os.path.join(self.out, "consensus")
        peers = {h: ConsensusDir(cdir, h, planned=self._planned(),
                                 lease_s=3600.0)
                 for h in range(sc.hosts)}
        parked: set = set(self.standby) if self.policy is not None else set()
        for h, c in peers.items():
            if h not in parked:
                c.register()
                self._members.add(h)

        threads: Dict[int, threading.Thread] = {}

        def _start_host(h: int) -> None:
            sup = self._build_supervisor(h, cdir, scenario_path)
            self._sups[h] = sup

            def _run(h=h, sup=sup):
                self.results[h] = sup.run()

            t = threading.Thread(target=_run, name=f"fleet-sup-{h}",
                                 daemon=True)
            threads[h] = t
            t.start()

        for h in range(sc.hosts):
            if h not in parked:
                _start_host(h)

        monitor = (CapacityMonitor(self.policy,
                                   hosts_live=sc.hosts - len(parked))
                   if self.policy is not None else None)
        tailer = LedgerTailer()
        elastic: set = set()        # hosts an up-decision admitted
        gone: set = set()           # hosts a down-decision removed for good
        handoff_done: set = set()
        pending = list(self.actions)
        down: set = set()
        t_start = time.monotonic()
        force_after = t_start + timeout_s * 0.75
        last_fleet_emit = 0.0
        while any(t.is_alive() for t in threads.values()):
            now = time.monotonic()
            if now - t_start > timeout_s:
                for sup in self._sups.values():
                    sup.request_stop()
                break
            # fleet clock: every live gated host must have reached the tick
            live = [h for h, t in threads.items()
                    if t.is_alive() and h not in down]
            clock = min((self._read_tick(h) for h in live), default=None)
            while pending and ((clock is not None
                                and clock >= pending[0].tick)
                               or now > force_after or not live):
                act = pending.pop(0)
                if act.action == "leave":
                    self._set_member(peers, act.host, False, act.tick)
                    down.add(act.host)
                elif act.action == "register":
                    self._set_member(peers, act.host, True, act.tick)
                    down.discard(act.host)
            if monitor is not None and clock is not None:
                self._autoscale_step(monitor, tailer, clock, live, peers,
                                     parked, elastic, gone, down,
                                     fleet_ledger, _start_host)
                self._handoff_step(gone, handoff_done, live)
            self._publish_hold(pending, sc.consensus_host in live)
            if now - last_fleet_emit >= 1.0:
                last_fleet_emit = now
                fleet_ledger.emit("fleet", hosts_live=len(live),
                                  goodput_ratio=None, slo_breaches=None,
                                  final=False, tick=clock)
            time.sleep(0.1)
        for t in threads.values():
            t.join(timeout=max(timeout_s * 0.25, 30.0))

        from tpu_dist.sim.fleet import FleetLedger

        stitched = FleetLedger.discover(self.out)
        report = stitched.report()
        report["supervisors"] = {
            str(h): {"status": getattr(r, "status", "unjoined"),
                     "attempts": [a.failure_class
                                  for a in getattr(r, "attempts", ())]}
            for h, r in sorted(self.results.items())}
        acct = report.get("fleet") or {}
        fleet_ledger.emit("fleet", hosts_live=0,
                          goodput_ratio=acct.get("goodput_ratio"),
                          slo_breaches=report.get("slo_breaches"),
                          final=True)
        fleet_ledger.close()
        with open(os.path.join(self.out, "report.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
        # the run's summary: fleet.goodput_ratio, and beside it the
        # autoscaler's reaction time (lower is better)
        lag = autoscale_lag_ticks(sc.events, self.decisions)
        with open(os.path.join(self.out, "headline.json"), "w") as f:
            json.dump({"metric": "fleet_sim_goodput",
                       "value": acct.get("goodput_ratio"),
                       "unit": "ratio",
                       "fleet": {"goodput_ratio": acct.get("goodput_ratio"),
                                 "slo_breaches": report.get("slo_breaches"),
                                 "hosts": sc.hosts,
                                 **({"autoscale_lag_ticks": lag,
                                     "autoscale_decisions":
                                         len(self.decisions)}
                                    if self.policy is not None else {})}},
                      f, indent=1)
        return report


def autoscale_lag_ticks(events, decisions) -> Optional[int]:
    """Ticks from the scenario's first ``burst`` event to the policy's
    first ``up`` decision; None where the run had no burst or never
    scaled up."""
    burst0 = min((int(ev["tick"]) for ev in events
                  if ev["type"] == "burst"), default=None)
    up0 = next((d["tick"] for d in decisions
                if d["direction"] == "up"), None)
    if burst0 is None or up0 is None:
        return None
    return up0 - burst0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", required=True,
                    help="scenario JSON/YAML (tpu_dist.sim.scenario)")
    ap.add_argument("--out", required=True, help="fleet output directory")
    ap.add_argument("--timeout-s", type=float, default=0.0,
                    help="wall bound for the whole fleet (0 = derived "
                    "from the schedule)")
    ap.add_argument("--json", action="store_true",
                    help="print the fleet report JSON on stdout")
    args = ap.parse_args(argv)
    sim = FleetSim(args.scenario, args.out)
    report = sim.run(timeout_s=args.timeout_s or None)
    if args.json:
        print(json.dumps(report, default=str))
    else:
        acct = report.get("fleet") or {}
        print(f"fleet '{(report.get('scenario') or {}).get('name')}': "
              f"{len(report['hosts'])} host(s), goodput ratio "
              f"{acct.get('goodput_ratio')}, "
              f"{report.get('slo_breaches')} SLO breach(es), "
              f"restart histogram {report.get('restart_histogram')} — "
              f"full report: {os.path.join(args.out, 'report.json')}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
