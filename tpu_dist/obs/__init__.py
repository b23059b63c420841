"""tpu_dist.obs — one observability subsystem for every run.

Four pieces, one handle:

* :mod:`~tpu_dist.obs.ledger` — append-only JSONL of typed events (the
  source of truth; the epoch CSV and progress line render FROM it);
* :mod:`~tpu_dist.obs.trace` — the program's spans: one always-on bounded
  ring on the engine's clock, each span also a ``jax.profiler`` annotation
  (so any profiler session holds them beside the device's operations);
* :mod:`~tpu_dist.obs.skew` — cross-host step-time allgather every K steps
  (straggler index, p50/p99/spread);
* :mod:`~tpu_dist.obs.watchdog` — trailing-median hang detector that dumps
  thread stacks + HBM to stderr and the ledger, once per stall.

:class:`RunObs` wires them from a config (``ledger_path`` /
``watchdog_factor`` / ``skew_every`` / ``log_csv`` / ``profile_dir``) so the
image Trainer, the LMTrainer, ``engine.generate`` and the serving tools all
feed the SAME records instead of bespoke logging stacks. MFU per step is
computed here against the device's bf16 peak; on backends with no published
peak (CPU, virtual) the field stays non-null by normalizing against a
nominal ``TPU_DIST_NOMINAL_PEAK_TFLOPS`` (default 1.0 — i.e. the value
reads as model TFLOP/s) and ``run_start`` carries ``peak_is_nominal`` so
readers can tell the two apart.
"""

from __future__ import annotations

import atexit
import dataclasses
import os
import signal
import sys
import threading
import time
import traceback
from typing import Optional

from tpu_dist.obs import faults, trace
from tpu_dist.obs.attr import bucket_totals, cost_buckets, emit_cost_model
from tpu_dist.obs.flightrec import FlightRecorder
from tpu_dist.obs.goodput import (GoodputAccumulator, GoodputMonitor,
                                  attempt_path, discover_attempt_paths,
                                  job_accounting, next_attempt_index,
                                  split_attempts)
from tpu_dist.obs.health import HealthError, HealthSentry, validate_health
from tpu_dist.obs.ledger import (EVENT_SCHEMA, EpochCsvSink, Ledger,
                                 ProgressSink, per_process_path, phase_totals,
                                 read_ledger)
from tpu_dist.obs.metrics import (MetricsRegistry, metrics_ledger_sink,
                                  serve_metrics)
from tpu_dist.obs.skew import SkewMonitor
from tpu_dist.obs.trace import StepTracer, profile_session, step_annotation
from tpu_dist.obs.watchdog import Watchdog

__all__ = ["EVENT_SCHEMA", "EpochCsvSink", "FlightRecorder",
           "GoodputAccumulator", "GoodputMonitor", "HealthError",
           "HealthSentry", "Ledger", "MetricsRegistry", "ProgressSink",
           "RunObs", "SkewMonitor", "StepTracer", "Watchdog",
           "attempt_path", "bucket_totals", "cost_buckets",
           "discover_attempt_paths", "emit_cost_model", "faults",
           "job_accounting",
           "metrics_ledger_sink", "next_attempt_index", "per_process_path",
           "phase_totals", "profile_session", "read_ledger",
           "serve_metrics", "split_attempts", "step_annotation", "trace"]


# RunObs.run_end writes the span ring (JSONL) to <ledger_path> + this: a
# name no glob over ledgers (run*.jsonl, run.a*.jsonl, run.p*.jsonl) matches
SPANS_SUFFIX = ".spans"


def effective_peak_tflops() -> tuple:
    """(peak_tflops, is_nominal): the device's published bf16 peak, or the
    nominal fallback that keeps per-step MFU non-null on CPU/virtual
    backends (MFU then reads as model TFLOP/s per chip). An unlisted TPU
    raises (utils.mfu.lookup_peak)."""
    import jax
    from tpu_dist.utils.mfu import peak_tflops_for

    peak = peak_tflops_for(jax.devices()[0])
    if peak:
        return float(peak), False
    return float(os.environ.get("TPU_DIST_NOMINAL_PEAK_TFLOPS", "1.0")), True


class RunObs:
    """Per-run observability handle: ledger + tracer + skew + watchdog.

    Built unconditionally by both engines (a pathless ledger costs nothing),
    so call sites never guard on "is observability on". ``unit`` names the
    throughput unit of this run's step records ("img/s" | "tok/s").
    """

    def __init__(self, kind: str, cfg, mesh=None, unit: str = "items/s",
                 plan_info=None):
        import jax

        self.kind = kind
        self.cfg = cfg
        self.unit = unit
        # resolved step plan (tpu_dist.plan): {'source', 'hash', 'knobs',
        # 'device_kind'} from plan.compile.resolve_config_plan — stamped
        # into run_start and emitted as its own 'plan' event so reports
        # and the tuner's measured-refinement loop can key runs by plan
        self.plan_info = plan_info
        pidx = jax.process_index()
        self.is_main = pidx == 0
        # run lineage (obs.goodput): one logical job = N restart attempts,
        # each writing its OWN ledger (run.jsonl, run.a1.jsonl, ... — the
        # restart analog of the .pN multi-process story) so the attempt
        # tools can stitch the timeline back with restart gaps visible.
        # attempt=-1 auto-picks the next free index from files on disk.
        base_path = getattr(cfg, "ledger_path", "") or ""
        attempt = int(getattr(cfg, "attempt", 0) or 0)
        if attempt < 0:
            # probes THIS process's own prior files, so process 0 creating
            # the bare ledger first never makes a later-starting peer of
            # the same attempt self-assign the next index
            attempt = (next_attempt_index(base_path, pidx)
                       if base_path else 0)
        self.attempt = attempt
        self.job_id = (getattr(cfg, "job_id", "") or
                       (os.path.splitext(os.path.basename(base_path))[0]
                        if base_path else None))
        ledger_path = per_process_path(
            attempt_path(base_path, attempt), pidx)
        self.ledger = Ledger(ledger_path or None, process_index=pidx)
        if getattr(cfg, "log_csv", "") and self.is_main:
            # the legacy per-epoch CSV becomes a VIEW of the epoch event
            self.ledger.add_sink(EpochCsvSink(cfg.log_csv))
        profile_dir = getattr(cfg, "profile_dir", "") or ""
        # this run owns a profile_dir session (the program's spans are in
        # ANY session's trace; this only says whose session it is)
        self.profiling = bool(profile_dir) and self.is_main
        self.profile_dir = profile_dir
        self.tracer = StepTracer(prefix="train.")
        skew_every = getattr(cfg, "skew_every", 0) or 0
        self.skew = (SkewMonitor(skew_every, ledger=self.ledger)
                     if skew_every > 0 else None)
        wd_factor = getattr(cfg, "watchdog_factor", 0.0) or 0.0
        self.watchdog = (Watchdog(wd_factor, ledger=self.ledger)
                         if wd_factor > 0 else None)
        # numerical-health sentry (obs.health): consumes the fused step
        # probes + loss at each drain; skip/halt policy from the config
        self.health = HealthSentry(
            policy=validate_health(getattr(cfg, "health", "record")),
            spike_z=getattr(cfg, "health_spike_z", 8.0) or 0.0,
            ledger=self.ledger)
        # live metrics export (obs.metrics): the registry is fed by a
        # ledger sink — everything emitted (steps, stalls, skew, health,
        # hbm, decode) reaches the scrape through the one event stream
        self.metrics = MetricsRegistry()
        self.ledger.add_sink(metrics_ledger_sink(self.metrics))
        self.metrics_server = None
        metrics_port = getattr(cfg, "metrics_port", 0) or 0
        if metrics_port > 0:
            # .pN story for ports: process i serves metrics_port + i
            self.metrics_server = serve_metrics(self.metrics,
                                                metrics_port + pidx)
        # flight recorder (obs.flightrec): always-on ring of recent events
        # + triggered bundle capture, fed — like the metrics registry — by
        # the one ledger event stream, so watchdog stalls, health trips and
        # skew-straggler spikes all produce a bundle without new plumbing.
        # The profiler-window veto keeps it off the global profiler when a
        # profile_dir session owns it.
        self.flightrec = FlightRecorder(
            dir=getattr(cfg, "flightrec_dir", "") or "",
            ledger=self.ledger,
            trace_steps=getattr(cfg, "flightrec_trace_steps", 3),
            profiler_busy=lambda: self.profiling,
            process_index=pidx)
        self.ledger.add_sink(self.flightrec.sink)
        # goodput accounting + progress-SLO watch (obs.goodput): another
        # ledger sink — periodic 'goodput' partitions and 'slo' breach
        # events ride the same one-event-stream fan-out, so the metrics
        # gauges and the flight recorder see them with no new plumbing
        self.goodput = GoodputMonitor(
            self.ledger,
            every_s=getattr(cfg, "goodput_every_s", 60.0),
            slo_steps_per_min=getattr(cfg, "slo_steps_per_min", 0.0),
            slo_throughput=getattr(cfg, "slo_throughput", 0.0),
            unit=unit)
        self.ledger.add_sink(self.goodput.sink)
        self._prev_sigusr1 = None
        # deterministic fault injection (obs.faults): the config knob wins
        # over TPU_DIST_FAULTS; ledger + attempt context registered at
        # run_start so every injection site (checkpoint writer, launch)
        # can emit its 'fault' event without new plumbing
        if getattr(cfg, "faults", ""):
            faults.install(cfg.faults)
        # supervisor liveness: touch a heartbeat file at each proven-progress
        # beat (parallel.supervisor sets the env var for its children; the
        # ledger tail is the other liveness signal)
        self._hb_path = os.environ.get("TPU_DIST_HEARTBEAT_FILE", "") \
            if self.is_main else ""
        self._hb_last = 0.0
        self.peak_tflops, self.peak_is_nominal = effective_peak_tflops()
        self._mesh_info = (
            {name: int(size) for name, size in mesh.shape.items()}
            if mesh is not None else None)
        self._t0 = time.time()
        self.steps = 0
        self._ended = False
        self._crash_tb: Optional[str] = None
        self._prev_excepthook = None
        self._prev_sigterm = None
        # coordinated preemption (round 13): a loop that can snapshot
        # enables this, and a SIGTERM then REQUESTS a snapshot (flag +
        # deadline) instead of the crash guard's immediate run_end — the
        # loop finishes the in-flight step, checkpoints, and exits with
        # parallel.supervisor.PREEMPT_SNAPSHOT_RC
        self._preempt_enabled = False
        self._preempt_event = threading.Event()
        self.preempt_deadline_s: Optional[float] = None
        self.preempt_source: Optional[str] = None

    # -- coordinated preemption ----------------------------------------
    def enable_preempt_snapshot(self) -> None:
        """Loops with a snapshot path call this before :meth:`run_start`:
        SIGTERM becomes a snapshot REQUEST the loop drains at its next
        step boundary rather than an immediate crash-guard shutdown."""
        self._preempt_enabled = True

    def request_preemption(self, deadline_s: Optional[float] = None,
                           source: str = "sigterm") -> None:
        """Arm the snapshot request (idempotent). ``deadline_s`` defaults
        to the supervisor-forwarded ``TPU_DIST_PREEMPT_DEADLINE_S``."""
        if self._preempt_event.is_set():
            return
        if deadline_s is None:
            try:
                deadline_s = float(
                    os.environ.get("TPU_DIST_PREEMPT_DEADLINE_S", "30"))
            except ValueError:
                deadline_s = 30.0
        self.preempt_deadline_s = deadline_s
        self.preempt_source = source
        self._preempt_event.set()

    def preempt_pending(self) -> bool:
        return self._preempt_event.is_set()

    # -- lifecycle ------------------------------------------------------
    def run_start(self, **extra) -> None:
        """``extra``: what the engine knows of its own start and the record
        should carry (``Trainer``: ``build_s``, ``build_compiles``)."""
        import jax

        self._t0 = time.time()
        self._ended = False
        faults.set_ledger(self.ledger)
        # fault-gating context: under a supervisor, TPU_DIST_ATTEMPT (its
        # launch counter) is authoritative — the ledger ordinal does not
        # advance across ledgerless deaths (a pre-RunObs rendezvous crash),
        # so gating on it would aim attempt-conditioned faults at the
        # wrong launch. Standalone runs have no env var; the two coincide.
        try:
            fault_attempt = int(
                os.environ.get("TPU_DIST_ATTEMPT", "") or self.attempt)
        except ValueError:
            fault_attempt = self.attempt
        faults.set_context(attempt=fault_attempt)
        try:
            mesh_epoch = int(os.environ.get("TPU_DIST_MESH_EPOCH", "0") or 0)
        except ValueError:
            mesh_epoch = 0
        self.ledger.emit(
            "run_start", kind=self.kind,
            config=dataclasses.asdict(self.cfg)
            if dataclasses.is_dataclass(self.cfg) else dict(self.cfg),
            mesh=self._mesh_info,
            devices=sorted({d.device_kind for d in jax.local_devices()}),
            process_count=jax.process_count(),
            device_count=jax.device_count(),
            peak_tflops=self.peak_tflops,
            peak_is_nominal=self.peak_is_nominal,
            jax_version=jax.__version__,
            job_id=self.job_id, attempt=self.attempt,
            resumed_from=getattr(self.cfg, "resume", "") or None,
            # elastic lineage (parallel.consensus): reports tell a
            # degraded layout and its rendezvous epoch from the planned one
            degraded=os.environ.get("TPU_DIST_DEGRADED") == "1",
            mesh_epoch=mesh_epoch,
            # step-plan identity (tpu_dist.plan): which tuned plan drove
            # this run's step compilation (None = hand-set knobs)
            plan_hash=(self.plan_info or {}).get("hash"),
            plan_source=(self.plan_info or {}).get("source"),
            plan_knobs=(self.plan_info or {}).get("knobs"),
            **extra)
        if self.plan_info:
            self.ledger.emit(
                "plan", source=self.plan_info.get("source"),
                plan_hash=self.plan_info.get("hash"),
                knobs=self.plan_info.get("knobs"),
                device_kind=self.plan_info.get("device_kind"))
        self._arm_crash_guard()

    def run_end(self, status: Optional[str] = None, **extra) -> None:
        """Final rollup + shutdown. Idempotent (the crash guard's atexit
        hook and a loop's ``finally`` may both call it). ``status`` is
        derived from the active exception when not given — the loops call
        this from a ``finally``, where ``sys.exc_info()`` still sees the
        in-flight crash — so an unhandled exception stamps
        ``status="crashed"`` plus a truncated traceback without any
        call-site ceremony. The ledger file is line-buffered, so every
        prior event is already on disk even if this emit never runs."""
        if self._ended:
            return
        self._ended = True
        self._disarm_crash_guard()
        if self.watchdog is not None:
            self.watchdog.stop()
        # finalize a profiler window left open (a stall with no subsequent
        # steps) BEFORE the final emits below land in the ring
        self.flightrec.close()
        if status is None:
            exc = sys.exc_info()[1]
            if exc is None and self._crash_tb is not None:
                status = "crashed"
                extra.setdefault("error", self._crash_tb)
            elif isinstance(exc, KeyboardInterrupt):
                status = "interrupted"
            elif exc is not None:
                status = "crashed"
                extra.setdefault("error", "".join(
                    traceback.format_exception(type(exc), exc,
                                               exc.__traceback__))[-2000:])
            else:
                status = "ok"
        # the final goodput partition (obs.goodput): always one 'goodput'
        # event per attempt, however short the run — the attempt tools and
        # the metrics snapshot below both read it. Exception-guarded: the
        # crash paths (atexit/SIGTERM) reach here too
        try:
            self.goodput.emit_goodput(final=True)
        except Exception:
            pass
        # the registry's final values survive in the flight record after
        # the scrape endpoint is gone
        self.ledger.emit("metrics_snapshot", metrics=self.metrics.snapshot())
        self.ledger.emit("run_end", steps=self.steps,
                         seconds=round(time.time() - self._t0, 3),
                         status=status, health_trips=self.health.trips,
                         **extra)
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self.ledger.path:
            # the program's spans, beside the ledger (obs.trace)
            try:
                trace.ring().dump(self.ledger.path + SPANS_SUFFIX)
            except OSError:
                pass  # the crash paths reach here too
        self.ledger.close()

    # -- crash-safe shutdown -------------------------------------------
    # An unhandled exception reaches run_end via the loops' finally (and
    # sys.exc_info stamps it); the guard covers the paths finally cannot:
    # SIGTERM (the scheduler's preemption signal — default handling kills
    # the process with no cleanup) and interpreter exit without run_end
    # (a caller that never wrapped the loop). Armed at run_start, disarmed
    # at run_end; emit is microseconds on a line-buffered file.
    def _arm_crash_guard(self) -> None:
        atexit.register(self._atexit_end)
        self._prev_excepthook = sys.excepthook
        sys.excepthook = self._excepthook
        try:
            if threading.current_thread() is threading.main_thread():
                self._prev_sigterm = signal.signal(signal.SIGTERM,
                                                   self._on_sigterm)
        except (ValueError, OSError):  # non-main thread / exotic platform
            self._prev_sigterm = None
        try:
            # operator-initiated diagnosis: kill -USR1 <pid> captures a
            # flight-recorder bundle without touching the run
            if threading.current_thread() is threading.main_thread():
                self._prev_sigusr1 = signal.signal(signal.SIGUSR1,
                                                   self._on_sigusr1)
        except (ValueError, OSError, AttributeError):  # no SIGUSR1 on win
            self._prev_sigusr1 = None

    def _disarm_crash_guard(self) -> None:
        try:
            atexit.unregister(self._atexit_end)
        except Exception:
            pass
        if self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
            self._prev_excepthook = None
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except (ValueError, OSError):
                pass
            self._prev_sigterm = None
        if self._prev_sigusr1 is not None:
            try:
                signal.signal(signal.SIGUSR1, self._prev_sigusr1)
            except (ValueError, OSError):
                pass
            self._prev_sigusr1 = None

    def _on_sigusr1(self, signum, frame) -> None:
        self.flightrec.trigger("sigusr1")
        prev = self._prev_sigusr1
        if callable(prev):
            prev(signum, frame)

    def _excepthook(self, exc_type, exc, tb) -> None:
        # record the traceback for the atexit emit, then defer to the
        # previous hook (never swallow the crash report)
        self._crash_tb = "".join(
            traceback.format_exception(exc_type, exc, tb))[-2000:]
        prev = self._prev_excepthook or sys.__excepthook__
        prev(exc_type, exc, tb)

    def _atexit_end(self) -> None:
        if not self._ended:
            self.run_end(status="crashed" if self._crash_tb else "ok",
                         **({"error": self._crash_tb}
                            if self._crash_tb else {}))

    def _on_sigterm(self, signum, frame) -> None:
        if self._preempt_enabled and not self._ended:
            # coordinated path: flag only (signal-safe — no locks, no
            # I/O); the loop finishes the in-flight step, snapshots, and
            # owns the run_end + exit
            self.request_preemption(source="SIGTERM")
            return
        # capture BEFORE run_end: disarming inside it nulls _prev_sigterm,
        # and a previously-installed handler (a preemption checkpoint
        # hook, say) must still be chained
        prev = self._prev_sigterm
        self.run_end(status="crashed", error="SIGTERM")
        if callable(prev):
            prev(signum, frame)
        else:
            raise SystemExit(143)

    # -- per-step -------------------------------------------------------
    def step(self, step: int, loss: Optional[float], n_items: float,
             wall_s: float, data_s: float, dispatch_s: float,
             device_s: float, device_flops: Optional[float] = None,
             steps_in_dispatch: int = 1, warm: bool = False,
             comm_s: Optional[float] = None, **extra) -> dict:
        """Record one optimizer step (or one K-step dispatch window).

        ``n_items`` is the GLOBAL item count of the record (images or
        tokens across all steps in the dispatch); ``device_flops`` is the
        per-device model FLOPs of ONE optimizer step, from which TFLOP/s
        and MFU derive. ``comm_s`` is the communication share of the
        dispatch where the engine can isolate it (explicit bucketed grad
        sync: a standalone-probe estimate; None under fused/GSPMD
        schedules) — it OVERLAPS device_s, see the EVENT_SCHEMA note. ``warm=True`` marks the record that carried the
        XLA compile (its dispatch_s is compile-dominated; ledger_report
        excludes warm records from phase shares and trends, matching the
        loops' own warm-excluded throughput convention). Also feeds the
        skew monitor. The hang watchdog is NOT fed here — step records
        land only at drain boundaries, while the watchdog needs the
        per-iteration cadence (:meth:`heartbeat`); feeding it boundary-
        clustered single-step durations would false-fire on any run whose
        print window exceeds factor x one step.
        """
        wall = max(wall_s, 1e-9)
        throughput = n_items / wall
        tflops = mfu = None
        if device_flops:
            tflops = device_flops * steps_in_dispatch / wall / 1e12
            mfu = tflops / self.peak_tflops
        rec = self.ledger.emit(
            "step", step=step, loss=loss,
            throughput=round(throughput, 1), unit=self.unit,
            data_s=round(data_s, 6), dispatch_s=round(dispatch_s, 6),
            device_s=round(device_s, 6),
            comm_s=round(comm_s, 6) if comm_s is not None else None,
            mfu=float(f"{mfu:.4g}") if mfu is not None else None,
            tflops=float(f"{tflops:.4g}") if tflops is not None else None,
            steps_in_dispatch=steps_in_dispatch, warm=warm,
            items=n_items, **extra)
        self.steps += steps_in_dispatch
        if self.skew is not None:
            self.skew.record(step, wall_s, data_s,
                             n_steps=steps_in_dispatch)
        return rec

    def fire_step_faults(self, step: int) -> dict:
        """Step-scoped fault-injection check (obs.faults), called by the
        loops once per dispatch iteration: the process-level sites
        (hard_exit/hang/preempt_sigterm) act inside, and the returned
        ``{site: Fault}`` mapping names the data-level effects the loop
        must apply itself (``nan_batch``, ``preempt_deadline`` — the
        Fault carries site args like the injected deadline). No-op and
        near-free when no plan is active."""
        return faults.fire_step(step, ledger=self.ledger)

    def heartbeat(self) -> None:
        """Device progress proven (a drain's blocking device_get returned)
        — the watchdog's arming signal. The loops call this at every drain
        sync point; the watchdog derives the duration itself (time since
        the previous beat), so its trailing median tracks the print-window
        cadence being watched — off-boundary iterations only ENQUEUE work
        and prove nothing about the devices (Watchdog.beat)."""
        if self.watchdog is not None:
            self.watchdog.beat()
        # supervisor liveness: proven progress also touches the heartbeat
        # file (parallel.supervisor watches its mtime beside the ledger
        # tail). Throttled and best-effort — liveness reporting must never
        # take the run down, even on a full disk.
        if self._hb_path:
            now = time.time()
            if now - self._hb_last >= 1.0:
                self._hb_last = now
                try:
                    with open(self._hb_path, "w") as f:
                        f.write(f"{now}\n")
                except OSError:
                    pass

    # -- phase transitions ---------------------------------------------
    def pause(self) -> None:
        """Entering a phase where step completions legitimately stop
        (validation, checkpoint gather) — silence the watchdog."""
        if self.watchdog is not None:
            self.watchdog.pause()

    def resume(self) -> None:
        if self.watchdog is not None:
            self.watchdog.resume()
