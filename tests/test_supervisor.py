"""Self-healing (round 10): elastic supervisor + deterministic faults.

Every failure class the supervisor claims to remediate is PRODUCED here on
demand — policy math and classification as pure units, each restart path
against a stdlib-only fake child (sub-second per attempt), checkpoint
blast-radius hardening against real containers, and one chaos acceptance
smoke where a supervised LM run survives an injected hard kill mid-epoch
with no manual intervention (ISSUE 10 acceptance). The full elastic-shrink
variant (rendezvous loss -> degraded dp-only relaunch of a real script) is
slow-marked.
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from tpu_dist.obs import faults
from tpu_dist.obs.goodput import discover_attempt_paths, job_accounting, \
    split_attempts
from tpu_dist.obs.health import HealthError
from tpu_dist.obs.ledger import read_ledger
from tpu_dist.parallel.launch import LaunchInfo, rendezvous_with_retry
from tpu_dist.parallel.supervisor import (CrashLoopError, RestartPolicy,
                                          Supervisor, classify_attempt,
                                          compute_backoff, degraded_env,
                                          latest_checkpoint, run_supervised)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    """Fault plans are process-global; tests must not leak them."""
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults._reset_for_tests()
    yield
    faults._reset_for_tests()


# ---------------------------------------------------------------------------
# policy math + classification (pure, no processes — lint.sh runs the same
# surface without jax as a CI gate)

def test_backoff_is_exponential_and_capped():
    pol = RestartPolicy(backoff_base_s=1.0, backoff_max_s=8.0)
    assert compute_backoff(0, pol) == 0.0
    assert [compute_backoff(n, pol) for n in (1, 2, 3, 4, 10)] == \
        [1.0, 2.0, 4.0, 8.0, 8.0]


def _end(status=None, error=None):
    return {"event": "run_end", "steps": 3, "seconds": 1.0,
            "status": status, "error": error}


@pytest.mark.parametrize("records,rc,killed,stderr,want", [
    ([_end("ok")], 0, False, "", "clean"),
    ([_end("ok")], None, False, "", "clean"),              # report-side view
    ([_end("crashed", "HealthError: val_loss spike z=9.1")], 1, False, "",
     "health_halt"),
    ([_end("crashed", "SIGTERM")], 143, False, "", "preemption"),
    ([], -signal.SIGTERM, False, "", "preemption"),
    ([], 1, False, "rendezvous failed: could not reach coordinator",
     "rendezvous"),
    ([], 1, False, "grpc DEADLINE_EXCEEDED", "rendezvous"),
    ([{"event": "stall", "idle_s": 9.0}], -9, True, "", "stall"),
    # died mid-stall without our kill (OOM killer / operator)
    ([{"event": "stall", "idle_s": 9.0}], -9, False, "", "stall"),
    ([], 13, False, "", "crash"),
])
def test_classify_attempt_failure_classes(records, rc, killed, stderr, want):
    assert classify_attempt(records, rc, killed, stderr) == want


def test_classify_stall_kill_beats_run_end():
    # our own SIGKILL after a confirmed stall wins over any ledger story
    assert classify_attempt([_end("ok")], -9, True, "") == "stall"


def test_degraded_env_shrinks_and_marks():
    env, survivors = degraded_env({"TPU_DIST_NUM_PROCESSES": "4"}, lost=1)
    assert survivors == 3
    assert env["TPU_DIST_NUM_PROCESSES"] == "3"
    assert env["TPU_DIST_DEGRADED"] == "1"
    # floor at one survivor; a single-process env is never marked degraded
    env, survivors = degraded_env({"TPU_DIST_NUM_PROCESSES": "1"}, lost=1)
    assert survivors == 1 and "TPU_DIST_DEGRADED" not in env


# ---------------------------------------------------------------------------
# fault-spec grammar + matching (obs.faults)

def test_fault_spec_grammar_roundtrip():
    plan = faults.FaultPlan.parse(
        "hard_exit@step=10,attempt=0,code=7; nan_batch@step=3;"
        "rendezvous_fail@times=2")
    assert plan.sites() == {"hard_exit", "nan_batch", "rendezvous_fail"}
    hard = plan.faults[0]
    assert hard.when == {"step": 10, "attempt": 0}
    assert hard.args == {"code": 7.0}
    assert plan.faults[2].times == 2


@pytest.mark.parametrize("spec", [
    "explode@step=1",          # unknown site
    "hard_exit@step",          # malformed condition
    "hard_exit@step=ten",      # non-numeric value
])
def test_fault_spec_rejects_bad_entries(spec):
    with pytest.raises(ValueError):
        faults.FaultPlan.parse(spec)


def test_fault_matching_step_attempt_times():
    plan = faults.FaultPlan.parse("nan_batch@step=5,attempt=1,times=2")
    # wrong attempt never fires, whatever the step
    assert plan.fire("nan_batch", step=9, attempt=0) is None
    # step is ">= N at first opportunity" (window dispatch may skip N)
    assert plan.fire("nan_batch", step=4, attempt=1) is None
    assert plan.fire("nan_batch", step=6, attempt=1) is not None
    assert plan.fire("nan_batch", step=7, attempt=1) is not None  # times=2
    assert plan.fire("nan_batch", step=8, attempt=1) is None      # spent


def test_fault_env_var_installs_lazily(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "nan_batch@step=0")
    monkeypatch.setenv("TPU_DIST_ATTEMPT", "2")  # supervisor's child export
    faults._reset_for_tests()
    assert set(faults.fire_step(0)) == {"nan_batch"}
    assert faults._context["attempt"] == 2


# ---------------------------------------------------------------------------
# latest_checkpoint: the supervisor's jax-free resume pointer

def test_latest_checkpoint_prefers_pointer_then_mtime(tmp_path):
    d = str(tmp_path)
    assert latest_checkpoint(d) is None
    old = os.path.join(d, "lm-checkpoint.r10.msgpack")
    new = os.path.join(d, "lm-checkpoint.msgpack")
    for p in (old, new):
        with open(p, "wb") as f:
            f.write(b"x")
    os.utime(old, (1, 1))
    # no pointer yet: newest msgpack by mtime
    assert latest_checkpoint(d) == new
    with open(os.path.join(d, "lm-checkpoint.index.json"), "w") as f:
        json.dump({"newest": "lm-checkpoint.r10.msgpack"}, f)
    # pointer wins (it only ever names a fully-committed container)
    assert latest_checkpoint(d) == old
    # a pointer naming a missing file is ignored, not trusted
    with open(os.path.join(d, "lm-checkpoint.index.json"), "w") as f:
        json.dump({"newest": "gone.msgpack"}, f)
    assert latest_checkpoint(d) == new


def test_latest_checkpoint_multi_arch_newest_pointer_wins(tmp_path):
    # a dir that ever held another arch's checkpoints: the NEWEST pointer
    # is the resume target, not the alphabetically-first one (resuming an
    # LM run from a stale lenet container would crash-loop on geometry)
    d = str(tmp_path)
    for arch, age in (("lenet", 1), ("lm", 2)):
        ck = os.path.join(d, f"{arch}-checkpoint.msgpack")
        with open(ck, "wb") as f:
            f.write(b"x")
        idx = os.path.join(d, f"{arch}-checkpoint.index.json")
        with open(idx, "w") as f:
            json.dump({"newest": f"{arch}-checkpoint.msgpack"}, f)
        os.utime(idx, (age, age))
    assert latest_checkpoint(d).endswith("lm-checkpoint.msgpack")


# ---------------------------------------------------------------------------
# rendezvous retry (parallel.launch hardening)

_INFO = LaunchInfo("10.0.0.1:8476", 2, 0, "env")


def test_rendezvous_retry_succeeds_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("boom")

    waits = []
    used = rendezvous_with_retry(flaky, _INFO, retries=5, timeout_s=60,
                                 backoff_s=0.5, sleep=waits.append)
    assert used == 3 and len(calls) == 3
    assert waits == [0.5, 1.0]  # exponential


def test_rendezvous_retry_exhaustion_names_the_coordinator():
    def dead():
        raise ConnectionError("connection refused")

    with pytest.raises(RuntimeError) as ei:
        rendezvous_with_retry(dead, _INFO, retries=3, timeout_s=60,
                              backoff_s=0.0, sleep=lambda s: None)
    msg = str(ei.value)
    assert "10.0.0.1:8476" in msg and "env method" in msg
    assert "3 attempt(s)" in msg and "connection refused" in msg


def test_rendezvous_retry_respects_total_deadline():
    waits = []
    with pytest.raises(RuntimeError) as ei:
        rendezvous_with_retry(lambda: (_ for _ in ()).throw(OSError("x")),
                              _INFO, retries=100, timeout_s=5.0,
                              backoff_s=4.0, sleep=waits.append)
    # first wait (4s) fits the 5s deadline; the second (8s) would not
    assert waits == [4.0]
    assert "2 attempt(s)" in str(ei.value)


def test_rendezvous_fault_site_fails_first_k_attempts():
    faults.install("rendezvous_fail@times=2")
    calls = []
    used = rendezvous_with_retry(lambda: calls.append(1), _INFO, retries=5,
                                 timeout_s=60, backoff_s=0.0,
                                 sleep=lambda s: None)
    assert used == 3 and len(calls) == 1  # two injected failures, then in


# ---------------------------------------------------------------------------
# the supervisor policy loop against a stdlib-only fake child: each failure
# class produced for real (subprocess, ledger tail, exit codes), seconds not
# minutes because the child fakes the *training*, never the failure

_CHILD = r"""
import json, os, signal, sys, time

def emit(f, event, **kw):
    f.write(json.dumps({"event": event, "ts": time.time(), **kw}) + "\n")
    f.flush()

argv = sys.argv[1:]
base = argv[argv.index("--ledger-base") + 1]
behaviors = json.loads(argv[argv.index("--behaviors") + 1])
attempt = int(os.environ.get("TPU_DIST_ATTEMPT", "0"))
b = behaviors[min(attempt, len(behaviors) - 1)]
root, ext = os.path.splitext(base)
path = base if attempt == 0 else f"{root}.a{attempt}{ext}"
with open(path, "a") as f:
    emit(f, "run_start", attempt=attempt)
    if b == "dead":
        sys.exit(3)  # dies before its first step (crash-loop fodder)
    if b == "rdzv":
        print("rendezvous failed: could not reach coordinator",
              file=sys.stderr, flush=True)
        sys.exit(1)
    if b == "shrunk_clean":
        ok = (os.environ.get("TPU_DIST_NUM_PROCESSES") == "1"
              and os.environ.get("TPU_DIST_DEGRADED") == "1"
              and "--mesh-shape" in argv)
        if not ok:
            sys.exit(9)
    emit(f, "step", step=0)
    if b == "faultloop":
        sys.path.insert(0, {root_repo!r})
        from tpu_dist.obs import faults
        for step in range(1, 6):
            faults.fire_step(step)
            emit(f, "step", step=step)
    if b == "halt":
        emit(f, "run_end", steps=1, seconds=0.1, status="crashed",
             error="HealthError: val_loss spike z=9.1")
        sys.exit(2)
    if b == "sigterm":
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(30)
    if b == "hang":
        emit(f, "stall", idle_s=9.0, threshold_s=1.0, stacks="")
        time.sleep(60)
    emit(f, "run_end", steps=1, seconds=0.1, status="ok")
"""


@pytest.fixture
def fake_child(tmp_path):
    """A supervised 'training command' factory: behaviors[n] scripts the
    n-th attempt (stdlib-only child — ~50ms per attempt)."""
    script = tmp_path / "child.py"
    script.write_text(_CHILD.replace("{root_repo!r}", repr(ROOT)))
    ledger = str(tmp_path / "run.jsonl")

    def make(behaviors, env=None, **policy_kw):
        kw = dict(max_restarts=3, backoff_base_s=0.01, backoff_max_s=0.02,
                  stall_timeout_s=10.0, stall_grace_s=0.3, crash_loop_k=3)
        kw.update(policy_kw)
        child_env = dict(os.environ)
        child_env.update(env or {})
        return Supervisor(
            [sys.executable, str(script), "--ledger-base", ledger,
             "--behaviors", json.dumps(behaviors)],
            ledger=ledger, policy=RestartPolicy(**kw), env=child_env,
            forward_flags=False, poll_s=0.05), ledger

    return make


def test_supervisor_clean_run_is_one_attempt(fake_child):
    sup, _ = fake_child(["clean"])
    res = sup.run()
    assert res.ok and res.status == "clean"
    assert [a.failure_class for a in res.attempts] == ["clean"]


def test_supervisor_restarts_after_fault_injected_exit(fake_child):
    # the real obs.faults plumbing inside the child: hard_exit at step 3 of
    # attempt 0 (os._exit — no run_end, SIGKILL-class death), attempt 1
    # runs the same loop to completion because the spec is attempt-gated
    sup, ledger = fake_child(
        ["faultloop", "faultloop"],
        env={"TPU_DIST_FAULTS": "hard_exit@step=3,attempt=0,code=13"})
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == ["crash", "clean"]
    assert res.attempts[0].returncode == 13
    assert res.attempts[0].steps == 3  # steps 0..2 landed before the kill
    assert res.attempts[1].ledger.endswith(".a1.jsonl")


def test_supervisor_health_halt_classified_and_restarted(fake_child):
    sup, _ = fake_child(["halt", "clean"])
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == ["health_halt", "clean"]


def test_supervisor_preemption_classified(fake_child):
    sup, _ = fake_child(["sigterm", "clean"])
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == ["preemption", "clean"]


def test_supervisor_kills_confirmed_stall_and_restarts(fake_child):
    # the child's own watchdog 'stall' event with no progress after it:
    # SIGKILL after stall_grace_s, restart, clean finish — well under the
    # stall_timeout_s idle path
    t0 = time.monotonic()
    sup, _ = fake_child(["hang", "clean"])
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == ["stall", "clean"]
    assert res.attempts[0].returncode in (-signal.SIGKILL, 137)
    assert time.monotonic() - t0 < 10.0  # grace path, not the 60s sleep


def test_supervisor_crash_loop_cutoff(fake_child):
    # K consecutive pre-first-step deaths stop the supervisor with a
    # diagnosis instead of burning max_restarts (ISSUE 10 acceptance)
    sup, _ = fake_child(["dead"], max_restarts=10, crash_loop_k=3)
    res = sup.run()
    assert res.status == "crash_loop" and not res.ok
    assert len(res.attempts) == 3
    assert all(a.steps == 0 for a in res.attempts)


def test_supervisor_rendezvous_loss_shrinks_mesh(fake_child):
    # confirmed host loss = TWO consecutive rendezvous-class failures
    # (the first full-size retry rides out a transient coordinator
    # outage); then the relaunch env drops to the survivors, is marked
    # degraded, and carries the dp-only mesh reset flags — the child
    # itself verifies all three (exits 9 otherwise). forward_flags on:
    # the degraded flags ride the same append path as --resume
    sup, ledger = fake_child(["rdzv", "rdzv", "shrunk_clean"],
                             env={"TPU_DIST_NUM_PROCESSES": "2"})
    sup.forward_flags = True
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == \
        ["rendezvous", "rendezvous", "clean"]
    assert sup.degraded
    assert sup.env["TPU_DIST_NUM_PROCESSES"] == "1"


def test_supervisor_single_rendezvous_failure_keeps_full_mesh(fake_child):
    # a transient outage (one rendezvous failure, then in) must NOT cost
    # a host: the first retry is full-size and undegraded
    sup, _ = fake_child(["rdzv", "clean"],
                        env={"TPU_DIST_NUM_PROCESSES": "2"})
    res = sup.run()
    assert res.ok
    assert [a.failure_class for a in res.attempts] == ["rendezvous", "clean"]
    assert not sup.degraded
    assert sup.env["TPU_DIST_NUM_PROCESSES"] == "2"


def test_supervisor_death_never_orphans_the_child(fake_child):
    # a dying supervisor (scheduler SIGTERM -> SystemExit, or any internal
    # error unwinding run()) must take the live child down with it — an
    # orphaned trainer would race its own requeue on the same ledger and
    # checkpoint dir
    sup, _ = fake_child(["hang"])
    pids = []
    real_popen = subprocess.Popen

    def spying_popen(*a, **kw):
        proc = real_popen(*a, **kw)
        pids.append(proc.pid)
        return proc

    calls = []

    def dying_sleep(s):
        if len(calls) >= 3:  # child is up and hanging; now "get killed"
            raise SystemExit(143)
        calls.append(s)
        time.sleep(s)

    sup._sleep = dying_sleep
    subprocess.Popen = spying_popen
    try:
        with pytest.raises(SystemExit):
            sup.run()
    finally:
        subprocess.Popen = real_popen
    assert pids
    # _run_child's finally terminated AND reaped the child synchronously
    # before the exception propagated — the pid must be gone already
    with pytest.raises(OSError):
        os.kill(pids[0], 0)


def test_supervise_cli_end_to_end(fake_child, tmp_path):
    # the actual CLI surface: python -m tpu_dist.supervise -- <cmd>
    _, ledger = fake_child(["clean"])
    child = str(tmp_path / "child.py")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_dist.supervise", "--ledger", ledger,
         "--no-forward-flags", "--backoff-s", "0.01", "--",
         sys.executable, child, "--ledger-base", ledger,
         "--behaviors", '["clean"]'],
        capture_output=True, text=True, cwd=ROOT, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "clean: 1 attempt(s) a0=clean" in proc.stderr


# ---------------------------------------------------------------------------
# in-process flavor: run_supervised (the engines' max_restarts opt-in)

@dataclasses.dataclass
class _Cfg:
    resume: str = ""
    checkpoint_dir: str = ""
    ledger_path: str = "run.jsonl"
    attempt: int = 0
    max_restarts: int = 2
    restart_backoff_s: float = 0.0
    crash_loop_k: int = 3


class _Trainer:
    """Scripted in-process trainer: outcomes[n] is attempt n's fate."""

    def __init__(self, outcomes):
        self.outcomes = list(outcomes)
        self.cfgs = []

    def __call__(self, cfg):  # the make_trainer factory
        self.cfgs.append(cfg)
        fate = self.outcomes[min(len(self.cfgs) - 1, len(self.outcomes) - 1)]
        steps = 0 if fate == "dead" else 5
        t = SimpleNamespace(obs=SimpleNamespace(steps=steps))
        if fate == "halt":
            def fit():
                raise HealthError("val_loss spike z=9.1")
        elif fate in ("crash", "dead"):
            def fit():
                raise ValueError("boom")
        else:
            def fit():
                return 42.0
        t.fit = fit
        return t


def test_run_supervised_halt_restarts_from_newest_checkpoint(tmp_path):
    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "lm-checkpoint.msgpack").write_bytes(b"x")
    (ck / "lm-checkpoint.index.json").write_text(
        json.dumps({"newest": "lm-checkpoint.msgpack"}))
    factory = _Trainer(["halt", "clean"])
    cfg = _Cfg(checkpoint_dir=str(ck))
    assert run_supervised(factory, cfg, sleep=lambda s: None) == 42.0
    assert len(factory.cfgs) == 2
    # attempt 0 keeps the caller's resume; the restart points at the
    # newest valid checkpoint with auto attempt lineage
    assert factory.cfgs[0].resume == ""
    assert factory.cfgs[1].resume == str(ck / "lm-checkpoint.msgpack")
    assert all(c.attempt == -1 for c in factory.cfgs)  # ledger_path set


def test_run_supervised_ctor_failure_is_a_policied_attempt():
    # an OOM/FS blip while REBUILDING the trainer is a classifiable
    # pre-first-step death (backoff + crash-loop counting), not an abort
    # of the whole supervised run
    calls = []

    def factory(run_cfg):
        calls.append(run_cfg)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED during init")
        t = SimpleNamespace(obs=SimpleNamespace(steps=5))
        t.fit = lambda: 7.0
        return t

    assert run_supervised(factory, _Cfg(), sleep=lambda s: None) == 7.0
    assert len(calls) == 2


def test_run_supervised_exhaustion_reraises():
    factory = _Trainer(["crash"])
    with pytest.raises(ValueError, match="boom"):
        run_supervised(factory, _Cfg(max_restarts=1), sleep=lambda s: None)
    assert len(factory.cfgs) == 2  # 1 restart = 2 attempts


def test_run_supervised_crash_loop_raises_diagnosis():
    factory = _Trainer(["dead"])
    with pytest.raises(CrashLoopError, match="first step"):
        run_supervised(factory, _Cfg(max_restarts=10, crash_loop_k=2),
                       sleep=lambda s: None)
    assert len(factory.cfgs) == 2  # cut off by K, not max_restarts


# ---------------------------------------------------------------------------
# checkpoint blast radius: keep-last-K retention, CRC fallback, ENOSPC

def _img_state():
    import jax

    from tpu_dist.engine.state import TrainState, init_model
    from tpu_dist.models import create_model
    from tpu_dist.ops import make_optimizer

    model = create_model("lenet")
    params, stats = init_model(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=10)
    return TrainState.create(params, stats, tx)


def test_keep_retention_and_pointer(tmp_path):
    from tpu_dist.engine import checkpoint as ckpt

    d = str(tmp_path)
    state = _img_state()
    for step in (10, 20, 30):
        ckpt.save_checkpoint(d, state.replace(step=step), epoch=step // 10,
                             best_acc1=0.0, arch="lenet", is_best=False,
                             keep=2)
    main = os.path.join(d, "lenet-checkpoint.msgpack")
    assert ckpt.retained_checkpoints(main) == [
        os.path.join(d, "lenet-checkpoint.r30.msgpack"),
        os.path.join(d, "lenet-checkpoint.r20.msgpack")]  # r10 pruned
    with open(os.path.join(d, "lenet-checkpoint.index.json")) as f:
        index = json.load(f)
    assert index["newest"] == "lenet-checkpoint.msgpack"
    assert index["step"] == 30
    assert latest_checkpoint(d) == main  # the supervisor's resume target


def test_corrupt_newest_falls_back_to_retained(tmp_path, capsys):
    # ISSUE 10 acceptance: truncating the newest checkpoint makes the next
    # resume fall back to the previous retained checkpoint, loudly
    from tpu_dist.engine import checkpoint as ckpt

    d = str(tmp_path)
    state = _img_state()
    for step in (10, 20):
        ckpt.save_checkpoint(d, state.replace(step=step), epoch=step // 10,
                             best_acc1=0.0, arch="lenet", is_best=False,
                             keep=2)
    main = os.path.join(d, "lenet-checkpoint.msgpack")
    with open(main, "r+b") as f:  # torn write: half the container
        f.truncate(os.path.getsize(main) // 2)
    restored, meta = ckpt.load_checkpoint(main, _img_state())
    # the r20 retained sibling is a hard link to the truncated newest, so
    # the first INTACT fallback is r10 — a few steps lost, run saved
    assert meta["step"] == 10
    err = capsys.readouterr().err
    assert "corrupt" in err and "RETAINED" in err
    assert int(restored.step) == 10


def test_corrupt_with_no_fallback_raises(tmp_path):
    from tpu_dist.engine import checkpoint as ckpt

    d = str(tmp_path)
    ckpt.save_checkpoint(d, _img_state(), epoch=1, best_acc1=0.0,
                         arch="lenet", is_best=False)  # keep=0: no siblings
    main = os.path.join(d, "lenet-checkpoint.msgpack")
    with open(main, "r+b") as f:
        f.truncate(os.path.getsize(main) - 7)
    with pytest.raises(ckpt.CheckpointCorruptError, match="no intact"):
        ckpt.load_checkpoint(main, _img_state())


def test_structure_mismatch_never_falls_back(tmp_path):
    # every retained sibling shares the structure — falling back would
    # silently resume an incompatible run; the error names the real cause
    from tpu_dist.engine import checkpoint as ckpt
    from tpu_dist.engine.state import TrainState
    from tpu_dist.ops import make_optimizer

    d = str(tmp_path)
    for step in (10, 20):
        ckpt.save_checkpoint(d, _img_state().replace(step=step),
                             epoch=step // 10, best_acc1=0.0, arch="lenet",
                             is_best=False, keep=2)
    import jax.numpy as jnp
    other = TrainState.create(
        {"w": jnp.zeros((3,))}, {},
        make_optimizer(0.1, 0.9, 0.0, steps_per_epoch=10))
    with pytest.raises(ValueError, match="structure"):
        ckpt.load_checkpoint(os.path.join(d, "lenet-checkpoint.msgpack"),
                             other)


def test_enospc_fault_leaves_previous_checkpoint_valid(tmp_path):
    # injected full disk on the SECOND write: the pointer and container on
    # disk stay the first, fully-committed state — exactly what the
    # supervisor's restart will resume from
    from tpu_dist.engine import checkpoint as ckpt

    d = str(tmp_path)
    state = _img_state()
    ckpt.save_checkpoint(d, state.replace(step=10), epoch=1, best_acc1=0.0,
                         arch="lenet", is_best=False, keep=2)
    faults.install("ckpt_enospc")
    with pytest.raises(OSError) as ei:
        ckpt.save_checkpoint(d, state.replace(step=20), epoch=2,
                             best_acc1=0.0, arch="lenet", is_best=False,
                             keep=2)
    import errno
    assert ei.value.errno == errno.ENOSPC
    faults._reset_for_tests()
    main = latest_checkpoint(d)
    restored, meta = ckpt.load_checkpoint(main, _img_state())
    assert meta["step"] == 10  # the ENOSPC'd write never advanced anything


def test_async_enospc_surfaces_on_wait(tmp_path):
    from tpu_dist.engine import checkpoint as ckpt

    d = str(tmp_path)
    faults.install("ckpt_enospc")
    ckpt.save_checkpoint(d, _img_state(), epoch=1, best_acc1=0.0,
                         arch="lenet", is_best=False, async_write=True)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        ckpt.wait_for_async_save(d)


def test_async_writers_are_per_dir(tmp_path):
    # the round-10 fix: two checkpoint dirs no longer share one writer
    # slot — dir B's wait neither joins nor steals dir A's error
    import threading

    from tpu_dist.engine import checkpoint as ckpt

    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    gate = threading.Event()
    state = _img_state()
    orig = ckpt._write

    def slow_write(ckpt_dir, *a, **kw):
        if os.path.abspath(ckpt_dir) == os.path.abspath(da):
            gate.wait(timeout=30)
        return orig(ckpt_dir, *a, **kw)

    ckpt._write, _saved = slow_write, orig
    try:
        ckpt.save_checkpoint(da, state, 1, 0.0, "lenet", False,
                             async_write=True)
        t0 = time.monotonic()
        ckpt.save_checkpoint(db, state, 1, 0.0, "lenet", False,
                             async_write=True)
        ckpt.wait_for_async_save(db)  # must NOT block on dir A's writer
        assert time.monotonic() - t0 < 5.0
        assert os.path.exists(os.path.join(db, "lenet-checkpoint.msgpack"))
    finally:
        gate.set()
        ckpt._write = _saved
        ckpt.wait_for_async_save()


# ---------------------------------------------------------------------------
# chaos acceptance smoke (ISSUE 10): a supervised LM run survives an
# injected hard kill mid-epoch — auto-restart via attempt lineage, resume
# from the last good checkpoint, clean finish, stitched-ledger evidence —
# with no manual intervention anywhere

def _script_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_DIST") and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


_LM_TINY = ["--epochs", "2", "--batch-size", "4", "--seq-len", "32",
            "--d-model", "32", "--num-layers", "1", "--num-heads", "2",
            "--vocab-size", "64", "--synth-tokens", "2000",
            "--print-freq", "1"]


@pytest.mark.slow  # tier-1 budget offset (round 13): same supervised-LM
# restart shape as the IN-budget round-13 acceptance
# (tests/test_elastic.py::test_preempt_deadline_snapshot_resumes_exact_step
# — two real attempts, checkpoint resume, stitched report), and the
# hard-kill class itself keeps its cheap in-budget twin
# (test_supervisor_restarts_after_fault_injected_exit)
def test_chaos_smoke_supervised_lm_survives_hard_kill(tmp_path):
    ledger = str(tmp_path / "run.jsonl")
    # 15 steps/epoch; the epoch-1 checkpoint exists when step 20 dies
    sup = Supervisor(
        [sys.executable, os.path.join(ROOT, "scripts", "8.lm_longcontext.py"),
         *_LM_TINY],
        ledger=ledger, ckpt_dir=str(tmp_path / "ck"),
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05,
                             stall_timeout_s=300.0),
        env=_script_env(TPU_DIST_FAULTS="hard_exit@step=20,attempt=0"),
        poll_s=0.1)
    res = sup.run()
    assert res.ok, [(a.failure_class, a.returncode) for a in res.attempts]
    assert [a.failure_class for a in res.attempts] == ["crash", "clean"]
    assert res.attempts[0].steps >= 15  # died mid-epoch 2, after the ckpt

    records = []
    for p in discover_attempt_paths(ledger):
        records += read_ledger(p, validate=False, strict=False)
    # the injection is on the record, distinguishable from organic failure
    fault_events = [r for r in records if r.get("event") == "fault"]
    assert [f["site"] for f in fault_events] == ["hard_exit"]
    # attempt lineage: two run_starts, the restart resumed from the newest
    # valid checkpoint the supervisor found via the pointer file
    starts = [r for r in records if r.get("event") == "run_start"]
    assert [s["attempt"] for s in starts] == [0, 1]
    assert starts[1]["config"]["resume"].endswith("lm-checkpoint.msgpack")
    # stitched goodput charges the crash->restart window as restart_gap
    acc = job_accounting(split_attempts(records))
    assert acc["categories"]["restart_gap"] > 0
    # and the final report classifies the failure, injected vs organic
    sys.path.insert(0, ROOT)
    from tools.ledger_report import restarts_section
    lines = []
    rep = restarts_section(records, out=lines.append)
    assert rep["attempts"][0]["class"] == "crash"
    assert rep["attempts"][0]["injected"] == ["hard_exit"]
    assert rep["attempts"][1]["class"] == "clean"
    assert rep["injected_faults"] == 1 and rep["organic_failures"] == 0
    assert not rep["crash_loop"]


@pytest.mark.slow  # tier-1 budget: full elastic-shrink variant; the cheap
# fake-child twin (test_supervisor_rendezvous_loss_shrinks_mesh) stays in
def test_elastic_shrink_after_rendezvous_loss_real_script(tmp_path):
    # a 2-process job whose coordinator never comes back: attempts 0+1
    # exhaust the rendezvous retries (injected), the supervisor re-forms
    # the mesh dp-only on the 1 survivor, and the degraded relaunch
    # completes a real single-process distributed init + training run
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ledger = str(tmp_path / "run.jsonl")
    sup = Supervisor(
        [sys.executable, os.path.join(ROOT, "scripts", "8.lm_longcontext.py"),
         "--epochs", "1", "--batch-size", "4", "--seq-len", "32",
         "--d-model", "32", "--num-layers", "1", "--num-heads", "2",
         "--vocab-size", "64", "--synth-tokens", "1000",
         "--print-freq", "1"],
        ledger=ledger, ckpt_dir=str(tmp_path / "ck"),
        policy=RestartPolicy(max_restarts=2, backoff_base_s=0.05,
                             stall_timeout_s=300.0),
        env=_script_env(
            TPU_DIST_COORDINATOR=f"127.0.0.1:{port}",
            TPU_DIST_NUM_PROCESSES="2", TPU_DIST_PROCESS_ID="0",
            TPU_DIST_RENDEZVOUS_RETRIES="2",
            TPU_DIST_RENDEZVOUS_BACKOFF_S="0.05",
            # attempts 0 AND 1 exhaust their retries (host loss needs two
            # consecutive rendezvous failures before the mesh shrinks);
            # attempt 2 runs fault-free on the 1 survivor
            TPU_DIST_FAULTS="rendezvous_fail@attempt=0,times=2;"
                            "rendezvous_fail@attempt=1,times=2"),
        poll_s=0.1)
    res = sup.run()
    assert res.ok, [(a.failure_class, a.returncode) for a in res.attempts]
    assert [a.failure_class for a in res.attempts] == \
        ["rendezvous", "rendezvous", "clean"]
    assert sup.degraded
    assert sup.env["TPU_DIST_NUM_PROCESSES"] == "1"
    # the degraded attempt ran with the mesh reset to dp-only over the
    # survivors (mesh_shape cleared by the relaunch flags)
    recs = read_ledger(res.attempts[1].ledger, validate=False, strict=False)
    start = next(r for r in recs if r.get("event") == "run_start")
    assert start["config"]["mesh_shape"] is None
    assert list(start["config"]["mesh_axes"]) == ["data"]
