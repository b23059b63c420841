"""Test env: 8 virtual CPU devices in one process.

SURVEY.md §4: the reference has no tests; our distributed logic is exercised
without a pod via XLA host-platform virtual devices — the clean analog of
"multi-node without a real cluster".
MUST be set before jax initializes, hence conftest import time.
"""

import os

# Tests run on 8 virtual CPU devices, pinned through jax.config (no backend
# has been initialized yet) so the suite does not depend on the caller's
# JAX_PLATFORMS. They neither read nor write a persistent compilation
# cache: entry points switch one on (tpu_dist.runtime), and a test run
# must not depend on what an earlier run left on disk. Set before jax is
# imported, and inherited by every subprocess a test starts.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Most of a CPU test's wall time is XLA:CPU optimizing a tiny program that
# then runs for milliseconds. The suite checks this repo's logic, not XLA's
# optimizer (the chip's compiler is exercised by tests/test_chip_compile.py
# and chip_smoke.py), so ask for a cheap compile — the serial tier-1 run
# does not fit its 870 s limit otherwise (PR 21: 1049 s without, on the
# sandbox). A caller's explicit setting wins.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from tpu_dist._compat import set_cpu_device_count  # noqa: E402

set_cpu_device_count(8)


# ---- tier-1 budget self-observability -----------------------------------
# The suite runs 620-870s against an 870s timeout (±30% machine variance,
# ROADMAP budget guardrail); budget creep was being rediscovered by
# timeout instead of tracked. Every run writes its wall time and the
# top-20 test durations to TPU_DIST_TIER1_DURATIONS (default
# /tmp/tier1_durations.json) and prints one summary line, so a creeping
# test is visible in the run that introduced it. Hooks are best-effort:
# budget telemetry must never fail the suite.

import time as _time

_suite_t0 = _time.time()
_durations = []  # (seconds, nodeid) across setup+call+teardown


def pytest_runtest_logreport(report):
    try:
        if report.duration:
            _durations.append((float(report.duration), report.nodeid))
    except Exception:
        pass


def _is_full_suite(config) -> bool:
    """Only the tier-1-shaped run may overwrite the budget artifact: a
    `pytest tests/test_x.py -k one` or `-m slow` run would otherwise
    clobber the full-suite record the hook exists to track. The tier-1
    marker filter `-m 'not slow'` (and no filter at all) still counts."""
    if getattr(config.option, "keyword", ""):
        return False
    if getattr(config.option, "markexpr", "") not in ("", "not slow"):
        return False
    for a in config.invocation_params.args:
        a = str(a)
        if a.endswith(".py") or "::" in a:
            return False
    return True


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    try:
        import json

        wall = _time.time() - _suite_t0
        # sum setup/call/teardown phases per test, rank by total
        per_test = {}
        for secs, nodeid in _durations:
            per_test[nodeid] = per_test.get(nodeid, 0.0) + secs
        top = sorted(per_test.items(), key=lambda kv: -kv[1])[:20]
        path = os.environ.get("TPU_DIST_TIER1_DURATIONS",
                              "/tmp/tier1_durations.json")
        wrote = ""
        if _is_full_suite(config):
            with open(path, "w") as f:
                json.dump({"wall_s": round(wall, 1),
                           "tests": len(per_test),
                           "exitstatus": int(exitstatus),
                           "top": [{"nodeid": n, "s": round(s, 2)}
                                   for n, s in top]}, f, indent=1)
            wrote = f"; top-20 -> {path}"
        slowest = (f"; slowest {top[0][1]:.1f}s {top[0][0]}"
                   if top else "")
        terminalreporter.write_line(
            f"tier1-budget: {wall:.1f}s wall, {len(per_test)} tests"
            f"{slowest}{wrote}")
    except Exception:
        pass
