"""Device-side telemetry (reference statistics.sh:1-4, the nvidia-smi analog).

The reference samples GPU memory + utilization to CSV every 500 ms with
nvidia-smi from a *separate process*. TPU device memory is only visible to
the owning process (the XLA client), so the analog is in-process: a daemon
thread samples ``device.memory_stats()`` — the runtime's live HBM counters
(bytes_in_use / peak_bytes_in_use / bytes_limit) — at the same cadence,
alongside host RSS. ``scripts/statistics.sh`` keeps the out-of-process host
view; engines start this sampler when ``--telemetry-csv`` is set.

CPU/virtual backends return no memory_stats; columns are left empty there so
the same CSV schema works in tests.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

import jax

CSV_HEADER = "ts,hbm_bytes_in_use,hbm_peak_bytes,hbm_bytes_limit,host_rss_kb"


def device_memory_stats(device: Optional[jax.Device] = None) -> dict:
    """memory_stats() of the first addressable device; {} when the backend
    does not expose counters (CPU, some virtual platforms)."""
    dev = device or jax.local_devices()[0]
    try:
        stats = dev.memory_stats()
    except Exception:
        return {}
    return stats or {}


def peak_hbm_bytes(device: Optional[jax.Device] = None) -> Optional[int]:
    """High-water HBM mark since process start (the per-epoch CSV column).

    This is the allocator's own peak counter — it covers every compiled
    program and live buffer, which is what an OOM postmortem needs; the
    per-program view lives in compiled.memory_analysis() (tests/test_pp.py
    uses it to pin 1F1B's O(S) activation flatness).
    """
    return device_memory_stats(device).get("peak_bytes_in_use")


def program_hbm_bytes(jitted_fn, *args) -> Optional[int]:
    """Static peak-HBM estimate of ONE compiled program from XLA's own
    buffer assignment (compiled.memory_analysis()): arguments + outputs +
    temps - donated aliases. Works on every backend — the CPU's
    memory_stats() returns None — because it reads the executable, not
    allocator counters.

    CALL ORDER CONTRACT: probe AFTER the function's first real dispatch.
    The AOT ``lower().compile()`` here does not seed jit's dispatch cache,
    so probing first compiles the program twice (the round-5 advisor's
    double-compile finding); probed second, the lowering hits the trace/
    compilation cache and the probe is cheap. The engines enforce this by
    statement ORDER — the probe sits directly below the dispatch call in
    the same loop iteration (gated on ``_program_hbm is None`` so it runs
    once) — which also keeps the column on single-dispatch runs."""
    return program_stats(jitted_fn, *args)["hbm_bytes"]


def program_stats(jitted_fn, *args, with_hlo: bool = False) -> dict:
    """{'hbm_bytes', 'flops'[, 'hlo']} of ONE compiled program in ONE AOT
    lower+compile (both the buffer assignment and the cost model read the
    same executable, so probing them together halves the — cached, but not
    free — lowering work). Same post-dispatch call-order contract as
    :func:`program_hbm_bytes`. Either value is None when the backend does
    not expose it. A program that fails to lower or compile gives Nones
    off-TPU and RAISES on platform ``tpu``: a step the chip's compiler
    refuses must not leave MFU and HBM silently null. On a multi-step (lax.scan) window program the cost
    model counts the scan body ONCE, so ``flops`` approximates one
    optimizer step's FLOPs there, not the window's.

    ``with_hlo=True`` additionally returns the OPTIMIZED (post-fusion) HLO
    text of the same executable under ``'hlo'`` — the input to
    :func:`tpu_dist.obs.attr.cost_buckets` — so cost attribution reuses
    this probe's lower+compile instead of paying its own. Off by default:
    the text can run to megabytes on real step programs."""
    out = {"hbm_bytes": None, "flops": None}
    if with_hlo:
        out["hlo"] = None
    try:
        compiled = jitted_fn.lower(*args).compile()
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        return out
    if with_hlo:
        try:
            out["hlo"] = compiled.as_text()
        except Exception:
            pass
    try:
        ma = compiled.memory_analysis()
        out["hbm_bytes"] = int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    try:
        flops = float(compiled.cost_analysis()["flops"])
        out["flops"] = flops if flops > 0 else None
    except Exception:
        pass
    return out


def _host_rss_kb() -> Optional[int]:
    try:
        with open(f"/proc/{os.getpid()}/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def start_hbm_sampler(path: str, interval_s: float = 0.5,
                      ledger=None) -> Callable[[], None]:
    """Write `CSV_HEADER` rows to ``path`` every ``interval_s`` until the
    returned stop() is called. Daemon thread: it never blocks exit.

    The returned stop() is idempotent and crash-safe: the file handle is
    flushed+closed in the sampler thread's ``finally`` (so a sampler
    exception still closes it exactly once), and repeated stop() calls are
    no-ops after the first. When a run :class:`~tpu_dist.obs.ledger.Ledger`
    is passed, each sample also lands there as an ``hbm`` event, so the
    JSONL record carries the memory timeline alongside the step records.
    """
    f = open(path, "w", buffering=1)
    f.write(CSV_HEADER + "\n")
    stop = threading.Event()

    def run():
        try:
            dev = jax.local_devices()[0]
            while not stop.is_set():
                s = device_memory_stats(dev)
                rss = _host_rss_kb()
                row = (time.time(), s.get("bytes_in_use", ""),
                       s.get("peak_bytes_in_use", ""),
                       s.get("bytes_limit", ""), rss or "")
                f.write(",".join(str(x) for x in row) + "\n")
                if ledger is not None:
                    ledger.emit("hbm",
                                bytes_in_use=s.get("bytes_in_use"),
                                peak_bytes=s.get("peak_bytes_in_use"),
                                bytes_limit=s.get("bytes_limit"),
                                host_rss_kb=rss)
                stop.wait(interval_s)
        finally:
            # the ONLY close site: a second stop() or a sampler crash can
            # neither double-close nor leave the handle open
            if not f.closed:
                f.flush()
                f.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()

    def stop_fn():
        if stop.is_set():  # idempotent: later calls are no-ops
            return
        stop.set()
        t.join(timeout=5)

    return stop_fn
