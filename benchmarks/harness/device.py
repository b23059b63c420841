"""The chip under the benchmark: the device check, the table of peaks, the
compile meter and the memory reading.

The peaks are the benchmark's own copy (the program keeps its tables in
``tpu_dist/utils/mfu.py`` and ``tpu_dist/obs/attr.py``; a later PR may change
those, not this yardstick). ``require_tpu`` and ``CompileMeter`` are copied
from ``chip_smoke.py`` (PR 21).
"""

from __future__ import annotations

import sys
from typing import Sequence

#: published peaks of ONE chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 10**9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unlisted kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmarks/harness/device.py (known: {sorted(PEAKS)})") from None


def require_tpu(chips: int) -> Sequence:
    """The first ``chips`` devices, or exit non-zero with no result line
    when JAX reports another platform than ``tpu`` or fewer devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} tpu device(s), JAX reports "
              f"{len(devices)} x {devices[0].platform!r}: refusing to "
              "measure anything else", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


class CompileMeter:
    """Backend compilations and their seconds, from JAX's own monitoring
    events. A persistent-cache hit still counts as one compilation (its
    seconds are the retrieval) and is counted as a hit beside."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"count": self.count, "seconds": self.seconds,
                "cache_hits": self.cache_hits}


def memory_fields(devices: Sequence, program_temp_bytes: int = 0) -> dict:
    """Device memory on the fullest of ``devices``, each reading under a
    name of its own: ``memory_allocator_peak_bytes`` is the allocator's
    ``peak_bytes_in_use`` (measured, but it leaves out the temporaries XLA
    plans inside an executable on this runtime: a ResNet window read
    0.89 GB where its ``memory_analysis()`` plans 1.33 GB, PERF.md section
    6), ``memory_live_bytes`` what is allocated as the window closes,
    ``memory_planned_temp_bytes`` the timed program's planned temporaries
    (computed by the compiler, not measured). ``memory_peak_bytes`` is the
    larger of the allocator's peak and live + planned. All 0 where the
    backend has no counters (the CPU)."""
    out = {"memory_peak_bytes": 0, "memory_allocator_peak_bytes": 0,
           "memory_live_bytes": 0,
           "memory_planned_temp_bytes": int(program_temp_bytes)}
    for d in devices:
        stats = d.memory_stats() or {}
        live = int(stats.get("bytes_in_use", 0))
        counted = int(stats.get("peak_bytes_in_use", 0))
        peak = max(counted, live + int(program_temp_bytes) if live else 0)
        if peak >= out["memory_peak_bytes"]:
            out.update(memory_peak_bytes=peak, memory_live_bytes=live,
                       memory_allocator_peak_bytes=counted)
    return out
