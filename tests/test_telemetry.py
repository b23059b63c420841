"""Device telemetry utilities (reference statistics.sh analog, C22)."""

import csv
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpu_dist.utils.telemetry import (CSV_HEADER, device_memory_stats,
                                      peak_hbm_bytes, program_hbm_bytes,
                                      start_hbm_sampler)


def test_device_memory_stats_never_raises():
    """CPU/virtual backends expose no counters; the API degrades to {}."""
    s = device_memory_stats()
    assert isinstance(s, dict)
    assert peak_hbm_bytes() is None or peak_hbm_bytes() > 0


def test_program_hbm_bytes_from_compiled_program():
    """XLA's static memory analysis works on EVERY backend (the CPU
    returns no allocator counters), so the epoch-CSV peak column is never
    empty on a jitted engine step."""
    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    n = program_hbm_bytes(f, x)
    assert n is not None and n >= x.size * 4  # at least the argument bytes


def test_program_hbm_bytes_returns_none_on_non_jitted():
    assert program_hbm_bytes(lambda x: x, jnp.ones(())) is None


def test_hbm_sampler_writes_schema_and_rows(tmp_path):
    path = os.path.join(str(tmp_path), "tele.csv")
    stop = start_hbm_sampler(path, interval_s=0.05)
    time.sleep(0.3)
    stop()
    with open(path) as f:
        rows = list(csv.reader(f))
    assert rows[0] == CSV_HEADER.split(",")
    assert len(rows) >= 3          # several 50ms samples in 300ms
    assert float(rows[1][0]) > 0   # ts column
    import sys as _sys
    if _sys.platform == "linux":   # /proc-backed; empty elsewhere by design
        assert rows[1][4] != ""    # host RSS
    # stop() is idempotent-safe to the file: no rows after close
    n = len(rows)
    time.sleep(0.1)
    with open(path) as f:
        assert len(list(csv.reader(f))) == n
