"""Process launch / rendezvous layer (reference C23/C25 + the four rendezvous
flavors of SURVEY.md §5).

The reference rendezvouses four ways — env:// from torch.distributed.launch
(2.distributed.py:98), tcp:// (3.multiprocessing_distributed.py:102), file://
on a shared FS keyed by SLURM_JOBID (6.distributed_slurm_main.py:93-101), and
an MPI/Gloo controller under horovodrun (5.run.sh:3). On TPU these collapse to
one thing: coordinator-address discovery for ``jax.distributed.initialize``
over DCN. This module abstracts that discovery, in priority order:

1. explicit args / tpu_dist env (TPU_DIST_COORDINATOR, TPU_DIST_NUM_PROCESSES,
   TPU_DIST_PROCESS_ID)  — env:// equivalent;
2. Slurm env (SLURM_PROCID/SLURM_NPROCS/SLURM_JOB_NODELIST) — variant-6
   equivalent, same rank math;
3. TPU pod metadata — ``jax.distributed.initialize()`` with no args
   autodetects on Cloud TPU;
4. nothing set -> single-process (variants 1-style local run).
"""

from __future__ import annotations

import os
import re
import sys
import time
from dataclasses import dataclass
from typing import Callable, Optional

from tpu_dist.obs import faults as _faults


@dataclass
class LaunchInfo:
    coordinator: Optional[str]
    num_processes: int
    process_id: int
    method: str  # env | slurm | tpu-metadata | local


def _slurm_first_host(nodelist: str) -> str:
    """Expand 'prefix[a-b,c],other' to its first hostname (no external tools)."""
    m = re.match(r"([^\[,]+)(\[([^\]]+)\])?", nodelist)
    if not m:
        return nodelist.split(",")[0]
    prefix, _, body = m.groups()
    if not body:
        return prefix
    first = body.split(",")[0].split("-")[0]
    return prefix + first


def epoch_coordinator(coordinator: str, epoch: int) -> str:
    """Offset the coordinator port by the consensus mesh epoch
    (``TPU_DIST_MESH_EPOCH``, parallel.consensus): every re-formed mesh
    rendezvouses on a FRESH port, so a shrink/re-expansion relaunch never
    reconnects to the previous epoch's half-dead coordination service —
    the stale-coordinator hang the PR-10 rendezvous retries could only
    time out of, not avoid. Pure; unparseable inputs pass through."""
    if not coordinator or epoch <= 0 or ":" not in coordinator:
        return coordinator
    host, _, port = coordinator.rpartition(":")
    try:
        return f"{host}:{int(port) + epoch}"
    except ValueError:
        return coordinator


def detect_launch(coordinator: Optional[str] = None,
                  num_processes: Optional[int] = None,
                  process_id: Optional[int] = None,
                  port: int = 8476) -> LaunchInfo:
    env = os.environ
    if coordinator or env.get("TPU_DIST_COORDINATOR"):
        try:
            epoch = int(env.get("TPU_DIST_MESH_EPOCH", "0") or 0)
        except ValueError:
            epoch = 0
        return LaunchInfo(
            epoch_coordinator(coordinator or env["TPU_DIST_COORDINATOR"],
                              epoch),
            int(num_processes if num_processes is not None
                else env.get("TPU_DIST_NUM_PROCESSES", "1")),
            int(process_id if process_id is not None
                else env.get("TPU_DIST_PROCESS_ID", "0")),
            "env")
    if "SLURM_PROCID" in env and env.get("SLURM_NPROCS", "1") != "1":
        # reference 6.distributed_slurm_main.py:89-94: rank from SLURM_PROCID,
        # world from SLURM_NPROCS; file:// rendezvous becomes coordinator TCP.
        # The tpu_dist consensus overrides (dense renumbering + epoch) must
        # win over the static Slurm env: a supervisor relaunch after host
        # loss exports shrunken TPU_DIST_* values while SLURM_* still
        # describes the original allocation.
        host = _slurm_first_host(env.get("SLURM_JOB_NODELIST", "localhost"))
        try:
            epoch = int(env.get("TPU_DIST_MESH_EPOCH", "0") or 0)
        except ValueError:
            epoch = 0
        return LaunchInfo(
            epoch_coordinator(f"{host}:{port}", epoch),
            int(num_processes if num_processes is not None
                else env.get("TPU_DIST_NUM_PROCESSES")
                or env["SLURM_NPROCS"]),
            int(process_id if process_id is not None
                else env.get("TPU_DIST_PROCESS_ID") or env["SLURM_PROCID"]),
            "slurm")
    workers = [h for h in env.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    if len(workers) > 1 or env.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return LaunchInfo(None, -1, -1, "tpu-metadata")
    return LaunchInfo(None, 1, 0, "local")


def rendezvous_with_retry(init_fn: Callable[[], None], info: LaunchInfo,
                          retries: Optional[int] = None,
                          timeout_s: Optional[float] = None,
                          backoff_s: Optional[float] = None,
                          sleep: Callable[[float], None] = time.sleep) -> int:
    """Bounded retry + exponential backoff around one rendezvous call.

    A flaky coordinator (still booting, preempted mid-restart, transient
    DNS) used to surface as a raw grpc stack from deep inside
    ``jax.distributed.initialize``; a supervised restart needs the
    rendezvous to *ride out* the window where peers come back up. Retries
    ``init_fn`` up to ``TPU_DIST_RENDEZVOUS_RETRIES`` times (default 5)
    with ``TPU_DIST_RENDEZVOUS_BACKOFF_S``-based exponential backoff
    (default 2s, doubling, capped at 30s) under a
    ``TPU_DIST_RENDEZVOUS_TIMEOUT_S`` TOTAL deadline (default 300s).
    Returns the number of attempts used; on exhaustion raises ONE clear
    error naming the coordinator, method, and attempt count. The
    ``rendezvous_fail`` fault site (obs.faults) injects the failure
    deterministically — ``times=K`` fails the first K attempts."""
    env = os.environ
    retries = int(env.get("TPU_DIST_RENDEZVOUS_RETRIES", "5")
                  if retries is None else retries)
    timeout_s = float(env.get("TPU_DIST_RENDEZVOUS_TIMEOUT_S", "300")
                      if timeout_s is None else timeout_s)
    backoff_s = float(env.get("TPU_DIST_RENDEZVOUS_BACKOFF_S", "2")
                      if backoff_s is None else backoff_s)
    t0 = time.monotonic()
    last: Optional[BaseException] = None
    for attempt in range(1, max(retries, 1) + 1):
        try:
            if _faults.fire("rendezvous_fail", attempt_no=attempt):
                raise ConnectionError("injected rendezvous failure")
            init_fn()
            return attempt
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # grpc failures arrive as assorted types
            last = e
            elapsed = time.monotonic() - t0
            wait = min(backoff_s * (2 ** (attempt - 1)), 30.0)
            if attempt >= retries or elapsed + wait >= timeout_s:
                break
            print(f"rendezvous attempt {attempt}/{retries} with "
                  f"{info.coordinator} failed ({e}); retrying in "
                  f"{wait:.1f}s", file=sys.stderr, flush=True)
            sleep(wait)
    raise RuntimeError(
        f"rendezvous failed: could not reach coordinator "
        f"{info.coordinator!r} ({info.method} method, process "
        f"{info.process_id}/{info.num_processes}) after {attempt} "
        f"attempt(s) over {time.monotonic() - t0:.1f}s "
        f"(TPU_DIST_RENDEZVOUS_RETRIES={retries}, "
        f"TPU_DIST_RENDEZVOUS_TIMEOUT_S={timeout_s:g}). "
        f"Last error: {last}") from last


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> LaunchInfo:
    """Multi-host init (idempotent). The hvd.init()/init_process_group
    analog; every script calls it first, so it is also where the
    persistent compilation cache is switched on (tpu_dist.runtime)."""
    import jax

    from tpu_dist.runtime import enable_compile_cache
    enable_compile_cache()
    info = detect_launch(coordinator, num_processes, process_id)
    if info.method == "local":
        return info
    if info.method == "tpu-metadata":
        # multi-host metadata is present, so a failed autodetect is an
        # error to surface, not a reason to train alone
        jax.distributed.initialize()
        return LaunchInfo(None, jax.process_count(), jax.process_index(),
                          "tpu-metadata")
    rendezvous_with_retry(
        lambda: jax.distributed.initialize(
            coordinator_address=info.coordinator,
            num_processes=info.num_processes,
            process_id=info.process_id),
        info)
    return info
