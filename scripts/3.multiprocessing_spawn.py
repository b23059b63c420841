#!/usr/bin/env python
"""Variant 3 — in-process spawn of the worker pool (mp.spawn equivalent).

Reference: 3.multiprocessing_distributed.py — `mp.spawn(main_worker,
nprocs=device_count)` forks one child per GPU, tcp://127.0.0.1:23456
rendezvous (reference 3.multiprocessing_distributed.py:84,102).

TPU-native: a single process already drives all local chips, so a local spawn
is unnecessary for TPU (SURVEY.md §2b process-manager row) — but the
capability is preserved for parity as the CPU simulation of a multi-host run:
with TPU_DIST_NPROCS_SPAWN=N (and JAX_PLATFORMS=cpu) this script forks N
children that rendezvous over loopback TCP via jax.distributed (the tcp://
analog). Unset (the TPU default) it trains directly. Nothing here gives a
child its own chip, so the spawn refuses any platform but cpu: on a TPU host
the first child would hold the chip and the other N-1 would hang on it.
"""

import os
import subprocess
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from tpu_dist.configs import TrainConfig, parse_config
from tpu_dist.engine import Trainer
from tpu_dist.parallel import launch

DEFAULTS = TrainConfig(arch="resnet18", epochs=2, batch_size=3200,
                       dataset="cifar10", variant="jit")
RDZV = "127.0.0.1:23456"  # reference 3.multiprocessing_distributed.py:102


def spawn(nprocs: int, argv):
    """mp.spawn equivalent: fork workers with injected rendezvous env."""
    procs = []
    for rank in range(nprocs):
        env = dict(os.environ,
                   TPU_DIST_COORDINATOR=RDZV,
                   TPU_DIST_NUM_PROCESSES=str(nprocs),
                   TPU_DIST_PROCESS_ID=str(rank))
        procs.append(subprocess.Popen([sys.executable, __file__, *argv], env=env))
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"worker exit codes {rc}")


if __name__ == "__main__":
    nprocs = int(os.environ.pop("TPU_DIST_NPROCS_SPAWN", "0"))
    if nprocs > 1 and "TPU_DIST_PROCESS_ID" not in os.environ:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SystemExit(
                f"TPU_DIST_NPROCS_SPAWN={nprocs} is the CPU simulation of "
                "several hosts: run it with JAX_PLATFORMS=cpu. On a TPU "
                "host one process drives every local chip; N children "
                "would all wait for the chip the first one holds.")
        spawn(nprocs, sys.argv[1:])
        sys.exit(0)
    cfg = parse_config(defaults=DEFAULTS, description=__doc__)
    info = launch.initialize()
    print(f"[proc {info.process_id}/{info.num_processes}] rendezvous={info.method}")
    best = Trainer(cfg).fit()
    print(f"best_acc1 {best * 100:.3f}")
