#!/usr/bin/env python
"""Variant 7 — the flagship TPU-native path.

The "sixth backend" the reference never had: ResNet-50 / CIFAR-10 on a TPU
pod. jit+mesh data parallelism, bf16 compute with fp32 master weights and BN
stats, on-device normalize fused into the step, double-buffered host->HBM
prefetch, exact psum'd distributed eval, process-0 checkpointing with real
resume. Single chip to multi-host pod with the same script: processes join
via tpu_dist.parallel.launch (TPU metadata / TPU_DIST_* / Slurm env).
"""

import argparse
import dataclasses
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from tpu_dist.configs import TrainConfig, add_args
from tpu_dist.engine import Trainer
from tpu_dist.parallel import launch

DEFAULTS = TrainConfig(arch="resnet50", epochs=10, batch_size=1024,
                       dataset="cifar10", variant="jit", precision="bf16",
                       steps_per_dispatch=16, log_csv="jax_tpu.csv")

if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    add_args(parser, DEFAULTS)
    # sentinel default: 'not passed' is distinguishable from an explicit 16,
    # so the jit-only 16-step default downgrades for shard_map but any
    # EXPLICIT value (prefix abbreviations included — argparse resolves
    # them) reaches Trainer's validation and errors clearly
    parser.set_defaults(steps_per_dispatch=None)
    ns = parser.parse_args()
    if ns.steps_per_dispatch is None:
        ns.steps_per_dispatch = (DEFAULTS.steps_per_dispatch
                                 if ns.variant == "jit" else 1)
    cfg = TrainConfig(**{f.name: getattr(ns, f.name)
                         for f in dataclasses.fields(TrainConfig)})
    info = launch.initialize()
    print(f"[proc {info.process_id}/{info.num_processes}] via {info.method}")
    if cfg.max_restarts > 0:
        # in-process self-healing (parallel.supervisor): HealthError halts
        # and organic crashes rebuild the trainer with attempt lineage and
        # resume from the newest valid checkpoint. Process-killing faults
        # need the subprocess flavor: python -m tpu_dist.supervise -- ...
        from tpu_dist.parallel.supervisor import run_supervised
        best = run_supervised(Trainer, cfg)
    else:
        best = Trainer(cfg).fit()
    print(f"best_acc1 {best * 100:.3f}")
