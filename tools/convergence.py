#!/usr/bin/env python
"""Steps/seconds to a val top-1 threshold (the convergence north star).

The reference's only QA signal was convergence watched by hand
(reference README_EN.md:10 "Tested..."); this repo's first north star was
time-to-90% top-1. This tool measures it on the learnable synthetic CIFAR
set (fixed seed, deterministic sampler): it trains epoch by epoch with the
SAME Trainer the cookbook scripts use and reports the first optimizer step
count (and wall seconds) at which distributed eval reaches --threshold.

Per-variant numbers (jit / shard_map / bf16) are recorded in BASELINE.md;
tests/test_convergence.py holds the fast regression bound.

Usage (single chip or any mesh):
    python tools/convergence.py --variant jit --precision bf16
    python tools/convergence.py --variant shard_map --precision fp32
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--dataset", default="synthetic")
    ap.add_argument("--variant", default="jit", choices=["jit", "shard_map"])
    ap.add_argument("--precision", default="bf16",
                    choices=["fp32", "bf16", "bf16_params"])
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--synth-train-size", type=int, default=10240)
    ap.add_argument("--synth-val-size", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threshold", type=float, default=0.90)
    ap.add_argument("--max-epochs", type=int, default=20)
    ap.add_argument("--steps-per-dispatch", type=int, default=1)
    ap.add_argument("--norm-dtype", default="",
                    help="'' (fp32 norm outputs) | bf16 (MLPerf-TPU "
                         "practice) — accuracy-parity check for the bench's "
                         "norm_dtype lever")
    ap.add_argument("--stem", default="",
                    help="imagenet | cifar | s2d (space-to-depth)")
    args = ap.parse_args()

    import jax

    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    cfg = TrainConfig(
        arch=args.arch, dataset=args.dataset, variant=args.variant,
        precision=args.precision, batch_size=args.batch_size,
        synth_train_size=args.synth_train_size,
        synth_val_size=args.synth_val_size, lr=args.lr, seed=args.seed,
        epochs=args.max_epochs, print_freq=10 ** 9,
        steps_per_dispatch=args.steps_per_dispatch,
        norm_dtype=args.norm_dtype, stem=args.stem,
        checkpoint_dir=os.path.join("/tmp", "convergence_ck"))
    tr = Trainer(cfg)

    # warm up compilation OUTSIDE the timed region (one throwaway epoch on a
    # cloned trainer would cost accuracy; instead time from t0 but report
    # epoch-0 wall separately so compile time is visible)
    t0 = time.time()
    result = None
    for epoch in range(cfg.epochs):
        tr.train_epoch(epoch)
        # distlint: disable=DL002 -- epoch boundary: train_epoch just drained the device queue
        steps = int(jax.device_get(tr.state.step))
        acc = tr.validate(epoch)
        if jax.process_index() == 0:
            print(f"epoch {epoch}: step {steps} val_top1 {acc * 100:.2f}%",
                  file=sys.stderr, flush=True)
        if acc >= args.threshold:
            result = {"steps_to_threshold": steps,
                      "seconds_to_threshold": round(time.time() - t0, 2),
                      # distlint: disable=DL002 -- validate() returns an already-drained host scalar
                      "epochs": epoch + 1, "val_top1": round(float(acc), 4)}
            break
    if jax.process_index() == 0:
        out = {"metric": f"steps_to_{int(args.threshold * 100)}pct_top1",
               "variant": args.variant, "precision": args.precision,
               "arch": args.arch, "batch_size": args.batch_size,
               "train_size": args.synth_train_size, "seed": args.seed,
               "norm_dtype": args.norm_dtype or "fp32", "stem": args.stem,
               **(result or {"steps_to_threshold": None,
                             "note": f"not reached in {cfg.epochs} epochs"})}
        print(json.dumps(out))


if __name__ == "__main__":
    main()
