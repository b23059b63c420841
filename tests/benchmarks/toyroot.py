"""A checkout in ``tmp_path`` that holds the benchmark plus a toy
configuration, traffic mix, cell and per-layer metric of every family: each
added as NEW files and APPENDED entries of ``BENCHMARK.json``, with no edit
to a file that is there (the benchmark's promise to later PRs)."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_LM = dict(family="lm", num_layers=2, d_model=64, num_heads=4,
              head_dim=16, mlp_dim=256, max_positions=64, vocab_size=512)
TOY_RESNET = dict(family="resnet", arch="resnet50", stage_sizes=[3, 4, 6, 3],
                  base_width=64, expansion=4, num_classes=10, image_size=32,
                  image_channels=3)
# fp32 at toy size: the program agrees with the reference to ~1e-6, the
# int8 control is off by percents
TRAIN_LIMITS = dict(loss_rel_gap=1e-4, grad_norm_gap=1e-3,
                    grad_sample_rel_err=1e-3,
                    update_norm_gap=1e-3, window_loss_ratio=1000.0,
                    window_program_loss_gap=1e-4,
                    window_program_update_norm_gap=1e-3,
                    window_program_update_sample_rel_err=1e-3)

TOY_METRIC = '''"""A toy per-layer metric: whole train_epoch calls counted."""


def read(obs):
    recs = obs.get("step_records")
    return len(recs) if recs else None
'''


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def make(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "tpu_dist"), os.path.join(root, "tpu_dist"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(root, "benchmarks")
    _dump(f"{b}/configs/toy-lm.json", TOY_LM)
    _dump(f"{b}/configs/toy-resnet.json", TOY_RESNET)
    _dump(f"{b}/traffic/toy-epochs.json",
          dict(kind="epochs", dataset="synthetic-cifar10", train_images=8))
    _dump(f"{b}/traffic/toy-lm-epochs.json",
          dict(kind="epochs", steps_per_epoch=4))
    _dump(f"{b}/traffic/toy-chat.json", dict(
        kind="open_loop", rate_per_s=8.0, preroll_s=0.5,
        prompt=dict(median=16, sigma=0.5, min=4, max=40),
        answer=dict(median=12, sigma=0.4, min=6, max=24)))
    _dump(f"{b}/workloads/toy-resnet.train.json", dict(
        family="image_trainer", trace_seconds=2,
        engine=dict(batch_size=4, precision="fp32", steps_per_dispatch=2,
                    optimizer="sgd", lr=0.01, momentum=0.9,
                    weight_decay=1e-4),
        control=dict(reference_quant="int8",
                     window_faults=["one_step_dropped", "half_batch"]),
        # BatchNorm over a batch of 4 amplifies float32 rounding in the
        # second step; a step that returns its state reads 1.0
        check=dict(steps=2, limits=dict(TRAIN_LIMITS, update_norm_gap=5e-2))))
    _dump(f"{b}/workloads/toy-lm.train.json", dict(
        family="lm_trainer", trace_seconds=2,
        engine=dict(batch_size=2, seq_len=64, precision="fp32", attn="flash",
                    attn_block=64, optimizer="adamw", lr=2e-4,
                    weight_decay=0.1, steps_per_dispatch=1),
        control=dict(engine=dict(quant="int8")),
        check=dict(steps=3, limits=TRAIN_LIMITS)))
    _dump(f"{b}/workloads/toy-lm.serve.json", dict(
        family="lm_server", trace_seconds=2,
        engine=dict(precision="fp32", attn="full", attn_block=64),
        serve=dict(max_slots=4, page_size=8, num_pages=64, max_len=64),
        control=dict(serve=[dict(quant="int8_wo")]),
        check=dict(sample_requests=12, limits=dict(
            served_token_gap_max=1e-5, served_token_gap_mean=1e-6))))
    with open(f"{b}/layer_metrics/toy_records.py", "w") as f:
        f.write(TOY_METRIC)
    spec["configs"] += [
        dict(name="toy-lm", source="toy", reduced=[], why="toy",
             file="benchmarks/configs/toy-lm.json"),
        dict(name="toy-resnet", source="toy", reduced=[], why="toy",
             file="benchmarks/configs/toy-resnet.json")]
    spec["workloads"] += [
        dict(name="toy-resnet.train", config="toy-resnet",
             traffic="toy-epochs", chips=1, why="toy"),
        dict(name="toy-lm.train", config="toy-lm", traffic="toy-lm-epochs",
             chips=1, why="toy"),
        dict(name="toy-lm.serve", config="toy-lm", traffic="toy-chat",
             chips=1, why="toy")]
    for m in spec["end_to_end"]:
        if m["name"] == "train_mfu":
            m["workloads"] += ["toy-resnet.train", "toy-lm.train"]
        elif "workloads" in m:
            m["workloads"].append("toy-lm.serve")     # gap_p95_ms
    spec["per_layer"].append(dict(
        name="toy_records", unit="count", better="higher",
        source="program_span", layer="engines", moves="train_mfu",
        workloads=["toy-lm.train"]))
    _dump(os.path.join(root, "BENCHMARK.json"), spec)
    return root


def run_toy(root: str, name: str, seed: int = 3, seconds: float = 2.0,
            trace: bool = False, family_patch=None):
    """Drive everything behind the command but the look for a chip: the
    test passes the device requirement in (the CPU's first device, under
    the v5e's peaks so the arithmetic has a table to read)."""
    import sys
    import time

    import jax

    sys.path.insert(0, REPO)
    from benchmarks import run
    from benchmarks.harness import cell as cells
    from benchmarks.harness import device

    device.PEAKS.setdefault("cpu", device.PEAKS["TPU v5 lite"])
    cell = cells.load_cell(root, name)
    if family_patch is not None:
        family_patch(cells)
    workdir = os.path.join(root, f"work_{name}_{seed}_{int(trace)}")
    os.makedirs(workdir, exist_ok=True)
    return run.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                        workdir, t0=time.monotonic())
