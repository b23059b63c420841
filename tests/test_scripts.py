"""Cookbook smoke tests: every scripts/N.py entrypoint runs.

The suite otherwise tests the library; these run the actual CLI surface the
README advertises — parser, per-variant defaults, launch.initialize, Trainer
wiring — for one tiny synthetic epoch each, in a subprocess on CPU (the same
scripts run unchanged on TPU; see .claude/skills/verify for the TPU drive).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(ROOT, "scripts")

TINY = ["--epochs", "1", "--batch-size", "32", "--arch", "lenet",
        "--dataset", "synthetic-mnist", "--synth-train-size", "96",
        "--synth-val-size", "32", "--workers", "1", "--print-freq", "100"]


def run_script(tmp, name, args, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_DIST") and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, name), *args],
        env=env, cwd=str(tmp), capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{name} rc={proc.returncode}\nstdout:\n{proc.stdout[-2000:]}\n"
        f"stderr:\n{proc.stderr[-2000:]}")
    return proc.stdout


def ck(tmp):
    return ["--checkpoint-dir", os.path.join(str(tmp), "ck")]


def test_script_1_dataparallel(tmp_path):
    out = run_script(tmp_path, "1.dataparallel.py", TINY + ck(tmp_path))
    assert "best_acc1" in out
    assert os.path.exists(tmp_path / "dataparallel.csv")  # C21 CSV default


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_2_distributed(tmp_path):
    out = run_script(tmp_path, "2.distributed.py", TINY + ck(tmp_path))
    assert "rendezvous=local" in out and "best_acc1" in out


def test_script_3_spawn_two_processes(tmp_path):
    out = run_script(tmp_path, "3.multiprocessing_spawn.py",
                     TINY + ck(tmp_path),
                     env_extra={"TPU_DIST_NPROCS_SPAWN": "2"})
    assert "best_acc1" in out


def test_script_3_spawn_refuses_any_platform_but_cpu(tmp_path):
    """Nothing gives a spawned child its own chip: off the CPU simulation
    the parent stops with a message before any child can hang on a held
    chip (and before it touches a backend itself)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_DIST") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="tpu", TPU_DIST_NPROCS_SPAWN="2")
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "3.multiprocessing_spawn.py")],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "JAX_PLATFORMS=cpu" in proc.stderr and "[proc" not in proc.stdout


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_4_bf16(tmp_path):
    out = run_script(tmp_path, "4.bf16_distributed.py", TINY + ck(tmp_path))
    assert "best_acc1" in out


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_5_allreduce(tmp_path):
    out = run_script(tmp_path, "5.allreduce_distributed.py",
                     TINY + ck(tmp_path))
    assert "best_acc1" in out


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_5_2_mnist(tmp_path):
    out = run_script(tmp_path, "5.2.mnist.py", TINY + ck(tmp_path))
    assert "best_acc1" in out


def test_script_6_slurm_fallback_local(tmp_path):
    # no SLURM env -> local single-process; dataset overridden to synthetic
    out = run_script(tmp_path, "6.distributed_slurm.py", TINY + ck(tmp_path))
    assert "best_acc1" in out
    assert os.path.exists(tmp_path / "distributed.csv")


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_7_flagship_windowed(tmp_path):
    # keep the flagship's windowed dispatch path (K>1) but shrink the model
    out = run_script(tmp_path, "7.jax_tpu.py",
                     TINY + ck(tmp_path) + ["--steps-per-dispatch", "2"])
    assert "best_acc1" in out
    assert os.path.exists(tmp_path / "jax_tpu.csv")


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_8_lm(tmp_path):
    out = run_script(tmp_path, "8.lm_longcontext.py",
                     ["--steps", "3", "--batch-size", "4", "--seq-len", "32",
                      "--d-model", "32", "--num-layers", "1", "--num-heads",
                      "2", "--print-freq", "1", "--synth-tokens", "2000",
                      "--vocab-size", "64", "--generate", "8",
                      "--checkpoint-dir", os.path.join(str(tmp_path), "ck")])
    assert "corpus=synth-affine-train" in out  # real corpus, not fixed batch
    assert "throughput" in out
    assert "ppl" in out            # held-out perplexity surface
    assert "affine rule" in out    # --generate surface


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_script_8_lm_pipeline_mode(tmp_path):
    out = run_script(tmp_path, "8.lm_longcontext.py",
                     ["--mesh", "data=2,stage=2", "--steps", "3",
                      "--batch-size", "4", "--seq-len", "32", "--d-model",
                      "32", "--num-layers", "2", "--num-heads", "2",
                      "--print-freq", "1", "--pp-microbatches", "2",
                      "--synth-tokens", "2000", "--vocab-size", "64"],
                     env_extra={"XLA_FLAGS":
                                "--xla_force_host_platform_device_count=4"})
    assert "mode=pp-gpipe" in out and "throughput" in out and "ppl" in out


def test_script_evaluate_flag(tmp_path):
    # reference -e/--evaluate path (C1): eval-only run, no training
    out = run_script(tmp_path, "5.2.mnist.py",
                     TINY + ck(tmp_path) + ["--evaluate"])
    assert "best_acc1" in out


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_tool_lm_convergence(tmp_path):
    out = run_script(tmp_path, "../tools/lm_convergence.py",
                     ["--synth-tokens", "60000", "--batch-size", "16",
                      "--seq-len", "128", "--d-model", "64", "--threshold",
                      "20", "--max-epochs", "4", "--vocab-size", "128"])
    assert "steps_to_ppl_20" in out


@pytest.mark.slow  # tier-1 budget (PR 7): 14s end-to-end sampler run; the sampler/peak-HBM mechanics stay covered by test_telemetry.py units
def test_telemetry_csv_and_peak_hbm_column(tmp_path):
    """--telemetry-csv samples the 500ms device/host CSV (reference
    statistics.sh analog, C22) and the per-epoch CSV carries the peak-HBM
    column (empty value on CPU, where the backend exposes no
    memory counters — the COLUMN must still exist)."""
    import csv as csv_mod

    tele = os.path.join(str(tmp_path), "tele.csv")
    run_script(tmp_path, "1.dataparallel.py",
               TINY + ck(tmp_path) + ["--telemetry-csv", tele])
    with open(tele) as f:
        rows = list(csv_mod.reader(f))
    assert rows[0] == ["ts", "hbm_bytes_in_use", "hbm_peak_bytes",
                       "hbm_bytes_limit", "host_rss_kb"]
    assert len(rows) >= 2          # ran long enough for >= 1 sample
    assert float(rows[1][0]) > 0   # ts
    assert rows[1][4] != ""        # host RSS always present on linux

    with open(tmp_path / "dataparallel.csv") as f:
        epoch_rows = list(csv_mod.reader(f))
    assert len(epoch_rows[0]) == 4  # start, secs, img/s, peak_hbm
