"""True multi-process jax.distributed execution.

The reference's identity is multi-process distributed training
(reference 2.distributed.py:98 env:// rendezvous,
3.multiprocessing_distributed.py:84,102 mp.spawn + loopback tcp://). Every
other test in this suite emulates distribution with 8 virtual devices in ONE
process; these tests actually spawn separate OS processes that rendezvous via
``jax.distributed`` over loopback TCP — the first-ever execution of
``launch.initialize``'s distributed path and of ``prefetch_to_device``'s
``make_array_from_process_local_data`` branch (the multi-controller pitfall
where a bare device_put would silently drop the other process's shard).

Check: a 2-process x 2-device run must produce the SAME trained parameters as
a 1-process x 4-device run on the identical global workload (same global
batch content, same seed) — distribution must be invisible to the math.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "mp_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(outdir: str, nprocs: int, local_devices: int) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_DIST") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               TPU_DIST_TEST_OUT=outdir,
               TPU_DIST_LOCAL_DEVICES=str(local_devices),
               TPU_DIST_EXPECT_PROCS=str(nprocs))
    return env


def run_workers(tmp, tag: str, nprocs: int, local_devices: int,
                timeout: int = 420, worker: str = WORKER,
                extra_env: dict = None) -> str:
    outdir = os.path.join(tmp, tag)
    os.makedirs(outdir, exist_ok=True)
    base = _worker_env(outdir, nprocs, local_devices)
    base.update(extra_env or {})
    procs = []
    port = _free_port()
    for rank in range(nprocs):
        env = dict(base)
        if nprocs > 1:  # env:// rendezvous (reference 2.distributed.py:98)
            env.update(TPU_DIST_COORDINATOR=f"127.0.0.1:{port}",
                       TPU_DIST_NUM_PROCESSES=str(nprocs),
                       TPU_DIST_PROCESS_ID=str(rank))
        log = open(os.path.join(outdir, f"worker-{rank}.log"), "w")
        procs.append((rank, log, subprocess.Popen(
            [sys.executable, worker], env=env, cwd=ROOT,
            stdout=log, stderr=subprocess.STDOUT)))
    failed = []
    for rank, log, p in procs:
        try:
            rc = p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            rc = -9
        log.close()
        if rc != 0:
            with open(os.path.join(outdir, f"worker-{rank}.log")) as f:
                failed.append(f"worker {rank} rc={rc}\n{f.read()[-2000:]}")
    assert not failed, "\n".join(failed)
    return outdir


def _load(outdir: str):
    with open(os.path.join(outdir, "result.json")) as f:
        result = json.load(f)
    with np.load(os.path.join(outdir, "params.npz")) as z:
        params = {k: z[k] for k in z.files}
    return result, params


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("mp"))
    single = run_workers(tmp, "single", nprocs=1, local_devices=4)
    multi = run_workers(tmp, "multi", nprocs=2, local_devices=2)
    return _load(single), _load(multi)


def test_multiprocess_rendezvous(runs):
    (res1, _), (res2, _) = runs
    assert res1["process_count"] == 1 and res1["method"] == "local"
    assert res2["process_count"] == 2 and res2["method"] == "env"
    # both completed the same number of optimizer steps
    assert res1["step"] == res2["step"] > 0


def test_multiprocess_params_match_single_process(runs):
    """2 procs x 2 devices == 1 proc x 4 devices, parameter-for-parameter."""
    (_, p1), (_, p2) = runs
    assert p1.keys() == p2.keys() and len(p1) > 0
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"leaf {k}")


def test_multiprocess_metrics_match(runs):
    (res1, _), (res2, _) = runs
    # distributed eval (psum'd metric sums, padding masked) must agree too
    assert res1["best_acc1"] == pytest.approx(res2["best_acc1"], abs=1e-3)


def test_multiprocess_windowed_device_data_matches(runs, tmp_path):
    """steps_per_dispatch>1 with the HBM-resident indexed data path across 2
    REAL processes == the single-process per-batch run: exercises
    make_array_from_process_local_data on (K,B) index windows (each process
    contributes only its sampler shard's indices)."""
    windowed = run_workers(str(tmp_path), "windowed", nprocs=2,
                           local_devices=2,
                           extra_env={"TPU_DIST_TEST_K": "2"})
    (_, p1), _ = runs  # the fixture's single-process per-batch run
    _, p2 = _load(windowed)
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"leaf {k}")


def test_multiprocess_lm_params_match_single_process(tmp_path):
    """The LM engine across 2 REAL processes == 1 process (the bit-match
    requirement): same corpus, same sampler rows, same final
    parameters — including the HBM-resident windowed path, whose (K, B)
    index windows cross make_array_from_process_local_data."""
    worker = os.path.join(ROOT, "tests", "mp_lm_worker.py")
    single = run_workers(str(tmp_path), "lm-single", nprocs=1,
                         local_devices=4, worker=worker)
    multi = run_workers(str(tmp_path), "lm-multi", nprocs=2,
                        local_devices=2, worker=worker,
                        extra_env={"TPU_DIST_TEST_K": "2"})
    (res1, p1), (res2, p2) = _load(single), _load(multi)
    assert res1["process_count"] == 1 and res2["process_count"] == 2
    assert res2["method"] == "env"
    assert res1["step"] == res2["step"] > 0
    assert p1.keys() == p2.keys() and len(p1) > 0
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"leaf {k}")
    assert res1["best_ppl"] == pytest.approx(res2["best_ppl"], rel=1e-3)


def test_multiprocess_lm_loss_chunk_matches_full(tmp_path):
    """--loss-chunk (round 4, chunked vocab CE) across 2 REAL processes
    trains to the same parameters as the 2-process full-logits run — the
    chunked custom_vjp is process-topology-invariant."""
    worker = os.path.join(ROOT, "tests", "mp_lm_worker.py")
    full = run_workers(str(tmp_path), "lm-full", nprocs=2,
                       local_devices=2, worker=worker)
    chunk = run_workers(str(tmp_path), "lm-chunk", nprocs=2,
                        local_devices=2, worker=worker,
                        extra_env={"TPU_DIST_TEST_LOSS_CHUNK": "40"})
    (res1, p1), (res2, p2) = _load(full), _load(chunk)
    assert res1["process_count"] == res2["process_count"] == 2
    assert p1.keys() == p2.keys() and len(p1) > 0
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"leaf {k}")


@pytest.mark.parametrize("mode", ["tp", "sp", "pp", "ep"])
def test_multiprocess_model_parallel_matches_single(tmp_path, mode):
    """TP / SP / PP / EP train steps with the MODEL axis spanning 2 REAL
    processes == the same mesh in one process (the
    last untested distribution regime): Megatron collectives, the ring
    ppermute, the pipeline stage hop, and the MoE expert dispatch each
    cross a jax.distributed process boundary."""
    worker = os.path.join(ROOT, "tests", "mp_modes_worker.py")
    env = {"TPU_DIST_TEST_MPMODE": mode}
    single = run_workers(str(tmp_path), f"{mode}-single", nprocs=1,
                         local_devices=4, worker=worker, extra_env=env)
    multi = run_workers(str(tmp_path), f"{mode}-multi", nprocs=2,
                        local_devices=2, worker=worker, extra_env=env)
    (res1, p1), (res2, p2) = _load(single), _load(multi)
    assert res1["process_count"] == 1 and res2["process_count"] == 2
    assert res1["step"] == res2["step"] == 3
    assert res1["loss_sum"] == pytest.approx(res2["loss_sum"], rel=1e-4)
    assert p1.keys() == p2.keys() and len(p1) > 0
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"{mode} leaf {k}")


def test_multiprocess_shard_map_engine_matches_single(tmp_path):
    """The explicit-collective (horovod-equivalent) image engine across 2
    real processes == single process — the shard_map psum path over a real
    boundary, with bf16 gradient compression on."""
    env = {"TPU_DIST_TEST_VARIANT": "shard_map",
           "TPU_DIST_TEST_COMPRESSION": "bf16"}
    single = run_workers(str(tmp_path), "sm-single", nprocs=1,
                         local_devices=4, extra_env=env)
    multi = run_workers(str(tmp_path), "sm-multi", nprocs=2,
                        local_devices=2, extra_env=env)
    (_, p1), (_, p2) = _load(single), _load(multi)
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=2e-4, atol=2e-5,
                                   err_msg=f"leaf {k}")


def test_multiprocess_sharded_checkpoint(tmp_path):
    """FSDP leaves sharded ACROSS processes (non-addressable) save and
    restore bit-exactly — the collective process_allgather path."""
    worker = os.path.join(ROOT, "tests", "mp_ckpt_worker.py")
    outdir = run_workers(str(tmp_path), "ckpt", nprocs=2, local_devices=2,
                         worker=worker)
    with open(os.path.join(outdir, "ckpt_result.json")) as f:
        res = json.load(f)
    assert res["nonaddressable_leaves"] > 0
    assert res["meta_epoch"] == 1
    assert res["ok"], res
