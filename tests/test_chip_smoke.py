"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script's own phases — the same functions, the same checks — run here
through the entry points it exposes (``run_single_chip`` / ``run_multichip``)
with interpreted kernels, LeNet and a 2-layer d64 LM: a wrong path, argument
or control flow fails here, before it costs chip time. The tiny sizes live
HERE, not in an option of the program, and nothing below is a measurement.
"""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from tpu_dist.models.registry import register  # noqa: E402


TINY = chip_smoke.Sizes(
    arch="lenet", image_dataset="synthetic-mnist", image_batch=32,
    steps_per_dispatch=2, image_val=32, image_precision="fp32",
    num_layers=2, d_model=64, num_heads=4, seq_len=32, vocab_size=64,
    lm_batch=4, lm_precision="fp32", lm_steps=2, multichip_lm_steps=2,
    multichip_image_steps=2, max_slots=2, page_size=8,
    prompt_lens=(5, 11), new_tokens=4)


@register("smoke_mlp")
class _SmokeMLP(nn.Module):
    """No dropout and no batch statistics, so the explicit-psum engine on
    four devices and one device do the same math (LeNet's per-device
    dropout keys would not)."""

    num_classes: int = 10
    dtype: jax.numpy.dtype = jax.numpy.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        x = nn.relu(nn.Dense(32, dtype=self.dtype)(x))
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)


def test_single_chip_phases_run_on_cpu(tmp_path):
    rep = chip_smoke.run_single_chip(TINY, seed=0, workdir=str(tmp_path))
    assert rep.ok, rep.results
    assert list(rep.results) == [
        "runtime", "image_trainer", "lm_trainer", "lm_fused_adamw",
        "lm_int8", "serve_default", "serve_int8_flash"]
    r = rep.results
    assert r["runtime"]["launch"] == "local"
    assert r["image_trainer"]["windows"] == 2
    assert r["image_trainer"]["resumed_windows"] == 2
    assert r["lm_trainer"]["steps"] == TINY.lm_steps
    assert r["lm_fused_adamw"]["steps"] == r["lm_int8"]["steps"] == 1
    # off the chip every kernel is interpreted — and says so
    assert {r[p]["kernels"] for p in ("lm_trainer", "lm_fused_adamw",
                                      "lm_int8", "serve_int8_flash")} \
        == {"interpreted"}
    # fp32 on the CPU: the paged path is bit-identical to generate
    assert r["serve_default"]["greedy_exact"] == "4/4"
    assert r["serve_int8_flash"]["requests"] == "4/4"


def test_multichip_comparisons_on_four_virtual_devices(tmp_path):
    sizes = dataclasses.replace(TINY, arch="smoke_mlp", explicit_norm="")
    rep = chip_smoke.run_multichip(sizes, seed=0, workdir=str(tmp_path),
                                   devices=jax.devices()[:4])
    assert rep.ok, rep.results
    assert list(rep.results) == ["image_dp_jit", "image_dp_psum",
                                 "lm_one_device", "lm_tp", "lm_fsdp",
                                 "lm_sp"]
    for name in ("image_dp_jit", "image_dp_psum", "lm_tp", "lm_fsdp",
                 "lm_sp"):
        assert rep.results[name]["agrees"], rep.results[name]
        assert rep.results[name]["devices_in_use"] == 4
    assert rep.results["lm_tp"]["sharded_leaves"] > 0
    assert rep.results["lm_fsdp"]["sharded_leaves"] > 0
    assert rep.results["lm_sp"]["mode"] == "sp-ring"


def test_device_check_refuses_cpu(capsys):
    """No code path of the smoke continues on the CPU: the device check
    stops with a non-zero exit and no result line."""
    for argv in ([], ["--multichip"]):
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(argv)
        assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert out.out == "" and "refusing to run" in out.err


def test_script_alone_stops_with_no_result(monkeypatch, capsys):
    """On a chip but without the program beside it (a directory that holds
    chip_smoke.py and nothing else) the script exits non-zero before any
    phase, and prints no result."""
    import types

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    monkeypatch.setitem(sys.modules, "tpu_dist", None)  # import fails
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code == 3
    out = capsys.readouterr()
    assert out.out == "" and "not importable" in out.err


@pytest.mark.parametrize("noise,fault,agrees", [
    (1e-3, 1.0, True),     # bf16 rounding on the update: 0.1%
    (1e-3, 0.75, False),   # a replica's gradient dropped: the update is 3/4
    (1e-1, 1.0, False),    # ten percent is not rounding
])
def test_bf16_comparison_counts_in_roundoff(noise, fault, agrees):
    """The bf16 branch of the --multichip comparison (the chip's; the CPU
    cases above run the fp32 one): rounding passes, a sharding fault that
    scales or perturbs the update does not."""
    import numpy as np

    rng = np.random.default_rng(0)
    p0 = rng.normal(size=4096).astype(np.float32)
    upd = 1e-3 * rng.normal(size=4096).astype(np.float32)
    ref = {"loss_first": 10.9, "loss_last": 9.8, "params0": p0,
           "params": p0 + upd}
    got = {"loss_first": 10.9 + 5e-5, "loss_last": 9.8 + 1e-3, "params0": p0,
           "params": p0 + fault * upd * (1 + noise * rng.normal(size=4096))}
    out = chip_smoke._compare("lm_tp", ref, got, "bf16")
    assert out["agrees"] is agrees, out
    assert out["same_init"]
    assert out["tol"]["update_rel_err"] == pytest.approx(2.0 ** -5, rel=1e-2)
