"""Plan IR + compiler + auto-tuner (tpu_dist.plan, round 15).

Three layers, cheapest first:

* **no-jax units** — the Plan IR (round-trip, hash determinism,
  validation, the mesh-axis authority pin) and the tuner (exact expected
  winner over the checked-in canned measurement file, byte-determinism,
  trial-specificity) exercise modules that must import under the
  scripts/lint.sh jax blocker;
* **config -> plan** — ``plan_from_config`` of each trainer mode's config
  gives exactly the expected Plan (the one decision point the trainers
  compile), refuses what ``Plan.validate`` refuses, and inverts
  ``apply_plan_to_config`` over the tuner's whole candidate space;
* **CPU parity** — every mode lowers through ``compile_train_step(plan)``
  and the flavors agree on the loss/param trajectory;
* **engine acceptance** — both engines accept an emitted plan file via
  the new ``plan`` config knob, stamp it into run_start + a ``plan``
  ledger event, and ledger_report renders it.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_dist.plan.ir import (DEFAULT_OPT_BLOCK_ROWS, DEFAULT_QUANT_BLOCK,
                              KNOWN_AXES, Plan, PlanError,
                              apply_plan_to_config, load_plan_file,
                              plan_for_device, plan_from_config, plan_hash,
                              plan_knob_summary)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TUNE_CI = os.path.join(REPO, "scripts", "tune_ci.json")


@pytest.fixture
def clean_plan_globals():
    """Restore the plan-owned trace-time globals (fused switch, Pallas
    blocks) after a test that activates a plan."""
    yield
    from tpu_dist.ops import pallas_quant, pallas_sgd
    from tpu_dist.ops.quant import set_fused_quant

    set_fused_quant(None)
    pallas_quant.set_quant_blocks()
    pallas_sgd.set_block_rows()


# ---- IR units (no jax in the modules under test) --------------------------

def test_plan_roundtrip_and_hash_determinism():
    p = Plan(engine="lm", quant="int8", sync="explicit",
             grad_bucket_mb=25.0, window="indexed", steps_per_dispatch=16,
             quant_block=(256, 128, 0), opt_block_rows=1024).validate()
    q = Plan.from_json(p.to_json())
    assert q == p and hash(q) == hash(p)
    assert plan_hash(p) == plan_hash(q)
    # canonical JSON: key order in the input dict must not matter
    d = p.to_dict()
    shuffled = dict(sorted(d.items(), reverse=True))
    assert plan_hash(Plan.from_dict(shuffled)) == plan_hash(p)
    # any knob change moves the hash
    assert plan_hash(Plan(engine="lm", quant="int8", fused_quant="on")) \
        != plan_hash(Plan(engine="lm", quant="int8"))


def test_plan_validation_rejects_illegal_combinations():
    bad = [
        dict(engine="lm", quant="int4"),
        dict(engine="lm", tp_impl="ring"),                 # needs tp+explicit
        dict(engine="lm", grad_bucket_mb=25.0),            # needs explicit
        dict(engine="lm", layout="sp"),                    # needs explicit
        dict(engine="lm", layout="tp", sync="explicit"),   # tp+explicit=ring
        dict(engine="lm", grad_accum_steps=2, steps_per_dispatch=4,
             window="indexed"),
        dict(engine="lm", adasum=True, sync="explicit"),   # image knob
        dict(engine="lm", window="stacked"),               # image window
        dict(engine="image", layout="sp", sync="explicit"),
        dict(engine="image", loss_chunk=64),
        dict(engine="image", window="indexed", sync="explicit"),
        dict(engine="lm", quant_block=(100, 128, 0)),      # bm % 8
        dict(engine="lm", quant_block=(128, 64, 0)),       # bn % 128
        dict(engine="lm", quant_block=(128, 128, 64)),     # bk % 128
        dict(engine="lm", opt_block_rows=100),
    ]
    for kw in bad:
        with pytest.raises(PlanError):
            Plan(**kw).validate()
    # the image explicit step MAY bucket while ring-pmean'ing over 'model'
    Plan(engine="image", sync="explicit", layout="tp", tp_impl="ring",
         grad_bucket_mb=25.0).validate()


def test_plan_mesh_validation():
    p = Plan(engine="lm", layout="tp", sync="explicit", tp_impl="ring")
    p.validate_against_mesh({"data": 4, "model": 2})
    with pytest.raises(PlanError):
        p.validate_against_mesh({"data": 8})          # no model axis
    with pytest.raises(PlanError):
        Plan(engine="lm").validate_against_mesh({"batch": 8})  # unknown axis


def test_known_axes_matches_mesh_authority():
    """plan.ir mirrors the parallel/mesh.py *_AXIS authority jax-free; an
    axis added there MUST land here too (same AST pin distlint DL003
    uses — neither module imports the other)."""
    tree = ast.parse(open(os.path.join(
        REPO, "tpu_dist", "parallel", "mesh.py")).read())
    axes = [n.value.value for n in ast.walk(tree)
            if isinstance(n, ast.Assign) and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Name)
            and n.targets[0].id.endswith("_AXIS")
            and isinstance(n.value, ast.Constant)]
    assert tuple(axes) == KNOWN_AXES


def test_load_plan_file_and_device_selection(tmp_path):
    full = Plan(engine="lm", quant="int8").to_dict()
    doc = {"version": 1, "plans": {"v5 lite": full,
                                   "default": Plan(engine="lm").to_dict()}}
    path = tmp_path / "plans.json"
    path.write_text(json.dumps(doc))
    plans = load_plan_file(str(path))
    # substring match (the PEAK table convention), then the default
    assert plan_for_device(plans, "TPU v5 lite").quant == "int8"
    assert plan_for_device(plans, "cpu").quant == "none"
    del plans["default"]
    with pytest.raises(PlanError):
        plan_for_device(plans, "cpu")
    # a bare single-plan file keys as 'default'
    path.write_text(json.dumps(full))
    assert plan_for_device(load_plan_file(str(path)), "anything") \
        == Plan.from_dict(full)
    # unknown fields refuse loudly (typo'd knob files must not no-op)
    path.write_text(json.dumps({**full, "qant": "int8"}))
    with pytest.raises(PlanError):
        load_plan_file(str(path))


def test_apply_plan_to_config_both_engines():
    from tpu_dist.configs import LMConfig, TrainConfig

    p = Plan(engine="lm", quant="int8", sync="explicit",
             grad_bucket_mb=25.0, window="indexed", steps_per_dispatch=16,
             loss_chunk=128, health="skip")
    cfg = apply_plan_to_config(LMConfig(seq_len=64), p)
    assert (cfg.quant, cfg.grad_bucket_mb, cfg.steps_per_dispatch,
            cfg.loss_chunk, cfg.health, cfg.data_placement) == \
        ("int8", 25.0, 16, 128, "skip", "device")
    assert cfg.seq_len == 64            # non-plan fields untouched
    ip = Plan(engine="image", sync="explicit", grad_compression="bf16",
              predivide_factor=2.0)
    icfg = apply_plan_to_config(TrainConfig(), ip)
    assert icfg.variant == "shard_map"
    assert icfg.grad_compression == "bf16"
    assert icfg.gradient_predivide_factor == 2.0
    assert apply_plan_to_config(
        TrainConfig(), Plan(engine="image")).variant == "jit"
    with pytest.raises(PlanError):
        apply_plan_to_config(TrainConfig(), p)      # lm plan, image config


def test_plan_knob_summary_is_the_non_default_diff():
    assert plan_knob_summary(Plan(engine="lm")) == {}
    s = plan_knob_summary(Plan(engine="lm", quant="int8",
                               quant_block=(256, 128, 0)))
    assert s == {"quant": "int8", "quant_block": [256, 128, 0]}


# ---- tuner (no jax in the modules under test) -----------------------------

def test_tuner_exact_winner_over_canned_measurements():
    """The checked-in scripts/tune_ci.json names its winner exactly: the
    measured-refinement trial (int8 + bucket 25 + 16-step indexed window +
    256x128 tiles + 1024-row optimizer blocks) must beat every analytic
    candidate."""
    from tpu_dist.plan.tune import tune

    text, results = tune(measurement_files=[TUNE_CI])
    res = results["TPU v5 lite"]
    best = res["best"]
    assert best["measured"] and best["step_s"] == pytest.approx(0.0021)
    knobs = plan_knob_summary(best["plan"])
    assert knobs == {"sync": "explicit", "quant": "int8",
                     "grad_bucket_mb": 25.0, "window": "indexed",
                     "steps_per_dispatch": 16,
                     "quant_block": [256, 128, 0], "opt_block_rows": 1024}
    # the emitted file round-trips through the config-knob loader
    doc = json.loads(text)
    sel = Plan.from_dict(doc["plans"]["TPU v5 lite"])
    assert plan_hash(sel) == best["hash"]
    # peaks resolved from the real tables (v5e), not the nominal fallback
    assert not res["peaks"]["nominal"]
    assert res["peaks"]["tflops"] == pytest.approx(197.0)


def test_tuner_is_byte_deterministic():
    from tpu_dist.plan.tune import tune

    t1, _ = tune(measurement_files=[TUNE_CI])
    t2, _ = tune(measurement_files=[TUNE_CI])
    assert t1 == t2


def test_tuner_without_measurements_still_ranks():
    """No comm_bench file: pure analytic roofline — int8+fused beats fp
    on a compute-bound workload, and the result stays deterministic."""
    from tpu_dist.plan.tune import search

    r1 = search(device_kind="TPU v4")
    r2 = search(device_kind="TPU v4")
    assert [c["hash"] for c in r1["ranked"]] == \
        [c["hash"] for c in r2["ranked"]]
    assert r1["best"]["plan"].quant == "int8"
    assert r1["best"]["plan"].fused_quant == "auto"   # auto = fused on TPU


def test_trial_specificity_and_hash_keying():
    from tpu_dist.plan.tune import trial_step_seconds

    plan = Plan(engine="lm", quant="int8", grad_bucket_mb=25.0,
                sync="explicit")
    trials = [
        {"knobs": {"quant": "int8"}, "step_s": 0.5},
        {"knobs": {"quant": "int8", "grad_bucket_mb": 25.0},
         "step_s": 0.25},                       # more specific: wins
        {"knobs": {"quant": "none"}, "step_s": 0.1},   # does not match
    ]
    assert trial_step_seconds(trials, plan, {}) == 0.25
    trials.append({"plan_hash": plan_hash(plan), "knobs": {},
                   "step_s": 0.125})            # exact hash: beats subsets
    assert trial_step_seconds(trials, plan, {}) == 0.125


def test_comm_estimates_scale_to_workload_bytes():
    from tpu_dist.plan.tune import comm_estimates, normalize_workload

    meas = {"results": [
        {"bench": "grad_sync", "bytes": 1e8, "bucketed_s": 0.01,
         "monolithic_s": 0.02},
        {"bench": "grad_sync", "bytes": 1e9, "bucketed_s": 0.1,
         "monolithic_s": 0.2}]}
    w = normalize_workload({"n_params": 50e6})   # 2e8 grad bytes
    est = comm_estimates(meas, w)
    # nearest row (1e8) scaled linearly to 2e8 bytes
    assert est["sync_bucketed_s"] == pytest.approx(0.02)
    assert est["sync_monolithic_s"] == pytest.approx(0.04)
    assert comm_estimates(None, w) == {}


def test_tools_tune_cli_deterministic_and_ledger(tmp_path):
    """python -m tools.tune over the canned file: byte-identical plan
    JSON across two runs (the acceptance criterion) + a schema-valid
    `tune` ledger event."""
    env = dict(os.environ, PYTHONPATH=REPO)
    led = tmp_path / "tune.jsonl"
    outs = []
    for i in range(2):
        r = subprocess.run(
            [sys.executable, "-m", "tools.tune", "--comm-bench", TUNE_CI,
             "--json"] + (["--ledger", str(led)] if i == 0 else []),
            capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout)
    assert outs[0] == outs[1] and outs[0].strip()
    from tpu_dist.obs.ledger import read_ledger

    tunes = [r for r in read_ledger(str(led)) if r["event"] == "tune"]
    assert len(tunes) == 1
    doc = json.loads(outs[0])
    assert tunes[0]["best_hash"] == doc["plans"]["TPU v5 lite"]["hash"]
    assert tunes[0]["candidates"] > 10 and tunes[0]["measured"] is True


# ---- config -> plan: every trainer mode, pinned exactly (no compiles) ------

DP4 = {"data": 4}
DP_TP = {"data": 2, "model": 2}
DP_SP = {"data": 2, "seq": 2}
IMG = dict(engine="image")
LM = dict(engine="lm")
SP = dict(engine="lm", layout="sp", sync="explicit")
RING = dict(layout="tp", sync="explicit", tp_impl="ring")

# (id, config kind, config fields, mesh axis sizes, observed window, Plan)
CONFIG_MODES = [
    ("img-jit", "image", dict(health="skip"), DP4, "none",
     Plan(**IMG, health="skip")),
    ("img-jit-drops-mean-path-knobs", "image",
     dict(grad_compression="bf16", gradient_predivide_factor=2.0), DP4,
     "none", Plan(**IMG)),
    ("img-stacked", "image", dict(steps_per_dispatch=4), DP4, "stacked",
     Plan(**IMG, window="stacked", steps_per_dispatch=4)),
    ("img-indexed", "image",
     dict(steps_per_dispatch=16, precision="bf16"), {"data": 1}, "indexed",
     Plan(**IMG, window="indexed", steps_per_dispatch=16,
          precision="bf16")),
    ("img-indexed-k1", "image", dict(data_placement="device"), DP4,
     "indexed", Plan(**IMG, window="indexed")),
    ("img-accum", "image", dict(grad_accum_steps=4), DP4, "none",
     Plan(**IMG, grad_accum_steps=4)),
    ("img-shard_map-compression-predivide", "image",
     dict(variant="shard_map", grad_compression="bf16",
          gradient_predivide_factor=2.0), DP4, "none",
     Plan(**IMG, sync="explicit", grad_compression="bf16",
          predivide_factor=2.0)),
    ("img-shard_map-bucket", "image",
     dict(variant="shard_map", grad_bucket_mb=25.0), DP4, "none",
     Plan(**IMG, sync="explicit", grad_bucket_mb=25.0)),
    ("img-shard_map-adasum", "image",
     dict(variant="shard_map", adasum=True), DP4, "none",
     Plan(**IMG, sync="explicit", adasum=True)),
    ("img-ring", "image",
     dict(variant="shard_map", tp_impl="ring", quant="int8"), DP_TP, "none",
     Plan(**IMG, **RING, quant="int8")),
    ("lm-gspmd", "lm", dict(loss_chunk=64, moe_aux_weight=0.5), DP4, "none",
     Plan(**LM, loss_chunk=64, aux_weight=0.5)),
    ("lm-gspmd-fsdp-ep-stage-are-not-plan-layouts", "lm", dict(fsdp=True),
     {"data": 2, "expert": 2, "stage": 1}, "none", Plan(**LM)),
    ("lm-tp-gspmd", "lm", dict(quant="int8", precision="bf16"), DP_TP,
     "none", Plan(**LM, layout="tp", quant="int8", precision="bf16")),
    ("lm-accum", "lm", dict(grad_accum_steps=2, loss_chunk=32), DP4, "none",
     Plan(**LM, grad_accum_steps=2, loss_chunk=32)),
    ("lm-bucket", "lm", dict(grad_bucket_mb=25.0), DP4, "none",
     Plan(**LM, sync="explicit", grad_bucket_mb=25.0)),
    ("lm-bucket-indexed", "lm",
     dict(grad_bucket_mb=25.0, steps_per_dispatch=2), DP4, "indexed",
     Plan(**LM, sync="explicit", grad_bucket_mb=25.0, window="indexed",
          steps_per_dispatch=2)),
    ("lm-ring", "lm", dict(tp_impl="ring", moe_aux_weight=0.0), DP_TP,
     "none", Plan(**LM, **RING, aux_weight=0.0)),
    ("lm-ring-needs-a-model-axis", "lm", dict(tp_impl="ring"), DP4, "none",
     Plan(**LM)),
    ("lm-sp", "lm", dict(loss_chunk=8), DP_SP, "none",
     Plan(**SP, loss_chunk=8)),
    ("lm-sp-indexed", "lm", dict(steps_per_dispatch=2), DP_SP, "indexed",
     Plan(**SP, window="indexed", steps_per_dispatch=2)),
    ("lm-indexed", "lm", dict(steps_per_dispatch=2, health="halt"), DP4,
     "indexed",
     Plan(**LM, window="indexed", steps_per_dispatch=2, health="halt")),
]


def _config(kind, fields):
    from tpu_dist.configs import LMConfig, TrainConfig

    return (TrainConfig if kind == "image" else LMConfig)(**fields)


@pytest.mark.parametrize("kind,fields,mesh_shape,window,expect",
                         [c[1:] for c in CONFIG_MODES],
                         ids=[c[0] for c in CONFIG_MODES])
def test_plan_from_config_modes(kind, fields, mesh_shape, window, expect):
    """The ONE decision from a config to a step program: each trainer
    mode's config, on its mesh, with the window the trainer observed,
    gives exactly this Plan (what the trainers hand the compiler)."""
    assert plan_from_config(_config(kind, fields), mesh_shape,
                            window) == expect


# (id, config kind, config fields, mesh axis sizes, window, message)
CONFIG_REFUSALS = [
    ("img-unknown-variant", "image", dict(variant="pmap"), DP4, "none",
     "variant"),
    ("img-unknown-health", "image", dict(health="panic"), DP4, "none",
     "health"),
    ("img-jit-bucket", "image", dict(grad_bucket_mb=25.0), DP4, "none",
     "sync='explicit'"),
    ("img-jit-adasum", "image", dict(adasum=True), DP4, "none", "adasum"),
    ("img-jit-ring", "image", dict(tp_impl="ring"), DP_TP, "none",
     "tp_impl='ring'"),
    ("img-ring-without-model-axis", "image",
     dict(variant="shard_map", tp_impl="ring"), DP4, "none", "mesh axis"),
    ("img-accum-with-window", "image",
     dict(grad_accum_steps=2, steps_per_dispatch=4), DP4, "none",
     "mutually exclusive"),
    ("img-accum-shard_map", "image",
     dict(grad_accum_steps=2, variant="shard_map"), DP4, "none", "jit"),
    ("img-shard_map-window", "image",
     dict(variant="shard_map", steps_per_dispatch=4), DP4, "stacked",
     "compiler-partitioned"),
    ("img-shard_map-device-data", "image",
     dict(variant="shard_map", data_placement="device"), DP4, "indexed",
     "compiler-partitioned"),
    ("lm-unknown-tp-impl", "lm", dict(tp_impl="nccl"), DP_TP, "none",
     "tp_impl"),
    ("lm-unknown-health", "lm", dict(health="panic"), DP4, "none", "health"),
    ("lm-tp-bucket", "lm", dict(grad_bucket_mb=25.0), DP_TP, "none",
     "lm tp/sp layouts"),
    ("lm-sp-bucket", "lm", dict(grad_bucket_mb=25.0), DP_SP, "none",
     "lm tp/sp layouts"),
    ("lm-sp-accum", "lm", dict(grad_accum_steps=2), DP_SP, "none", "jit"),
    ("lm-ring-accum", "lm", dict(tp_impl="ring", grad_accum_steps=2),
     DP_TP, "none", "jit"),
    ("lm-accum-indexed", "lm", dict(grad_accum_steps=2), DP4, "indexed",
     "mutually exclusive"),
]


@pytest.mark.parametrize("kind,fields,mesh_shape,window,message",
                         [c[1:] for c in CONFIG_REFUSALS],
                         ids=[c[0] for c in CONFIG_REFUSALS])
def test_plan_from_config_refuses(kind, fields, mesh_shape, window,
                                  message):
    """The mode exclusions have ONE home: the trainers raise none of
    these themselves, they surface Plan.validate's (a ValueError)."""
    with pytest.raises(PlanError, match=message):
        plan_from_config(_config(kind, fields), mesh_shape, window)


@pytest.mark.parametrize("engine,devices", [("image", 1), ("image", 8),
                                            ("lm", 1), ("lm", 8)])
def test_plan_config_roundtrip_over_tune_space(engine, devices):
    """plan_from_config inverts apply_plan_to_config over everything the
    tuner can emit: write a candidate into a config, read the plan the
    config runs back out. The three switches activate_plan owns have no
    config field (they are process-level trace-time switches), so they
    are compared as carried."""
    import dataclasses

    from tpu_dist.plan.tune import default_space

    base = _config(engine, {})
    space = default_space(engine, devices)
    assert len(space) > 10
    for p in space:
        cfg = apply_plan_to_config(base, p)
        got = plan_from_config(cfg, {"data": devices}, p.window)
        assert dataclasses.replace(
            got, fused_quant=p.fused_quant, quant_block=p.quant_block,
            opt_block_rows=p.opt_block_rows) == p, plan_hash(p)


# ---- CPU loss parity: every mode through the ONE compiler -----------------
# The table above pins which plan each trainer mode compiles; the tests
# below prove the LOWERINGS themselves: every mode (jit,
# shard_map/bucketed, windowed, ring, sp,
# × quant) trains through compile(plan) and the flavors agree on the
# loss trajectory. Sub-meshes (4 of the 8 virtual devices) keep the SPMD
# compiles cheap — tier-1 budget.

def _leaves_close(a, b, rtol=1e-5, atol=1e-6):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=rtol, atol=atol)


def _lm_fixture(quant="none"):
    import jax
    import jax.numpy as jnp

    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops import make_optimizer

    V, L, D = 32, 16, 32
    model = tiny_lm(vocab_size=V, num_layers=1, d_model=D, num_heads=4,
                    max_len=L, quant=quant)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng},
                        np.zeros((1, L), np.int32), train=False)["params"]
    tx = make_optimizer(0.01, 0.9, 0.0)
    rows = np.random.RandomState(0).randint(0, V, (8, L + 1)).astype(
        np.int32)

    def fresh():
        return TrainState.create(jax.tree.map(jnp.copy, params), {}, tx)

    return model, tx, rows, fresh, rng


def _plan_step(plan, **binds_kw):
    from tpu_dist.plan.compile import Bindings, compile_train_step

    return compile_train_step(plan, Bindings(**binds_kw))


def test_lm_plan_loss_parity_across_modes(clean_plan_globals):
    """jit / bucketed-shard_map / indexed-window / ring / sp / int8 all
    lower through the one compiler and agree: the dp flavors match the
    jit baseline's loss trajectory, the window matches K sequential
    steps, and int8 tracks the fp loss (op-level tracking is pinned in
    test_quant)."""
    import jax

    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.parallel.mesh import make_mesh

    model, tx, rows, fresh, rng = _lm_fixture()
    devs = jax.devices()[:4]
    mesh = make_mesh((4,), ("data",), devices=devs)
    rows_b = np.random.RandomState(1).randint(
        0, 32, rows.shape).astype(np.int32)
    batch_a = (rows[:, :-1], rows[:, 1:])
    batch_b = (rows_b[:, :-1], rows_b[:, 1:])
    inp, tgt = batch_a
    binds = dict(mesh=mesh, model=model, tx=tx)

    # baseline: the gspmd jit template, 2 sequential steps
    jit_step = _plan_step(Plan(engine="lm"), **binds)
    s = fresh()
    s, m1 = jit_step(s, *batch_a, rng)
    s, m2 = jit_step(s, *batch_b, rng)
    base_losses = (float(m1["loss_sum"]), float(m2["loss_sum"]))
    base_params = s.params

    # explicit bucketed dp: same math, different (explicit) collectives
    bstep = _plan_step(Plan(engine="lm", sync="explicit",
                            grad_bucket_mb=25.0), **binds)
    s = fresh()
    s, bm = bstep(s, *batch_a, rng)
    assert float(bm["loss_sum"]) == pytest.approx(base_losses[0], rel=1e-5)
    s, bm2 = bstep(s, *batch_b, rng)
    assert float(bm2["loss_sum"]) == pytest.approx(base_losses[1],
                                                   rel=1e-4)
    _leaves_close(s.params, base_params, rtol=1e-4)

    # indexed window: one 2-step dispatch over the HBM-resident row
    # matrix == the 2 sequential jit steps (identical math incl. the
    # per-step rng fold; window step i gathers the rows whose device-side
    # shift reproduces batch i exactly)
    wstep = _plan_step(Plan(engine="lm", window="indexed",
                            steps_per_dispatch=2), **binds)
    rows16 = jax.device_put(np.concatenate([rows, rows_b]))
    idx = np.arange(16, dtype=np.int32).reshape(2, 8)
    s = fresh()
    s, wm = wstep(s, rows16, idx, rng)
    assert float(wm["loss_sum"]) == pytest.approx(
        base_losses[0] + base_losses[1], rel=1e-6)
    _leaves_close(s.params, base_params, rtol=1e-6)

    # ring TP over (2, 2): fp loss parity with the jit dp baseline
    mesh_ring = make_mesh((2, 2), ("data", "model"), devices=devs)
    ring_step = _plan_step(
        Plan(engine="lm", sync="explicit", layout="tp", tp_impl="ring"),
        mesh=mesh_ring, model=model, tx=tx)
    s = fresh()
    s, rm = ring_step(s, inp, tgt, rng)
    assert float(rm["loss_sum"]) == pytest.approx(base_losses[0], rel=2e-4)

    # sp over (2, 2): ring attention, psum'd sums == the global sums
    from functools import partial

    ctor = partial(tiny_lm, vocab_size=32, num_layers=1, d_model=32,
                   num_heads=4, max_len=16)
    mesh_sp = make_mesh((2, 2), ("data", "seq"), devices=devs)
    sp_step = _plan_step(Plan(engine="lm", layout="sp", sync="explicit"),
                         mesh=mesh_sp, model_ctor=ctor, tx=tx)
    s = fresh()
    s, sm = sp_step(s, inp, tgt, rng)
    assert float(sm["loss_sum"]) == pytest.approx(base_losses[0], rel=2e-4)
    assert float(sm["count"]) == float(m1["count"])

    # int8: the same jit template with quantized matmuls tracks fp
    qmodel, qtx, _, qfresh, _ = _lm_fixture(quant="int8")
    qstep = _plan_step(Plan(engine="lm", quant="int8"),
                       mesh=mesh, model=qmodel, tx=qtx)
    s, qm = qstep(qfresh(), inp, tgt, rng)
    assert float(qm["loss_sum"]) == pytest.approx(base_losses[0], rel=0.05)


def test_image_plan_loss_parity_across_modes():
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_dist.engine.state import TrainState, init_model
    from tpu_dist.parallel.mesh import make_mesh
    from tpu_dist.plan.compile import Bindings

    import flax.linen as nn

    class _MLP(nn.Module):
        """BN- and dropout-free: the jit and shard_map flavors are then
        bit-comparable (per-replica BN stats and per-device rng folds are
        the two DESIGNED divergences — test_engine pins them)."""

        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    devs = jax.devices()[:4]
    mesh = make_mesh((4,), ("data",), devices=devs)
    model = _MLP()
    rng = jax.random.PRNGKey(0)
    params, bs = init_model(model, rng, (2, 28, 28, 1))
    tx = optax.sgd(0.1)
    transform = lambda x, r: x.astype(jnp.float32) / 255.0

    def fresh():
        return TrainState.create(jax.tree.map(jnp.copy, params),
                                 jax.tree.map(jnp.copy, bs), tx)

    imgs = np.random.RandomState(0).randint(
        0, 255, (8, 28, 28, 1)).astype(np.uint8)
    lbls = (np.arange(8) % 10).astype(np.int32)
    binds = dict(mesh=mesh, model=model, tx=tx, transform=transform)
    jit_step = _plan_step(Plan(engine="image"), **binds)
    s = fresh()
    s, m1 = jit_step(s, imgs, lbls, rng)
    s, m2 = jit_step(s, imgs[::-1], lbls[::-1], rng)

    # explicit shard_map flavor: LeNet is BN-free, so updates are
    # bit-comparable with the jit flavor (the steps.py contract)
    sm_step = _plan_step(Plan(engine="image", sync="explicit"), **binds)
    t = fresh()
    t, n1 = sm_step(t, imgs, lbls, rng)
    assert float(n1["loss_sum"]) == pytest.approx(float(m1["loss_sum"]),
                                                  rel=1e-5)
    t, n2 = sm_step(t, imgs[::-1], lbls[::-1], rng)
    _leaves_close(t.params, s.params, rtol=1e-4)

    # stacked window: one 2-step dispatch == the 2 sequential jit steps
    # (identical rng folds — the stacked lowering's contract)
    w_step = _plan_step(Plan(engine="image", window="stacked",
                             steps_per_dispatch=2), **binds)
    w = fresh()
    w, wm = w_step(w, np.stack([imgs, imgs[::-1]]),
                   np.stack([lbls, lbls[::-1]]), rng)
    assert float(wm["loss_sum"]) == pytest.approx(
        float(m1["loss_sum"]) + float(m2["loss_sum"]), rel=1e-6)
    _leaves_close(w.params, s.params, rtol=1e-6)

    # the eval lowering: forward-only, needs neither tx nor a train
    # transform binding
    from tpu_dist.plan.compile import compile_eval_step

    ev = compile_eval_step(Plan(engine="image"),
                           Bindings(mesh=mesh, model=model,
                                    eval_transform=transform))
    out = ev(params, bs, imgs, lbls, np.ones(8, np.float32))
    logits = model.apply({"params": params, "batch_stats": bs},
                         transform(imgs, None), train=False)
    top1 = float(np.sum(np.argmax(np.asarray(logits), -1) == lbls))
    assert float(out["correct1"]) == top1
    assert float(out["count"]) == 8.0


def test_fused_quant_plan_blocks_are_bit_identical(clean_plan_globals):
    """activate_plan flips the fused kernel + block sizes; any legal
    (bm, bn, bk) produces bit-identical fused matmuls (the bk chunking is
    exact int32 accumulation)."""
    import jax.numpy as jnp

    from tpu_dist.ops import pallas_quant as pq
    from tpu_dist.ops.quant import fused_quant_active
    from tpu_dist.plan.compile import activate_plan

    x = jnp.asarray(np.random.RandomState(0).normal(size=(24, 256)),
                    jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).normal(size=(256, 192)),
                    jnp.float32)
    activate_plan(Plan(engine="lm", quant="int8", fused_quant="on"))
    assert fused_quant_active()
    assert pq.quant_blocks() == DEFAULT_QUANT_BLOCK
    ref = np.asarray(pq.fused_quant_matmul(x, w))
    activate_plan(Plan(engine="lm", quant="int8", fused_quant="on",
                       quant_block=(64, 256, 128), opt_block_rows=256))
    assert pq.quant_blocks() == (64, 256, 128)
    from tpu_dist.ops.pallas_sgd import block_rows

    assert block_rows() == 256
    assert np.array_equal(ref, np.asarray(pq.fused_quant_matmul(x, w)))
    # review regression (PR 15): a RAGGED out-features dim (128 < n <
    # blk_n, n % 128 != 0) under a widened bn tile must lane-round, not
    # hand Mosaic a ragged (k, 200) block — and stay bit-identical
    w200 = jnp.asarray(np.random.RandomState(2).normal(size=(256, 200)),
                       jnp.float32)
    activate_plan(Plan(engine="lm", quant="int8", fused_quant="on"))
    ref200 = np.asarray(pq.fused_quant_matmul(x, w200))
    activate_plan(Plan(engine="lm", quant="int8", fused_quant="on",
                       quant_block=(128, 256, 0)))
    assert np.array_equal(ref200, np.asarray(pq.fused_quant_matmul(x, w200)))
    activate_plan(Plan(engine="lm", fused_quant="off"))
    assert not fused_quant_active()


# ---- engine acceptance: the config `plan` knob ----------------------------

def test_lm_trainer_accepts_emitted_plan_file(tmp_path, clean_plan_globals):
    """ACCEPTANCE: tools/tune.py's emitted plan file drives a real LM run
    through the config knob — knobs applied, run_start stamped, a `plan`
    event emitted, ledger_report renders it."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer
    from tpu_dist.obs.ledger import read_ledger
    from tpu_dist.plan.tune import tune

    text, results = tune(measurement_files=[TUNE_CI])
    best_hash = results["TPU v5 lite"]["best"]["hash"]
    doc = json.loads(text)
    # retarget the emitted per-device entry at this machine's device kind
    plan_doc = {"version": 1,
                "plans": {"default": doc["plans"]["TPU v5 lite"]}}
    path = tmp_path / "plans.json"
    path.write_text(json.dumps(plan_doc))
    ledger = tmp_path / "run.jsonl"
    cfg = LMConfig(seq_len=32, vocab_size=64, num_layers=1, d_model=32,
                   num_heads=4, batch_size=16, synth_tokens=6000, epochs=1,
                   max_steps=2, ledger_path=str(ledger), watchdog_factor=0,
                   plan=str(path))
    t = LMTrainer(cfg)
    # the plan's knobs landed in the config before the engine built steps
    assert t.cfg.quant == "int8" and t.cfg.grad_bucket_mb == 25.0
    assert t.cfg.steps_per_dispatch == 16 and t.mode == "dp-bucketed"
    t.fit()
    recs = read_ledger(str(ledger))
    start = [r for r in recs if r["event"] == "run_start"][0]
    assert start["plan_hash"] == best_hash
    assert start["plan_source"] == str(path)
    plan_events = [r for r in recs if r["event"] == "plan"]
    assert len(plan_events) == 1
    assert plan_events[0]["plan_hash"] == best_hash
    assert plan_events[0]["knobs"]["quant"] == "int8"
    # ledger_report renders + returns the plan section
    from tools.ledger_report import summarize

    summary = summarize(recs, out=lambda s: None)
    assert summary["plan"]["plan_hash"] == best_hash
    assert summary["run"]["plan_hash"] == best_hash


def test_image_trainer_accepts_plan_and_auto(tmp_path, clean_plan_globals):
    """The image engine takes a plan file (variant flip to shard_map) and
    the 'auto' knob (analytic search, pruned to what the config runs)."""
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine.loop import Trainer

    plan = Plan(engine="image", sync="explicit", grad_bucket_mb=25.0)
    path = tmp_path / "plan.json"
    path.write_text(plan.to_json())
    ledger = tmp_path / "img.jsonl"
    cfg = TrainConfig(dataset="synthetic", arch="lenet", batch_size=64,
                      synth_train_size=128, synth_val_size=64, epochs=1,
                      watchdog_factor=0, plan=str(path),
                      ledger_path=str(ledger))
    t = Trainer(cfg)
    assert t.cfg.variant == "shard_map"
    assert t.cfg.grad_bucket_mb == 25.0
    assert t._plan_info["hash"] == plan_hash(plan)
    t.fit()
    from tpu_dist.obs.ledger import read_ledger

    recs = read_ledger(str(ledger))
    assert [r for r in recs if r["event"] == "run_start"][0]["plan_hash"] \
        == plan_hash(plan)
    assert [r for r in recs if r["event"] == "plan"]
    # 'auto' must never break a working config: quant stays off for a
    # conv arch, and the resolved plan passes the engine's own validation
    cfg2 = TrainConfig(dataset="synthetic", arch="lenet", batch_size=64,
                       synth_train_size=256, synth_val_size=64, epochs=1,
                       watchdog_factor=0, plan="auto")
    t2 = Trainer(cfg2)
    assert t2._plan_info["source"] == "auto"
    assert t2.cfg.quant == "none"


def test_auto_plan_carries_unsearched_config_knobs(clean_plan_globals):
    """Review regression (PR 15): 'auto' tunes only what it searches —
    precision/grad accumulation/chunked CE/health stay the config's
    choice instead of being reset to Plan defaults."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.plan.compile import resolve_config_plan

    cfg = LMConfig(plan="auto", precision="bf16", grad_accum_steps=4,
                   loss_chunk=1024, health="skip", seq_len=32,
                   vocab_size=64, num_layers=1, d_model=32)
    out, info = resolve_config_plan(cfg)
    assert info is not None and info["source"] == "auto"
    assert out.precision == "bf16"
    assert out.grad_accum_steps == 4
    assert out.loss_chunk == 1024
    assert out.health == "skip"
    # accumulation legally excludes windowed/bucketed candidates, so the
    # chosen plan must not have flipped those on either
    assert out.steps_per_dispatch == 1 and out.grad_bucket_mb == 0.0


def test_block_env_seeds_are_validated():
    """Review regression (PR 15): the TPU_DIST_QUANT_BLOCKS /
    TPU_DIST_OPT_BLOCK_ROWS env seeds ride the validated setters (the ONE
    legality rule in plan.ir) — malformed values fail loudly at import,
    not as a Mosaic tiling abort at first trace."""
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import tpu_dist.ops.pallas_quant")
    env = dict(os.environ, PYTHONPATH=REPO,
               TPU_DIST_QUANT_BLOCKS="100,128,0")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "bm=100" in r.stderr
    env["TPU_DIST_QUANT_BLOCKS"] = "256"          # wrong arity
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "expected 'bm,bn,bk'" in r.stderr
    code2 = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
             "import tpu_dist.ops.pallas_sgd")
    env2 = dict(os.environ, PYTHONPATH=REPO,
                TPU_DIST_OPT_BLOCK_ROWS="100")
    r = subprocess.run([sys.executable, "-c", code2], env=env2, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "opt_block_rows=100" in r.stderr


def test_resolve_config_plan_none_is_noop():
    from tpu_dist.configs import LMConfig
    from tpu_dist.plan.compile import resolve_config_plan

    cfg = LMConfig()
    out, info = resolve_config_plan(cfg)
    assert out is cfg and info is None
    out, info = resolve_config_plan(LMConfig(plan="none"))
    assert info is None
