"""Comm/compute overlap subsystem (parallel.overlap): ring collective
matmul, ring allreduce, bucketed gradient sync, and their engine plumbing.

In-budget tests keep models tiny (2 layers, d32) and assert EXACT-shape /
allclose parity of the decomposed collectives against their fused
references on the virtual 8-device mesh; full trainer-level dp x tp ring
parity, the ring x int8 composition, and the ViT ring Trainer run are
marked slow (each carries multi-program XLA compiles), as are the
model-level forward-parity and engine-step-parity checks — the same
decompositions are pinned in-budget at the function level, keeping this
file's tier-1 footprint to a few seconds."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist._compat import shard_map
from tpu_dist.parallel.collectives import ring_allreduce
from tpu_dist.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from tpu_dist.parallel.overlap import (bucketed_grad_sync, grad_buckets,
                                       ring_allgather_matmul,
                                       ring_matmul_reduce_scatter,
                                       validate_tp_impl)


def _model_mesh(n):
    return make_mesh((n,), (MODEL_AXIS,), devices=jax.devices()[:n])


# ------------------------------------------------------- ring allreduce
def test_ring_allreduce_matches_psum():
    """Chunked two-pass ppermute ring == fused psum, including a length
    that does not divide the axis size (internal padding)."""
    mesh = make_mesh()
    for shape in ((13,), (4, 5), (8, 16)):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8,) + shape),
                        jnp.float32)

        def run(f):
            g = shard_map(lambda v: f(v[0])[None], mesh=mesh,
                          in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
                          check_vma=False)
            return np.asarray(jax.jit(g)(x))

        ring = run(lambda v: ring_allreduce(v, DATA_AXIS, 8))
        fused = run(lambda v: jax.lax.psum(v, DATA_AXIS))
        np.testing.assert_allclose(ring, fused, rtol=1e-6, atol=1e-6)


# ------------------------------------------------- ring collective matmul
def test_ring_collective_matmul_matches_einsum():
    """AG-matmul and matmul-RS return EXACTLY the shapes of the fused
    einsums they decompose, with values allclose — and the quantized
    matmul rides the same ring within int8 tolerance."""
    n, b, L, D, F = 4, 2, 16, 12, 24
    mesh = _model_mesh(n)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, L, D)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(D, F)) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(F, D)) * 0.2, jnp.float32)

    def pair(xs, a, c):
        h = ring_allgather_matmul(xs, a, MODEL_AXIS)
        assert h.shape == (b, L, F // n)      # exact shape of x@a's shard
        out = ring_matmul_reduce_scatter(h, c, MODEL_AXIS)
        assert out.shape == (b, L // n, D)    # exact shape of (x@a)@c's shard
        return h, out

    f = jax.jit(shard_map(
        pair, mesh=mesh,
        in_specs=(P(None, MODEL_AXIS, None), P(None, MODEL_AXIS),
                  P(MODEL_AXIS, None)),
        out_specs=(P(None, None, MODEL_AXIS), P(None, MODEL_AXIS, None)),
        check_vma=False))
    h, out = f(x, w1, w2)
    ref_h = jnp.einsum("bld,df->blf", x, w1)
    assert h.shape == ref_h.shape and out.shape == x.shape
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.einsum("blf,fd->bld",
                                                     ref_h, w2)),
                               rtol=1e-5, atol=1e-5)

    # int8 composition: the per-chunk quantized matmul (ops.quant) — scales
    # are per activation row / per weight channel, so chunking the sequence
    # preserves them; parity with the fused quant einsum is loss-of-
    # precision-level, not bitwise (accumulation order)
    from tpu_dist.ops.quant import quant_matmul

    mm8 = lambda a, c: quant_matmul(a, c, "int8")
    f8 = jax.jit(shard_map(
        lambda xs, a: ring_allgather_matmul(xs, a, MODEL_AXIS, matmul=mm8),
        mesh=mesh, in_specs=(P(None, MODEL_AXIS, None), P(None, MODEL_AXIS)),
        out_specs=P(None, None, MODEL_AXIS), check_vma=False))
    ref8 = quant_matmul(x, w1, "int8")
    np.testing.assert_allclose(np.asarray(f8(x, w1)), np.asarray(ref8),
                               rtol=5e-2, atol=5e-2)


# --------------------------------------------------- bucketed grad sync
def test_grad_buckets_rules():
    """Size-targeted grouping: consecutive fill, oversized leaf alone,
    dtype change closes a bucket."""
    mk = lambda size, dt=jnp.float32: jnp.zeros((size,), dt)
    leaves = [mk(100), mk(100), mk(10_000), mk(50), mk(50, jnp.bfloat16)]
    groups = grad_buckets(leaves, bucket_bytes=1000)
    assert groups == [[0, 1], [2], [3], [4]]
    assert grad_buckets([mk(10)], 1.0) == [[0]]  # oversized still buckets


def test_bucketed_grad_sync_matches_monolithic():
    """The decomposed bucket reduce-scatter+all-gather sync == per-leaf
    pmean, across ragged shapes, several buckets, and both impls."""
    mesh = make_mesh()
    rng = np.random.default_rng(1)
    tree = {"a": rng.normal(size=(8, 37)), "b": rng.normal(size=(8, 3, 5)),
            "c": rng.normal(size=(8, 501)), "d": rng.normal(size=(8, 2))}
    tree = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), tree)

    def run(f):
        g = shard_map(
            lambda t: jax.tree.map(lambda v: v[None],
                                   f(jax.tree.map(lambda u: u[0], t))),
            mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
            check_vma=False)
        out = jax.jit(g)(tree)
        return {k: np.asarray(v)[0] for k, v in out.items()}

    mono = run(lambda t: jax.tree.map(
        lambda g: jax.lax.pmean(g, DATA_AXIS), t))
    for impl in ("rs_ag", "ring"):
        buck = run(lambda t: bucketed_grad_sync(
            t, DATA_AXIS, bucket_mb=0.001, mean=True, axis_size=8,
            impl=impl))
        for k in mono:
            np.testing.assert_allclose(buck[k], mono[k], rtol=1e-5,
                                       atol=1e-6, err_msg=f"{impl}:{k}")


# ------------------------------------------------- model-level ring parity
def _tiny_lm(**kw):
    from tpu_dist.models.transformer import tiny_lm
    return tiny_lm(vocab_size=64, num_layers=2, d_model=32, num_heads=4,
                   max_len=32, **kw)


@pytest.mark.slow
def test_ring_lm_forward_parity():
    """tp_impl='ring' TransformerLM == the plain model, from the SAME
    params (the trees are identical by construction): logits assembled
    from the per-device seq chunks match the fused forward."""
    n = 4
    mesh = _model_mesh(n)
    model = _tiny_lm()
    tokens = np.random.default_rng(0).integers(0, 64, (2, 16)).astype(
        np.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens,
                        train=False)["params"]
    ref = model.apply({"params": params}, tokens, train=False)
    ring = model.clone(tp_impl="ring")
    f = jax.jit(shard_map(
        lambda p, t: ring.apply({"params": p}, t, train=False),
        mesh=mesh, in_specs=(P(), P()),
        out_specs=P(None, MODEL_AXIS, None), check_vma=False))
    out = f(params, tokens)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_vit_ring_forward_parity():
    """ViT maps tp_impl='ring' onto the full-token ring_ar flavor (the
    [CLS] token forbids an even sequence split): logits match the plain
    model from the same params."""
    from tpu_dist.models.vit import ViT

    n = 4
    mesh = _model_mesh(n)
    model = ViT(num_classes=5, patch_size=4, num_layers=2, d_model=32,
                num_heads=4)
    x = np.random.default_rng(0).normal(size=(2, 8, 8, 3)).astype(
        np.float32)
    params = model.init({"params": jax.random.PRNGKey(0)}, x,
                        train=False)["params"]
    ref = model.apply({"params": params}, x, train=False)
    ring = model.clone(tp_impl="ring")
    f = jax.jit(shard_map(
        lambda p, t: ring.apply({"params": p}, t, train=False),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(params, x)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ engine step parity
@pytest.mark.slow
def test_lm_bucketed_step_matches_jit_dp():
    """One optimizer step through the explicit bucketed-sync dp step ==
    the jit/GSPMD dp step (loss equal, updated params allclose)."""
    from tpu_dist.plan.compile import Bindings, compile_train_step
    from tpu_dist.plan.ir import Plan
    from tpu_dist.engine.state import TrainState
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.mesh import replicated

    mesh = make_mesh()
    model = _tiny_lm()
    rows = np.random.default_rng(0).integers(0, 64, (8, 17)).astype(
        np.int32)
    inputs, targets = rows[:, :-1], rows[:, 1:]
    params = model.init({"params": jax.random.PRNGKey(0)}, inputs,
                        train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=100)
    state = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh))
    key = jax.random.PRNGKey(1)
    st_jit, m_jit = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))(
        state, inputs, targets, key)
    st_b, m_b = compile_train_step(
        Plan(engine="lm", sync="explicit", grad_bucket_mb=0.0005,
             donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))(
        state, inputs, targets, key)
    assert float(m_jit["loss_sum"]) == pytest.approx(
        float(m_b["loss_sum"]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(st_jit.params),
                    jax.tree.leaves(st_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------- knob gating
def test_overlap_knob_validation():
    from tpu_dist.configs import LMConfig, TrainConfig
    from tpu_dist.engine import Trainer
    from tpu_dist.engine.lm_loop import LMTrainer

    with pytest.raises(ValueError, match="tp_impl"):
        validate_tp_impl("nccl")
    lm = dict(synth_tokens=2000, vocab_size=64, seq_len=32, num_layers=1,
              d_model=32, num_heads=4, batch_size=8, epochs=1, seed=0)
    with pytest.raises(ValueError, match="seq_len"):
        LMTrainer(LMConfig(mesh_shape=(2, 4), mesh_axes=("data", "model"),
                           tp_impl="ring", **{**lm, "seq_len": 30}))
    with pytest.raises(ValueError, match="pure-dp"):
        LMTrainer(LMConfig(fsdp=True, grad_bucket_mb=25.0, **lm))
    img = dict(dataset="synthetic-mnist", arch="lenet", epochs=1,
               batch_size=16, synth_train_size=32, synth_val_size=16)
    # the mode exclusions are the plan's (plan.ir.Plan.validate), raised
    # when the trainer derives its plan; PlanError is a ValueError
    with pytest.raises(ValueError, match="sync='explicit'"):
        Trainer(TrainConfig(grad_bucket_mb=25.0, **img))
    with pytest.raises(ValueError, match="lm tp/sp layouts"):
        LMTrainer(LMConfig(mesh_shape=(2, 4), mesh_axes=("data", "model"),
                           grad_bucket_mb=25.0, **lm))
    with pytest.raises(ValueError, match="vit"):
        Trainer(TrainConfig(variant="shard_map", tp_impl="ring", **img))
    with pytest.raises(ValueError, match="num_heads"):
        # vit_tiny's 3 heads cannot split over a 2-wide model axis
        Trainer(TrainConfig(variant="shard_map", tp_impl="ring",
                            mesh_shape=(4, 2), mesh_axes=("data", "model"),
                            dataset="synthetic-cifar10", arch="vit_tiny",
                            epochs=1, batch_size=16, synth_train_size=32,
                            synth_val_size=16))


# ------------------------------------------------------------ comm bench
def test_comm_bench_cli(tmp_path):
    """tools/comm_bench.py runs green at tiny sizes and its ledger step
    records carry a MEASURED comm phase."""
    from tools.comm_bench import main
    from tpu_dist.obs import read_ledger

    path = str(tmp_path / "comm.jsonl")
    rc = main(["--sizes-mb", "0.01", "--dims", "16,16,32", "--iters", "1",
               "--bucket-mb", "0.005", "--ledger", path])
    assert rc == 0
    steps = [r for r in read_ledger(path) if r["event"] == "step"]
    assert steps and all(r["comm_s"] is not None and r["comm_s"] > 0
                         for r in steps)
    assert any(r["label"].startswith("matmul") for r in steps)


# ----------------------------------------------------------------- slow
@pytest.mark.slow
def test_ring_tp_trainer_loss_parity_vs_gspmd():
    """Full dp x tp train parity at the trainer level: tp_impl='ring' and
    the GSPMD TP engine reach the SAME val loss from the same seed (the
    acceptance bar: losses allclose on a multi-device CPU mesh)."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    base = dict(synth_tokens=8000, vocab_size=64, seq_len=32, num_layers=2,
                d_model=32, num_heads=4, batch_size=8, epochs=1, seed=0,
                lr=0.05, print_freq=100,
                mesh_shape=(2, 4), mesh_axes=("data", "model"))
    t_gspmd = LMTrainer(LMConfig(**base))
    t_gspmd.train_epoch(0)
    loss_gspmd = t_gspmd.validate(0)[0]
    t_ring = LMTrainer(LMConfig(tp_impl="ring", **base))
    assert t_ring.mode == "tp-ring"
    t_ring.train_epoch(0)
    loss_ring = t_ring.validate(0)[0]
    assert loss_ring == pytest.approx(loss_gspmd, rel=1e-4)


@pytest.mark.slow
def test_ring_int8_quant_composition():
    """quant='int8' rides the ring: the QuantDense int8 matmul runs inside
    the collective matmul chunks. Scales are per-shard (finer than GSPMD's
    global per-row amax), so parity with the GSPMD int8 path is loss-level,
    and both track the fp loss closely at init."""
    from tpu_dist.plan.compile import Bindings, compile_train_step
    from tpu_dist.plan.ir import Plan
    from tpu_dist.engine.state import TrainState
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.mesh import replicated
    from tpu_dist.parallel.tp import shard_lm_params

    mesh = make_mesh((2, 4), ("data", "model"))
    model = _tiny_lm(quant="int8")
    rows = np.random.default_rng(0).integers(0, 64, (8, 17)).astype(
        np.int32)
    inputs, targets = rows[:, :-1], rows[:, 1:]
    params = model.init({"params": jax.random.PRNGKey(0)}, inputs,
                        train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=100)
    key = jax.random.PRNGKey(1)

    from tpu_dist.engine.state import TrainState as TS
    tp_state = TS.create(params, {}, tx)
    tp_state = TS(step=jax.device_put(tp_state.step,
                                      NamedSharding(mesh, P())),
                  params=shard_lm_params(mesh, tp_state.params),
                  batch_stats={},
                  opt_state=jax.device_put(tp_state.opt_state,
                                           NamedSharding(mesh, P())),
                  loss_scale=None)
    gspmd_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    ring_state = jax.device_put(TrainState.create(params, {}, tx),
                                replicated(mesh))
    ring_step = compile_train_step(
        Plan(engine="lm", layout="tp", sync="explicit", tp_impl="ring",
             donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    losses = {"gspmd": [], "ring": []}
    for _ in range(3):
        tp_state, m1 = gspmd_step(tp_state, inputs, targets, key)
        ring_state, m2 = ring_step(ring_state, inputs, targets, key)
        losses["gspmd"].append(float(m1["loss_sum"]) / float(m1["count"]))
        losses["ring"].append(float(m2["loss_sum"]) / float(m2["count"]))
    np.testing.assert_allclose(losses["ring"], losses["gspmd"],
                               rtol=5e-2)
    assert losses["ring"][-1] < losses["ring"][0]  # it trains


@pytest.mark.slow
def test_vit_ring_trainer_matches_replicated():
    """The image engine's --tp-impl ring (ViT, variant shard_map, model
    mesh axis) matches the model-axis-replicated run batch for batch."""
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    base = dict(dataset="synthetic-cifar10", arch="vit_cifar", epochs=1,
                batch_size=64, synth_train_size=128, synth_val_size=64,
                seed=3, print_freq=100, lr=0.01, variant="shard_map",
                mesh_shape=(4, 2), mesh_axes=("data", "model"))
    ring = Trainer(TrainConfig(tp_impl="ring", **base)).train_epoch(0)
    repl = Trainer(TrainConfig(**base)).train_epoch(0)
    assert ring["loss"] == pytest.approx(repl["loss"], rel=1e-3)
