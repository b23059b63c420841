"""Engine integration tests on the 8-device mesh (SURVEY.md §4 plan).

Covers: loss decrease (convergence smoke), DDP-equiv vs horovod-equiv flavor
equivalence, single- vs multi-device update equivalence (the data-parallel
correctness property the reference could only test by training to accuracy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.data import make_transform
from tpu_dist.engine.state import TrainState, init_model
from tpu_dist.models import create_model
from tpu_dist.ops import make_optimizer
from tpu_dist.parallel.mesh import batch_sharding, make_mesh, replicated
from tpu_dist.plan.compile import (Bindings, compile_eval_step,
                                   compile_train_step)
from tpu_dist.plan.ir import Plan


def _setup(mesh, arch="lenet", lr=0.1, shape=(28, 28, 1)):
    model = create_model(arch)
    params, stats = init_model(model, jax.random.PRNGKey(0), (2,) + shape)
    tx = make_optimizer(lr, 0.9, 1e-4, steps_per_epoch=1000)
    state = jax.device_put(TrainState.create(params, stats, tx),
                           replicated(mesh))
    transform = make_transform(np.full(shape[-1:], 0.5, np.float32),
                               np.full(shape[-1:], 0.25, np.float32))
    return model, tx, state, transform


def _batch(n=64, shape=(28, 28, 1), seed=0):
    rng = np.random.default_rng(seed)
    imgs = rng.integers(0, 255, (n,) + shape).astype(np.uint8)
    labels = (imgs.astype(np.int32).sum(axis=(1, 2, 3)) % 10).astype(np.int32)
    return imgs, labels


def test_loss_decreases_on_learnable_batch():
    mesh = make_mesh()
    model, tx, state, transform = _setup(mesh)
    step = compile_train_step(
        Plan(engine="image"),
        Bindings(mesh=mesh, model=model, tx=tx, transform=transform))
    imgs, labels = _batch(64)
    sh = batch_sharding(mesh)
    imgs, labels = jax.device_put(imgs, sh), jax.device_put(labels, sh)
    rng = jax.random.PRNGKey(42)
    losses = []
    for _ in range(12):
        state, metrics = step(state, imgs, labels, rng)
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        m = jax.device_get(metrics)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert losses[-1] < losses[0] * 0.7, losses


class _MLP:
    """Tiny BN-free/dropout-free model: the flavor-equivalence property
    (grad of sharded-batch mean == psum of per-shard grad means) is exact
    only without batch-coupled layers (BN) or per-device RNG (dropout)."""

    def __new__(cls):
        import flax.linen as nn

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = True):
                x = x.reshape((x.shape[0], -1))
                x = nn.Dense(32)(x)
                x = nn.relu(x)
                return nn.Dense(10)(x)

        return MLP()


def test_jit_and_shard_map_flavors_agree_exactly():
    """DDP-equiv (compiler collectives) vs horovod-equiv (explicit psum)
    produce the same update — the TPU analog of reference variants 2 vs 5
    training identically. Exact for batch-decoupled models; BN models differ
    intentionally (global-batch vs per-replica statistics)."""
    mesh = make_mesh()
    model = _MLP()
    params, stats = init_model(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=1000)
    state = jax.device_put(TrainState.create(params, stats, tx),
                           replicated(mesh))
    transform = make_transform(np.full((1,), 0.5, np.float32),
                               np.full((1,), 0.25, np.float32))
    binds = Bindings(mesh=mesh, model=model, tx=tx, transform=transform)
    step_a = compile_train_step(Plan(engine="image", donate=False), binds)
    step_b = compile_train_step(
        Plan(engine="image", sync="explicit", donate=False), binds)
    imgs, labels = _batch(64)
    sh = batch_sharding(mesh)
    imgs, labels = jax.device_put(imgs, sh), jax.device_put(labels, sh)
    rng = jax.random.PRNGKey(0)

    sa, ma = step_a(state, imgs, labels, rng)
    sb, mb = step_b(state, imgs, labels, rng)
    for k in ("loss_sum", "correct1", "correct5", "count"):
        assert float(jax.device_get(ma[k])) == pytest.approx(
            float(jax.device_get(mb[k])), rel=1e-5), k
    fa = jnp.concatenate([x.ravel() for x in jax.tree.leaves(sa.params)])
    fb = jnp.concatenate([x.ravel() for x in jax.tree.leaves(sb.params)])
    np.testing.assert_allclose(np.asarray(fa), np.asarray(fb),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_single_vs_multi_device_same_update():
    """Data parallelism must not change the math: 1-device mesh and 8-device
    mesh see the same global batch -> same params after one step."""
    mesh8 = make_mesh()
    mesh1 = make_mesh(devices=jax.devices()[:1])
    model, tx, state8, transform = _setup(mesh8, arch="resnet18",
                                          shape=(32, 32, 3))
    _, _, state1, _ = _setup(mesh1, arch="resnet18", shape=(32, 32, 3))
    plan = Plan(engine="image", donate=False)
    step8 = compile_train_step(plan, Bindings(
        mesh=mesh8, model=model, tx=tx, transform=transform))
    step1 = compile_train_step(plan, Bindings(
        mesh=mesh1, model=model, tx=tx, transform=transform))
    imgs, labels = _batch(64, (32, 32, 3))
    rng = jax.random.PRNGKey(1)
    s8, _ = step8(state8, jax.device_put(imgs, batch_sharding(mesh8)),
                  jax.device_put(labels, batch_sharding(mesh8)), rng)
    s1, _ = step1(state1, jax.device_put(imgs, batch_sharding(mesh1)),
                  jax.device_put(labels, batch_sharding(mesh1)), rng)
    f8 = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s8.params)])
    f1 = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s1.params)])
    np.testing.assert_allclose(f8, f1, rtol=1e-4, atol=1e-6)


def test_eval_step_counts_mask_padding():
    mesh = make_mesh()
    model, tx, state, transform = _setup(mesh)
    estep = compile_eval_step(
        Plan(engine="image"),
        Bindings(mesh=mesh, model=model, eval_transform=transform))
    imgs, labels = _batch(32)
    sh = batch_sharding(mesh)
    # last 8 samples marked as sampler padding -> excluded from every metric
    valid = np.concatenate([np.ones(24, np.float32), np.zeros(8, np.float32)])
    m = jax.device_get(estep(state.params, state.batch_stats,
                             jax.device_put(imgs, sh),
                             jax.device_put(labels, sh),
                             jax.device_put(valid, sh)))
    assert float(m["count"]) == 24.0
    assert 0.0 <= float(m["correct1"]) <= 24.0
    assert float(m["correct5"]) >= float(m["correct1"])


def test_grad_compression_still_converges():
    mesh = make_mesh()
    model, tx, state, transform = _setup(mesh)
    step = compile_train_step(
        Plan(engine="image", sync="explicit", grad_compression="bf16"),
        Bindings(mesh=mesh, model=model, tx=tx, transform=transform))
    imgs, labels = _batch(64)
    sh = batch_sharding(mesh)
    imgs, labels = jax.device_put(imgs, sh), jax.device_put(labels, sh)
    rng = jax.random.PRNGKey(2)
    first = last = None
    for i in range(10):
        state, metrics = step(state, imgs, labels, rng)
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        m = jax.device_get(metrics)
        loss = float(m["loss_sum"]) / float(m["count"])
        first = loss if first is None else first
        last = loss
    assert last < first


@pytest.mark.slow  # tier-1 budget (PR 15): the stacked and indexed windows
# wrap the ONE step template through the ONE plan-compiler window pass now;
# in-budget siblings: tests/test_plan.py::test_image_plan_loss_parity_
# across_modes (stacked == sequential, bit-level) and
# test_indexed_multi_step_equals_host_batches below (the indexed twin)
def test_multi_step_equals_sequential_steps():
    """K steps in one scan dispatch == K sequential jit dispatches."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    model = _MLP()
    params, stats = init_model(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=1000)
    state0 = jax.device_put(TrainState.create(params, stats, tx),
                            replicated(mesh))
    transform = make_transform(np.full((1,), 0.5, np.float32),
                               np.full((1,), 0.25, np.float32))
    binds = Bindings(mesh=mesh, model=model, tx=tx, transform=transform)
    single = compile_train_step(Plan(engine="image", donate=False), binds)
    multi = compile_train_step(
        Plan(engine="image", window="stacked", donate=False), binds)

    k, b = 3, 32
    rng_np = np.random.default_rng(0)
    imgs = rng_np.integers(0, 255, (k, b, 28, 28, 1)).astype(np.uint8)
    lbls = rng_np.integers(0, 10, (k, b)).astype(np.int32)
    key = jax.random.PRNGKey(7)

    sh = batch_sharding(mesh)
    s_seq = state0
    total = 0.0
    for i in range(k):
        # distlint: disable=DL008 -- CPU equivalence test stages its own per-step operands; no input pipeline in play
        s_seq, m = single(s_seq, jax.device_put(imgs[i], sh),
                          jax.device_put(lbls[i], sh), key)
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        total += float(jax.device_get(m["loss_sum"]))

    sh2 = NamedSharding(mesh, P(None, "data"))
    s_multi, m_multi = multi(state0, jax.device_put(imgs, sh2),
                             jax.device_put(lbls, sh2), key)
    assert float(jax.device_get(m_multi["loss_sum"])) == pytest.approx(total, rel=1e-5)
    fa = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s_seq.params)])
    fb = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s_multi.params)])
    np.testing.assert_allclose(fa, fb, rtol=1e-5, atol=1e-7)
    assert int(jax.device_get(s_multi.step)) == k


def test_grad_accum_equals_big_batch():
    """K microbatches accumulated == one step over the concatenated batch
    (exact for batch-decoupled models)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    model = _MLP()
    params, stats = init_model(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=1000)
    state0 = jax.device_put(TrainState.create(params, stats, tx),
                            replicated(mesh))
    transform = make_transform(np.full((1,), 0.5, np.float32),
                               np.full((1,), 0.25, np.float32))
    binds = Bindings(mesh=mesh, model=model, tx=tx, transform=transform)
    big = compile_train_step(Plan(engine="image", donate=False), binds)
    accum = compile_train_step(
        Plan(engine="image", grad_accum_steps=4, donate=False), binds)

    k, b = 4, 16
    imgs, labels = _batch(k * b)
    key = jax.random.PRNGKey(3)
    s_big, m_big = big(state0, jax.device_put(imgs, batch_sharding(mesh)),
                       jax.device_put(labels, batch_sharding(mesh)), key)
    sh2 = NamedSharding(mesh, P(None, "data"))
    s_acc, m_acc = accum(state0,
                         jax.device_put(imgs.reshape(k, b, 28, 28, 1), sh2),
                         jax.device_put(labels.reshape(k, b), sh2), key)
    assert float(jax.device_get(m_acc["count"])) == k * b
    assert float(jax.device_get(m_acc["loss_sum"])) == pytest.approx(
        float(jax.device_get(m_big["loss_sum"])), rel=1e-5)
    fa = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(s_big.params)])
    fb = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(s_acc.params)])
    np.testing.assert_allclose(fa, fb, rtol=1e-5, atol=1e-7)


def test_indexed_multi_step_equals_host_batches():
    """Device-resident dataset + (K,B) index window == host-fed batches."""
    from tpu_dist.engine.steps import pack_images_for_device
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    model = _MLP()
    params, stats = init_model(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=1000)
    state0 = jax.device_put(TrainState.create(params, stats, tx),
                            replicated(mesh))
    transform = make_transform(np.full((1,), 0.5, np.float32),
                               np.full((1,), 0.25, np.float32))
    binds = Bindings(mesh=mesh, model=model, tx=tx, transform=transform,
                     image_shape=(28, 28, 1))
    single = compile_train_step(Plan(engine="image", donate=False), binds)
    indexed = compile_train_step(
        Plan(engine="image", window="indexed", donate=False), binds)

    n, k, b = 256, 3, 32
    rng_np = np.random.default_rng(1)
    images_all = rng_np.integers(0, 255, (n, 28, 28, 1)).astype(np.uint8)
    labels_all = rng_np.integers(0, 10, (n,)).astype(np.int32)
    idx = rng_np.integers(0, n, (k, b)).astype(np.int32)
    key = jax.random.PRNGKey(7)

    sh = batch_sharding(mesh)
    s_seq = state0
    for i in range(k):
        # distlint: disable=DL008 -- CPU equivalence test stages its own per-step operands; no input pipeline in play
        s_seq, _ = single(s_seq, jax.device_put(images_all[idx[i]], sh),
                          jax.device_put(labels_all[idx[i]], sh), key)

    packed = pack_images_for_device(images_all)
    assert packed.dtype == np.int32  # 28*28*1 is word-divisible -> packed path
    repl = replicated(mesh)
    s_idx, m = indexed(state0, jax.device_put(packed, repl),
                       jax.device_put(labels_all, repl),
                       jax.device_put(idx, NamedSharding(mesh, P(None, "data"))),
                       key)
    assert float(jax.device_get(m["count"])) == k * b
    fa = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(s_seq.params)])
    fb = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(s_idx.params)])
    np.testing.assert_allclose(fa, fb, rtol=1e-5, atol=1e-7)
    assert int(jax.device_get(s_idx.step)) == k


def _trainer_params(tmp, k, placement="auto", epochs=1):
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    cfg = TrainConfig(dataset="synthetic-mnist", arch="lenet", epochs=epochs,
                      batch_size=64, synth_train_size=320, synth_val_size=64,
                      seed=11, print_freq=100, checkpoint_dir=tmp,
                      steps_per_dispatch=k, data_placement=placement)
    tr = Trainer(cfg)
    tr.fit()
    return tr, np.concatenate([np.asarray(jax.device_get(x)).ravel()
                               for x in jax.tree.leaves(tr.state.params)])


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_trainer_windowed_device_data_matches_per_batch(tmp_path):
    """steps_per_dispatch=4 + HBM-resident dataset == the per-batch loop."""
    tr1, p1 = _trainer_params(str(tmp_path / "a"), k=1)
    tr4, p4 = _trainer_params(str(tmp_path / "b"), k=4)
    assert tr1.device_data is False and tr4.device_data is True
    assert (int(jax.device_get(tr1.state.step))
            == int(jax.device_get(tr4.state.step)) == 5)  # ceil(320/64)
    np.testing.assert_allclose(p1, p4, rtol=1e-5, atol=1e-7)


@pytest.mark.slow  # tier-1 budget (PR 7): near-duplicate of the device-data windowed parity (already slow); windowed train+eval stay exercised in-budget by test_windowed_eval_matches_host_eval
def test_trainer_windowed_host_mode_matches_per_batch(tmp_path):
    """steps_per_dispatch=2 with host-stacked windows (tail window of 1)."""
    _, p1 = _trainer_params(str(tmp_path / "a"), k=1)
    tr2, p2 = _trainer_params(str(tmp_path / "b"), k=2, placement="host")
    assert tr2.device_data is False
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-7)


def test_trainer_grad_accum_wiring(tmp_path):
    """--grad-accum-steps 2 through the Trainer: one optimizer step per
    GLOBAL batch (not per microbatch), metrics count every sample, and the
    model still learns. (Bit-exactness vs the big-batch step is covered by
    test_grad_accum_equals_big_batch; Trainer runs can't bit-match because
    dropout keys fold per microbatch.)"""
    import pytest
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    cfg = TrainConfig(dataset="synthetic-mnist", arch="lenet", epochs=2,
                      batch_size=64, synth_train_size=192, synth_val_size=64,
                      seed=11, print_freq=100, grad_accum_steps=2,
                      checkpoint_dir=str(tmp_path))
    tr = Trainer(cfg)
    first = tr.train_epoch(0)
    second = tr.train_epoch(1)
    # 3 global batches/epoch -> 3 optimizer steps each, NOT 6
    assert int(jax.device_get(tr.state.step)) == 6
    assert second["loss"] < first["loss"]
    assert tr.validate(0) > 0.3  # learnable synthetic data separates fast

    # invalid combos fail fast
    with pytest.raises(ValueError):
        Trainer(TrainConfig(dataset="synthetic-mnist", arch="lenet",
                            batch_size=64, synth_train_size=192,
                            grad_accum_steps=2, variant="shard_map"))
    with pytest.raises(ValueError):
        Trainer(TrainConfig(dataset="synthetic-mnist", arch="lenet",
                            batch_size=64, synth_train_size=192,
                            grad_accum_steps=2, steps_per_dispatch=4))


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_trainer_windowed_mid_epoch_resume_step_exact(tmp_path):
    """Interrupt between windows, resume -> same params as uninterrupted."""
    import os
    import pytest
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    kw = dict(dataset="synthetic-mnist", arch="lenet", epochs=1,
              batch_size=64, synth_train_size=320, synth_val_size=64,
              seed=11, print_freq=100, steps_per_dispatch=2)
    _, p_full = _trainer_params(str(tmp_path / "full"), k=2)

    tr_int = Trainer(TrainConfig(checkpoint_dir=str(tmp_path / "int"), **kw))
    real = tr_int.window_step
    calls = {"n": 0}

    def limited(*a, **kws):
        if calls["n"] == 2:  # after 2 windows = 4 of 5 batches
            raise KeyboardInterrupt
        calls["n"] += 1
        return real(*a, **kws)

    tr_int.window_step = limited
    with pytest.raises(KeyboardInterrupt):
        tr_int.fit()

    ck = os.path.join(str(tmp_path / "int"), "lenet-checkpoint.msgpack")
    tr_res = Trainer(TrainConfig(checkpoint_dir=str(tmp_path / "res"),
                                 resume=ck, **kw))
    assert tr_res._skip_batches == 4
    tr_res.fit()
    p_res = np.concatenate([np.asarray(jax.device_get(x)).ravel()
                            for x in jax.tree.leaves(tr_res.state.params)])
    np.testing.assert_allclose(p_full, p_res, rtol=1e-5, atol=1e-7)


def test_windowed_eval_matches_host_eval(tmp_path):
    """One-dispatch HBM-resident eval == the host-fed per-batch eval,
    including sampler-padding masking (exact sums both ways)."""
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    cfg = TrainConfig(dataset="synthetic-mnist", arch="lenet", epochs=1,
                      batch_size=64, synth_train_size=256,
                      synth_val_size=150,  # NOT a batch multiple: padding
                      seed=2, print_freq=100, steps_per_dispatch=4,
                      checkpoint_dir=str(tmp_path))
    tr = Trainer(cfg)
    assert tr._val_data_dev is not None
    tr.train_epoch(0)
    acc_dev = tr.validate(0)
    tr._val_data_dev = None  # force the host-fed path on the same state
    acc_host = tr.validate(0)
    assert acc_dev == acc_host


def _toy_trainer(tmp, **kw):
    from tpu_dist.configs import TrainConfig
    from tpu_dist.engine import Trainer

    kw = {"dataset": "synthetic-cifar10", "arch": "resnet18", **kw}
    return Trainer(TrainConfig(
        batch_size=64, synth_train_size=128, synth_val_size=64, seed=5,
        print_freq=100, checkpoint_dir=str(tmp), **kw))


def _assert_same_bits(got, want):
    """Leaf for leaf the same dtype, shape and bytes. The one exception is
    a ViT's ``pos_embed``: ``initializers.normal(0.02)`` is
    ``random.normal`` (sqrt(2) x erf_inv) times 0.02, and one program
    rounds the two factors' product once where two programs round twice:
    a last bit, in a quarter of the leaf."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        path = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, path
        a, b = np.asarray(a), np.asarray(b)
        if path.endswith("['pos_embed']"):
            assert np.abs(a.view(np.int32).astype(np.int64)
                          - b.view(np.int32)).max() <= 1, path
        else:
            assert a.tobytes() == b.tobytes(), path


@pytest.mark.parametrize("arch,kw", [
    ("resnet50", dict(optimizer="sgd", precision="bf16")),
    ("resnet18", dict(optimizer="fused_sgd")),
    ("resnet18", dict(optimizer="sgd", precision="bf16_params",
                      loss_scale=1024.0)),
    ("vit_tiny", dict(optimizer="adamw", loss_scale=2.0 ** 15)),
])
def test_trainer_state_is_bitwise_the_eager_one(tmp_path, arch, kw):
    """The state ``Trainer()`` makes in ONE compiled program equals, on the
    CPU backend, what eager ``model.init`` + storage cast + ``tx.init`` +
    loss scale gave, bit for bit, and is born replicated over the mesh."""
    from tpu_dist.ops import LossScaleState

    tr = _toy_trainer(tmp_path, arch=arch, **kw)
    h, w, c = tr.train_ds.image_shape
    variables = tr.model.init(
        {"params": tr.rng, "dropout": tr.rng},
        jnp.zeros((2, h, w, c), jnp.float32), train=False)
    eager = (variables["params"], variables.get("batch_stats", {}))
    want = TrainState.create(
        tr.policy.cast_params_for_storage(eager[0]), eager[1], tr.tx,
        LossScaleState.create(tr.cfg.loss_scale)
        if tr.cfg.loss_scale else None)
    assert len(jax.tree.leaves(tr.state)) > 8
    _assert_same_bits(tr.state, want)
    for leaf in jax.tree.leaves(tr.state):
        assert leaf.sharding.is_equivalent_to(replicated(tr.mesh), leaf.ndim)
    # the one definition of "initialise this model", called on its own
    _assert_same_bits(init_model(tr.model, tr.rng, (2, h, w, c)), eager)


# what Trainer() may compile: the state's program, the PRNG key's, the
# device-resident rows' placement; eager flax init alone made 56 for
# resnet18 and 203 for resnet50
_BUILD_COMPILES_AT_MOST = 6


@pytest.mark.parametrize("variant,k", [("jit", 2), ("shard_map", 1)])
def test_trainer_constructor_makes_a_handful_of_compilations(tmp_path,
                                                             variant, k):
    from tpu_dist.obs import trace

    tr = _toy_trainer(tmp_path, variant=variant, steps_per_dispatch=k)
    info = tr.build_info
    assert 1 <= info["build_compiles"] <= _BUILD_COMPILES_AT_MOST, info
    # the rows are placed on the device only for the windowed path
    parts = ["data", "init"] + ["place"] * tr.device_data
    assert tr.device_data == (k > 1)
    assert list(info["build_s"]) == ["total"] + parts
    assert info["build_s"]["total"] >= sum(info["build_s"][p] for p in parts)
    spans = trace.ring().snapshot()
    build = [s for s in spans if s.name == "train.build"][-1]
    assert [s.name for s in spans if s.parent == build.sid
            and s.name != "host.gc"] == ["build." + p for p in parts]
