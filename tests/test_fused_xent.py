"""Chunked vocab cross-entropy (ops.fused_xent) == the full-logits loss.

The chunked path exists so the (B, L, V) fp32 logits never materialize; these
tests pin that it is the SAME objective — value, metrics, and gradients wrt
features and head weight — including ragged row counts that need padding, the
bf16 compute path, and the end-to-end LMTrainer flag in jit and sp modes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.engine.lm_steps import lm_loss_and_metrics
from tpu_dist.ops.fused_xent import chunked_softmax_xent


def _case(b=2, l=24, d=16, v=97, seed=0, mask_frac=0.3):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, l, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, v) / np.sqrt(d), jnp.float32)
    t = jnp.asarray(rng.randint(0, v, (b, l)), jnp.int32)
    m = jnp.asarray(rng.rand(b, l) > mask_frac, jnp.float32)
    return x, w, t, m


def _full(x, w, t, m):
    logits = (x @ w).astype(jnp.float32)
    return lm_loss_and_metrics(logits, t, m)


@pytest.mark.parametrize("chunk", [1, 7, 16, 48, 4096])
def test_forward_matches_full(chunk):
    """Loss sum and correct1 equal the full-logits reference for chunk sizes
    that divide, straddle, and exceed the row count (B*L=48)."""
    x, w, t, m = _case()
    loss, correct = chunked_softmax_xent(x, w, t, m, chunk)
    loss_ref, metrics_ref = _full(x, w, t, m)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
    np.testing.assert_allclose(float(correct), float(metrics_ref["correct1"]),
                               rtol=0)


def test_gradients_match_full():
    """d(mean loss)/dx and /dw equal the full-logits path to fp32 tolerance —
    the custom_vjp recompute is the same math, not an approximation."""
    x, w, t, m = _case(seed=1)
    count = jnp.sum(m)

    def loss_chunked(x, w):
        loss, _ = chunked_softmax_xent(x, w, t, m, 13)
        return loss / count

    def loss_full(x, w):
        loss, _ = _full(x, w, t, m)
        return loss / count

    gx_c, gw_c = jax.grad(loss_chunked, argnums=(0, 1))(x, w)
    gx_f, gw_f = jax.grad(loss_full, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_f),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_f),
                               rtol=1e-4, atol=1e-6)


def test_masked_rows_contribute_nothing():
    """A fully-masked row changes neither the loss nor any gradient — padding
    rows (sampler wrap, chunk pad) are inert."""
    x, w, t, m = _case(seed=2, mask_frac=0.0)
    m = m.at[1, :].set(0.0)
    x_wild = x.at[1].set(1e3)  # garbage in the masked row

    def loss(x):
        return chunked_softmax_xent(x, w, t, m, 16)[0]

    np.testing.assert_allclose(float(loss(x)), float(loss(x_wild)), rtol=1e-6)
    g = jax.grad(loss)(x_wild)
    assert float(jnp.max(jnp.abs(g[1]))) == 0.0


def test_bf16_compute_close_to_fp32():
    """The bf16 head matmul (fp32 accumulation) stays within bf16 rounding of
    the fp32 loss — the policy the LM bf16 precision mode uses."""
    x, w, t, m = _case(seed=3)
    loss16, _ = chunked_softmax_xent(x, w, t, m, 16, jnp.bfloat16)
    loss32, _ = _full(x, w, t, m)
    np.testing.assert_allclose(float(loss16), float(loss32), rtol=2e-2)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_lm_trainer_loss_chunk_matches(tmp_path):
    """--loss-chunk N trains to the SAME parameters as the full-logits path
    (fp32, same seed) in the jit mode, and sp with loss_chunk agrees with
    dp to the usual cross-mode tolerance."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    tiny = dict(batch_size=8, seq_len=32, d_model=32, num_layers=2,
                num_heads=2, vocab_size=64, synth_tokens=3000, seed=3,
                print_freq=100, epochs=1, lr=1e-2, data_placement="host")

    def vec(tr):
        return np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in jax.tree_util.tree_leaves(
                                   jax.device_get(tr.state.params))])

    tr_full = LMTrainer(LMConfig(**tiny)); tr_full.fit()
    tr_chunk = LMTrainer(LMConfig(loss_chunk=40, **tiny)); tr_chunk.fit()
    np.testing.assert_allclose(vec(tr_chunk), vec(tr_full),
                               rtol=1e-4, atol=1e-5)

    sp = LMTrainer(LMConfig(mesh_shape=(2, 4), mesh_axes=("data", "seq"),
                            loss_chunk=16, **tiny))
    sp.fit()
    np.testing.assert_allclose(vec(sp), vec(tr_full), rtol=2e-3, atol=1e-4)


def test_lm_trainer_loss_chunk_eval_exact(tmp_path):
    """Chunked eval reports the same perplexity metrics as the full path."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    tiny = dict(batch_size=8, seq_len=32, d_model=32, num_layers=2,
                num_heads=2, vocab_size=64, synth_tokens=3000, seed=3,
                print_freq=100, epochs=1, lr=1e-2, data_placement="host",
                evaluate=True)
    loss_f, ppl_f, acc_f = LMTrainer(LMConfig(**tiny)).validate()
    loss_c, ppl_c, acc_c = LMTrainer(LMConfig(loss_chunk=24, **tiny)).validate()
    np.testing.assert_allclose(loss_c, loss_f, rtol=1e-5)
    np.testing.assert_allclose(acc_c, acc_f, rtol=1e-6)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_loss_chunk_under_tensor_parallel_matches_dp():
    """The chunked CE under Megatron TP: the head kernel arrives 'model'-
    sharded and GSPMD partitions the chunked scan's matmul + logsumexp —
    one tp+chunk step equals the dp full-logits step per-leaf."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.engine.lm_steps import make_lm_batches
    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.mesh import make_mesh, replicated
    from tpu_dist.plan.compile import Bindings, compile_train_step
    from tpu_dist.plan.ir import Plan
    from tpu_dist.parallel.tp import shard_lm_params

    V, L, B = 64, 32, 8
    rng_np = np.random.RandomState(1)
    tokens = rng_np.randint(0, V, (B, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = tiny_lm(vocab_size=V, max_len=L)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.01, 0.9, 0.0, steps_per_epoch=100)
    key = jax.random.PRNGKey(1)

    mesh_dp = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx),
                        replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=model, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    st_dp, _ = dp_step(st, jax.device_put(inputs, sh),
                       jax.device_put(targets, sh), key)

    mesh_tp = make_mesh((4, 2), ("data", "model"))
    st2 = TrainState.create(params, {}, tx)
    st2 = TrainState(
        step=jax.device_put(st2.step, NamedSharding(mesh_tp, P())),
        params=shard_lm_params(mesh_tp, st2.params), batch_stats={},
        opt_state=jax.device_put(st2.opt_state,
                                 NamedSharding(mesh_tp, P())),
        loss_scale=None)
    tp_step = compile_train_step(
        Plan(engine="lm", donate=False, loss_chunk=16),
        Bindings(mesh=mesh_tp, model=model, tx=tx))
    sh_tp = NamedSharding(mesh_tp, P("data"))
    st_tp, _ = tp_step(st2, jax.device_put(inputs, sh_tp),
                       jax.device_put(targets, sh_tp), key)

    flat_dp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_dp.params))[0]}
    flat_tp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_tp.params))[0]}
    for k in flat_dp:
        np.testing.assert_allclose(flat_tp[k], flat_dp[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_lm_trainer_pp_loss_chunk_matches(tmp_path):
    """--loss-chunk in the gpipe pipeline (the last-stage chunked head,
    round 4) trains to the same parameters as the pp full-logits path."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    def vec(tr):
        return np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in jax.tree_util.tree_leaves(
                                   jax.device_get(tr.state.params))])

    tiny = dict(mesh_shape=(2, 4), mesh_axes=("data", "stage"),
                pp_microbatches=2, batch_size=8, seq_len=32, d_model=32,
                num_layers=4, num_heads=2, vocab_size=64, synth_tokens=3000,
                seed=3, print_freq=100, epochs=1, lr=1e-2,
                data_placement="host")
    tr_full = LMTrainer(LMConfig(**tiny)); tr_full.fit()
    tr_chunk = LMTrainer(LMConfig(loss_chunk=40, **tiny)); tr_chunk.fit()
    np.testing.assert_allclose(vec(tr_chunk), vec(tr_full),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_loss_chunk_under_fsdp_matches_dp():
    """Chunked CE under ZeRO-3 (fsdp) placement: the head kernel arrives
    parameter-sharded over 'data' and GSPMD gathers it per chunk — one
    fsdp+chunk step equals the replicated dp full-logits step per-leaf."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.engine.lm_steps import make_lm_batches
    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.fsdp import shard_state_fsdp
    from tpu_dist.parallel.mesh import make_mesh, replicated
    from tpu_dist.plan.compile import Bindings, compile_train_step
    from tpu_dist.plan.ir import Plan

    V, L, B = 64, 32, 8
    rng_np = np.random.RandomState(2)
    tokens = rng_np.randint(0, V, (B, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = tiny_lm(vocab_size=V, max_len=L)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.01, 0.9, 0.0, steps_per_epoch=100)
    key = jax.random.PRNGKey(1)
    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P("data"))

    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    st_dp, _ = dp_step(st, jax.device_put(inputs, sh),
                       jax.device_put(targets, sh), key)

    st_f = shard_state_fsdp(mesh, TrainState.create(params, {}, tx),
                            min_size=256)
    f_step = compile_train_step(
        Plan(engine="lm", donate=False, loss_chunk=16),
        Bindings(mesh=mesh, model=model, tx=tx))
    st_fs, _ = f_step(st_f, jax.device_put(inputs, sh),
                      jax.device_put(targets, sh), key)

    flat_dp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_dp.params))[0]}
    flat_f = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
              jax.tree_util.tree_flatten_with_path(
                  jax.device_get(st_fs.params))[0]}
    for k in flat_dp:
        np.testing.assert_allclose(flat_f[k], flat_dp[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)
