"""Transformer LM engine: DP / DPxTP / DPxSP parallelism equivalence + training.

The core guarantee the reference could never state (it had no model or
sequence parallelism): the SAME weights and data produce the SAME loss and
updates under every parallelism layout.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist.engine.lm_steps import make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.ops import make_optimizer
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.plan.compile import Bindings, compile_train_step
from tpu_dist.plan.ir import Plan
from tpu_dist.parallel.tp import lm_param_specs, shard_lm_params

B, L, V = 8, 64, 256


@pytest.fixture(scope="module")
def setup():
    rng_np = np.random.default_rng(0)
    tokens = rng_np.integers(0, V, (B, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = tiny_lm(vocab_size=V, max_len=L)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.01, 0.9, 0.0, steps_per_epoch=100)
    return model, params, tx, inputs, targets


def _loss(m):
    # distlint: disable=DL002 -- test helper: drains one metrics tree for assertions
    m = jax.device_get(m)
    return float(m["loss_sum"]) / float(m["count"])


def _run_dp(setup_data, mesh):
    model, params, tx, inputs, targets = setup_data
    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))
    s, m = step(st, jax.device_put(inputs, sh), jax.device_put(targets, sh),
                jax.random.PRNGKey(1))
    return s, _loss(m)


def test_dp_trains(setup):
    mesh = make_mesh((8,), ("data",))
    _, loss = _run_dp(setup, mesh)
    assert 4.0 < loss < 8.0  # ~ln(256)=5.5 at init


def test_tp_matches_dp(setup):
    model, params, tx, inputs, targets = setup
    _, loss_dp = _run_dp(setup, make_mesh((8,), ("data",)))

    mesh = make_mesh((4, 2), ("data", "model"))
    specs = jax.tree.leaves(lm_param_specs(params),
                            is_leaf=lambda x: isinstance(x, P))
    assert sum(s != P() for s in specs) >= 8  # qkv/proj/mlp x layers + head
    st = TrainState.create(params, {}, tx)
    st = TrainState(step=jax.device_put(st.step, NamedSharding(mesh, P())),
                    params=shard_lm_params(mesh, st.params), batch_stats={},
                    opt_state=jax.device_put(st.opt_state,
                                             NamedSharding(mesh, P())),
                    loss_scale=None)
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))
    _, m = step(st, jax.device_put(inputs, sh), jax.device_put(targets, sh),
                jax.random.PRNGKey(1))
    assert _loss(m) == pytest.approx(loss_dp, abs=2e-4)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_sp_ring_matches_dp(setup):
    model, params, tx, inputs, targets = setup
    _, loss_dp = _run_dp(setup, make_mesh((8,), ("data",)))

    mesh = make_mesh((2, 4), ("data", "seq"))
    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", layout="sp", sync="explicit", donate=False),
        Bindings(mesh=mesh, tx=tx,
                 model_ctor=partial(tiny_lm, vocab_size=V, max_len=L)))
    sh = NamedSharding(mesh, P("data", "seq"))
    s, m = step(st, jax.device_put(inputs, sh), jax.device_put(targets, sh),
                jax.random.PRNGKey(1))
    assert _loss(m) == pytest.approx(loss_dp, abs=2e-4)
    # params updated identically to the DP run (replicated, exact psum'd grads)
    s_dp, _ = _run_dp(setup, make_mesh((8,), ("data",)))
    fa = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s.params)])
    fb = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(s_dp.params)])
    np.testing.assert_allclose(fa, fb, rtol=2e-3, atol=1e-5)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_lm_learns_structured_sequence():
    """Convergence smoke: deterministic next-token rule is learnable fast."""
    mesh = make_mesh((8,), ("data",))
    model = tiny_lm(vocab_size=64, max_len=32)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))

    rng_np = np.random.default_rng(1)
    start = rng_np.integers(0, 64, (16, 1))
    rows = [start]
    for _ in range(32):
        rows.append((rows[-1] * 3 + 1) % 64)
    tokens = np.concatenate(rows, axis=1).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    inputs = jax.device_put(inputs, sh)
    targets = jax.device_put(targets, sh)

    losses = []
    for i in range(25):
        st, m = step(st, inputs, targets, jax.random.PRNGKey(2))
        losses.append(_loss(m))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_fsdp_matches_dp_and_stays_sharded(setup):
    """ZeRO-3-style placement: same step fn, same math, sharded memory."""
    from tpu_dist.parallel.fsdp import fsdp_specs, shard_state_fsdp

    model, params, tx, inputs, targets = setup
    s_dp, loss_dp = _run_dp(setup, make_mesh((8,), ("data",)))

    mesh = make_mesh((8,), ("data",))
    st = shard_state_fsdp(mesh, TrainState.create(params, {}, tx))
    emb_spec = st.params["tok_emb"]["embedding"].sharding.spec
    assert emb_spec[0] == "data"  # actually sharded
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))
    s_f, m = step(st, jax.device_put(inputs, sh), jax.device_put(targets, sh),
                  jax.random.PRNGKey(1))
    assert _loss(m) == pytest.approx(loss_dp, rel=1e-5)
    fa = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(jax.device_get(s_dp.params))])
    fb = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(jax.device_get(s_f.params))])
    np.testing.assert_allclose(fa, fb, rtol=1e-4, atol=1e-6)
    # updates must not silently re-replicate the weights
    post = s_f.params["tok_emb"]["embedding"].sharding.spec
    assert post and post[0] == "data"
    # small leaves (norm scales) stay replicated by the min_size rule
    specs = fsdp_specs({"tiny": np.zeros((8,))}, 8)
    assert specs["tiny"] == P()


def test_lm_eval_step_exact_metrics():
    """Eval metric sums equal a hand-computed forward (counts, not means)."""
    import numpy as np
    from tpu_dist.engine.lm_steps import (lm_loss_and_metrics,
                                          make_lm_batches)
    from tpu_dist.plan.compile import compile_eval_step
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.parallel.mesh import make_mesh

    lm = tiny_lm(vocab_size=32, num_layers=1, d_model=32, num_heads=2,
                 max_len=16)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, 16), jnp.int32), train=False)["params"]
    tokens = np.random.default_rng(0).integers(0, 32, (8, 17)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    mesh = make_mesh((8,), ("data",))
    step = compile_eval_step(Plan(engine="lm"), Bindings(mesh=mesh, model=lm))
    m = jax.device_get(step(params, jnp.asarray(inputs), jnp.asarray(targets),
                            jnp.ones((inputs.shape[0],), jnp.float32)))

    logits = lm.apply({"params": params}, jnp.asarray(inputs), train=False)
    _, ref = lm_loss_and_metrics(logits, jnp.asarray(targets),
                                 jnp.ones(targets.shape, jnp.float32))
    assert float(m["count"]) == targets.size
    assert float(m["loss_sum"]) == pytest.approx(float(ref["loss_sum"]),
                                                 rel=1e-5)
    assert float(m["correct1"]) == float(ref["correct1"])
