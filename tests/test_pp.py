"""Pipeline parallelism (GPipe over 'stage') == data-parallel ground truth.

The whole point of a parallelism axis is that it changes WHERE compute runs,
never WHAT is computed: one pp train step over a (data, stage) mesh must
reproduce the plain jit DP step's loss, metrics, and updated parameters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.engine.lm_steps import make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.ops import make_optimizer
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.parallel.pp import (make_lm_pp_train_step,
                                  shard_state_pp, stack_pipeline_params,
                                  unstack_pipeline_params)
from tpu_dist.plan.compile import Bindings, compile_train_step
from tpu_dist.plan.ir import Plan

V, L, B, D = 64, 32, 8, 64


def _setup(num_layers=4):
    lm = tiny_lm(vocab_size=V, num_layers=num_layers, d_model=D, num_heads=4,
                 max_len=L)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=100)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, V, (B, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    return lm, params, tx, inputs, targets


def test_stack_unstack_roundtrip():
    _, params, _, _, _ = _setup()
    pp = stack_pipeline_params(params, num_stages=4)
    back = unstack_pipeline_params(pp)
    a = {jax.tree_util.keystr(p): v for p, v
         in jax.tree_util.tree_leaves_with_path(params)}
    b = {jax.tree_util.keystr(p): v for p, v
         in jax.tree_util.tree_leaves_with_path(back)}
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_blocks_not_divisible_raises():
    _, params, _, _, _ = _setup(num_layers=4)
    with pytest.raises(ValueError, match="not divisible"):
        stack_pipeline_params(params, num_stages=3)


def _maker(schedule):
    if schedule == "1f1b":
        from tpu_dist.parallel.pp import make_lm_pp_1f1b_train_step
        return make_lm_pp_1f1b_train_step
    return make_lm_pp_train_step


# tier-1 budget (PR 10): the pure-pp gpipe parity is a 9s near-duplicate —
# gpipe parity stays in-budget via
# test_quant.test_quant_pp_step_matches_dp[int8-gpipe] (same step builder).
# PR 11: the bare 1f1b parity (19s) is likewise covered in-budget by
# test_pp_1f1b_loss_chunk_matches_dp (same schedule + builder vs DP, with
# the stricter chunked-head path on top); both full-geometry params stay
# live in the slow suite
@pytest.mark.parametrize("schedule", [
    pytest.param("gpipe", marks=pytest.mark.slow),
    pytest.param("1f1b", marks=pytest.mark.slow)])
@pytest.mark.parametrize("mesh_shape,axes,microbatches", [
    ((1, 4), ("data", "stage"), 4),   # pure pipeline
    # tier-1 budget (PR 3): the dp x pp and blocks-per-stage layouts are
    # heavy near-duplicates of the pure-pp parity; slow-marked
    pytest.param((2, 4), ("data", "stage"), 2,
                 marks=pytest.mark.slow),   # dp x pp
    pytest.param((2, 2), ("data", "stage"), 4,
                 marks=pytest.mark.slow),   # 2 blocks per stage
])
def test_pp_step_matches_dp(mesh_shape, axes, microbatches, schedule):
    """Either pipeline schedule == plain DP, loss/metrics/params — a
    schedule changes WHEN microbatches run, never what is computed."""
    lm, params, tx, inputs, targets = _setup()
    key = jax.random.PRNGKey(1)

    # ground truth: plain DP on a 1-device mesh
    mesh_dp = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    st_dp = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=lm, tx=tx))
    sh = jax.sharding.NamedSharding(mesh_dp, jax.sharding.PartitionSpec("data"))
    st_dp, m_dp = dp_step(st_dp, jax.device_put(inputs, sh),
                          jax.device_put(targets, sh), key)

    # pipeline over (data, stage)
    ndev = int(np.prod(mesh_shape))
    mesh = make_mesh(mesh_shape, axes, devices=jax.devices()[:ndev])
    pp_params = stack_pipeline_params(params, num_stages=mesh.shape["stage"])
    st_pp = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    pp_step = _maker(schedule)(lm, tx, mesh, microbatches, donate=False)
    sh_pp = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    st_pp, m_pp = pp_step(st_pp, jax.device_put(inputs, sh_pp),
                          jax.device_put(targets, sh_pp), key)

    # identical loss/metric sums
    for k in ("loss_sum", "correct1", "count"):
        assert float(jax.device_get(m_pp[k])) == pytest.approx(
            float(jax.device_get(m_dp[k])), rel=1e-5), k

    # identical updated parameters, leaf for leaf
    back = unstack_pipeline_params(jax.device_get(st_pp.params))
    flat_dp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(jax.device_get(st_dp.params))}
    flat_pp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(back)}
    assert flat_dp.keys() == flat_pp.keys()
    for path in flat_dp:
        np.testing.assert_allclose(
            np.asarray(flat_dp[path]), np.asarray(flat_pp[path]),
            rtol=2e-5, atol=1e-7, err_msg=str(path))
    assert int(jax.device_get(st_pp.step)) == 1


def test_pp_1f1b_loss_chunk_matches_dp():
    """Chunked CE on the 1f1b head (round 5 — round 4 reached only gpipe):
    --loss-chunk swaps the last stage's full-logits head vjp for the
    ops.fused_xent custom_vjp inside head_loss; identical math, so a
    chunked 1f1b step must equal the plain DP step."""
    from tpu_dist.parallel.pp import make_lm_pp_1f1b_train_step

    lm, params, tx, inputs, targets = _setup()
    key = jax.random.PRNGKey(1)

    mesh_dp = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    st_dp = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=lm, tx=tx))
    sh = jax.sharding.NamedSharding(mesh_dp, jax.sharding.PartitionSpec("data"))
    st_dp, m_dp = dp_step(st_dp, jax.device_put(inputs, sh),
                          jax.device_put(targets, sh), key)

    mesh = make_mesh((2, 4), ("data", "stage"))
    pp_params = stack_pipeline_params(params, num_stages=4)
    st_pp = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    # chunk (17) deliberately does NOT divide the microbatch's token count
    # so the padded-tail path of the chunked kernel is exercised too
    pp_step = make_lm_pp_1f1b_train_step(lm, tx, mesh, 2, donate=False,
                                         loss_chunk=17)
    sh_pp = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    st_pp, m_pp = pp_step(st_pp, jax.device_put(inputs, sh_pp),
                          jax.device_put(targets, sh_pp), key)

    for k in ("loss_sum", "correct1", "count"):
        assert float(jax.device_get(m_pp[k])) == pytest.approx(
            float(jax.device_get(m_dp[k])), rel=1e-5), k
    back = unstack_pipeline_params(jax.device_get(st_pp.params))
    flat_dp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(jax.device_get(st_dp.params))}
    flat_pp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(back)}
    for path in flat_dp:
        np.testing.assert_allclose(
            np.asarray(flat_dp[path]), np.asarray(flat_pp[path]),
            rtol=2e-5, atol=1e-7, err_msg=str(path))


@pytest.mark.slow  # tier-1 budget (PR 20): multi-step convergence twin of the exact single-step parities that stay in-budget (test_pp_step_matches_dp, test_pp_1f1b_loss_chunk_matches_dp)
def test_pp_multiple_steps_converge():
    """Loss decreases over repeated pp steps (end-to-end sanity)."""
    lm, params, tx, inputs, targets = _setup()
    mesh = make_mesh((2, 4), ("data", "stage"))
    pp_params = stack_pipeline_params(params, 4)
    st = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    step = make_lm_pp_train_step(lm, tx, mesh, num_microbatches=2,
                                 donate=False)
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    di, dt = jax.device_put(inputs, sh), jax.device_put(targets, sh)
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(8):
        st, m = step(st, di, dt, key)
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        losses.append(float(jax.device_get(m["loss_sum"]))
                      / float(jax.device_get(m["count"])))
    assert losses[-1] < losses[0] * 0.85, losses
    assert losses == sorted(losses, reverse=True), losses  # monotone descent


@pytest.mark.slow  # tier-1 budget (PR 7): 27s memory-property compile; 1f1b stays covered by pp_step_matches_dp[1f1b] + the loss_chunk parity
def test_pp_1f1b_activation_memory_independent_of_microbatches():
    """THE 1F1B property: compiled temp (activation) memory is flat in M,
    while GPipe-by-autodiff grows linearly (it stashes every tick input).
    Asserted from XLA's own memory analysis of the compiled programs."""
    from tpu_dist.parallel.pp import make_lm_pp_1f1b_train_step

    lm, params, tx, _, _ = _setup()
    mesh = make_mesh((2, 4), ("data", "stage"))
    pp_params = stack_pipeline_params(params, 4)

    def temp_bytes(maker, m):
        b = 2 * m * 2  # fixed microbatch size: B = data * mb_rows * M
        tokens = np.zeros((b, L + 1), np.int32)
        inputs, targets = make_lm_batches(tokens)
        st0 = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
        sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data", None))
        step = maker(lm, tx, mesh, num_microbatches=m, donate=False)
        ma = step.lower(st0, jax.device_put(inputs, sh),
                        jax.device_put(targets, sh),
                        jax.random.PRNGKey(0)).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)

    g4, g16 = (temp_bytes(make_lm_pp_train_step, m) for m in (4, 16))
    f4, f16 = (temp_bytes(make_lm_pp_1f1b_train_step, m) for m in (4, 16))
    assert g16 > g4 * 2          # gpipe: O(M) activation stash
    assert f16 < f4 * 1.25       # 1f1b: flat (stash depth 2(S-1)+1)
    assert f16 < g16 / 3         # and far below gpipe at large M


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pp_dead_work_gated_per_stage(schedule):
    """Dead-work gating: in the optimized HLO every matmul
    — the full-vocab head, the embedding vjp scatter, AND the per-tick
    block compute — sits inside a lax.cond branch, so a stage executes the
    embed/head work only if it owns it and skips bubble ticks entirely.
    XLA's cost model counts both branches of a conditional, so the
    assertion is structural: ops traced inside lax.cond carry '/cond' in
    their op_name metadata, and no dot may live outside one."""
    import re

    lm, params, tx, inputs, targets = _setup()
    mesh = make_mesh((2, 4), ("data", "stage"))
    pp_params = stack_pipeline_params(params, 4)
    st = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    step = _maker(schedule)(lm, tx, mesh, num_microbatches=2, donate=False)
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    txt = step.lower(st, jax.device_put(inputs, sh),
                     jax.device_put(targets, sh),
                     jax.random.PRNGKey(0)).compile().as_text()

    dots = [ln for ln in txt.splitlines() if " dot(" in ln]
    assert len(dots) >= 6, "expected matmuls in the compiled pipeline"
    ungated = []
    for ln in dots:
        m = re.search(r'op_name="([^"]*)"', ln)
        if not (m and "cond" in m.group(1)):
            ungated.append(ln.strip()[:120])
    assert not ungated, f"matmuls outside lax.cond branches: {ungated}"

    # the embedding table's backward scatter-add is stage-0-gated too
    scatters = [ln for ln in txt.splitlines() if " scatter(" in ln]
    for ln in scatters:
        m = re.search(r'op_name="([^"]*)"', ln)
        assert m and "cond" in m.group(1), ln.strip()[:120]


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pp_tp_composition_matches_dp(schedule):
    """PP x TP over a (data=2, stage=2, model=2) mesh == plain DP: the
    pipeline schedule stays manual (shard_map) while 'model' runs as a
    GSPMD auto axis, so each stage's block math is Megatron-sharded —
    weights verifiably split over BOTH stage and model axes."""
    lm, params, tx, inputs, targets = _setup()
    key = jax.random.PRNGKey(1)

    mesh_dp = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    st_dp = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=lm, tx=tx))
    sh = jax.sharding.NamedSharding(mesh_dp, jax.sharding.PartitionSpec("data"))
    st_dp, m_dp = dp_step(st_dp, jax.device_put(inputs, sh),
                          jax.device_put(targets, sh), key)

    mesh = make_mesh((2, 2, 2), ("data", "stage", "model"))
    pp_params = stack_pipeline_params(params, num_stages=2)
    st_pp = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    # TP sharding actually applied: qkv kernel splits its LAST dim 2-ways
    w = st_pp.params["blocks"]["qkv"]["kernel"]
    assert w.addressable_shards[0].data.shape[-1] == w.shape[-1] // 2
    pp_step = _maker(schedule)(lm, tx, mesh, 2, donate=False)
    sh_pp = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    st_pp, m_pp = pp_step(st_pp, jax.device_put(inputs, sh_pp),
                          jax.device_put(targets, sh_pp), key)

    for k in ("loss_sum", "correct1", "count"):
        assert float(jax.device_get(m_pp[k])) == pytest.approx(
            float(jax.device_get(m_dp[k])), rel=1e-5), k
    back = unstack_pipeline_params(jax.device_get(st_pp.params))
    flat_dp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(jax.device_get(st_dp.params))}
    flat_pp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(back)}
    for path in flat_dp:
        np.testing.assert_allclose(
            np.asarray(flat_dp[path]), np.asarray(flat_pp[path]),
            rtol=2e-4, atol=1e-6, err_msg=f"{schedule} {path}")
