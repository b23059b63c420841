"""MoE layer + expert parallelism: dispatch math, training, EP equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist.engine.lm_steps import make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.models.moe import MoEMLP, MoETransformerLM
from tpu_dist.ops import make_optimizer
from tpu_dist.parallel.ep import ep_param_specs
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.plan.compile import Bindings, compile_train_step
from tpu_dist.plan.ir import Plan

V, L, B, E = 64, 32, 16, 4


def test_moe_mlp_shapes_and_aux():
    m = MoEMLP(num_experts=E)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)),
                    jnp.float32)
    variables = m.init(jax.random.PRNGKey(0), x)
    out, muts = m.apply(variables, x, mutable=["intermediates"])
    assert out.shape == x.shape
    (aux,) = muts["intermediates"]["aux_loss"]
    # balanced-uniform lower bound is 1.0; any gating gives >= 1
    # (plus the small z-loss term)
    assert float(aux) >= 0.99


def test_moe_capacity_drops_are_residual_passthrough():
    """With capacity factor ~0 every token is dropped -> MoE output is zero
    (the block's residual carries the activations)."""
    m = MoEMLP(num_experts=E, capacity_factor=1e-9)
    x = jnp.ones((1, 8, 16))
    variables = m.init(jax.random.PRNGKey(0), x)
    out = m.apply(variables, x)
    # capacity 1 per expert: at most E tokens contribute, rest are zeros
    nonzero_rows = jnp.sum(jnp.any(out.reshape(8, 16) != 0, axis=-1))
    assert int(nonzero_rows) <= E


@pytest.fixture(scope="module")
def moe_setup():
    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    rng_np = np.random.default_rng(0)
    tokens = rng_np.integers(0, V, (B, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    return model, params, tx, inputs, targets


@pytest.mark.slow  # tier-1 budget (PR 20): loss-goes-down smoke over the same moe_setup step whose math test_expert_parallel_matches_dp pins exactly in-budget
def test_moe_lm_trains(moe_setup):
    model, params, tx, inputs, targets = moe_setup
    mesh = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))
    inputs_d, targets_d = jax.device_put(inputs, sh), jax.device_put(targets, sh)
    losses = []
    for _ in range(15):
        st, m = step(st, inputs_d, targets_d, jax.random.PRNGKey(1))
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        mm = jax.device_get(m)
        losses.append(float(mm["loss_sum"]) / float(mm["count"]))
    assert losses[-1] < losses[0] * 0.9


def test_expert_parallel_matches_dp(moe_setup):
    model, params, tx, inputs, targets = moe_setup
    specs = [s for s in jax.tree.leaves(ep_param_specs(params),
                                        is_leaf=lambda x: isinstance(x, P))
             if s != P()]
    assert len(specs) == 4  # 2 layers x (w_in, w_out); gate NOT sharded

    mesh_dp = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx), replicated(mesh_dp))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=model, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    _, m_dp = step(st, jax.device_put(inputs, sh), jax.device_put(targets, sh),
                   jax.random.PRNGKey(1))

    mesh_ep = make_mesh((2, 4), ("data", "expert"))
    from tpu_dist.parallel.ep import shard_state_ep
    st_ep = shard_state_ep(mesh_ep, TrainState.create(params, {}, tx))
    assert st_ep.params["block0"]["moe"]["w_in"].sharding.spec[0] == "expert"
    # momentum buffers for expert weights are sharded too (EP memory scaling)
    mom_specs = [l.sharding.spec for l in jax.tree.leaves(st_ep.opt_state)
                 if hasattr(l, "ndim") and l.ndim == 3]
    assert mom_specs and all(s[0] == "expert" for s in mom_specs)
    step_ep = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_ep, model=model, tx=tx))
    sh_ep = NamedSharding(mesh_ep, P("data"))
    _, m_ep = step_ep(st_ep, jax.device_put(inputs, sh_ep),
                      jax.device_put(targets, sh_ep), jax.random.PRNGKey(1))
    a = float(jax.device_get(m_dp["loss_sum"]))
    b = float(jax.device_get(m_ep["loss_sum"]))
    assert b == pytest.approx(a, rel=1e-4)


def test_top2_routing_dispatches_two_experts():
    """Top-2: every token's combine weights sum to ~1 (renormalized gates
    over BOTH dispatched experts); top-1's sum to gate1 < 1."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 16)),
                    jnp.float32)
    m2 = MoEMLP(num_experts=E, router_top_k=2, capacity_factor=4.0)
    variables = m2.init(jax.random.PRNGKey(0), x)
    out, muts = m2.apply(variables, x, mutable=["intermediates"])
    assert out.shape == x.shape
    (mass2,) = muts["intermediates"]["combine_mass"]
    np.testing.assert_allclose(np.asarray(mass2),
                               np.ones_like(np.asarray(mass2)), atol=1e-5)
    m1 = MoEMLP(num_experts=E, router_top_k=1, capacity_factor=4.0)
    out1, muts1 = m1.apply(variables, x, mutable=["intermediates"])
    (mass1,) = muts1["intermediates"]["combine_mass"]
    # top-1 mass = gate1 strictly below 1 (softmax over E>=2 experts)
    assert float(jnp.max(mass1)) < 1.0
    # and the second expert's contribution changes the output
    assert float(jnp.max(jnp.abs(out - out1))) > 1e-6


@pytest.mark.slow  # tier-1 budget (PR 18): ~6s near-duplicate — the train
# loop stays covered in-budget by test_moe_lm_trains (top-1, same step
# builder) and top-2 routing semantics by the combine-mass unit +
# test_top2_capacity_overflow_drops_second_choice
def test_top2_moe_lm_trains(moe_setup):
    _, _, tx, inputs, targets = (*moe_setup,)
    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E,
                             router_top_k=2)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    mesh = make_mesh((8,), ("data",))
    state = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh = NamedSharding(mesh, P("data"))
    di, dt = jax.device_put(inputs, sh), jax.device_put(targets, sh)
    key = jax.random.PRNGKey(1)
    losses = []
    for _ in range(6):
        state, m = step(state, di, dt, key)
        # distlint: disable=DL002 -- CPU test: per-step loss assertion needs the value now
        losses.append(float(jax.device_get(m["loss_sum"]))
                      / float(jax.device_get(m["count"])))
    assert losses[-1] < losses[0], losses


def test_router_z_loss_in_aux():
    """z-loss contributes: scaling it changes the sown aux value."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 16)),
                    jnp.float32)
    lo = MoEMLP(num_experts=E, z_loss_coef=0.0)
    hi = MoEMLP(num_experts=E, z_loss_coef=10.0)
    variables = lo.init(jax.random.PRNGKey(0), x)
    _, m_lo = lo.apply(variables, x, mutable=["intermediates"])
    _, m_hi = hi.apply(variables, x, mutable=["intermediates"])
    (a_lo,) = m_lo["intermediates"]["aux_loss"]
    (a_hi,) = m_hi["intermediates"]["aux_loss"]
    assert float(a_hi) > float(a_lo)


def test_top2_capacity_overflow_drops_second_choice():
    """Top-2 under tight capacity: second-choice tokens queue BEHIND every
    first-choice token (GShard order), so when an expert's queue overflows
    the SECOND choices drop first — combine mass falls below 1 for exactly
    the over-capacity tokens, and the aux/diagnostic plumbing reports it."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 16, 16)),
                    jnp.float32)
    # capacity_factor chosen so cap < tokens-per-expert under any routing:
    # with E=4, S=16, top-2: cap = int(16/4 * 0.3 * 2) = 2 slots per expert
    # but 16 tokens place 32 choices -> 8 per expert on average >> 2
    m = MoEMLP(num_experts=E, router_top_k=2, capacity_factor=0.3)
    variables = m.init(jax.random.PRNGKey(0), x)
    out, muts = m.apply(variables, x, mutable=["intermediates"])
    (mass,) = muts["intermediates"]["combine_mass"]
    mass = np.asarray(mass)
    # overflow must actually occur and be visible in the diagnostic
    assert float(mass.min()) < 0.999, "no token lost any routing mass"
    # fully-dropped tokens (both choices over capacity) pass through as
    # zeros: their MoE output is exactly zero (residual carries them)
    fully_dropped = mass < 1e-6
    if fully_dropped.any():
        np.testing.assert_allclose(
            np.asarray(out).reshape(-1, 16)[fully_dropped.reshape(-1)], 0.0,
            atol=1e-6)
    # nothing ever exceeds mass 1 (each token contributes once per choice)
    assert float(mass.max()) <= 1.0 + 1e-5


@pytest.mark.slow  # tier-1 budget (PR 19): 13s compiled-FLOPs/memory property
# on the 8-way mesh; EP stays exercised in-budget by
# test_expert_parallel_matches_dp (same (data=1, expert=8) mesh, loss
# parity vs dp) and test_moe_tp_composition_matches_dp
def test_ep_actually_shards_expert_compute():
    """'EP is EP': on the SAME (data=1, expert=8) mesh
    with the SAME global batch, expert-sharding the params must cut the
    per-device compiled FLOPs (each device runs only its experts' MLPs) and
    live temp memory, not just the parameter bytes. GSPMD lowers the
    dispatch/combine einsums to expert-axis partial sums (an all-reduce
    formulation of the classic all-to-all exchange); if it silently
    all-gathered the experts instead, per-device FLOPs would NOT drop and
    this test fails."""
    from tpu_dist.parallel.ep import shard_state_ep

    moe = MoETransformerLM(vocab_size=V, num_layers=2, d_model=128,
                           num_heads=4, num_experts=8, max_len=L)
    params = moe.init({"params": jax.random.PRNGKey(0)},
                      jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=100)
    tokens = np.random.default_rng(0).integers(0, V, (B, L + 1)).astype(
        np.int32)
    i, t = make_lm_batches(tokens)
    mesh = make_mesh((1, 8), ("data", "expert"))
    from tpu_dist.parallel.mesh import batch_sharding
    sh = batch_sharding(mesh)

    def compiled(sharder):
        st = sharder(mesh, TrainState.create(params, {}, tx))
        step = compile_train_step(
            Plan(engine="lm", donate=False),
            Bindings(mesh=mesh, model=moe, tx=tx))
        return step.lower(st, jax.device_put(i, sh), jax.device_put(t, sh),
                          jax.random.PRNGKey(1)).compile()

    def flops(comp):
        ca = comp.cost_analysis()
        return float((ca[0] if isinstance(ca, list) else ca)["flops"])

    rep = compiled(lambda mesh, st: jax.device_put(st, replicated(mesh)))
    ep = compiled(shard_state_ep)
    f_rep, f_ep = flops(rep), flops(ep)
    assert f_ep < 0.5 * f_rep, (f_ep, f_rep)  # expert MLP work divided
    m_rep = int(rep.memory_analysis().temp_size_in_bytes)
    m_ep = int(ep.memory_analysis().temp_size_in_bytes)
    assert m_ep < m_rep, (m_ep, m_rep)
    # and the expert weights themselves live 1/8 per device
    st = shard_state_ep(mesh, TrainState.create(params, {}, tx))
    w = st.params["block0"]["moe"]["w_in"]
    assert w.addressable_shards[0].data.shape[0] == 1  # 8 experts / 8 devs


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_remat_matches_no_remat(moe_setup):
    """--remat with MoE: per-block rematerialization must
    change memory, never math — identical loss/metrics and updated params,
    with the sown aux-loss/router-mass intermediates surviving nn.remat."""
    _, _, tx, inputs, targets = moe_setup
    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P("data"))
    di, dt = jax.device_put(inputs, sh), jax.device_put(targets, sh)

    def one_step(remat):
        model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E,
                                 num_layers=4, remat=remat)
        params = model.init({"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, L), jnp.int32),
                            train=False)["params"]
        st = jax.device_put(TrainState.create(params, {}, tx),
                            replicated(mesh))
        step = compile_train_step(
            Plan(engine="lm", donate=False),
            Bindings(mesh=mesh, model=model, tx=tx))
        lowered = step.lower(st, di, dt, jax.random.PRNGKey(1)).compile()
        st, m = step(st, di, dt, jax.random.PRNGKey(1))
        return (jax.device_get(st.params), jax.device_get(m),
                int(lowered.memory_analysis().temp_size_in_bytes))

    p_plain, m_plain, mem_plain = one_step(False)
    p_remat, m_remat, mem_remat = one_step(True)
    for k in ("loss_sum", "correct1", "count", "router_mass_sum"):
        assert float(m_remat[k]) == pytest.approx(float(m_plain[k]),
                                                  rel=1e-5), k
    assert float(m_remat["router_mass_n"]) > 0  # sow survives nn.remat
    flat_a = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_leaves_with_path(p_plain)}
    flat_b = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_leaves_with_path(p_remat)}
    for path in flat_a:
        np.testing.assert_allclose(np.asarray(flat_b[path]),
                                   np.asarray(flat_a[path]),
                                   rtol=1e-5, atol=1e-7, err_msg=path)
    # and remat actually buys activation memory at depth
    assert mem_remat < mem_plain, (mem_remat, mem_plain)


@pytest.mark.slow  # tier-1 budget (PR 20): composition of two single-axis parities that stay in-budget (test_expert_parallel_matches_dp, test_lm.py::test_tp_matches_dp) — the PR 11 dp x tp convention
def test_moe_tp_composition_matches_dp(moe_setup):
    """MoE x TP: a (data=2, expert=2, model=2) mesh with
    expert weights Megatron-split over 'model' on top of their 'expert'
    shard must reproduce the replicated-DP step."""
    from tpu_dist.parallel.ep import shard_state_ep

    model, params, tx, inputs, targets = moe_setup
    mesh_dp = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx),
                        replicated(mesh_dp))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=model, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    st_dp, m_dp = step(st, jax.device_put(inputs, sh),
                       jax.device_put(targets, sh), jax.random.PRNGKey(1))

    mesh = make_mesh((2, 2, 2), ("data", "expert", "model"))
    st_tp = shard_state_ep(mesh, TrainState.create(params, {}, tx))
    w_in = st_tp.params["block0"]["moe"]["w_in"]
    assert w_in.sharding.spec == P("expert", None, "model")
    local = w_in.addressable_shards[0].data.shape
    assert local[0] == w_in.shape[0] // 2 and local[2] == w_in.shape[2] // 2
    qkv = st_tp.params["block0"]["qkv"]["kernel"]
    assert qkv.sharding.spec == P(None, "model")
    step_tp = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=model, tx=tx))
    sh_tp = NamedSharding(mesh, P("data"))
    st_tp, m_tp = step_tp(st_tp, jax.device_put(inputs, sh_tp),
                          jax.device_put(targets, sh_tp),
                          jax.random.PRNGKey(1))

    for k in ("loss_sum", "correct1", "count"):
        assert float(jax.device_get(m_tp[k])) == pytest.approx(
            float(jax.device_get(m_dp[k])), rel=1e-4), k
    flat_dp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(jax.device_get(st_dp.params))}
    flat_tp = {jax.tree_util.keystr(p): v for p, v in
               jax.tree_util.tree_leaves_with_path(jax.device_get(st_tp.params))}
    for path in flat_dp:
        np.testing.assert_allclose(np.asarray(flat_tp[path]),
                                   np.asarray(flat_dp[path]),
                                   rtol=2e-4, atol=2e-6, err_msg=path)


def test_moe_analytical_flops_accounting():
    """The MoE MFU formula: counts top_k-activated expert
    params (not all E) plus the dispatch/combine einsum term, and feeds a
    real (non-None) TFLOP/s figure through LMTrainer._mfu."""
    from tpu_dist.utils.mfu import lm_flops_per_token, moe_lm_flops_per_token

    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    kw = dict(num_layers=2, seq_len=L, d_model=64, num_experts=E,
              total_tokens=B * L)
    f1 = moe_lm_flops_per_token(params, router_top_k=1, **kw)
    f2 = moe_lm_flops_per_token(params, router_top_k=2, **kw)
    assert f2 > f1  # top-2 activates twice the expert params
    # dense formula over the same params counts ALL experts -> overstates
    dense_all = lm_flops_per_token(params, 2, L, 64)
    expert_sz = sum(int(np.prod(v.shape)) for p, v in
                    jax.tree_util.tree_leaves_with_path(params)
                    if "w_in" in jax.tree_util.keystr(p)
                    or "w_out" in jax.tree_util.keystr(p))
    assert f1 < dense_all + 12 * E * 64 * 64 * 2  # loose sanity ceiling
    assert f1 > 6.0 * expert_sz / E              # at least one expert's MLP

    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer
    cfg = LMConfig(batch_size=8, seq_len=32, d_model=32, num_layers=1,
                   num_heads=2, vocab_size=64, synth_tokens=2000,
                   num_experts=4, print_freq=100, epochs=1, max_steps=2)
    tr = LMTrainer(cfg)
    tr.train_epoch(0)
    tflops, _ = tr._mfu(1000.0)
    assert tflops is not None and tflops > 0


def test_moe_training_reports_router_mass(tmp_path):
    """The dropped-token diagnostic reaches the training surface: a dp-moe
    LMTrainer epoch's meters carry RMass (mean combine mass per token)."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = LMConfig(batch_size=8, seq_len=32, d_model=32, num_layers=1,
                   num_heads=2, vocab_size=64, synth_tokens=2000,
                   num_experts=4, print_freq=100, epochs=1, max_steps=3)
    tr = LMTrainer(cfg)
    metrics = tr.train_epoch(0)
    assert "rmass" in metrics
    assert 0.0 < metrics["rmass"] <= 1.0 + 1e-5


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_sp_composition_matches_dp():
    """MoE + sequence parallelism (round 4): with a router group size that
    divides the shard's tokens, sp grouping partitions each row into the
    SAME contiguous segments as the dp grouping, so one sp train step
    (aux_weight=0 — the balance loss averages differently across shards)
    equals one dp step parameter-for-parameter."""
    from functools import partial

    rng_np = np.random.default_rng(3)
    tokens = rng_np.integers(0, V, (8, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    # sp shard per device: (8/2) x (32/4) = 32 tokens; group 8 divides the
    # shard AND each row's 8-token segments, matching dp's row-major
    # (B*L)/8 grouping segment for segment
    ctor = partial(MoETransformerLM, vocab_size=V, max_len=L,
                   num_experts=E, group_size=8)
    model = ctor()
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    key = jax.random.PRNGKey(7)

    mesh_dp = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx),
                        replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", aux_weight=0.0, donate=False),
        Bindings(mesh=mesh_dp, model=model, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    st_dp, _ = dp_step(st, jax.device_put(inputs, sh),
                       jax.device_put(targets, sh), key)

    mesh_sp = make_mesh((2, 4), ("data", "seq"))
    st2 = jax.device_put(TrainState.create(params, {}, tx),
                         replicated(mesh_sp))
    sp_step = compile_train_step(
        Plan(engine="lm", layout="sp", sync="explicit", aux_weight=0.0,
             donate=False),
        Bindings(mesh=mesh_sp, model_ctor=ctor, tx=tx))
    sh_sp = NamedSharding(mesh_sp, P("data", "seq"))
    st_sp, _ = sp_step(st2, jax.device_put(inputs, sh_sp),
                       jax.device_put(targets, sh_sp), key)

    flat_dp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_dp.params))[0]}
    flat_sp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_sp.params))[0]}
    assert flat_dp.keys() == flat_sp.keys()
    for k in flat_dp:
        np.testing.assert_allclose(flat_sp[k], flat_dp[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_sp_trains_via_lm_trainer():
    """LMTrainer accepts data=2,seq=4 + --num-experts (the round-3 'not
    supported yet' rejection is gone) and trains + evaluates end to end."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = LMConfig(mesh_shape=(2, 4), mesh_axes=("data", "seq"),
                   num_experts=4, moe_group_size=8, batch_size=8,
                   seq_len=32, d_model=32, num_layers=2, num_heads=2,
                   vocab_size=64, synth_tokens=3000, seed=3, epochs=2,
                   optimizer="adamw", lr=3e-3, print_freq=100,
                   data_placement="host")
    tr = LMTrainer(cfg)
    tr.fit()
    loss, ppl, acc = tr.validate()
    assert np.isfinite(loss) and ppl < 64  # better than uniform


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_pp_gpipe_matches_dp():
    """MoE + pipeline (round 4, GPipe only): 4 MoE blocks over 4 stages,
    aux_weight=0 and a group size dividing each row's segments — one
    pp-gpipe step equals one dp step parameter-for-parameter."""
    from tpu_dist.parallel.pp import (make_lm_pp_train_step,
                                     shard_state_pp, stack_pipeline_params,
                                     unstack_pipeline_params)

    rng_np = np.random.default_rng(5)
    tokens = rng_np.integers(0, V, (8, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E,
                             num_layers=4, group_size=8)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    key = jax.random.PRNGKey(9)

    mesh_dp = make_mesh((8,), ("data",))
    st = jax.device_put(TrainState.create(params, {}, tx),
                        replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", aux_weight=0.0, donate=False),
        Bindings(mesh=mesh_dp, model=model, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    st_dp, m_dp = dp_step(st, jax.device_put(inputs, sh),
                          jax.device_put(targets, sh), key)

    mesh_pp = make_mesh((2, 4), ("data", "stage"))
    pp_params = stack_pipeline_params(params, 4)
    st_pp = shard_state_pp(mesh_pp, TrainState.create(pp_params, {}, tx))
    pp_step = make_lm_pp_train_step(model, tx, mesh_pp, num_microbatches=2,
                                    donate=False, aux_weight=0.0)
    sh_pp = NamedSharding(mesh_pp, P("data", None))
    st_pp2, m_pp = pp_step(st_pp, jax.device_put(inputs, sh_pp),
                           jax.device_put(targets, sh_pp), key)

    np.testing.assert_allclose(float(m_pp["loss_sum"]),
                               float(m_dp["loss_sum"]), rtol=1e-5)
    flat_dp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   jax.device_get(st_dp.params))[0]}
    flat_pp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(unstack_pipeline_params(
                   jax.device_get(st_pp2.params)))[0]}
    assert flat_dp.keys() == flat_pp.keys()
    for k in flat_dp:
        np.testing.assert_allclose(flat_pp[k], flat_dp[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_moe_pp_trains_via_lm_trainer(schedule):
    """LMTrainer drives MoE x pp end to end (aux ON) under BOTH schedules —
    the round-4 'MoE + pipeline requires gpipe' rejection is gone: the
    1f1b tick threads the router aux through its manual vjp."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    kw = dict(num_experts=4, moe_group_size=8, batch_size=8, seq_len=32,
              d_model=32, num_layers=4, num_heads=2, vocab_size=64,
              synth_tokens=3000, seed=3, epochs=2, optimizer="adamw",
              lr=3e-3, print_freq=100, data_placement="host",
              pp_microbatches=2, pp_schedule=schedule)
    cfg = LMConfig(mesh_shape=(2, 4), mesh_axes=("data", "stage"), **kw)
    tr = LMTrainer(cfg)
    tr.fit()
    loss, ppl, acc = tr.validate()
    assert np.isfinite(loss) and ppl < 64


def test_moe_pp_tp_trains_via_lm_trainer():
    """The TRAINER accepts MoE over a (data, stage, model) mesh — the
    round-5 composition reachable end to end, not just via the pp.py
    makers (guard regression: the 'MoE + pure tensor parallelism' check
    must exempt pipeline meshes)."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = LMConfig(mesh_shape=(2, 2, 2),
                   mesh_axes=("data", "stage", "model"),
                   num_experts=4, moe_group_size=8, batch_size=8,
                   seq_len=32, d_model=32, num_layers=4, num_heads=2,
                   vocab_size=64, synth_tokens=3000, seed=3, epochs=2,
                   optimizer="adamw", lr=3e-3, print_freq=100,
                   data_placement="host", pp_microbatches=2)
    tr = LMTrainer(cfg)
    assert tr.mode == "pp-gpipe+tp"
    tr.fit()
    loss, ppl, acc = tr.validate()
    assert np.isfinite(loss) and ppl < 64


@pytest.mark.slow  # tier-1 budget (PR 14): near-duplicate composition —
# MoE x pp parity vs dp stays in-budget via test_moe_pp_gpipe_matches_dp,
# and the 1f1b-vs-gpipe schedule equivalence (the only other variable
# here) is pinned pure-pp by test_pp.py::test_pp_1f1b_loss_chunk_matches_dp
def test_moe_pp_1f1b_matches_gpipe_with_aux():
    """MoE x 1f1b == MoE x GPipe *with the router aux loss ON* (round 5):
    the manual-vjp schedule must thread aux_weight/M per microbatch through
    each stage's vjp AND propagate the aux input-cotangent across the
    backward ppermute ring. GPipe-by-autodiff on the SAME microbatch
    geometry is the ground truth — the aux term is a per-apply mean of a
    product of group means, so it is schedule-geometry-dependent by
    construction (dp's global-batch aux differs mathematically; the CE
    loss and routing stay dp-identical and are asserted against dp in
    test_moe_pp_gpipe_matches_dp)."""
    from tpu_dist.parallel.pp import (make_lm_pp_1f1b_train_step,
                                      make_lm_pp_train_step,
                                      shard_state_pp, stack_pipeline_params,
                                      unstack_pipeline_params)

    rng_np = np.random.default_rng(5)
    tokens = rng_np.integers(0, V, (8, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E,
                             num_layers=4, group_size=8)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    key = jax.random.PRNGKey(9)
    mesh_pp = make_mesh((2, 4), ("data", "stage"))
    sh_pp = NamedSharding(mesh_pp, P("data", None))
    di, dt = jax.device_put(inputs, sh_pp), jax.device_put(targets, sh_pp)

    def run(maker):
        pp_params = stack_pipeline_params(params, 4)
        st = shard_state_pp(mesh_pp, TrainState.create(pp_params, {}, tx))
        step = maker(model, tx, mesh_pp, 2, donate=False, aux_weight=0.05)
        st2, m = step(st, di, dt, key)
        return (unstack_pipeline_params(jax.device_get(st2.params)),
                jax.device_get(m))

    p_g, m_g = run(make_lm_pp_train_step)
    p_f, m_f = run(make_lm_pp_1f1b_train_step)

    np.testing.assert_allclose(float(m_f["loss_sum"]),
                               float(m_g["loss_sum"]), rtol=1e-5)
    # the router-mass diagnostic reaches the 1f1b metrics too
    assert float(m_f["router_mass_n"]) > 0
    assert float(m_f["router_mass_n"]) == pytest.approx(
        float(m_g["router_mass_n"]), rel=1e-6)
    flat_g = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
              jax.tree_util.tree_flatten_with_path(p_g)[0]}
    flat_f = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
              jax.tree_util.tree_flatten_with_path(p_f)[0]}
    assert flat_g.keys() == flat_f.keys()
    for k in flat_g:
        np.testing.assert_allclose(flat_f[k], flat_g[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_moe_pp_tp_matches_pp(schedule):
    """MoE x pp x tp (round 5, the last composition hole): a (data=2,
    stage=2, model=2) mesh with the stacked expert kernels Megatron-split
    over 'model' on top of their 'stage' shard must reproduce the same
    schedule on a plain (data=2, stage=2) mesh — with the router aux loss
    ON, so the only variable is the 'model' partitioning (pp == dp is
    covered by test_moe_pp_gpipe_matches_dp; aux is schedule-geometry
    dependent, see test_moe_pp_1f1b_matches_gpipe_with_aux)."""
    from tpu_dist.parallel.pp import (make_lm_pp_1f1b_train_step,
                                      make_lm_pp_train_step,
                                      shard_state_pp, stack_pipeline_params,
                                      unstack_pipeline_params)

    rng_np = np.random.default_rng(7)
    tokens = rng_np.integers(0, V, (8, L + 1)).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    model = MoETransformerLM(vocab_size=V, max_len=L, num_experts=E,
                             num_layers=4, group_size=8)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, L), jnp.int32), train=False)["params"]
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    key = jax.random.PRNGKey(9)
    maker = (make_lm_pp_1f1b_train_step if schedule == "1f1b"
             else make_lm_pp_train_step)

    def run(mesh_shape, axes):
        ndev = int(np.prod(mesh_shape))
        mesh = make_mesh(mesh_shape, axes, devices=jax.devices()[:ndev])
        pp_params = stack_pipeline_params(params, mesh.shape["stage"])
        st = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
        if "model" in axes:
            # expert kernels split over BOTH stage and model axes: w_in is
            # (S, layers, E, D, F) with S on 'stage' and F on 'model'
            w_in = st.params["blocks"]["moe"]["w_in"]
            local = w_in.addressable_shards[0].data.shape
            assert local[0] == w_in.shape[0] // 2
            assert local[-1] == w_in.shape[-1] // 2
        step = maker(model, tx, mesh, 2, donate=False, aux_weight=0.05)
        sh = NamedSharding(mesh, P("data", None))
        st2, m = step(st, jax.device_put(inputs, sh),
                      jax.device_put(targets, sh), key)
        return (unstack_pipeline_params(jax.device_get(st2.params)),
                jax.device_get(m))

    p_pp, m_pp = run((2, 2), ("data", "stage"))
    p_tp, m_tp = run((2, 2, 2), ("data", "stage", "model"))

    np.testing.assert_allclose(float(m_tp["loss_sum"]),
                               float(m_pp["loss_sum"]), rtol=1e-4)
    flat_pp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(p_pp)[0]}
    flat_tp = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
               jax.tree_util.tree_flatten_with_path(p_tp)[0]}
    assert flat_pp.keys() == flat_tp.keys()
    for k in flat_pp:
        np.testing.assert_allclose(flat_tp[k], flat_pp[k],
                                   rtol=5e-4, atol=1e-5,
                                   err_msg=f"{schedule} {k}")


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_aux_weight_flag_reaches_objective():
    """--moe-aux-weight threads into the training objective: zero weight
    trains different parameters than the 0.01 default (same seed), and the
    router-gate grads vanish only in balance direction when weight=0."""
    from tpu_dist.configs import LMConfig
    from tpu_dist.engine.lm_loop import LMTrainer

    kw = dict(num_experts=4, batch_size=8, seq_len=32, d_model=32,
              num_layers=2, num_heads=2, vocab_size=64, synth_tokens=2000,
              seed=3, epochs=1, lr=1e-2, print_freq=100,
              data_placement="host")

    def vec(tr):
        return np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in jax.tree_util.tree_leaves(
                                   jax.device_get(tr.state.params))])

    t_default = LMTrainer(LMConfig(**kw)); t_default.fit()
    t_zero = LMTrainer(LMConfig(moe_aux_weight=0.0, **kw)); t_zero.fit()
    t_default2 = LMTrainer(LMConfig(moe_aux_weight=0.01, **kw))
    t_default2.fit()
    # explicit 0.01 == the default; 0.0 genuinely changes the objective
    np.testing.assert_allclose(vec(t_default2), vec(t_default), rtol=1e-6)
    assert not np.allclose(vec(t_zero), vec(t_default), rtol=1e-4)
