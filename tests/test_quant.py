"""Int8 quantized matmuls (ops.quant): numerics, STE, decode, shardings.

The quant subsystem's contract, pinned end to end: symmetric per-channel
quantization stays within half a scale step, the quantized forward tracks
the fp forward, the straight-through backward IS the fp backward, training
under quant="int8" still learns the tiny-LM harness, weight-only int8
decode reproduces bf16 greedy tokens, and the whole thing runs under a
dp x tp GSPMD mesh unchanged (scales are tiny replicated leaves).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist.engine.generate import generate
from tpu_dist.engine.lm_steps import make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.ops import make_optimizer
from tpu_dist.ops.quant import (QUANT_MODES, dequantize, quant_einsum,
                                quantize_int8, validate_quant,
                                wo_fake_quant, wo_quantize_params)
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.plan.compile import Bindings, compile_train_step
from tpu_dist.plan.ir import Plan
from tpu_dist.parallel.tp import shard_lm_params

V, L = 64, 32


def _lm(quant="none", **kw):
    return tiny_lm(vocab_size=V, num_layers=2, d_model=64, num_heads=4,
                   max_len=L, quant=quant, **kw)


def _params(lm, seed=0):
    return lm.init({"params": jax.random.PRNGKey(seed)},
                   jnp.zeros((1, L), jnp.int32), train=False)["params"]


# ---- quantize/dequantize ---------------------------------------------------

def test_roundtrip_error_within_half_scale():
    """Symmetric int8: |x - dequant(quant(x))| <= scale/2 elementwise, with
    one scale per output channel (amax over the contracting dim)."""
    w = jnp.asarray(np.random.default_rng(0).normal(size=(48, 24)) * 3.0,
                    jnp.float32)
    q, scale = quantize_int8(w, (0,))
    assert q.dtype == jnp.int8 and scale.shape == (1, 24)
    err = jnp.abs(dequantize(q, scale) - w)
    assert bool(jnp.all(err <= scale * 0.5 + 1e-6))
    # scale saturates at amax/127: the extreme element is exactly invertible
    assert bool(jnp.all(jnp.max(jnp.abs(dequantize(q, scale)), axis=0)
                        <= jnp.max(jnp.abs(w), axis=0) + 1e-6))


def test_all_zero_channel_quantizes_to_zero():
    w = jnp.zeros((16, 4), jnp.float32).at[:, 0].set(1.0)
    q, scale = quantize_int8(w, (0,))
    assert bool(jnp.all(q[:, 1:] == 0)) and bool(jnp.all(jnp.isfinite(scale)))


def test_validate_quant_rejects_unknown():
    for m in QUANT_MODES:
        assert validate_quant(m) == m
    with pytest.raises(ValueError):
        validate_quant("fp8")


# ---- quantized einsum ------------------------------------------------------

def test_quant_einsum_tracks_fp_dense():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    yq = quant_einsum("abd,dZ->abZ", x, w)
    yf = jnp.einsum("abd,dZ->abZ", x, w)
    # int8 x int8 with per-row/per-channel scales: ~1% relative error
    assert float(jnp.max(jnp.abs(yq - yf))) < 0.05 * float(jnp.max(jnp.abs(yf)))


def test_quant_einsum_batched_moe_spec():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)  # gecd
    w = jnp.asarray(rng.normal(size=(4, 16, 12)), jnp.float32)    # edf
    yq = quant_einsum("gecd,edf->gecf", a, w)
    yf = jnp.einsum("gecd,edf->gecf", a, w)
    assert float(jnp.max(jnp.abs(yq - yf))) < 0.05 * float(jnp.max(jnp.abs(yf)))


def test_ste_gradients_equal_fp_gradients():
    """The STE contract exactly: grads of the quantized dot == grads of the
    fp dot of the same operands (not merely 'close')."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    co = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)  # non-trivial g
    gq = jax.grad(lambda a, b: jnp.vdot(quant_einsum("ad,dZ->aZ", a, b), co),
                  argnums=(0, 1))(x, w)
    gf = jax.grad(lambda a, b: jnp.vdot(jnp.einsum("ad,dZ->aZ", a, b), co),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gq[0]), np.asarray(gf[0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gq[1]), np.asarray(gf[1]), rtol=1e-6)


def test_wo_fake_quant_ste_identity_gradient():
    w = jnp.asarray(np.random.default_rng(4).normal(size=(16, 8)), jnp.float32)
    g = jax.grad(lambda b: jnp.sum(wo_fake_quant(b) * 2.0))(w)
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.ones_like(w))


# ---- model-level forward agreement ----------------------------------------

def test_quant_forward_tracks_bf16_forward():
    """quant='int8' logits stay close to the unquantized model's on the SAME
    params — close enough that next-token ranking is preserved for the
    overwhelming majority of positions at init."""
    lm_fp = _lm()
    params = _params(lm_fp)
    tok = jnp.asarray(np.random.default_rng(5).integers(0, V, (4, L)),
                      jnp.int32)
    logits_fp = lm_fp.apply({"params": params}, tok, train=False)
    for mode in ("int8", "int8_wo"):
        logits_q = _lm(mode).apply({"params": params}, tok, train=False)
        rel = (jnp.max(jnp.abs(logits_q - logits_fp))
               / jnp.max(jnp.abs(logits_fp)))
        assert float(rel) < 0.15, (mode, float(rel))
        agree = jnp.mean((jnp.argmax(logits_q, -1)
                          == jnp.argmax(logits_fp, -1)).astype(jnp.float32))
        assert float(agree) > 0.9, (mode, float(agree))


def test_param_tree_identical_across_modes():
    """The quant knob must never fork param structure (checkpoints, TP rules
    and the warm-start graft all key on the tree)."""
    ref = jax.tree_util.tree_structure(_params(_lm()))
    for mode in ("int8", "int8_wo"):
        assert jax.tree_util.tree_structure(_params(_lm(mode))) == ref


# ---- training --------------------------------------------------------------

def _affine_rows(n=16):
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, V, (n, 1))]
    for _ in range(L):
        rows.append((rows[-1] * 5 + 7) % V)
    return np.concatenate(rows, axis=1).astype(np.int32)


def _train(lm, params, mesh, steps=60, lr=0.05):
    tx = make_optimizer(lr, 0.9, 0.0, steps_per_epoch=1000)
    state = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=lm, tx=tx))
    inputs, targets = make_lm_batches(_affine_rows())
    sh = NamedSharding(mesh, P("data"))
    di, dt = jax.device_put(inputs, sh), jax.device_put(targets, sh)
    key = jax.random.PRNGKey(1)
    m = None
    for _ in range(steps):
        state, m = step(state, di, dt, key)
        # distlint: disable=DL002 -- bounds the async queue on the CPU sim (trailing comment)
        jax.block_until_ready(state.step)  # bound the async queue (CPU sim)
    m = jax.device_get(m)
    return state, float(m["loss_sum"]) / float(m["count"])


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_int8_training_converges_on_tiny_lm_harness():
    """The tiny-LM convergence harness (the affine rule of
    test_generate/test_lm) under quant='int8': the quantized train step must
    drive the loss well below the ~ln(V)=4.16 init plateau, like the bf16
    path does — the existing parity bound for 'this engine still learns'."""
    mesh = make_mesh((8,), ("data",))
    lm_q = _lm("int8")
    _, loss_q = _train(lm_q, _params(lm_q), mesh)
    assert loss_q < 1.0, loss_q  # fp run reaches ~0.3; init is ~4.16


# ---- weight-only decode ----------------------------------------------------

def test_wo_quantize_params_structure():
    params = _params(_lm())
    wq = wo_quantize_params(params)
    # every dense kernel became int8 with a sibling fp32 scale
    for name in ("qkv", "proj", "mlp_in", "mlp_out"):
        sub = wq["block0"][name]
        assert sub["kernel"].dtype == jnp.int8
        assert sub["kernel_scale"].dtype == jnp.float32
    assert wq["lm_head"]["kernel"].dtype == jnp.int8
    # embeddings and norms untouched
    assert wq["tok_emb"]["embedding"].dtype == params["tok_emb"]["embedding"].dtype
    assert "scale" in wq["ln_f"] and wq["ln_f"]["scale"].dtype != jnp.int8


def test_int8_mode_refuses_prequantized_tree():
    """quant='int8' on a wo-quantized param tree must refuse loudly: the fp
    weights are gone, so the dynamic-activation int8 program cannot be
    built — silently running the wo path would return different numerics
    than the mode the caller asked for."""
    lm = _lm("int8")
    wq = wo_quantize_params(_params(_lm()))
    with pytest.raises(ValueError, match="pre-quantized"):
        lm.apply({"params": wq}, jnp.zeros((1, L), jnp.int32), train=False)


def test_generate_refuses_prequantized_tree_in_fp_modes():
    """generate() with quant='none' or 'int8' on a wo-quantized tree must
    refuse: plain nn.Dense would silently use the raw int8 kernels as
    weights (flax ignores the extra scale leaves) and decode garbage."""
    lm = _lm()
    wq = wo_quantize_params(_params(lm))
    prompt = jnp.zeros((1, 3), jnp.int32)
    for q in ("none", "int8"):
        with pytest.raises(ValueError, match="wo-quantized"):
            generate(lm, wq, prompt, steps=2, quant=q)


@pytest.mark.slow  # tier-1 budget (PR 11): the 27s training loop dominates; wo-greedy parity stays pinned in-budget by tests/test_serve.py::test_paged_greedy_bit_identical_int8_wo (wo greedy bit-equal across decode paths), test_wo_decode_params_are_int8_resident (int8-resident program) and test_quant_forward_tracks_bf16_forward (wo numerics)
def test_wo_decode_matches_bf16_greedy_on_trained_model():
    """Train the tiny LM on the affine rule, then weight-only int8 decode
    (cached AND full-recompute) must reproduce the bf16 path's greedy
    tokens exactly — per-channel int8 keeps the trained argmax margins."""
    mesh = make_mesh((8,), ("data",))
    lm = _lm()
    state, _ = _train(lm, _params(lm), mesh)
    params = jax.device_get(state.params)
    prompt = jnp.asarray([[3, (3 * 5 + 7) % V], [11, (11 * 5 + 7) % V]],
                         jnp.int32)
    ref = np.asarray(generate(lm, params, prompt, steps=12, use_cache=True))
    wo_cached = np.asarray(generate(lm, params, prompt, steps=12,
                                    use_cache=True, quant="int8_wo"))
    np.testing.assert_array_equal(ref, wo_cached)
    wo_full = np.asarray(generate(lm, params, prompt, steps=12,
                                  quant="int8_wo"))
    np.testing.assert_array_equal(ref, wo_full)


def test_wo_decode_params_are_int8_resident():
    """The decode program really consumes int8 weights (the memory-bound
    win), not a dequantized fp copy smuggled through the param tree."""
    params = _params(_lm())
    wq = wo_quantize_params(params)
    int8_bytes = sum(x.size for x in jax.tree.leaves(wq)
                     if x.dtype == jnp.int8)
    assert int8_bytes > 0
    # generate() accepts the PRE-quantized tree too (idempotent entry)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = generate(_lm(), wq, prompt, steps=4, use_cache=True,
                   quant="int8_wo")
    assert out.shape == (1, 7)


# ---- sharded smoke ---------------------------------------------------------

@pytest.mark.slow  # tier-1 budget (PR 15): int8 under dp x tp rides the
# ONE gspmd step template the plan compiler lowers for every GSPMD
# placement — the in-budget siblings are the plan parity suite's int8 leg
# (tests/test_plan.py::test_lm_plan_loss_parity_across_modes) and the fp
# tp-placement parity (tests/test_lm.py::test_tp_matches_dp)
def test_int8_train_step_under_dp_tp_mesh():
    """quant='int8' through the GSPMD dp x tp step: scales are tiny
    replicated leaves, so the Megatron param placement partitions the
    quantized program unchanged; loss matches the pure-DP quantized step."""
    lm = _lm("int8")
    params = _params(lm)
    inputs, targets = make_lm_batches(_affine_rows(8))
    tx = make_optimizer(0.01, 0.9, 0.0, steps_per_epoch=100)
    key = jax.random.PRNGKey(1)

    def run(mesh, place):
        st = TrainState.create(params, {}, tx)
        st = place(mesh, st)
        step = compile_train_step(
            Plan(engine="lm", donate=False),
            Bindings(mesh=mesh, model=lm, tx=tx))
        sh = NamedSharding(mesh, P("data"))
        _, m = step(st, jax.device_put(inputs, sh),
                    jax.device_put(targets, sh), key)
        m = jax.device_get(m)
        return float(m["loss_sum"]) / float(m["count"])

    loss_dp = run(make_mesh((8,), ("data",)),
                  lambda mesh, st: jax.device_put(st, replicated(mesh)))

    def place_tp(mesh, st):
        return TrainState(
            step=jax.device_put(st.step, NamedSharding(mesh, P())),
            params=shard_lm_params(mesh, st.params), batch_stats={},
            opt_state=jax.device_put(st.opt_state, NamedSharding(mesh, P())),
            loss_scale=None)

    loss_tp = run(make_mesh((4, 2), ("data", "model")), place_tp)
    assert np.isfinite(loss_dp) and np.isfinite(loss_tp)
    # quantization is elementwise + per-channel reduces: GSPMD partitioning
    # must not change the math beyond fp reduction order
    assert loss_tp == pytest.approx(loss_dp, rel=2e-3)


@pytest.mark.parametrize("schedule", [
    "gpipe",
    # tier-1 budget (PR 3): 1f1b x quant parity is a near-duplicate of
    # gpipe x quant (the schedules themselves are parity-pinned in
    # test_pp); slow-marked
    pytest.param("1f1b", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("quant", [
    # tier-1 budget (PR 15): the whole quant x pp cross matrix is
    # slow-tier now — pp forwards the knob into its stage blocks through
    # the SAME ops.quant.quant_matmul the plan-compiled dense paths pin
    # in-budget (tests/test_plan.py::test_lm_plan_loss_parity_across_modes
    # int8 leg + test_quant_einsum_tracks_fp_dense), and the pp schedules'
    # own parity stays in-budget in test_pp
    pytest.param("int8", marks=pytest.mark.slow),
    # tier-1 budget (PR 7): int8_wo x pp is an 11s near-duplicate of the
    # int8 x pp parity (wo-mode itself is parity-pinned in the decode and
    # dense-layer tests); slow-marked
    pytest.param("int8_wo", marks=pytest.mark.slow),
])
def test_quant_pp_step_matches_dp(quant, schedule):
    """Both quant modes compose with pipeline parallelism: one pp step
    (either schedule) over a (data=2, stage=2) mesh reproduces the plain-DP
    quantized step's loss/metric sums — the pp schedules forward the quant
    knob into their rebuilt stage blocks and route the last-stage head
    matmul through ops.quant (pp._head_logits), so pp changes WHERE the
    quantized program runs, never what it computes."""
    from tpu_dist.parallel.pp import (make_lm_pp_1f1b_train_step,
                                      make_lm_pp_train_step, shard_state_pp,
                                      stack_pipeline_params)
    maker = (make_lm_pp_1f1b_train_step if schedule == "1f1b"
             else make_lm_pp_train_step)
    lm = _lm(quant)
    params = _params(lm)
    inputs, targets = make_lm_batches(_affine_rows(8))
    tx = make_optimizer(0.01, 0.9, 0.0, steps_per_epoch=100)
    key = jax.random.PRNGKey(1)

    mesh_dp = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    st_dp = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh_dp))
    dp_step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh_dp, model=lm, tx=tx))
    sh = NamedSharding(mesh_dp, P("data"))
    _, m_dp = dp_step(st_dp, jax.device_put(inputs, sh),
                      jax.device_put(targets, sh), key)

    mesh = make_mesh((2, 2), ("data", "stage"), devices=jax.devices()[:4])
    pp_params = stack_pipeline_params(params, num_stages=2)
    st_pp = shard_state_pp(mesh, TrainState.create(pp_params, {}, tx))
    pp_step = maker(lm, tx, mesh, num_microbatches=2, donate=False)
    sh_pp = NamedSharding(mesh, P("data", None))
    _, m_pp = pp_step(st_pp, jax.device_put(inputs, sh_pp),
                      jax.device_put(targets, sh_pp), key)

    for k in ("loss_sum", "correct1", "count"):
        assert float(jax.device_get(m_pp[k])) == pytest.approx(
            float(jax.device_get(m_dp[k])), rel=1e-5), k


@pytest.mark.slow  # tier-1 budget (PR 11): wo x mesh decode smoke; the fp mesh-decode parity pins (test_generate.py::test_mesh_tp_decode_matches_single_device) and the wo decode residency/parity tests above stay in-budget
def test_wo_sharded_decode_smoke():
    """int8_wo decode under a data-sharded mesh: scale leaves replicate
    (parallel.tp rule) and the program runs end to end."""
    lm = _lm()
    params = _params(lm, seed=7)
    mesh = make_mesh((8,), ("data",))
    prompt = jnp.asarray(np.tile([[2, 9, 4]], (8, 1)), jnp.int32)
    ref = np.asarray(generate(lm, params, prompt, steps=6, use_cache=True,
                              quant="int8_wo"))
    sharded = np.asarray(generate(lm, params, prompt, steps=6, use_cache=True,
                                  quant="int8_wo", mesh=mesh))
    np.testing.assert_array_equal(ref, sharded)
