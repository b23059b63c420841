"""The ``nemotron_h`` block (Mamba-2, latent experts held as one rank of an
expert-parallel group, grouped-head attention; one sublayer a block) and
its two ops families against the plain reference
``benchmarks/reference/nemotron_h.py`` on seeded weights, at a toy size
that keeps the pattern: two periods with all three kinds, 16 experts of
which 4 are held, top 3, 2 groups, chunks of 8.

Tolerance. Both sides compute in float32 here; they differ by the order of
float32 roundings (the chunked matrix form against the token-by-token
recurrence, the masked dense or the sorted grouped product (the megablox
``gmm`` kernel, interpreted here) against a loop
over experts, XLA:CPU matmuls against ``highest``): logits of size ~4 agree
to 2e-4 relative, 5e-5 absolute. An expert off by one, a dropped row or a
state taken past a row's length moves them by 1e-2 to 1.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import nemotron_h as ref  # noqa: E402
from tpu_dist.models.nemotron_h import (NemotronHLM, layer_types,  # noqa: E402
                                        nemotron_h_lm)
from tpu_dist.ops import routed_experts as rx  # noqa: E402
from tpu_dist.ops.ssd import (head_tile, live_rows, ssd_scan,  # noqa: E402
                              ssd_step, ssd_step_live)

TOY = dict(hidden_size=64, num_hidden_layers=12,
           hybrid_override_pattern="MEME*EMEME*E", num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
           mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
           chunk_size=8, n_routed_experts=4, router_width=16,
           expert_share=dict(of=4, index=0), num_experts_per_tok=3,
           moe_intermediate_size=48, moe_latent_size=32,
           moe_shared_expert_intermediate_size=96, routed_scaling_factor=5,
           norm_eps=1e-5, vocab_size=256, init_std=0.125)


def toy_model(**kw):
    return nemotron_h_lm(**kw)      # the preset IS the toy size above


def engine_params(model, weights, dtype=None):
    """The reference's flat weights arranged as the model's tree."""
    from benchmarks.harness.trainers import as_engine_tree

    like = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    return as_engine_tree(weights, like, ref.ref_name, dtype)


def lively_weights(sizes=TOY, seed=1):
    """Seeded weights with no unit or zero leaf left (a swapped gain or a
    dropped bias must show)."""
    w = ref.make_weights(sizes, jax.random.PRNGKey(seed))
    return {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(100 + i),
                                           v.shape)
            if k.split(".")[-1] not in ("A_log", "dt_bias", "b_sel") else v
            for i, (k, v) in enumerate(sorted(w.items()))}


def test_layer_kinds_come_from_the_pattern_string():
    assert layer_types("ME*") == ("mamba2", "experts", "attention")
    assert ref.layer_kinds(TOY) == toy_model().layer_types
    assert toy_model().layer_types.count("experts") == 6
    assert toy_model().held == (0, 4)
    assert toy_model(expert_share=(4, 3)).held == (12, 4)
    with pytest.raises(ValueError, match="whole equal blocks"):
        toy_model(expert_share=(3, 0)).held


@pytest.mark.parametrize("length", [5, 48, 300])
def test_full_forward_agrees_with_the_reference(length):
    """5 and 48 tokens a row run the masked dense form of the routed layer
    (2 x 48 rows), 300 the sorted grouped one (600 rows: 1800 assignments,
    padded to the grouped kernel's 128-row tiles); none is a multiple of
    the chunk but 48."""
    model = toy_model()
    w = lively_weights()
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (2, length)), jnp.int32)
    want = ref.forward(w, toks, TOY)
    got = model.apply({"params": engine_params(model, w)}, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=5e-5)
    assert float(jnp.abs(want).max()) > 0.5       # logits of real size


# ------------------------------------------------------------- Mamba-2 ops

def _recurrence(x, dt, A, B, C, D, s0, lengths):
    """Token by token, in numpy float64: what both forms must equal."""
    x, dt, A, B, C, D, s = (np.asarray(v, np.float64)
                            for v in (x, dt, A, B, C, D, s0))
    b, length, h, p = x.shape
    rep = h // B.shape[2]
    y = np.zeros_like(x)
    for i in range(b):
        for t in range(int(lengths[i])):
            bt, ct = np.repeat(B[i, t], rep, 0), np.repeat(C[i, t], rep, 0)
            s[i] = (np.exp(dt[i, t] * A)[:, None, None] * s[i]
                    + (dt[i, t, :, None] * x[i, t])[..., None]
                    * bt[:, None, :])
            y[i, t] = (s[i] * ct[:, None, :]).sum(-1) + D[:, None] * x[i, t]
    return y, s


def _ssd_inputs(b, length, seed, h=4, p=8, g=2, n=16):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return dict(x=f(b, length, h, p),
                dt=jnp.asarray(r.uniform(0.01, 0.6, (b, length, h)),
                               jnp.float32),
                A=-jnp.asarray(r.uniform(1, 16, (h,)), jnp.float32),
                B=f(b, length, g, n), C=f(b, length, g, n), D=f(h),
                s0=f(b, h, p, n))


@pytest.mark.parametrize("length,chunk", [(5, 8), (8, 8), (21, 8), (37, 16)])
def test_chunked_form_is_the_recurrence_with_a_carried_in_state(length,
                                                                chunk):
    """Lengths that are no multiple of the chunk, a state handed in, and
    rows that end before the call does: their state is that of their last
    live token and the rows past it change nothing."""
    a = _ssd_inputs(2, length, seed=length)
    lengths = np.asarray([length, max(length - 3, 1)], np.int32)
    y, s = ssd_scan(a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"],
                    a["s0"], jnp.asarray(lengths), chunk=chunk)
    want_y, want_s = _recurrence(**a, lengths=lengths)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[i, :n], want_y[i, :n],
                                   rtol=2e-5, atol=2e-5)


def test_one_step_form_continues_the_chunked_one():
    a = _ssd_inputs(3, 14, seed=3)
    full = np.asarray([14, 14, 14], np.int32)
    want_y, want_s = _recurrence(**a, lengths=full)
    cut = lambda v, lo, hi: v[:, lo:hi]
    y, s = ssd_scan(cut(a["x"], 0, 11), cut(a["dt"], 0, 11), a["A"],
                    cut(a["B"], 0, 11), cut(a["C"], 0, 11), a["D"], a["s0"],
                    jnp.full((3,), 11, jnp.int32), chunk=4)
    for t in range(11, 14):
        # a row whose dt is 0 sits the tick out: its state stays
        dt = a["dt"][:, t].at[1].set(0.0)
        kept = np.asarray(s[1])
        y_t, s = ssd_step(a["x"][:, t], dt, a["A"], a["B"][:, t],
                          a["C"][:, t], a["D"], s)
        np.testing.assert_array_equal(np.asarray(s[1]), kept)
        np.testing.assert_allclose(np.asarray(y_t)[[0, 2]],
                                   want_y[[0, 2], t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s)[[0, 2]], want_s[[0, 2]],
                               rtol=2e-5, atol=2e-5)


LIVE = {"none": [], "one_row": [2], "scattered_half": [0, 2, 3, 6],
        "all_rows": list(range(8)), "last_row_only": [7]}


@pytest.mark.parametrize("fresh", [False, True], ids=["carried", "fresh"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_kernel_over_the_live_rows_is_the_one_step_form(live, fresh):
    """``ssd_step_live`` (interpreted here; two head tiles of two groups
    each) against ``ssd_step``: a live row's ``y`` and state agree to float32
    rounding (1e-6 of the largest value: the sum over ``n`` runs in the
    backend's order, and the products may fuse otherwise), a fresh one starts
    from zero whatever its slot holds, and a row that sits out keeps its
    state BIT FOR BIT and answers ``y == 0``; with no row live every state is
    what went in, the block the walk names on its first step included."""
    rows, (h, p, g, n) = 8, (8, 16, 4, 128)
    a = {k: v[:, 0] if k in ("x", "dt", "B", "C") else v
         for k, v in _ssd_inputs(rows, 1, seed=len(live), h=h, p=p, g=g,
                                 n=n).items()}
    on = np.zeros(rows, bool)
    on[LIVE[live]] = True
    # the fresh row is a live one where there is one (a row that sits out
    # is never fresh: the mixer says ``fresh & live``)
    new = np.zeros(rows, bool)
    new[LIVE[live][-1:]] = fresh
    assert head_tile(a["s0"], g) == h
    y, s = jax.jit(lambda a, on, new: ssd_step_live(
        a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], a["s0"],
        live_rows(on), new, tile=4))(a, jnp.asarray(on), jnp.asarray(new))
    want_y, want_s = ssd_step(
        a["x"], jnp.where(on[:, None], a["dt"], 0.0), a["A"], a["B"], a["C"],
        a["D"], jnp.where(new[:, None, None, None], 0.0, a["s0"]))
    y, s, want_y, want_s = map(np.asarray, (y, s, want_y, want_s))
    np.testing.assert_array_equal(s[~on], np.asarray(a["s0"])[~on])
    np.testing.assert_array_equal(y[~on], 0.0)
    for got, want in ((y[on], want_y[on]), (s[on], want_s[on])):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-6 * max(np.abs(want).max(initial=0), 1))
    if fresh and on.any():
        # nothing of the slot's old state is left in a fresh row's
        assert np.abs(s[new] - a["s0"][new]).max() > 0.5


@pytest.mark.parametrize("shape,dtype,groups,want", [
    ((64, 128, 64, 128), jnp.float32, 8, 32),    # the published mixer: 1 MB
    ((8, 8, 16, 128), jnp.float32, 2, 8),        # small enough whole
    ((8, 24, 64, 128), jnp.float32, 3, 24),      # whole groups of 8 heads
    ((8, 48, 64, 128), jnp.float32, 3, 16),      # 32 would split a group
    ((8, 8, 16, 16), jnp.float32, 2, 0),         # the toy's 16 states
    ((8, 8, 12, 128), jnp.float32, 2, 0),        # channels off the sublanes
    ((8, 8, 16, 192), jnp.float32, 2, 0),        # states off the lanes
    ((8, 8, 16, 128), jnp.bfloat16, 2, 0),       # a state that is not f32
    ((8, 2, 4096, 128), jnp.float32, 2, 0),      # one group over the block
], ids=["published", "small", "three_groups", "whole_groups_only",
        "toy_states", "odd_channels", "odd_states", "bf16_state",
        "group_too_large"])
def test_the_kernel_takes_a_state_by_its_shape_alone(shape, dtype, groups,
                                                     want):
    assert head_tile(jax.ShapeDtypeStruct(shape, dtype), groups) == want


# -------------------------------------------------------- the routed layer

ONE_LAYER = dict(TOY, num_hidden_layers=1, hybrid_override_pattern="E")


def _expert_layer(sizes, w, x):
    """One expert block of the MODEL over ``x`` [b, l, d], with the two
    counters it sows."""
    from tpu_dist.models.nemotron_h import NemotronHBlock

    share = sizes["expert_share"]
    model = toy_model(pattern="E", expert_share=(share["of"],
                                                 share["index"]))
    params = engine_params(model, w)["layer0"]
    blk = NemotronHBlock(
        "experts", (4, 2, 16), (8, 16, 16, 2, 4, 8),
        (16, model.held, 3, 5.0, 32, 48, 96), 1e-5, jnp.float32,
        None, "none")
    (out, _), sown = blk.apply({"params": params}, x,
                               mutable=["expert_counts"])
    counts = sown["expert_counts"]["moe"]
    # the form the layer was traced in, from the layer itself
    assert int(counts["grouped"][0]) == rx.grouped_calls(x.shape[0]
                                                         * x.shape[1])
    return out, int(counts["rows"][0]), int(counts["hit"][0])


def _share(index, of=4):
    return dict(ONE_LAYER, expert_share=dict(of=of, index=index),
                n_routed_experts=16 // of)


def _sliced(uncut, index, of=4):
    """The weights rank ``index`` of ``of`` holds of an uncut layer's."""
    n = 16 // of
    return {k: v[index * n:(index + 1) * n]
            if k.split(".")[-1] in ("w_in", "w_out") else v
            for k, v in uncut.items()}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares, the routed parts added up, with what every chip
    computes alike (the shared expert) counted once, equal the uncut layer
    of the reference."""
    uncut = lively_weights(_share(0, of=1), seed=5)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 20, 64))
    whole, _ = ref.layer_programs(_share(0, of=1))["experts"](
        x, ref.layer_weights(uncut, 0))
    parts = [_expert_layer(_share(i), _sliced(uncut, i), x)[0] - x
             for i in range(4)]
    with jax.default_matmul_precision("highest"):
        h = ref._rms(x, uncut["layer0.norm"], 1e-5)
        shared = (ref._relu2(h @ uncut["layer0.shared_in"])
                  @ uncut["layer0.shared_out"])
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(whole - x), rtol=2e-4, atol=5e-5)
    # and each share alone is the reference's under the same share
    for i in (0, 3):
        want, _ = ref.layer_programs(_share(i))["experts"](
            x, ref.layer_weights(_sliced(uncut, i), 0))
        np.testing.assert_allclose(
            np.asarray(_expert_layer(_share(i), _sliced(uncut, i), x)[0]),
            np.asarray(want), rtol=2e-4, atol=5e-5)


@pytest.mark.parametrize("rows", [40, 600])
def test_rows_that_all_choose_the_same_experts_drop_nothing(rows,
                                                            monkeypatch):
    """A batch whose rows all choose experts 1, 2 and 3 (a selection bias
    of +10 each): 3 x rows assignments on three of this rank's four
    experts, nothing capped, in the dense form (40 rows) and the sorted one
    (600); and both forms agree on the same rows."""
    sizes = _share(0)
    w = lively_weights(sizes, seed=6)
    w["layer0.b_sel"] = jnp.zeros((16,)).at[jnp.asarray([1, 2, 3])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, 64))
    want, chosen = ref.layer_programs(sizes)["experts"](
        x, ref.layer_weights(w, 0))
    assert bool(jnp.all(chosen[..., 1:4])) and int(chosen.sum()) == 3 * rows
    out, n_rows, n_hit = _expert_layer(sizes, w, x)
    assert (n_rows, n_hit) == (3 * rows, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=5e-5)
    # the other form on the same rows
    monkeypatch.setattr(rx, "DENSE_ROWS",
                        0 if rows <= rx.DENSE_ROWS else 1 << 20)
    other, o_rows, o_hit = _expert_layer(sizes, w, x)
    assert (o_rows, o_hit) == (n_rows, n_hit)
    np.testing.assert_allclose(np.asarray(other), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", [12, 600])
def test_counters_equal_a_count_made_by_hand(rows):
    """``expert_rows`` and ``experts_hit`` from the ops, against a count
    from the chosen indices in numpy; rows past the live length reach no
    expert and count nowhere."""
    r = np.random.default_rng(rows)
    logits = jnp.asarray(r.normal(size=(rows, 16)), jnp.float32)
    idx, w = rx.route(logits, jnp.zeros((16,)), 3, 5.0)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 5.0, rtol=1e-5)
    # the chosen are the 3 largest sigmoid scores
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.argsort(-np.asarray(logits), -1)[:, :3],
                                  -1))
    live = jnp.asarray(np.arange(rows) < rows - 5)
    u = jnp.asarray(r.normal(size=(rows, 32)), jnp.float32)
    w_in = jnp.asarray(r.normal(size=(4, 32, 48)), jnp.float32) * 0.1
    w_out = jnp.asarray(r.normal(size=(4, 48, 32)), jnp.float32) * 0.1
    out, n_rows, n_hit = rx.routed_experts(u, idx, w, live, w_in, w_out, 8)
    picked = np.asarray(idx)[:rows - 5]
    here = picked[(picked >= 8) & (picked < 12)]
    assert int(n_rows) == here.size and int(n_hit) == np.unique(here).size
    assert not np.asarray(out)[rows - 5:].any()       # dead rows: nothing
    # by hand, in numpy
    want = np.zeros((rows, 32))
    for i in range(rows - 5):
        for e, we in zip(np.asarray(idx)[i], np.asarray(w)[i]):
            if 8 <= e < 12:
                hid = np.maximum(np.asarray(u)[i] @ np.asarray(w_in)[e - 8],
                                 0.0) ** 2
                want[i] += we * (hid @ np.asarray(w_out)[e - 8])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


def test_int8_wo_quantises_the_experts_and_the_router_stays_float32():
    from tpu_dist.ops.quant import wo_quantize_params

    model = toy_model()
    params = engine_params(model, lively_weights())
    q = wo_quantize_params(params)
    moe = q["layer1"]["moe"]
    assert moe["w_in"].dtype == jnp.int8 and moe["w_out"].dtype == jnp.int8
    assert moe["w_in_scale"].shape == (4, 1, 48)
    assert moe["gate"]["kernel"].dtype == jnp.float32
    assert "kernel_scale" not in moe["gate"]
    for name in ("down", "up", "shared_in", "shared_out"):
        assert moe[name]["kernel"].dtype == jnp.int8, name
    assert q["layer0"]["mamba"]["in_proj"]["kernel"].dtype == jnp.int8
    assert q["lm_head"].dtype == jnp.float32
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 24)),
                       jnp.int32)
    pre = model.clone(quant="int8_wo").apply({"params": q}, toks)
    fake = model.clone(quant="int8_wo").apply({"params": params}, toks)
    # the pre-quantized tree and the fake-quantized weights are one model
    np.testing.assert_allclose(np.asarray(pre), np.asarray(fake),
                               rtol=1e-4, atol=1e-4)
    exact = model.apply({"params": params}, toks)
    assert float(jnp.abs(pre - exact).max()) > 1e-3
    with pytest.raises(NotImplementedError, match="int8_wo"):
        model.clone(quant="int8").apply({"params": params}, toks)


@pytest.mark.parametrize("holds,kernels", [
    ("stored", 6), ("int8_wo", 0), ("prequantized", 0), ("cast", 0)])
def test_layer_takes_the_kernel_form_only_over_weights_as_stored(holds,
                                                                 kernels):
    """The layer picks the routed products' form from what it holds: the
    experts as they lie in HBM go to the kernel over the hit list (one call
    in each of the toy's six expert layers); fake-quantised, dequantised or
    cast on the way in they keep the einsum form, which XLA fuses the
    producer into."""
    from tpu_dist.ops.quant import wo_quantize_params

    model = toy_model()
    params = engine_params(model, lively_weights())
    if holds == "int8_wo":
        model = model.clone(quant="int8_wo")
    elif holds == "prequantized":
        model, params = model.clone(quant="int8_wo"), wo_quantize_params(
            params)
    elif holds == "cast":
        model = model.clone(dtype=jnp.bfloat16)      # float32 parameters
    toks = jnp.zeros((1, 24), jnp.int32)
    text = str(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, toks))(params))
    assert text.count("name=hit_experts") == kernels


def test_registry_lists_the_model():
    from tpu_dist.models.registry import create_model, model_kind

    assert model_kind("nemotron_h_lm") == "lm"
    model = create_model("nemotron_h_lm", pattern="ME*", vocab_size=64)
    assert isinstance(model, NemotronHLM) and model.pattern == "ME*"
