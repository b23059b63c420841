"""The hybrid (Mamba-1 + grouped-head attention) LM block against the plain
reference ``benchmarks/reference/jamba.py`` on seeded weights, at a toy
size that keeps the pattern: period 4, offset 2, 8 layers, 2 KV heads under
4 query heads.

Tolerance. Both sides compute in float32 here; they differ by the order of
float32 roundings through 8 layers (the model's scan runs [state, channel],
the reference's [channel, state]; XLA:CPU matmuls against ``highest``):
logits of size ~1 agree to 2e-4 relative, 5e-5 absolute. A bfloat16 state,
softplus or exp anywhere in a Mamba layer moves logits by 1e-2.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import jamba as ref  # noqa: E402
from tpu_dist.models.hybrid import HybridLM, hybrid_lm, layer_types  # noqa: E402

TOY = dict(hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, intermediate_size=128,
           mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
           attn_layer_period=4, attn_layer_offset=2, rms_norm_eps=1e-6,
           vocab_size=256)


def toy_model(**kw):
    return hybrid_lm(**kw)          # the preset IS the toy size above


def engine_params(model, weights, dtype=None):
    """The reference's flat weights arranged as the model's tree."""
    from benchmarks.harness.trainers import as_engine_tree

    like = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    return as_engine_tree(weights, like, ref.ref_name, dtype)


def lively_weights(seed=1):
    """Seeded weights with no unit or zero leaf left (a swapped gain or a
    dropped bias must show)."""
    w = ref.make_weights(TOY, jax.random.PRNGKey(seed))
    return {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(100 + i),
                                           v.shape)
            if k.split(".")[-1] not in ("A_log", "dt_bias") else v
            for i, (k, v) in enumerate(sorted(w.items()))}


def test_layer_pattern_from_period_and_offset():
    assert layer_types(8, 4, 2) == (
        "mamba", "mamba", "attention", "mamba",
        "mamba", "mamba", "attention", "mamba")
    published = layer_types(28, 14, 7)
    assert [i for i, t in enumerate(published) if t == "attention"] == [7, 21]
    assert ref.layer_kinds(TOY) == toy_model().layer_types


def test_full_forward_agrees_with_the_reference():
    model = toy_model()
    w = lively_weights()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 48)),
                       jnp.int32)
    want = ref.forward(w, toks, TOY)
    got = model.apply({"params": engine_params(model, w)}, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=5e-5)
    assert float(jnp.abs(want).max()) > 0.5       # logits of real size


def test_a_bfloat16_softplus_would_fail_that_tolerance(monkeypatch):
    model = toy_model()
    w = lively_weights()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 48)),
                       jnp.int32)
    want = ref.forward(w, toks, TOY)
    real = jax.nn.softplus
    monkeypatch.setattr(jax.nn, "softplus", lambda x: real(
        x.astype(jnp.bfloat16)).astype(jnp.float32))
    got = model.apply({"params": engine_params(model, w)}, toks)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 1e-3


def test_head_is_tied_to_the_embedding():
    model = toy_model()
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    assert "lm_head" not in params and "tok_emb" in params
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    base = model.apply({"params": params}, toks)
    # scaling one embedding row scales that token's logit column
    emb = params["tok_emb"]["embedding"]
    bumped = {**params, "tok_emb": {"embedding": emb.at[200].multiply(3.0)}}
    moved = model.apply({"params": bumped}, toks)
    np.testing.assert_allclose(np.asarray(moved[..., 200]),
                               3.0 * np.asarray(base[..., 200]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(moved[..., :200]),
                               np.asarray(base[..., :200]), rtol=1e-6)


def test_published_configuration_counts_3_03_billion_parameters():
    cfg = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                      "jamba2-3b.json")))
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "families"))
    from benchmarks.families.hybrid_lm_server import model_fields

    model = HybridLM(**model_fields(cfg), dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 3.02e9 < n < 3.04e9, n
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    mamba, attn = shapes["layer0"], shapes["layer7"]
    assert "mamba" in mamba and "attn" in attn and "attn" in shapes["layer21"]
    m = mamba["mamba"]
    assert m["in_proj"]["kernel"].shape == (2560, 10240)
    assert m["conv_w"].shape == (4, 5120) and m["conv_b"].shape == (5120,)
    assert m["x_proj"]["kernel"].shape == (5120, 192)
    assert m["dt_proj"]["kernel"].shape == (160, 5120)
    assert m["A_log"].shape == (5120, 16) and m["D"].shape == (5120,)
    assert m["out_proj"]["kernel"].shape == (5120, 2560)
    assert [m[k]["scale"].shape for k in ("dt_norm", "b_norm", "c_norm")] \
        == [(160,), (16,), (16,)]
    assert 41.2e6 < count(m) < 41.3e6                 # the mixer: 41.24 M
    a = attn["attn"]
    assert a["q"]["kernel"].shape == (2560, 2560)
    assert a["k"]["kernel"].shape == a["v"]["kernel"].shape == (2560, 128)
    assert a["o"]["kernel"].shape == (2560, 2560)
    assert 13.7e6 < count(a) < 13.8e6                 # 13.76 M
    mlp = sum(count(mamba[k]) for k in ("gate", "up", "down"))
    assert mamba["gate"]["kernel"].shape == (2560, 8192)
    assert mamba["down"]["kernel"].shape == (8192, 2560)
    assert 62.9e6 < mlp < 63.0e6                      # 62.91 M
    assert shapes["tok_emb"]["embedding"].shape == (65536, 2560)
    # and the reference's own shapes are the same leaves
    want = ref.weight_shapes(cfg)
    from benchmarks.harness.trainers import path_names

    got = {ref.ref_name(path_names(p)): x.shape for p, x in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == want


def test_cache_layout_names_pages_and_slot_state():
    layout = toy_model(dtype=jnp.bfloat16).cache_layout()
    assert layout[2] == layout[6] == ("pages", 2, 16, 2)
    kind, state = layout[0]
    assert kind == "slot_state"
    assert state["ssm"] == ((16, 128), jnp.float32)       # float32 always
    assert state["conv"] == ((3, 128), jnp.bfloat16)


@pytest.mark.parametrize("quant", ["int8_wo", "int8"])
def test_projections_go_through_make_dense(quant):
    """quant= reaches every projection (the serving control is int8_wo):
    the param tree is the unquantized model's and the logits move."""
    base, q = toy_model(), toy_model(quant=quant)
    toks = jnp.asarray([[5, 6, 7, 8, 9, 10, 11, 12]], jnp.int32)
    params = base.init({"params": jax.random.PRNGKey(2)}, toks)["params"]
    a, b = base.apply({"params": params}, toks), q.apply(
        {"params": params}, toks)
    diff = float(jnp.abs(a - b).max() / jnp.abs(a).max())
    assert 1e-5 < diff < 1.0, diff   # 8-row int8 matrices are coarse
