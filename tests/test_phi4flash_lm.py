"""The decoder-hybrid-decoder LM (``models.phi4flash``: Mamba-1, window and
full differential attention, cross layers over one shared KV layer, gated
memory units) against the plain reference ``benchmarks/reference/
phi4flash.py`` on seeded weights, at a toy size that keeps the pattern: 12
layers are two periods of each half (4 / 3 / 1 / 2 / 2 of the five kinds),
two query pairs over one KV pair, a window of 8 under sequences of 40.

Tolerance. Both sides compute in float32; they differ by the order of
float32 roundings through 12 layers: logits of size ~5 agree to 2e-4
relative, 1e-4 absolute. A window one key too wide, a swapped head pair, a
lambda of another layer or a dropped bias moves them by 1e-2 or more.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import phi4flash as ref  # noqa: E402
from tpu_dist.models.phi4flash import (Phi4FlashLM, layer_types,  # noqa: E402
                                       phi4flash_lm)

TOY = dict(hidden_size=64, num_hidden_layers=12, num_attention_heads=4,
           num_key_value_heads=2, head_dim=64, intermediate_size=128,
           mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
           mb_per_layer=2, sliding_window=8, layer_norm_eps=1e-5,
           vocab_size=256,
           # 0.02 * sqrt(2560 / 64): the matrices move a logit as the
           # published width's do
           init_std=0.125)
RTOL, ATOL = 2e-4, 1e-4


def toy_model(**kw):
    return phi4flash_lm(**kw)       # the preset IS the toy size above


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


def test_layer_pattern_from_depth_and_mb_per_layer():
    assert layer_types(12) == (
        "mamba", "window", "mamba", "window", "mamba", "window",
        "mamba", "full", "gmu", "cross", "gmu", "cross")
    published = layer_types(32, 2)
    count = lambda kind: sum(t == kind for t in published)
    assert [count(k) for k in ("mamba", "window", "full", "cross", "gmu")] \
        == [9, 8, 1, 7, 7]
    assert published.index("full") == 17 and published[16] == "mamba"
    assert [i for i, t in enumerate(published) if t == "cross"] \
        == list(range(19, 32, 2))
    assert ref.layer_kinds(TOY) == toy_model().layer_types
    assert ref.layer_kinds(dict(num_hidden_layers=32, mb_per_layer=2)) \
        == published


def test_registry_builds_it_beside_hybrid_lm():
    from tpu_dist.models.registry import create_model, model_kind

    assert model_kind("phi4flash_lm") == "lm"
    model = create_model("phi4flash_lm", num_layers=12, d_model=64,
                         vocab_size=256)
    assert isinstance(model, Phi4FlashLM) and model.num_layers == 12


def test_full_forward_agrees_with_the_reference():
    model = toy_model()
    w = lively_weights()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)),
                       jnp.int32)
    want = ref.forward(w, toks, TOY)
    got = model.apply({"params": engine_params(model, w)}, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    assert float(jnp.abs(want).max()) > 0.5       # logits of real size


@pytest.mark.parametrize("what", ["window", "pairing", "lambda", "memory"])
def test_a_wrong_mechanism_fails_that_tolerance(what, monkeypatch):
    """The model run against a reference broken in one place: the window a
    key wider, a query pair reading the other KV head of its pair, every
    layer's lambda_init the first layer's, the gated memory units reading
    the scan of the first Mamba layer."""
    sizes = dict(TOY)
    if what == "window":
        sizes["sliding_window"] = TOY["sliding_window"] + 1
    elif what == "pairing":
        real = ref._differential
        monkeypatch.setattr(
            ref, "_differential",
            lambda q, k, v, *a, **kw: real(q, k[:, :, ::-1], v, *a, **kw))
    elif what == "lambda":
        monkeypatch.setattr(ref, "lambda_init", lambda i: 0.2)
    else:
        real_hidden = ref.hidden

        def first_mamba(weights, tokens, sz, programs=None):
            programs = dict(programs or ref.layer_programs(sz))
            mamba, seen = programs["mamba"], []

            def keep_first(x, w):
                x, y = mamba(x, w)
                seen.append(y)
                return x, seen[0]
            programs["mamba"] = keep_first
            return real_hidden(weights, tokens, sz, programs)
        monkeypatch.setattr(ref, "hidden", first_mamba)
    model = toy_model()
    w = lively_weights()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)),
                       jnp.int32)
    want = ref.forward(w, toks, sizes)
    got = model.apply({"params": engine_params(model, w)}, toks)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)


def test_published_widths_count_3_85_billion_parameters():
    """By shapes alone: nothing is allocated."""
    model = Phi4FlashLM()
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 3.84e9 < n < 3.86e9, n
    by_layer = {i: sum(int(np.prod(x.shape)) for x in
                       jax.tree_util.tree_leaves(shapes[f"layer{i}"]))
                for i in range(32)}
    # Mamba 119.9 M, window/full 98.3 M, cross 91.8 M, GMU 104.9 M
    assert [round(by_layer[i] / 1e6, 1) for i in (0, 1, 17, 19, 18)] \
        == [119.9, 98.3, 98.3, 91.8, 104.9]


def test_the_jamba_family_block_is_unchanged_bit_for_bit():
    """``MambaMixer`` gained two fields for this family (``inner_norms``,
    ``hand_on``); the Jamba toy model's logits are the bits they were at the
    parent commit (this digest was taken there, same installation, with the
    lines below)."""
    from benchmarks.reference import jamba
    from test_hybrid_lm import TOY as JAMBA_TOY
    from test_hybrid_lm import engine_params as jamba_params
    from test_hybrid_lm import toy_model as jamba_model

    model = jamba_model()
    w = jamba.make_weights(JAMBA_TOY, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(5).integers(0, 256, (2, 48)),
                       jnp.int32)
    got = np.asarray(model.apply({"params": jamba_params(model, w)}, toks))
    assert hashlib.sha256(got.tobytes()).hexdigest() == JAMBA_DIGEST
    # and its mixers keep their three inner norms
    assert {"dt_norm", "b_norm", "c_norm"} <= set(
        jamba_params(model, w)["layer0"]["mamba"])


JAMBA_DIGEST = ("6b6e42f90c7243138a7a9f3c4a69621e"
                "0b9b875195fd6a134978f0d73bf139d6")
