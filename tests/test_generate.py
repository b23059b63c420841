"""Autoregressive decoding: determinism, shapes, and learned-rule recovery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.engine.generate import generate
from tpu_dist.engine.lm_steps import make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.models.transformer import tiny_lm
from tpu_dist.ops import make_optimizer
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.plan.compile import Bindings, compile_train_step
from tpu_dist.plan.ir import Plan

V, L = 64, 32


def _lm_and_params(seed=0):
    lm = tiny_lm(vocab_size=V, num_layers=2, d_model=64, num_heads=4,
                 max_len=L)
    params = lm.init({"params": jax.random.PRNGKey(seed)},
                     jnp.zeros((1, L), jnp.int32), train=False)["params"]
    return lm, params


def test_greedy_is_deterministic_and_shaped():
    lm, params = _lm_and_params()
    prompt = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)
    a = generate(lm, params, prompt, steps=8)
    b = generate(lm, params, prompt, steps=8)
    assert a.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a[:, :4]), np.asarray(prompt))
    assert int(jnp.min(a)) >= 0 and int(jnp.max(a)) < V


def test_sampling_uses_rng():
    lm, params = _lm_and_params()
    prompt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    a = generate(lm, params, prompt, steps=12, temperature=1.0,
                 rng=jax.random.PRNGKey(0))
    b = generate(lm, params, prompt, steps=12, temperature=1.0,
                 rng=jax.random.PRNGKey(1))
    assert not np.array_equal(np.asarray(a[:, 4:]), np.asarray(b[:, 4:]))


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_trained_lm_generates_the_learned_rule():
    """Train on the affine next-token stream (x -> 5x+7 mod V, the script-8
    dataset), then greedy generation must follow the rule."""
    lm, params = _lm_and_params()
    tx = make_optimizer(0.05, 0.9, 0.0, steps_per_epoch=1000)
    mesh = make_mesh((8,), ("data",))
    state = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh))
    step = compile_train_step(
        Plan(engine="lm", donate=False),
        Bindings(mesh=mesh, model=lm, tx=tx))

    rng = np.random.default_rng(0)
    start = rng.integers(0, V, (16, 1))
    rows = [start]
    for _ in range(L):
        rows.append((rows[-1] * 5 + 7) % V)  # noiseless rule
    tokens = np.concatenate(rows, axis=1).astype(np.int32)
    inputs, targets = make_lm_batches(tokens)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P("data"))
    di, dt = jax.device_put(inputs, sh), jax.device_put(targets, sh)
    key = jax.random.PRNGKey(1)
    for _ in range(60):
        state, _ = step(state, di, dt, key)
        # keep the async dispatch queue bounded: a 60-deep unfetched queue
        # intermittently SIGABRTs the virtual-device CPU backend
        # distlint: disable=DL002 -- bounds the virtual-device async queue (SIGABRT workaround above)
        jax.block_until_ready(state.step)

    prompt = jnp.asarray([[3, (3 * 5 + 7) % V]], jnp.int32)
    out = np.asarray(generate(lm, jax.device_get(state.params), prompt,
                              steps=16))
    follows = sum(int(out[0, i + 1]) == (int(out[0, i]) * 5 + 7) % V
                  for i in range(1, 17))
    assert follows >= 13, (follows, out)


def test_cached_decode_matches_full_recompute():
    """KV-cache decode produces the SAME greedy continuation as the
    full-recompute path (the cache is an optimization, not a model change)."""
    lm, params = _lm_and_params(seed=4)
    prompt = jnp.asarray([[1, 9, 17, 25], [2, 4, 8, 16]], jnp.int32)
    full = generate(lm, params, prompt, steps=10)
    cached = generate(lm, params, prompt, steps=10, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_cached_decode_matches_sampling_stream():
    """Same rng + temperature > 0: cached and full paths sample the SAME
    tokens (the cache must not perturb the rng stream)."""
    lm, params = _lm_and_params(seed=5)
    prompt = jnp.asarray([[7, 3, 11, 2]], jnp.int32)
    key = jax.random.PRNGKey(42)
    full = generate(lm, params, prompt, steps=8, temperature=0.8, rng=key)
    cached = generate(lm, params, prompt, steps=8, temperature=0.8, rng=key,
                      use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_sample_truncation_unit():
    """The sampler math alone (engine.generate._sample, no model): top_k=1
    == argmax at any temperature, a peaked small-p nucleus == argmax, a
    permissive nucleus stays in-vocab — the cheap tier-1 sibling of the
    model-level truncation tests below (slow-marked, PR 11 budget)."""
    from tpu_dist.engine.generate import _sample

    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 1, (4, V)).astype(np.float32))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    k1, _ = _sample(logits, 2.0, jax.random.PRNGKey(0), top_k=1)
    np.testing.assert_array_equal(greedy, np.asarray(k1))
    peaked, _ = _sample(logits, 0.05, jax.random.PRNGKey(1), top_p=0.5)
    np.testing.assert_array_equal(greedy, np.asarray(peaked))
    free, _ = _sample(logits, 1.0, jax.random.PRNGKey(2), top_p=0.9)
    free = np.asarray(free)
    assert free.min() >= 0 and free.max() < V


@pytest.mark.slow  # tier-1 budget (PR 11): model-level twin of the _sample truncation unit above (test_sample_truncation_unit keeps k-truncation pinned in-budget)
def test_top_k_restricts_to_best_tokens():
    """top_k=1 sampling == greedy argmax regardless of temperature/rng."""
    lm, params = _lm_and_params(seed=6)
    prompt = jnp.asarray([[5, 9]], jnp.int32)
    greedy = generate(lm, params, prompt, steps=8)
    k1 = generate(lm, params, prompt, steps=8, temperature=2.0, top_k=1,
                  rng=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))


@pytest.mark.slow  # tier-1 budget (PR 11): model-level twin of the _sample truncation unit (test_sample_truncation_unit keeps nucleus masking pinned in-budget)
def test_top_p_nucleus_keeps_valid_tokens():
    """top_p sampling only ever emits tokens inside the nucleus: with a
    peaked distribution and small p, it matches greedy."""
    lm, params = _lm_and_params(seed=7)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    # temperature -> 0+ peaks the distribution so the nucleus is one token
    greedy = generate(lm, params, prompt, steps=6)
    p_small = generate(lm, params, prompt, steps=6, temperature=0.05,
                       top_p=0.5, rng=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(p_small))
    # and a permissive nucleus still emits in-vocab tokens
    out = generate(lm, params, prompt, steps=6, temperature=1.0, top_p=0.9,
                   rng=jax.random.PRNGKey(4), use_cache=True)
    assert int(jnp.min(out)) >= 0 and int(jnp.max(out)) < V


def test_generate_zero_steps_returns_prompt():
    """steps=0 is a no-op in BOTH paths (the cache prefill must not clamp
    its first-token write into the last prompt column)."""
    import jax.numpy as jnp
    model, params = _lm_and_params()
    prompt = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) % 7
    for use_cache in (False, True):
        out = generate(model, params, prompt, 0, use_cache=use_cache)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))


def test_mesh_data_sharded_decode_matches_single_device():
    """Batch-sharded decode over a ('data',) mesh emits the SAME greedy
    tokens as single-device decode, both full-recompute and KV-cache paths
    (VERDICT r4 #3: sharded inference must be bit-identical on tokens)."""
    lm, params = _lm_and_params(seed=11)
    mesh = make_mesh((8,), ("data",))
    prompt = jnp.tile(jnp.asarray([[1, 5, 9, 2]], jnp.int32), (8, 1))
    prompt = prompt.at[:, 0].set(jnp.arange(8))  # distinct rows per shard
    single = generate(lm, params, prompt, steps=10)
    for use_cache in (False, True):
        sharded = generate(lm, params, prompt, steps=10, mesh=mesh,
                           use_cache=use_cache)
        np.testing.assert_array_equal(np.asarray(single), np.asarray(sharded))


def test_mesh_tp_decode_matches_single_device():
    """TP decode (heads + vocab sharded over 'model') matches single-device
    greedy tokens; KV cache shards its heads axis."""
    lm, params = _lm_and_params(seed=12)
    mesh = make_mesh((4,), ("model",), devices=jax.devices()[:4])
    prompt = jnp.asarray([[3, 7, 1, 4], [2, 2, 9, 9]], jnp.int32)
    single = generate(lm, params, prompt, steps=10)
    for use_cache in (False, True):
        tp = generate(lm, params, prompt, steps=10, mesh=mesh,
                      use_cache=use_cache)
        np.testing.assert_array_equal(np.asarray(single), np.asarray(tp))


@pytest.mark.slow  # tier-1 budget (PR 11): the dp x tp composition of two single-axis parity pins that stay in-budget (test_mesh_data_sharded_decode_matches_single_device, test_mesh_tp_decode_matches_single_device)
def test_mesh_dp_tp_decode_matches_single_device():
    """2-D ('data','model') decode: batch AND heads sharded together."""
    lm, params = _lm_and_params(seed=13)
    mesh = make_mesh((2, 4), ("data", "model"))
    prompt = jnp.asarray([[3, 7, 1, 4], [8, 2, 9, 9]], jnp.int32)
    single = generate(lm, params, prompt, steps=8, use_cache=True)
    both = generate(lm, params, prompt, steps=8, use_cache=True, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(both))


def test_mesh_tp_decode_rejects_indivisible_heads():
    import pytest
    lm, params = _lm_and_params(seed=14)  # tiny_lm: 4 heads
    mesh = make_mesh((8,), ("model",))
    with pytest.raises(ValueError, match="num_heads"):
        generate(lm, params, jnp.ones((1, 4), jnp.int32), steps=4, mesh=mesh)


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_mesh_sampled_decode_reproduces_replicated_rng():
    """temperature>0 under a data mesh: the rng is replicated, so sampling
    is still deterministic given the key, and matches single-device."""
    lm, params = _lm_and_params(seed=15)
    mesh = make_mesh((8,), ("data",))
    prompt = jnp.tile(jnp.asarray([[6, 1, 3, 8]], jnp.int32), (8, 1))
    key = jax.random.PRNGKey(7)
    single = generate(lm, params, prompt, steps=8, temperature=0.7, rng=key,
                      use_cache=True)
    sharded = generate(lm, params, prompt, steps=8, temperature=0.7, rng=key,
                       use_cache=True, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(sharded))


def _moe_and_params(seed=0, **kw):
    from tpu_dist.models.moe import MoETransformerLM

    moe = MoETransformerLM(vocab_size=V, num_layers=2, d_model=64,
                           num_heads=4, num_experts=2, max_len=L, **kw)
    params = moe.init({"params": jax.random.PRNGKey(seed)},
                      jnp.zeros((1, L), jnp.int32), train=False)["params"]
    return moe, params


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_cached_decode_matches_full_recompute():
    """MoE KV-cache decode == full recompute under drop-free capacity
    (capacity_factor >= E/k): per-expert capacity is group-LENGTH-dependent
    (cap = S/E * factor), and the prefill groups P tokens while the full
    path groups the whole padded buffer — only a capacity that admits every
    token makes the two dispatch patterns identical. B=1 additionally
    removes cross-row queue interference."""
    moe, params = _moe_and_params(seed=21, capacity_factor=2.0)
    prompt = jnp.asarray([[3, 9, 27, 17]], jnp.int32)
    full = generate(moe, params, prompt, steps=10)
    cached = generate(moe, params, prompt, steps=10, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


@pytest.mark.slow  # tier-1 budget (PR 11): MoE twin of the dense rng-stream pin (test_cached_decode_matches_sampling_stream stays; test_moe_cached_decode_batched_is_valid keeps MoE cached mechanics in-budget)
def test_moe_cached_decode_sampling_stream():
    moe, params = _moe_and_params(seed=22, capacity_factor=2.0)
    prompt = jnp.asarray([[5, 1, 8, 2]], jnp.int32)
    key = jax.random.PRNGKey(11)
    full = generate(moe, params, prompt, steps=6, temperature=0.9, rng=key)
    cached = generate(moe, params, prompt, steps=6, temperature=0.9,
                      rng=key, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_moe_cached_decode_batched_is_valid():
    """B>1 MoE cached decode: in-vocab tokens, prompt preserved (exact
    full-path equality is not guaranteed under capacity pressure — see
    generate() docstring — but the mechanics must hold)."""
    moe, params = _moe_and_params(seed=23)
    prompt = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6], [4, 4, 4, 4]],
                         jnp.int32)
    out = generate(moe, params, prompt, steps=8, use_cache=True)
    assert out.shape == (3, 12)
    np.testing.assert_array_equal(np.asarray(out[:, :4]), np.asarray(prompt))
    assert int(jnp.min(out)) >= 0 and int(jnp.max(out)) < V


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_top2_cached_decode_matches_full():
    moe, params = _moe_and_params(seed=24, router_top_k=2,
                                  capacity_factor=1.0)  # top-2: E/k = 1
    prompt = jnp.asarray([[2, 6, 10, 14]], jnp.int32)
    full = generate(moe, params, prompt, steps=8)
    cached = generate(moe, params, prompt, steps=8, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


@pytest.mark.slow  # tier-1 budget (PR 3): heavy; covered by cheaper siblings in-budget
def test_moe_ep_sharded_decode_matches_single_device():
    """EP decode: expert weights sharded over 'expert' (GShard dispatch
    all-to-alls via GSPMD) emit the same greedy tokens as single-device,
    full-recompute AND cached paths (drop-free capacity)."""
    moe, params = _moe_and_params(seed=25, capacity_factor=2.0)
    mesh = make_mesh((2, 2), ("data", "expert"), devices=jax.devices()[:4])
    prompt = jnp.asarray([[4, 8, 15, 16], [23, 42, 7, 1]], jnp.int32)
    single = generate(moe, params, prompt, steps=8, use_cache=True)
    for use_cache in (False, True):
        ep = generate(moe, params, prompt, steps=8, mesh=mesh,
                      use_cache=use_cache)
        np.testing.assert_array_equal(np.asarray(single), np.asarray(ep))
