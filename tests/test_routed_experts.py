"""``ops.routed_experts.routed_experts`` up to ``DENSE_ROWS`` rows: the
Pallas call that walks the hit list (interpreted here) against the einsum
form over every held expert, which a layer with computed weights
(``stored=False``) still takes. Float32 toys: the two differ only in the
order of their float32 additions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.ops import routed_experts as rx

HELD_LO, HELD, ROUTER, TOP_K = 8, 4, 16, 4
LATENT, WIDTH = 32, 48
# which of the four held experts the live rows choose between them
HIT_SETS = {"all": [0, 1, 2, 3], "one": [2], "only_the_last": [3],
            "a_non_contiguous_half": [0, 2], "none": []}


def _weights(seed=0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(HELD, LATENT, WIDTH)), jnp.float32)
            * 0.1,
            jnp.asarray(r.normal(size=(HELD, WIDTH, LATENT)), jnp.float32)
            * 0.1)


def _rows(rows, hit, seed):
    """``rows`` rows of which every third (from the second) is dead, and all
    are with ``hit`` empty. Live row r chooses ``len(hit) - r % len(hit)``
    of ``hit`` (row 0 all of them: between them the live rows hit exactly
    ``hit``), a dead row every held expert; the other choices name experts
    held elsewhere."""
    r = np.random.default_rng(seed)
    absent = [e for e in range(ROUTER) if not HELD_LO <= e < HELD_LO + HELD]
    live = np.array([bool(hit) and i % 3 != 1 for i in range(rows)])
    idx = np.empty((rows, TOP_K), np.int32)
    for i in range(rows):
        mine = (np.roll(hit, -i)[:len(hit) - i % len(hit)] if live[i]
                else np.arange(HELD))
        idx[i] = np.concatenate([
            HELD_LO + mine,
            r.choice(absent, TOP_K - len(mine), replace=False)]).astype(
                np.int32)[r.permutation(TOP_K)]
    w = r.uniform(0.2, 1.0, (rows, TOP_K)).astype(np.float32)
    u = r.normal(size=(rows, LATENT)).astype(np.float32)
    return (jnp.asarray(u), jnp.asarray(idx),
            jnp.asarray(5.0 * w / w.sum(-1, keepdims=True)),
            jnp.asarray(live))


@pytest.mark.parametrize("hit", sorted(HIT_SETS))
@pytest.mark.parametrize("rows", [1, 7, 64])
def test_kernel_form_is_the_einsum_form(rows, hit):
    chosen = HIT_SETS[hit]
    u, idx, w, live = _rows(rows, chosen, seed=rows + len(chosen))
    w_in, w_out = _weights()
    got, g_rows, g_hit = rx.routed_experts(u, idx, w, live, w_in, w_out,
                                           HELD_LO)
    want, w_rows, w_hit = rx.routed_experts(u, idx, w, live, w_in, w_out,
                                            HELD_LO, stored=False)
    # the two counters are the routing's, whatever form the products take
    picked = np.asarray(idx)[np.asarray(live)] - HELD_LO
    here = picked[(picked >= 0) & (picked < HELD)]
    assert int(g_rows) == int(w_rows) == here.size
    assert int(g_hit) == int(w_hit) == len(chosen)
    assert sorted(np.unique(here)) == chosen
    assert got.shape == (rows, LATENT) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)
    assert not np.asarray(got)[~np.asarray(live)].any()   # dead rows: zeros
    if chosen:
        assert np.abs(np.asarray(got)).max() > 1e-2
    else:
        assert not np.asarray(got).any() and int(g_rows) == 0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, a Pallas call's
    own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _tick_shaped(stored):
    """The cell's tick, as shapes: 64 rows over 128 held experts of 1024 ->
    2688 -> 1024 in bfloat16."""
    rows, held, latent, width = 64, 128, 1024, 2688
    s = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(
        lambda u, idx, w, live, w_in, w_out: rx.routed_experts(
            u, idx, w, live, w_in, w_out, 128, stored=stored))(
        s((rows, latent), jnp.bfloat16), s((rows, 22), jnp.int32),
        s((rows, 22), jnp.float32), s((rows,), jnp.bool_),
        s((held, latent, width), jnp.bfloat16),
        s((held, width, latent), jnp.bfloat16))
    eqns = list(_eqns(closed.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    wide = [v.aval.shape for e in eqns for v in e.outvars
            if np.prod(v.aval.shape, dtype=np.int64) >= held * rows * width]
    return calls, wide


def test_tick_shaped_call_is_one_pallas_call_and_nothing_wide_around_it():
    calls, wide = _tick_shaped(stored=True)
    assert len(calls) == 1
    assert calls[0].params["name"] == "hit_experts"
    # the grid walks (held experts, width tiles) under two prefetched
    # scalars: the hit list and its length
    grid = calls[0].params["grid_mapping"]
    assert grid.grid == (128, 3) and grid.num_index_operands == 2
    assert [v.aval.shape for v in calls[0].outvars] == [(64, 1024)]
    assert wide == []            # no [held, rows, width] value outside it
    # and the form that computed weights keep does hold one, in no kernel
    calls, wide = _tick_shaped(stored=False)
    assert calls == [] and (128, 64, 2688) in wide


@pytest.mark.parametrize("rows,calls", [
    (1, 0), (64, 0), (rx.DENSE_ROWS, 0), (rx.DENSE_ROWS + 1, 2)])
def test_grouped_calls_are_none_up_to_the_dense_forms_row_limit(rows, calls):
    assert rx.grouped_calls(rows) == calls


@pytest.mark.parametrize("sizes,want", [
    ([2, 1, 5, 1], [0, 1, 2, 3]), ([0, 0, 3, 0], [2, 2, 2, 2]),
    ([0, 0, 0, 9], [3, 3, 3, 3]), ([1, 0, 4, 0], [0, 2, 2, 2]),
    ([0, 0, 0, 0], [0, 0, 0, 0]), ([0, 7, 0, 1, 1, 0], [1, 3, 4, 4, 4, 4])])
def test_hit_list_is_ascending_and_then_its_last_again(sizes, want):
    """What the kernel's index maps walk: past the hit ones every entry
    names the block the last real step named, so nothing is copied for it."""
    got = rx._hit_list(jnp.asarray(sizes, jnp.int32))
    assert got.dtype == jnp.int32 and got.tolist() == want
