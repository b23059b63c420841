"""The state-space ops (``tpu_dist.ops.selective_scan``): the Pallas kernel
(interpreted here) against the ``lax.scan`` form against a numpy loop, the
carried state, the per-row lengths, the one-step form and the convolution's
tail.

Tolerances. Everything of the recurrence is float32, so the three forms
differ by the order of float32 roundings only: 2e-5 relative to the largest
value over a few hundred steps. A state or an ``exp`` kept in bfloat16
(8 bits) would be off by 4e-3 a step: two hundred times the tolerance
(``test_a_bfloat16_state_would_fail`` shows it).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.ops.selective_scan import (causal_conv1d, scan_blocks,
                                         selective_scan, ssm_step)

TOL = 2e-5


def _inputs(b, l, ch, n=16, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    return dict(
        u=jnp.asarray(f(b, l, ch), dtype),
        delta=jnp.asarray(np.log1p(np.exp(f(b, l, ch) - 2.0))),
        A=jnp.asarray(-np.exp(0.3 * f(ch, n))), B=jnp.asarray(f(b, l, n)),
        C=jnp.asarray(f(b, l, n)), D=jnp.asarray(f(ch)),
        s0=jnp.asarray(0.5 * f(b, n, ch)))


def _numpy_scan(u, delta, A, B, C, D, s0, lengths):
    u, delta, A, B, C, D, s = (np.asarray(x, np.float64)
                               for x in (u, delta, A, B, C, D, s0))
    b, l, ch = u.shape
    y = np.zeros((b, l, ch))
    for i in range(b):
        for t in range(l):
            if t < lengths[i]:
                s[i] = (np.exp(delta[i, t][None, :] * A.T) * s[i]
                        + (delta[i, t] * u[i, t])[None, :] * B[i, t][:, None])
            y[i, t] = (C[i, t][:, None] * s[i]).sum(0) + D * u[i, t]
    return y, s


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), \
        np.abs(got - want).max()


@pytest.mark.parametrize("ch,l,kernel", [
    (256, 64, True),      # lane-wide channels: the kernel, interpreted
    (128, 48, True),
    (24, 40, False),      # tiny widths of the toy models: the plain form
    (256, 8, False),      # a length that is no whole group
])
def test_scan_forms_agree_with_a_numpy_loop(ch, l, kernel):
    x = _inputs(2, l, ch)
    lengths = np.array([l, l - 5], np.int32)
    assert (scan_blocks(l, ch) is not None) == kernel
    y, s = selective_scan(**x, lengths=jnp.asarray(lengths))
    want_y, want_s = _numpy_scan(**x, lengths=lengths)
    _close(s, want_s)
    for i, n in enumerate(lengths):        # rows past a length mean nothing
        _close(y[i, :n], want_y[i, :n])
    if kernel:                             # and against the lax.scan form
        import tpu_dist.ops.selective_scan as ss

        live = jnp.arange(l)[None, :] < jnp.asarray(lengths)[:, None]
        plain_y, plain_s = ss._scan_plain(
            x["u"], jnp.where(live[:, :, None], x["delta"], 0.0),
            x["A"].T, x["B"], x["C"], x["D"], x["s0"])
        _close(s, plain_s)
        _close(y[0], plain_y[0])


def test_kernel_blocks_do_not_change_the_result():
    x = _inputs(1, 128, 256, seed=3)
    lengths = jnp.asarray([100], jnp.int32)
    import tpu_dist.ops.selective_scan as ss

    base = selective_scan(**x, lengths=lengths)
    args = ss._scan_args(**x, lengths=lengths)
    for blocks in [(16, 128), (32, 256), (128, 128)]:
        got = ss._scan_pallas(*args, *blocks, None)
        _close(got[1], base[1])
        _close(got[0][:, :100], base[0][:, :100])


@pytest.mark.parametrize("ch", [128, 24])
def test_a_scan_in_two_parts_carries_its_state(ch):
    l1, l2 = 32, 48
    x = _inputs(2, l1 + l2, ch, seed=1)
    full = jnp.asarray([l1 + l2] * 2, jnp.int32)
    y, s = selective_scan(**x, lengths=full)
    part = lambda a, b: {k: (v[:, a:b] if k in ("u", "delta", "B", "C")
                             else v) for k, v in x.items()}
    y1, s1 = selective_scan(**part(0, l1),
                            lengths=jnp.asarray([l1] * 2, jnp.int32))
    y2, s2 = selective_scan(**{**part(l1, l1 + l2), "s0": s1},
                            lengths=jnp.asarray([l2] * 2, jnp.int32))
    _close(s2, s)
    _close(jnp.concatenate([y1, y2], axis=1), y)


def test_a_length_of_zero_leaves_the_state_untouched():
    x = _inputs(2, 32, 128, seed=2)
    _, s = selective_scan(**x, lengths=jnp.asarray([0, 32], jnp.int32))
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(x["s0"][0]))
    assert np.abs(np.asarray(s[1] - x["s0"][1])).max() > 1e-2


@pytest.mark.parametrize("ch", [128, 24])
def test_ssm_step_is_a_scan_of_length_one(ch):
    x = _inputs(3, 1, ch, seed=4)
    y, s = selective_scan(**x, lengths=jnp.ones((3,), jnp.int32))
    y1, s1 = ssm_step(x["u"][:, 0], x["delta"][:, 0], x["A"], x["B"][:, 0],
                      x["C"][:, 0], x["D"], x["s0"])
    _close(s1, s)
    _close(y1, y[:, 0])
    # a row whose delta is 0 keeps its state
    _, held = ssm_step(x["u"][:, 0], jnp.zeros_like(x["delta"][:, 0]),
                       x["A"], x["B"][:, 0], x["C"][:, 0], x["D"], x["s0"])
    np.testing.assert_array_equal(np.asarray(held), np.asarray(x["s0"]))


def test_a_bfloat16_state_would_fail():
    """What the tolerance is tight enough to catch: the same scan with its
    state rounded to bfloat16 after every step."""
    x = _inputs(1, 64, 24, seed=5)
    lengths = np.array([64], np.int32)
    want_y, want_s = _numpy_scan(**x, lengths=lengths)
    s = x["s0"]
    for t in range(64):
        _, s = ssm_step(x["u"][:, t], x["delta"][:, t], x["A"], x["B"][:, t],
                        x["C"][:, t], x["D"], s)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
    err = np.abs(np.asarray(s, np.float64) - want_s).max()
    assert err > 20 * TOL * max(np.abs(want_s).max(), 1.0), err


def test_conv_with_a_carried_tail_is_the_conv_over_the_joined_sequence():
    r = np.random.default_rng(6)
    b, l1, l2, ch, k = 2, 9, 7, 24, 4
    u = jnp.asarray(r.standard_normal((b, l1 + l2, ch)), jnp.float32)
    w = jnp.asarray(r.standard_normal((k, ch)), jnp.float32)
    bias = jnp.asarray(r.standard_normal((ch,)), jnp.float32)
    zeros = jnp.zeros((b, k - 1, ch), jnp.float32)
    whole, tail = causal_conv1d(u, w, bias, zeros,
                                jnp.asarray([l1 + l2] * b, jnp.int32))
    # by hand: out[t] = bias + sum_j w[j] * u[t - (k - 1) + j]
    padded = np.pad(np.asarray(u), ((0, 0), (k - 1, 0), (0, 0)))
    want = np.asarray(bias) + sum(np.asarray(w)[j] * padded[:, j:j + l1 + l2]
                                  for j in range(k))
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(u[:, -3:]))
    first, t1 = causal_conv1d(u[:, :l1], w, bias, zeros,
                              jnp.asarray([l1] * b, jnp.int32))
    second, t2 = causal_conv1d(u[:, l1:], w, bias, t1,
                               jnp.asarray([l2] * b, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, second], axis=1)),
        np.asarray(whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(tail))


def test_conv_tail_is_taken_at_the_length_not_at_the_buckets_end():
    r = np.random.default_rng(7)
    u = jnp.asarray(r.standard_normal((3, 8, 24)), jnp.float32)
    w = jnp.ones((4, 24), jnp.float32)
    old = jnp.asarray(r.standard_normal((3, 3, 24)), jnp.float32)
    _, tail = causal_conv1d(u, w, jnp.zeros((24,)), old,
                            jnp.asarray([5, 0, 2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(u[0, 2:5]))
    np.testing.assert_array_equal(np.asarray(tail[1]), np.asarray(old[1]))
    np.testing.assert_array_equal(                # shorter than the tail
        np.asarray(tail[2]),
        np.concatenate([np.asarray(old[2, 2:]), np.asarray(u[2, :2])]))
    # the tick's form: one row in, the tail shifts by one
    _, step = causal_conv1d(u[:, :1], w, jnp.zeros((24,)), old,
                            jnp.asarray([1, 0, 1], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(step[0]),
        np.concatenate([np.asarray(old[0, 1:]), np.asarray(u[0, :1])]))
    np.testing.assert_array_equal(np.asarray(step[1]), np.asarray(old[1]))
