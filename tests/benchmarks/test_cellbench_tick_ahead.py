"""``tick_ahead_share.serve`` (PR 27) on a toy engine's own spans: the share
of decode ticks dispatched one ahead of the host's read, 0.0 (not nothing)
for a program whose ``serve.tick`` spans lack the ``ahead`` attribute, and
the older span readers still reading the same spans."""

import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import toyroot  # noqa: E402

sys.path.insert(0, toyroot.REPO)
from benchmarks.harness.window import StepRecord  # noqa: E402
from benchmarks.trace import program_spans as ps  # noqa: E402
from test_cellbench_spans import _reader  # noqa: E402


@pytest.fixture(scope="module")
def served():
    """Six requests through a two-slot toy engine, stepped as the
    benchmark's loop steps it; the observations a reader is handed."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
    from tpu_dist.models.transformer import tiny_lm

    lm = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                 max_len=32)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    eng = ServeEngine(lm, params, ServeConfig(max_slots=2, page_size=8,
                                              num_pages=16))
    r = np.random.default_rng(1)
    for i in range(6):
        assert eng.submit(DecodeRequest(
            i, r.integers(0, 64, (int(r.integers(2, 9)),)).astype(np.int32),
            int(r.integers(4, 10))))
    steps, done = [], []
    while eng.queue or any(s is not None for s in eng.slots):
        c0, t0 = (eng.ticks, eng.prefills), time.monotonic()
        done += eng.step()
        steps.append(StepRecord(t0, time.monotonic(), eng.ticks - c0[0],
                                eng.prefills - c0[1]))
    assert len(done) == 6
    return {"engine_steps": steps}, eng, done


def test_share_of_ticks_ahead_on_a_toy_engines_spans(served):
    obs, eng, _ = served
    share = _reader("tick_ahead_share.serve")(obs)
    assert 0.0 < share <= 100.0
    st = eng.stats()
    # one busy spell: every tick but the first was dispatched ahead
    assert share == pytest.approx(100.0 * st["ticks_ahead"] / st["ticks"])
    assert st["ticks_ahead"] == st["ticks"] - 1


def test_spans_without_the_attribute_read_zero_not_nothing(served,
                                                           monkeypatch):
    """The parent of PR 27: the same spans, no ``ahead``."""
    obs, _, _ = served
    stripped = [sp._replace(attrs={k: v for k, v in sp.attrs.items()
                                   if k != "ahead"})
                for sp in ps.ring_spans()]
    monkeypatch.setattr(ps, "ring_spans", lambda: stripped)
    assert _reader("tick_ahead_share.serve")(obs) == 0.0
    # and a program with no ring at all, or no tick in the window: nothing
    monkeypatch.setattr(ps, "ring_spans", lambda: None)
    assert _reader("tick_ahead_share.serve")(obs) is None
    monkeypatch.setattr(ps, "ring_spans", lambda: stripped)
    assert _reader("tick_ahead_share.serve")(
        {"engine_steps": [StepRecord(0.0, 1.0, 0, 0)]}) is None


@pytest.mark.parametrize("name", ["tick_host_ms.serve",
                                  "token_gap_p99_ms.serve",
                                  "state_slots.serve", "tick_ms"])
def test_the_older_readers_still_read_the_same_spans(served, name):
    obs, _, _ = served
    value = _reader(name)(obs)
    assert value is not None and value >= 0.0


def test_token_times_rise_strictly_within_a_request(served):
    obs, _, done = served
    times = ps.token_times(ps.serving_spans(obs))
    assert sorted(times) == sorted(c.rid for c in done)
    for c in done:
        assert len(times[c.rid]) == c.n_generated
        assert np.all(np.diff(times[c.rid]) > 0)
        assert np.all(np.diff(c.token_ts) > 0)
