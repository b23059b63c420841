"""``obs.trace.gc_seconds`` (PR 38): every garbage collection counted, the
full ones as ``host.gc`` spans under whatever they interrupted."""

import gc

import pytest

from tpu_dist.obs import trace


def _gc_spans(since):
    return [sp for sp in trace.ring().snapshot()
            if sp.sid > since and sp.name == "host.gc"]


@pytest.fixture()
def quiet_gc():
    """No collection but the test's own."""
    trace.gc_seconds()
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def test_a_full_collection_is_a_span_under_the_span_it_interrupted(quiet_gc):
    before = trace.gc_seconds()
    with trace.ring().span("tick.emit") as emit:
        junk = [[i] for i in range(1000)]
        junk.append(junk)                       # a cycle to find
        del junk
        gc.collect()
    (sp,) = _gc_spans(emit.sid - 1)
    assert sp.parent == emit.sid
    assert emit.start <= sp.start <= sp.end <= emit.start + emit.seconds
    assert sp.attrs["generation"] == len(gc.get_stats()) - 1
    assert sp.attrs["collected"] >= 1
    # its seconds are in the counter (two clock reads inside the span's)
    assert 0.0 < trace.gc_seconds() - before <= sp.end - sp.start
    # outside any span it has no parent
    gc.collect()
    assert _gc_spans(emit.sid)[-1].parent is None


def test_a_young_collection_counts_and_leaves_no_span(quiet_gc):
    with trace.ring().span("mark") as mark:
        pass
    before = trace.gc_seconds()
    for _ in range(50):
        gc.collect(0)
    assert trace.gc_seconds() > before
    assert _gc_spans(mark.sid) == []


def test_registering_twice_installs_one_callback():
    trace.gc_seconds()
    n = len(gc.callbacks)
    for _ in range(3):
        trace.gc_seconds()
    assert len(gc.callbacks) == n
    with trace.ring().span("mark") as mark:
        pass
    gc.collect()
    assert len(_gc_spans(mark.sid)) == 1


@pytest.mark.parametrize("engine", ["serve", "image", "lm"])
def test_every_engine_constructor_installs_the_callback(engine, monkeypatch,
                                                        tmp_path):
    """The first ``gc_seconds()`` call of a process is an engine's."""
    calls = []
    real = trace.gc_seconds
    monkeypatch.setattr(trace, "gc_seconds",
                        lambda: calls.append(1) or real())
    if engine == "serve":
        import jax
        import jax.numpy as jnp

        from tpu_dist.engine.serve import ServeConfig, ServeEngine
        from tpu_dist.models.transformer import tiny_lm

        lm = tiny_lm(vocab_size=32, num_layers=1, d_model=16, num_heads=2,
                     max_len=16)
        params = lm.init({"params": jax.random.PRNGKey(0)},
                         jnp.zeros((1, 16), jnp.int32), train=False)["params"]
        eng = ServeEngine(lm, params, ServeConfig(max_slots=1, page_size=8,
                                                  num_pages=4))
        assert calls
        st = eng.stats()
        assert st["prefill_own_s"] == 0.0 and st["gc_pause_s"] >= 0.0
    elif engine == "image":
        from tpu_dist.configs import TrainConfig
        from tpu_dist.engine import Trainer

        Trainer(TrainConfig(
            dataset="synthetic-mnist", arch="lenet", batch_size=64,
            synth_train_size=128, synth_val_size=64, print_freq=100,
            checkpoint_dir=str(tmp_path)))
        assert calls
    else:
        from tpu_dist.engine.lm_loop import LMConfig, LMTrainer

        LMTrainer(LMConfig(vocab_size=64, seq_len=32, d_model=32,
                           num_layers=1, num_heads=2, batch_size=8,
                           synth_tokens=3000, print_freq=100,
                           checkpoint_dir=str(tmp_path)))
        assert calls
