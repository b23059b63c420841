"""``prefill_ahead_share.serve`` (PR 39): the share of the window's
waited-for ``serve.prefill`` spans whose program was called while an
earlier prefill of the same step was still unread. Known answers on spans
made by hand (with the attribute, without it, no prefill in the window:
a share, 0.0, nothing), a toy engine's own burst, and the toy cell's
command path."""

import json
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
from benchmarks.trace import admissions  # noqa: E402
from benchmarks.trace import program_spans as ps  # noqa: E402
from test_cellbench_admissions import _by_hand  # noqa: E402
from test_cellbench_spans import _reader, _traced  # noqa: E402

NAME = "prefill_ahead_share.serve"


def _with_ahead(spans, flags):
    """``spans`` with ``ahead`` set on the waited-for prefills, in order."""
    flags = iter(flags)
    return [sp._replace(attrs={**sp.attrs, "ahead": next(flags)})
            if sp.name == "serve.prefill" and "behind_s" in sp.attrs else sp
            for sp in spans]


@pytest.mark.parametrize("flags,want", [((0, 1), 50.0), ((1, 1), 100.0),
                                        ((0, 0), 0.0), (None, 0.0)])
def test_share_on_spans_made_by_hand(monkeypatch, flags, want):
    """``flags`` None: the parent's spans, which lack the attribute, read
    0.0 and not nothing."""
    spans, obs = _by_hand()
    if flags is not None:
        spans = _with_ahead(spans, flags)
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    assert _reader(NAME)(obs) == want


def test_nothing_to_read_reads_nothing(monkeypatch):
    spans, obs = _by_hand()
    # no prefill in the window
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    assert _reader(NAME)({"engine_steps": [
        StepRecord(0.0, 1.0, 0, 0)]}) is None
    # prefills nobody waited for (a program older than ``behind_s``)
    bare = [sp._replace(attrs={k: v for k, v in sp.attrs.items()
                               if k not in ("behind_s", "issued")})
            for sp in spans]
    monkeypatch.setattr(ps, "ring_spans", lambda: bare)
    assert _reader(NAME)(obs) is None
    # no ring at all
    monkeypatch.setattr(ps, "ring_spans", lambda: None)
    assert _reader(NAME)(obs) is None


@pytest.fixture(scope="module")
def burst():
    """Seven requests in the queue of a three-slot toy engine before its
    first step, stepped as the benchmark's loop steps it."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.engine.serve import DecodeRequest, ServeConfig, ServeEngine
    from tpu_dist.models.transformer import tiny_lm

    lm = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                 max_len=32)
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    eng = ServeEngine(lm, params, ServeConfig(max_slots=3, page_size=8,
                                              num_pages=24))
    r = np.random.default_rng(2)
    for i in range(7):
        assert eng.submit(DecodeRequest(
            i, r.integers(0, 64, (int(r.integers(2, 20)),)).astype(np.int32),
            int(r.integers(3, 9))))
    steps, done = [], []
    while eng.queue or any(s is not None for s in eng.slots):
        c0, t0 = (eng.ticks, eng.prefills), time.monotonic()
        done += eng.step()
        steps.append(StepRecord(t0, time.monotonic(), eng.ticks - c0[0],
                                eng.prefills - c0[1]))
    assert len(done) == 7
    return {"engine_steps": steps}, eng


def test_share_on_a_toy_engines_burst(burst, monkeypatch):
    obs, eng = burst
    st = eng.stats()
    # the first step admits three: the second and third ran ahead
    assert 2 <= st["prefills_ahead"] < st["prefills"] == 7
    share = _reader(NAME)(obs)
    assert share == pytest.approx(100.0 * st["prefills_ahead"] / 7)
    prefills = admissions.waited_prefills(ps.serving_spans(obs))
    assert sum(sp.attrs["ahead"] for sp in prefills) == st["prefills_ahead"]
    # the spans' own times still add up to the engine's counter
    assert sum(admissions.own_s(sp) for sp in prefills) == pytest.approx(
        st["prefill_own_s"], abs=1e-5)
    assert all(admissions.own_s(sp) >= 0 for sp in prefills)
    # the parent: the same spans without the attribute
    stripped = [sp._replace(attrs={k: v for k, v in sp.attrs.items()
                                   if k != "ahead"})
                for sp in ps.ring_spans()]
    monkeypatch.setattr(ps, "ring_spans", lambda: stripped)
    assert _reader(NAME)(obs) == 0.0


def test_metric_on_a_traced_toy_cell(tmp_path):
    """Through the command's own path: the entry appended to
    ``BENCHMARK.json`` finds its file and lands in the result line."""
    root = toyroot.make(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    entry = next(m for m in spec["per_layer"] if m["name"] == NAME)
    assert (entry["layer"], entry["moves"], entry["unit"], entry["better"],
            entry["source"]) == ("engines", "gap_p95_ms", "%", "higher",
                                 "program_span")
    serving = next(m["workloads"] for m in spec["end_to_end"]
                   if m["name"] == "gap_p95_ms")
    assert [w for w in entry["workloads"] if not w.startswith("toy")] == [
        w for w in serving if not w.startswith("toy")]
    entry["workloads"].append("toy-lm.serve")
    json.dump(spec, open(path, "w"))
    res = _traced(root, "toy-lm.serve", seed=11, seconds=3.0)
    assert res["correct"]
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
