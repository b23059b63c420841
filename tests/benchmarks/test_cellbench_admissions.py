"""The five readers of PR 38 (``prefill_own_ms.serve``,
``prefill_us_per_token.serve``, ``gap_prefill_share.serve``,
``gap_gc_share.serve``, ``prefill_device_share.serve``): known answers on
spans made by hand, a toy engine's own run, a traced toy cell, and nothing
(None, no exception) on a program whose spans lack what they read."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import toyroot  # noqa: E402

sys.path.insert(0, toyroot.REPO)
from benchmarks.harness.window import StepRecord  # noqa: E402
from benchmarks.trace import admissions  # noqa: E402
from benchmarks.trace import program_spans as ps  # noqa: E402
from test_cellbench_spans import (_TPU_LIKE, _reader, _summary,  # noqa: E402
                                  _traced)
from test_cellbench_tick_ahead import served  # noqa: E402,F401

SPAN_SIDE = ["prefill_own_ms.serve", "prefill_us_per_token.serve",
             "gap_prefill_share.serve", "gap_gc_share.serve"]
ALL = SPAN_SIDE + ["prefill_device_share.serve"]


def _by_hand(gc_pause=True):
    """Request 7 decodes in 50 ms ticks; request 8 is admitted behind a tick
    in flight (10 ms of it left), request 9 with none in flight; a 10 ms
    full collection between two steps. Returns (spans, observations)."""
    from tpu_dist.obs.trace import Span

    spans, sid, steps = [], iter(range(1, 1000)), []

    def add(name, start, end, parent=None, **attrs):
        sp = Span(next(sid), name, start, end, parent, attrs)
        spans.append(sp)
        return sp.sid

    def step(start, end, rids, prefill=None):
        st = add("serve.step", start, end)
        ad = add("serve.admit", start, start + 0.0005, st, n=0)
        at = start + 0.001
        if prefill is not None:
            rid, plen, bucket, issued, behind, length = prefill
            pf = add("serve.prefill", at, at + length, ad, rid=rid,
                     prompt_len=plen, bucket=bucket, shared_len=0,
                     issued=at + issued, behind_s=behind)
            add("prefill.dispatch", at, at + issued + 0.001, pf)
            add("prefill.behind", at + issued + 0.001,
                at + issued + 0.001 + behind, pf)
            add("prefill.wait", at + issued + 0.001 + behind,
                at + length - 0.001, pf)
            at += length + 0.001
        add("serve.tick", at, end, st, rids=rids)
        steps.append(StepRecord(start, end, 1, int(prefill is not None)))

    for k in range(4):
        step(10.0 + 0.05 * k, 10.05 + 0.05 * k, [7])
    # own (10.231 - 10.204) - 0.010 = 17 ms; stall 30 ms
    step(10.20, 10.28, [7, 8], (8, 9, 16, 0.003, 0.010, 0.030))
    step(10.28, 10.33, [7, 8])
    if gc_pause:
        add("host.gc", 10.335, 10.345, None, generation=2, collected=5)
    # own (10.376 - 10.353) - 0 = 23 ms; stall 25 ms
    step(10.35, 10.43, [7, 8, 9], (9, 40, 64, 0.002, 0.0, 0.025))
    step(10.43, 10.48, [8, 9])
    return spans, {"engine_steps": steps}


# decoding spells: 7 from 10.05 to 10.43, 8 from 10.231 to 10.48, 9 from
# 10.376 to 10.48
_DECODING = (10.43 - 10.05) + (10.48 - 10.231) + (10.48 - 10.376)


@pytest.mark.parametrize("name,want", [
    ("prefill_own_ms.serve", (17.0 + 23.0) / 2),
    ("prefill_us_per_token.serve", 1e6 * (0.017 + 0.023) / (9 + 40)),
    # request 7 stands behind both admissions, request 8 behind the second
    ("gap_prefill_share.serve", 100 * (0.017 + 2 * 0.023) / _DECODING),
    # requests 7 and 8 are decoding while the collection runs
    ("gap_gc_share.serve", 100 * 2 * 0.010 / _DECODING),
    # what the median of the whole spans still reads
    ("prefill_stall_ms.serve", (30.0 + 25.0) / 2),
])
def test_readers_on_spans_made_by_hand(monkeypatch, capsys, name, want):
    spans, obs = _by_hand()
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    assert _reader(name)(obs) == pytest.approx(want)
    if name == "prefill_own_ms.serve":
        out = capsys.readouterr().out
        assert "prefill bucket 16: 1 prefills, own 17.000 ms, behind the " \
            "tick in flight 10.000 ms, own = 0.34 pure-tick passes" in out
        assert "prefill bucket 64: 1 prefills, own 23.000 ms, behind the " \
            "tick in flight 0.000 ms" in out


def test_a_window_without_a_full_collection_reads_zero_not_nothing(
        monkeypatch):
    spans, obs = _by_hand(gc_pause=False)
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    assert _reader("gap_gc_share.serve")(obs) == 0.0
    # ... and a program that does not time its collections, nothing
    from tpu_dist.obs import trace

    monkeypatch.delattr(trace, "gc_seconds")
    assert _reader("gap_gc_share.serve")(obs) is None


def test_own_time_with_and_without_a_tick_in_flight():
    spans, _ = _by_hand()
    behind, alone = admissions.waited_prefills(spans)
    assert behind.attrs["behind_s"] == 0.010 and alone.attrs["behind_s"] == 0
    assert admissions.own_s(behind) == pytest.approx(0.017)
    assert admissions.own_s(alone) == pytest.approx(0.023)
    # the own time ends where the span ends, and is not the request's own
    assert admissions.own_intervals([behind]) == [
        (pytest.approx(10.214), behind.end, 8)]
    spells = admissions.decoding_spells(spans)
    assert set(spells) == {7, 8, 9}
    assert admissions.decoding_overlap(spells, 10.36, 10.37) == \
        pytest.approx(0.02)
    assert admissions.decoding_overlap(spells, 10.36, 10.37, but=8) == \
        pytest.approx(0.01)
    assert admissions.share_of_decoding([], []) is None


def test_device_side_counts_what_is_not_the_ticks(monkeypatch, capsys):
    spans, obs = _by_hand()
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    secs = {"%fusion.7 = f32[8,32]{1,0} fusion(f32[8,64]{1,0} %a)": 2.0,
            "%copy.9 = f32[64,32]{0,1} copy(f32[64,32]{1,0} %w)": 1.0,
            # a prefill's instructions: one that shares the tick's name but
            # not its shape, one that collides on all three (the tick's)
            "%fusion.7 = f32[64,32]{1,0} fusion(f32[64,64]{1,0} %a)": 0.6,
            "%convolution.3 = bf16[64,128]{1,0} convolution(%x, %w)": 0.3,
            "%add.6 = f32[8,32]{1,0} add(f32[8,32] %l, f32[8,32] %r)": 0.1}
    obs = {**obs, "trace": _summary(secs), "hlo_text": _TPU_LIKE}
    read = _reader("prefill_device_share.serve")
    assert read(obs) == pytest.approx(100 * 0.9 / 4.0)
    assert "0.900000 s of 4.000000 s busy; the window's 2 prefills' own " \
        "time by the spans: 0.040000 s" in capsys.readouterr().out
    assert read({**obs, "trace": None}) is None
    assert read({**obs, "hlo_text": None}) is None


@pytest.mark.parametrize("name", ALL)
def test_spans_without_behind_s_read_nothing(monkeypatch, name):
    """The parent of PR 38: the same spans without ``prefill.behind``,
    ``behind_s``, ``issued`` and ``host.gc``, and no ``gc_seconds``."""
    from tpu_dist.obs import trace

    spans, obs = _by_hand()
    parents = [sp._replace(attrs={k: v for k, v in sp.attrs.items()
                                  if k not in ("behind_s", "issued")})
               for sp in spans
               if sp.name not in ("prefill.behind", "host.gc")]
    monkeypatch.setattr(ps, "ring_spans", lambda: parents)
    monkeypatch.delattr(trace, "gc_seconds")
    obs = {**obs, "trace": _summary({"%x = f32[1]{0} add(%a, %b)": 1.0}),
           "hlo_text": _TPU_LIKE}
    assert _reader(name)(obs) is None
    # the accepted reader of the same spans reads what it read
    assert _reader("prefill_stall_ms.serve")(obs) == pytest.approx(27.5)
    # no ring at all, or a window that holds nothing
    monkeypatch.setattr(ps, "ring_spans", lambda: None)
    assert _reader(name)(obs) is None
    monkeypatch.setattr(ps, "ring_spans", lambda: parents)
    assert _reader(name)({**obs, "engine_steps": [
        StepRecord(0.0, 1.0, 0, 0)]}) is None


def test_readers_on_a_toy_engines_run(served):  # noqa: F811
    obs, eng, done = served
    spans = ps.serving_spans(obs)
    prefills = admissions.waited_prefills(spans)
    assert len(prefills) == len(done) == 6
    kids = ps.children(spans)
    for pf in prefills:
        parts = sorted(kids[pf.sid], key=lambda k: k.start)
        assert [k.name for k in parts] == ["prefill.dispatch",
                                           "prefill.behind", "prefill.wait"]
        # the three children cover the span but for the host's few lines
        # after the first token is read
        assert parts[0].start - pf.start < 2e-4
        assert 0 <= admissions.own_s(pf) <= pf.end - pf.start
    st = eng.stats()
    own = sum(admissions.own_s(pf) for pf in prefills)
    assert own == pytest.approx(st["prefill_own_s"], abs=1e-5)
    assert _reader("prefill_own_ms.serve")(obs) == pytest.approx(
        1e3 * own / 6)
    tokens = sum(c.prompt_len for c in done)
    assert _reader("prefill_us_per_token.serve")(obs) == pytest.approx(
        1e6 * own / tokens)
    share = _reader("gap_prefill_share.serve")(obs)
    assert 0.0 < share < 100.0
    # two slots: an admission holds back at most the one other request, so
    # the requests' own counters add up to what the spans say
    behind = sum(c.behind_prefill_s for c in done)
    spells = admissions.decoding_spells(spans)
    assert share == pytest.approx(
        100 * behind / sum(b - a for a, b in spells.values()), rel=1e-3)
    gc_share = _reader("gap_gc_share.serve")(obs)
    assert gc_share is not None and 0.0 <= gc_share < 100.0
    assert all(c.behind_gc_s >= 0.0 for c in done)
    assert st["gc_pause_s"] >= max(c.behind_gc_s for c in done)


def test_new_metrics_on_a_traced_toy_cell(tmp_path):
    """Through the command's own path: the entries appended to
    ``BENCHMARK.json`` find their files and land in the result line."""
    root = toyroot.make(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for m in spec["per_layer"]:
        if m["name"] in ALL:
            assert m["layer"] == "engines" and m["moves"] == "gap_p95_ms"
            m["workloads"].append("toy-lm.serve")
    json.dump(spec, open(path, "w"))
    res = _traced(root, "toy-lm.serve", seed=7, seconds=3.0)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(ALL) <= set(m)
    assert 0 < m["prefill_own_ms.serve"]
    assert res["metrics"]["prefill_us_per_token.serve"]["unit"] == "us"
    assert 0 <= m["gap_prefill_share.serve"] <= 100
    assert 0 <= m["gap_gc_share.serve"] <= 100
    assert 0 <= m["prefill_device_share.serve"] <= 100
