"""The readers of the program's own spans and scopes (PR 24): the seven
per-layer metrics on toy runs and on programs compiled on the CPU, each
giving None where the program has no such span or scope (as an older
checkout has not), and ``benchmarks/trace/program_spans.py`` on intervals
made by hand and on the small trace recorded on the chip."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import toyroot  # noqa: E402

sys.path.insert(0, toyroot.REPO)
from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.trace import program_spans as ps  # noqa: E402
from benchmarks.trace import reduce as tr  # noqa: E402
from benchmarks.trace import scopes  # noqa: E402

SPAN_METRICS = {
    "toy-lm.serve": ["tick_host_ms.serve", "prefill_stall_ms.serve",
                     "token_gap_p99_ms.serve", "paged_read_share.serve"],
    "toy-lm.train": ["xent_share.train", "optimizer_share.train",
                     "obs_emit_ms.train"],
    "toy-resnet.train": ["optimizer_share.train", "obs_emit_ms.train"],
}


def _reader(name):
    return cells.load_module(
        os.path.join(toyroot.REPO, "benchmarks", "layer_metrics",
                     name + ".py"), "metric_" + name.replace(".", "_")).read


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy checkout, with the toy cells appended to the new metrics'
    ``workloads`` lists (as a later PR's cell would be)."""
    root = toyroot.make(tmp_path_factory.mktemp("spans"))
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for cell, names in SPAN_METRICS.items():
        for m in spec["per_layer"]:
            if m["name"] in names:
                m["workloads"].append(cell)
    json.dump(spec, open(path, "w"))
    return root


def _traced(root, name, seed, seconds=2.0):
    """A traced toy run with made-up device operations on the recorded
    spans' clock (the CPU has no device plane), as the LM test does."""
    real = tr.reduce_file

    def fake(path, offsets_s=None):
        _, spans = tr.read_xplane(path)
        lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
        ops = [[("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                 lo, lo + 0.25 * (hi - lo))]]
        return tr.summarize(ops, spans, offsets_s)

    tr.reduce_file = fake
    try:
        return toyroot.run_toy(root, name, seed=seed, seconds=seconds,
                               trace=True)
    finally:
        tr.reduce_file = real


# ------------------------------------------------------------ span readers

def test_serving_span_metrics_on_a_toy_run(root):
    res = _traced(root, "toy-lm.serve", seed=5, seconds=3.0)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"]
    # the host's own share of a tick is inside the tick, and not all of it
    assert 0 < m["tick_host_ms.serve"] < m["tick_ms"]
    assert m["prefill_stall_ms.serve"] > 0
    # a percentile of single gaps is not below the typical tick
    assert m["token_gap_p99_ms.serve"] >= 0.9 * m["tick_ms"]
    assert res["metrics"]["token_gap_p99_ms.serve"]["unit"] == "ms"
    # the made-up device operations carry no scope: nothing to read
    assert "paged_read_share.serve" not in m


def test_training_span_metric_on_a_toy_run(root):
    res = _traced(root, "toy-lm.train", seed=13)
    m = res["metrics"]
    assert res["correct"]
    assert 0 < m["obs_emit_ms.train"]["value"] < 1e3
    assert "xent_share.train" not in m and "optimizer_share.train" not in m


@pytest.mark.parametrize("name,obs", [
    ("tick_host_ms.serve", {"engine_steps": [tr.Interval]}),
    ("prefill_stall_ms.serve", {"engine_steps": [tr.Interval]}),
    ("token_gap_p99_ms.serve", {"engine_steps": [tr.Interval]}),
    ("obs_emit_ms.train", {"step_records": [{"step": 1,
                                             "steps_in_dispatch": 1}]}),
])
def test_span_readers_give_none_without_the_ring(monkeypatch, name, obs):
    """A program that lacks the ring (the parent of PR 24): no value, no
    exception, and the result line leaves the metric out."""
    monkeypatch.setattr(ps, "ring_spans", lambda: None)
    assert _reader(name)(obs) is None
    monkeypatch.setattr(ps, "ring_spans", lambda: [])
    assert _reader(name)(obs) is None


def test_ring_spans_is_none_for_a_program_without_a_ring(monkeypatch):
    from tpu_dist.obs import trace

    assert ps.ring_spans() is not None
    monkeypatch.delattr(trace, "ring")
    assert ps.ring_spans() is None


def test_serving_readers_on_spans_made_by_hand(monkeypatch):
    from tpu_dist.obs.trace import Span

    class Step:                       # the benchmark's StepRecord
        def __init__(self, start, end):
            self.start, self.end = start, end

    spans, sid = [], iter(range(1, 1000))

    def add(name, start, end, parent=None, **attrs):
        sp = Span(next(sid), name, start, end, parent, attrs)
        spans.append(sp)
        return sp.sid

    t = 10.0
    for k in range(6):                # six pure ticks of 50 ms, 4 ms host
        st = add("serve.step", t, t + 0.050, tick=k)
        add("serve.evict", t, t + 0.001, st, n=0)
        add("serve.admit", t + 0.001, t + 0.002, st, n=0)
        tk = add("serve.tick", t + 0.002, t + 0.050, st, rids=[7])
        add("tick.wait", t + 0.003, t + 0.049, tk)
        t += 0.050
    # a step with an admission: 30 ms prefill, then the tick
    st = add("serve.step", t, t + 0.080, tick=6)
    ad = add("serve.admit", t, t + 0.031, st, n=1)
    add("serve.prefill", t + 0.001, t + 0.031, ad, rid=8, prompt_len=9,
        bucket=16, shared_len=0)
    tk = add("serve.tick", t + 0.032, t + 0.080, st, rids=[7, 8])
    add("tick.wait", t + 0.033, t + 0.079, tk)
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    obs = {"engine_steps": [Step(10.0, 10.05), Step(10.05, t + 0.080)]}
    assert _reader("tick_host_ms.serve")(obs) == pytest.approx(4.0)
    assert _reader("prefill_stall_ms.serve")(obs) == pytest.approx(30.0)
    # request 7: five gaps of 50 ms and the one across the admission, 80
    assert _reader("token_gap_p99_ms.serve")(obs) == pytest.approx(80.0)
    # a window that holds none of it reads nothing
    empty = {"engine_steps": [Step(0.0, 1.0)]}
    assert _reader("tick_host_ms.serve")(empty) is None


# ----------------------------------------------------------- scope readers

def _as_events(hlo_text, seconds_of):
    """Device events named as the profiler names them (the instruction
    without its metadata), ``seconds_of(op_name)`` of own time each."""
    import re

    out = {}
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m is None or " = " not in line or "fused_computation" in line:
            continue
        ev = re.sub(r",?\s*metadata=\{[^{}]*\}", "", line.strip())
        ev = ev[5:] if ev.startswith("ROOT ") else ev
        secs = seconds_of(m.group(1))
        if secs:
            out[ev if ev.startswith("%") else "%" + ev] = secs
    return out


def _summary(op_seconds):
    return tr.TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1,
                           op_seconds=op_seconds, idle_seconds={})


@pytest.fixture(scope="module")
def toy_programs():
    """The decode tick and the LM train step at a toy size, compiled on
    the CPU: their optimized HLO names the program's scopes."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.engine.lm_steps import make_lm_train_step
    from tpu_dist.engine.serve import ServeConfig, ServeEngine, _tick_program
    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops.flash_attention import flash_attention_fn
    from tpu_dist.ops.optim import make_optimizer
    from tpu_dist.parallel.mesh import make_mesh

    lm = tiny_lm(vocab_size=64, num_layers=1, d_model=32, num_heads=2,
                 max_len=32, attn_fn=flash_attention_fn(block_k=32))
    params = lm.init({"params": jax.random.PRNGKey(0)},
                     jnp.zeros((1, 32), jnp.int32), train=False)["params"]
    eng = ServeEngine(lm, params, ServeConfig(max_slots=2, page_size=8,
                                              num_pages=8))
    n = len(eng.slots)
    tick = _tick_program(eng.model, 0.0, 0, 0.0, None).lower(
        eng.params, eng.pool.layers(),
        jnp.zeros((n, eng.max_pages_per_seq), jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
        eng._rng).compile().as_text()
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    tx = make_optimizer(1e-3, 0.9, 0.1, kind="adamw")
    state = TrainState.create(params, {}, tx)
    x = jnp.zeros((2, 32), jnp.int32)
    step = make_lm_train_step(lm, tx, mesh, donate=False).lower(
        state, x, x, jax.random.PRNGKey(1)).compile().as_text()
    return {"tick": tick, "step": step}


def test_toy_programs_name_their_scopes(toy_programs):
    assert scopes.Program(toy_programs["tick"]).keys_in("paged_read")
    step = scopes.Program(toy_programs["step"])
    for scope in ("loss", "optimizer", "flash_attention"):
        assert step.keys_in(scope), scope
    # forward and backward: jvp(loss) and transpose(jvp(loss))
    assert any("transpose(jvp(loss))" in n for n in step.own.values())
    assert not scopes.in_scope("loss").search("jit(step)/jvp(loss_fn)/mul")


_TPU_LIKE = """HloModule jit_step

%fused_computation.7 (p0: f32[8,64], p1: f32[64,32]) -> f32[8,32] {
  %p0 = f32[8,64]{1,0} parameter(0)
  %p1 = f32[64,32]{1,0} parameter(1)
  %dot.1 = f32[8,32]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/jvp(LM)/lm_head/dot_general"}
  ROOT %exp.2 = f32[8,32]{1,0} exponential(%dot.1), metadata={op_name="jit(step)/jvp(loss)/exp"}
}

%fused_computation.8 (p0: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0} parameter(0)
  ROOT %gather.3 = bf16[16,8]{1,0} gather(%p0.1), metadata={op_name="jit(tick)/block0/paged_read/gather"}
}

ENTRY %main (a: f32[8,64], w: f32[64,32], k: bf16[16,8]) -> f32[8,32] {
  %a = f32[8,64]{1,0} parameter(0)
  %w = f32[64,32]{1,0} parameter(1)
  %k = bf16[16,8]{1,0} parameter(2)
  %copy.9 = f32[64,32]{0,1} copy(%w)
  %fusion.7 = f32[8,32]{1,0} fusion(%a, %copy.9), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step)/jvp(LM)/lm_head/dot_general"}
  %fusion.8 = bf16[16,8]{1,0} fusion(%k), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(tick)/block0/paged_read/gather"}
  %convert.5 = f32[16,8]{1,0:T(8,128)} convert(%fusion.8)
  ROOT %add.6 = f32[8,32]{1,0} add(%fusion.7, %fusion.7), metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_scope_membership_as_the_tpu_compiler_leaves_the_names():
    """A fusion carries one operation's name and fuses the others'; the
    compiler's own casts and copies carry none (PERF.md, PR 24)."""
    prog = scopes.Program(_TPU_LIKE)
    names = lambda scope: {k.split()[0] for k in prog.keys_in(scope)}
    # named after the matrix product, but it fuses the loss's exponential
    assert names("loss") == {"exp.2", "fusion.7"}
    # the nameless cast of the gathered rows belongs to the gather's scope;
    # the nameless copy of a parameter to none
    assert names("paged_read") == {"gather.3", "fusion.8", "convert.5"}
    assert names("optimizer") == {"add.6"}
    assert not names("flash_attention")
    secs = {"%fusion.7 = f32[8,32]{1,0} fusion(f32[8,64]{1,0} %a)": 2.0,
            "%convert.5 = f32[16,8]{1,0:T(8,128)S(1)} convert(bf16[16,8] %f)": 3.0,
            "%convert.5 = f32[99,8]{1,0} convert(bf16[99,8] %f)": 7.0,   # alien
            "%copy.9 = f32[64,32]{0,1} copy(f32[64,32]{1,0} %w)": 1.0}
    assert scopes.scope_seconds(secs, _TPU_LIKE, "paged_read") == 3.0
    assert scopes.scope_seconds(secs, _TPU_LIKE, "loss") == 2.0


@pytest.mark.parametrize("name,scope,program,key", [
    ("paged_read_share.serve", "paged_read", "tick", "engine_steps"),
    ("xent_share.train", "loss", "step", "step_records"),
    ("optimizer_share.train", "optimizer", "step", "step_records"),
])
def test_scope_readers_on_a_compiled_toy_program(toy_programs, name, scope,
                                                 program, key):
    text = toy_programs[program]
    hit = scopes.in_scope(scope)
    events = _as_events(text, lambda op: 3.0 if hit.search(op) else 1.0)
    inside = sum(v for v in events.values() if v == 3.0)
    assert 0 < inside < sum(events.values())
    # an event of ANOTHER program: the name of a scoped instruction, but
    # another result shape. It must not be joined
    scoped = next(k for k, v in events.items() if v == 3.0)
    alien = scoped.split(" = ", 1)[0] + " = f32[977,3]{1,0} fusion(%x)"
    events[alien] = 50.0
    obs = {"trace": _summary(events), "hlo_text": text, key: [object()]}
    want = 100.0 * inside / sum(events.values())
    assert _reader(name)(obs) == pytest.approx(want)
    # the same program without the scope (the parent): nothing to read
    bare = text.replace(scope, "unnamed")
    assert _reader(name)({**obs, "hlo_text": bare}) is None
    # no trace, no text, or the other family's cell
    assert _reader(name)({**obs, "trace": None}) is None
    assert _reader(name)({**obs, "hlo_text": None}) is None
    assert _reader(name)({"trace": obs["trace"], "hlo_text": text}) is None


# ------------------------------------------------------- program_spans.py

def test_idle_goes_to_the_innermost_program_span():
    ops = [("op", 10.0, 20.0), ("op", 50.0, 60.0)]
    program = [("serve.step", 0.0, 45.0), ("tick.build", 0.0, 8.0),
               ("tick.dispatch", 8.0, 12.0), ("tick.emit", 30.0, 40.0)]
    got = ps.idle_by_program_span(ops, program, 0.0, 70.0)
    assert got == {"tick.build": 8.0, "tick.dispatch": 2.0,
                   "serve.step": 15.0, "tick.emit": 10.0,
                   ps.NO_SPAN: 5.0 + 10.0}
    # only the idle time under the benchmark's own step span
    got = ps.idle_by_program_span(ops, program, 0.0, 70.0,
                                  within=[("bench:step", 0.0, 42.0)])
    assert got == {"tick.build": 8.0, "tick.dispatch": 2.0,
                   "serve.step": 12.0, "tick.emit": 10.0}
    assert ps.named_child_share(got, "serve.step") == pytest.approx(20 / 32)


def test_device_clock_shift_from_causality():
    """The device cannot be busy between a wait that drained it and the
    next dispatch: work recorded 3 early (18..32 for a dispatch at 20 and a
    wait that returned at 36) must be moved by 2..4."""
    events = [("tick.wait", 5.0, 10.0, 1), ("tick.dispatch", 20.0, 21.0, 2),
              ("tick.wait", 21.0, 36.0, 3), ("tick.dispatch", 45.0, 46.0, 4),
              ("tick.build", 40.0, 45.0, 5)]
    assert ps.drained_intervals(events) == [(10.0, 20.0), (36.0, 45.0)]
    ops = [("a", 18.0, 25.0), ("b", 24.0, 32.0)]
    assert ps.busy_blocks([(s, e) for _, s, e in ops]) == [(18.0, 32.0)]
    assert ps.device_clock_shift(ops, events, radius_ns=30.0) == (2.0, 4.0)
    # recorded where causality already holds: zero lies in the bounds
    lo, hi = ps.device_clock_shift([("a", 21.0, 35.0)], events, 30.0)
    assert lo <= 0.0 <= hi
    # nothing constrains it / nothing satisfies it
    assert ps.device_clock_shift(ops, [], 30.0) is None
    assert ps.device_clock_shift([("a", 0.0, 100.0)], events, 30.0) is None


def test_clock_offset_joins_the_two_clocks_on_sid():
    ring = [{"sid": i, "start": 100.0 + i} for i in range(1, 41)]
    events = [("serve.step", (5.0 + i) * 1e9 + (2e3 if i == 7 else 0.0),
               0.0, i) for i in range(1, 41)] + [("other", 1.0, 2.0, None)]
    got = ps.clock_offset(events, ring)
    assert got["joined"] == 40
    assert got["offset_s"] == pytest.approx(-95.0)
    assert got["residual_max_s"] == pytest.approx(2e-6, rel=1e-3)
    assert got["residual_p95_s"] < 1e-9
    assert ps.clock_offset(events, []) is None


def test_program_spans_on_the_recorded_trace():
    """Eight ticks recorded on one TPU v5e (``record_spans.py``): 2 ms of
    ``tick.build`` and 1 ms of ``tick.emit`` of host work around a 4096^3
    matmul under ``serve.step``, then a 30 ms sleep outside any span. As
    recorded, every matmul lies BEFORE the dispatch span that caused it:
    the trace's device timeline runs 1-2 ms ahead of its host timeline."""
    base = os.path.join(HERE, "recorded_spans_v5e")
    assert os.path.getsize(base + ".xplane.pb") < 200_000
    device_ops, _ = tr.read_xplane(base + ".xplane.pb")
    events = ps.read_program_events(base + ".xplane.pb")
    dispatches = sorted(s for n, s, _, _ in events if n == "tick.dispatch")
    matmuls = sorted(s for n, s, e in device_ops[0] if e - s > 3e5)
    assert len(dispatches) == len(matmuls) == 8
    assert all(m < d for m, d in zip(matmuls, dispatches))   # effect first
    lo, hi = ps.device_clock_shift(device_ops[0], events)
    assert 0.5e6 < lo < hi < 3e6                             # 1-2 ms, in ns
    got = ps.analyse(base + ".xplane.pb", ring_path=base + ".spans.jsonl")
    assert got["program_events"] == 8 * 5
    assert got["device_clock_shift_s"]["lo"] == pytest.approx(lo * 1e-9)
    step = got["idle_under"]["bench:step"]
    # with the device's events moved, the 2 ms of build are idle again
    assert step["tick.build"] == pytest.approx(8 * 0.002, rel=0.2)
    assert got["idle_under_as_recorded"]["bench:step"]["tick.build"] \
        < 0.8 * step["tick.build"]
    assert 8 * 0.001 <= step["tick.emit"] <= 8 * 0.002   # sleep overshoots
    assert ps.named_child_share(step, "serve.step") >= 0.95
    sleep = got["idle_under"]["bench:sleep"]
    assert sleep[ps.NO_SPAN] == pytest.approx(8 * 0.030, rel=0.1)
    assert ps.named_child_share(sleep, "serve.step") == 0.0
    # the engine's clock against the profiler's, from the 40 spans in both
    assert got["clock"]["joined"] == 40
    assert got["clock"]["residual_p95_s"] < 1e-4
    want = json.load(open(base + ".json"))
    assert got["idle_s"] == pytest.approx(want["idle_s"])
