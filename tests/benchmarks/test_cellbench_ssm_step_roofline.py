"""``ssm_step_roofline.serve`` (PR 45): the bytes the one-step Mamba-2
update needs (``benchmarks/kernels/ssd_step.py``) and the reader that holds
the tick's ``ssm_step`` scope to them
(``benchmarks/layer_metrics/ssm_step_roofline.serve.py``), on a canned
``obs``: made-up ``serve.tick`` spans on a clock of their own, a three-line
program text and device seconds for its instructions. No chip and no engine
here; the numbers are the expert cell's shape of numbers, not a
measurement."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CELL = "nemotron-3-super-120b-a12b-ep4.serve-assistant"
NAME = "ssm_step_roofline.serve"
# far from time.monotonic's readings: the ring is the process's, and other
# tests' spans lie in it
T0 = 5.0e8

STEP = ('%multiply_reduce_fusion.{i} = (bf16[64,128,64]{{2,1,0}}, '
        'f32[64,128,64,128]{{3,2,1,0}}) fusion(%p.{i}), kind=kLoop, '
        'calls=%fused.{i}, metadata={{op_name="jit(tick)/NemotronHLM/layer'
        '{i}/mamba/mamba_mixer/{scope}/mul"}}')
OTHER = ('%fusion.9 = bf16[64,4096]{1,0} fusion(%q), kind=kOutput, '
         'calls=%fused.9, metadata={op_name="jit(tick)/NemotronHLM/layer1/'
         'moe/moe/routed_experts/dot"}')


def _program(scope="ssm_step", layers=5):
    steps = [STEP.format(i=i, scope=scope) for i in range(layers)]
    text = "ENTRY %main (p: f32[2]) -> f32[2] {\n" + "\n".join(
        "  " + line for line in steps + [OTHER]) + "\n}\n"
    return text, steps


class _Trace:
    def __init__(self, op_seconds):
        self.op_seconds = op_seconds


class _Step:
    def __init__(self, start, end):
        self.start, self.end = start, end


def _reader():
    from benchmarks.harness import cell as cells

    return cells.load_module(
        os.path.join(REPO, "benchmarks", "layer_metrics", NAME + ".py"),
        "bench_metric_ssm_step_roofline_serve")


def _obs(t0, slots_by_tick, tick_ms, step_ms, scope="ssm_step", **attrs):
    """``len(slots_by_tick)`` ticks of ``tick_ms`` on a made-up clock from
    ``t0``, ``state_slots`` as listed (left out where None), and a trace in
    which the five step instructions took ``step_ms`` a tick together."""
    from benchmarks.harness import cell as cells
    from tpu_dist.obs import trace

    now = [t0]
    for slots in slots_by_tick:
        held = {} if slots is None else {"state_slots": slots}
        with trace.ring().span("serve.tick", now=lambda: now[0],
                               rids=list(range(slots or 0)), **held, **attrs):
            now[0] += 1e-3 * tick_ms
    text, steps = _program(scope)
    n = len(slots_by_tick)
    seconds = {line: 1e-3 * step_ms * n / len(steps) for line in steps}
    seconds[OTHER] = 1e-3 * 4.0 * n
    return {"cell": cells.load_cell(REPO, CELL),
            "engine_steps": [_Step(t0 - 1.0, now[0] + 1.0)],
            "trace": _Trace(seconds), "hlo_text": text,
            "device_kind": "TPU v5 lite"}


def test_the_counts_are_the_live_rows_state_twice_and_their_operands():
    from benchmarks.kernels import ssd_step as k

    state = 128 * 64 * 128 * 4
    assert state == 4_194_304                              # 4.19 MB a row
    small = 2 * 128 * 64 * 2 + 128 * 4 + 2 * 8 * 128 * 2   # x, y, dt, B, C
    assert k.step(1, 128, 64, 128, 8)["bytes"] == 2 * state + small
    # a tick of the cell at 23.4 live slots over five layers: 0.98 GB, and
    # 1.2 ms at the v5e's 819 GB/s; every slot live: 2.7 GB, 3.3 ms
    cost = k.step(23.4 * 5, 128, 64, 128, 8)
    assert cost["bytes"] == pytest.approx(0.985e9, rel=2e-3)
    floor = k.least_seconds(cost, {"hbm_bytes_per_s": 819e9})
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(1.203e-3, rel=2e-3)
    assert k.least_seconds(k.step(64 * 5, 128, 64, 128, 8),
                           {"hbm_bytes_per_s": 819e9})["seconds"] \
        == pytest.approx(3.29e-3, rel=2e-3)
    # float32 activations move twice the small operands, the same state
    assert k.step(1, 128, 64, 128, 8, 4)["bytes"] == 2 * state + (
        2 * 128 * 64 * 4 + 128 * 4 + 2 * 8 * 128 * 4)


def test_the_parents_shape_of_numbers_reads_about_29_percent(capsys):
    """The plain form passes over all 64 slots: 4.1 ms a tick in the scope
    with 23.4 slots live (PERF_LEDGER.jsonl, PR 44: ``ssm_step_share.serve``
    28.38% of a 12.42 ms tick's 7.85 s busy, ``state_slots.serve`` 23.4)."""
    obs = _obs(T0, [23, 24, 23, 24, 23] * 20, 12.42, 4.1)
    got = _reader().read(obs)
    assert got == pytest.approx(29.4, abs=0.3)
    out = capsys.readouterr().out
    assert "100 ticks, 117.0 live rows a tick over 5 Mamba-2 layers" in out
    assert "4.100 ms a tick in the trace, HBM floor 1.204 ms" in out


def test_the_kernels_shape_of_numbers_reads_its_share_of_the_floor():
    """The same rows in 1.5 ms a tick: 80% of the floor; and a window in
    which every slot decodes holds the plain form to its own 79%."""
    assert _reader().read(_obs(T0 + 100, [23, 24] * 50, 9.8, 1.5)) \
        == pytest.approx(80.3, abs=0.5)
    assert _reader().read(_obs(T0 + 200, [64] * 50, 16.0, 4.16)) \
        == pytest.approx(79.1, abs=0.5)


def test_absent_without_the_scope_the_attribute_or_a_trace():
    read = _reader().read
    # a program that names no such scope (another family's tick)
    assert read(_obs(T0 + 300, [23] * 10, 12.0, 4.0, scope="attn")) is None
    # spans without the attribute (an older program), or with no slot held
    assert read(_obs(T0 + 400, [None] * 10, 12.0, 4.0)) is None
    assert read(_obs(T0 + 500, [0] * 10, 12.0, 4.0)) is None
    # an untraced run, a run with no program text, no serving window
    obs = _obs(T0 + 600, [23] * 10, 12.0, 4.0)
    assert read({**obs, "trace": None}) is None
    assert read({**obs, "hlo_text": None}) is None
    assert read({k: v for k, v in obs.items() if k != "engine_steps"}) is None
    # no span of the window at all
    assert read({**obs, "engine_steps": [_Step(T0 + 700, T0 + 701)]}) is None


def test_the_benchmark_lists_the_metric_for_the_expert_cell_alone():
    import json

    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms",
        "workloads": [CELL]}]
    from benchmarks.harness import cell as cells

    assert NAME in {m["name"] for m in cells.load_cell(REPO, CELL).per_layer}
    assert NAME not in {m["name"] for m in cells.load_cell(
        REPO, "jamba2-3b.serve-chat-burst").per_layer}
