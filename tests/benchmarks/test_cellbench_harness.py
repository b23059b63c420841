"""The benchmark's own arithmetic, on the CPU and without an engine: the
traffic generator, percentiles, the trace reduction, the FLOPs and kernel
formulas against hand counts, the comparison rule, and that
``BENCHMARK.json`` and the files it names fit together and fit the
contract's limits."""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import check, device, stats, traffic, window  # noqa: E402
from benchmarks.trace import reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
MIX = json.load(open(os.path.join(REPO, "benchmarks", "traffic",
                                  "serve-chat.json")))


# ------------------------------------------------------------------ traffic

def test_open_loop_schedule_is_deterministic_per_seed():
    a = traffic.open_loop_schedule(MIX, 7, 20.0, 50257, 2048)
    b = traffic.open_loop_schedule(MIX, 7, 20.0, 50257, 2048)
    c = traffic.open_loop_schedule(MIX, 2**31 + 9, 20.0, 50257, 2048)
    assert np.array_equal(a.due, b.due)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.due, c.due)


def test_every_seed_gets_the_same_set_of_sizes_and_gaps():
    a = traffic.open_loop_schedule(MIX, 1, 20.0, 50257, 2048)
    b = traffic.open_loop_schedule(MIX, 2, 20.0, 50257, 2048)
    w = a.in_window
    assert sorted(a.prompt_len[w]) == sorted(b.prompt_len[b.in_window])
    assert a.in_window.sum() == int(MIX["rate_per_s"] * 20.0)
    gaps = lambda s: np.sort(np.diff(s.due[s.in_window]))
    # all gaps but the seed's first are the same multiset, to rounding
    assert np.allclose(np.sort(np.diff(np.sort(a.due[w])))[5:-5].mean(),
                       gaps(b)[5:-5].mean(), rtol=0.05)
    p, q = MIX["prompt"], MIX["answer"]
    assert a.prompt_len.min() >= p["min"] and a.prompt_len.max() <= p["max"]
    assert a.answer_len.min() >= 1 and a.answer_len.max() <= q["max"]
    assert (a.prompt_len + a.answer_len).max() <= 2048
    assert 0.0 <= a.due[w].min() and a.due[w].max() < 20.0
    assert (a.due[~w] < 0).all() and (a.due[~w] >= -MIX["preroll_s"]).all()
    med = np.median(a.prompt_len[w])
    assert 0.9 * p["median"] <= med <= 1.1 * p["median"]


class _FakeEngine(window.EngineAdapter):
    """Serves each request in two steps of 10 virtual ms; rejects #3."""

    def __init__(self, clock):
        self.clock, self.live, self.t, self.p = clock, [], 0, 0

    def submit(self, i):
        if i == 3:
            return False
        self.live.append([i, 2])
        return True

    def busy(self):
        return bool(self.live)

    def counters(self):
        return self.t, self.p

    def step(self):
        self.clock[0] += 0.010
        self.t += 1
        done = []
        for item in self.live:
            item[1] -= 1
        for item in [x for x in self.live if x[1] == 0]:
            self.live.remove(item)
            done.append(type("C", (), {"rid": item[0]})())
        return done


def test_open_loop_submits_on_schedule_and_reports_lateness():
    clock = [100.0]
    due = np.array([0.0, 0.005, 0.2, 0.21, 0.5])

    def sleep(s):
        clock[0] += s

    eng = _FakeEngine(clock)
    res = window.drive_open_loop(eng, due, 100.0, now=lambda: clock[0],
                                 sleep=sleep)
    assert sorted(res.completions) == [0, 1, 2, 4]
    assert list(res.accepted) == [True, True, True, False, True]
    late = res.submit_ts - (100.0 + due)
    # request 1 fell due while a step ran: it waited for the step's end
    assert late[0] == 0.0 and late[1] == pytest.approx(0.005)
    assert all(s.ticks == 1 and s.prefills == 0 for s in res.steps)
    # idle between 0.02 and 0.2: the loop slept, it did not spin
    assert len(res.steps) == 2 + 2 + 2 + 1 or len(res.steps) <= 8


def test_percentile_places_failed_requests_at_infinity():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs[:94], 95, missing=6) == math.inf
    assert stats.percentile(xs[:96], 95, missing=4) == 95
    assert math.isnan(stats.percentile([], 95))
    assert stats.spread([10, 10, 10, 11, 9, 10]) == pytest.approx(
        (10.25 - 9.75) / 10)


def test_epoch_loop_counts_whole_calls_only():
    clock = [0.0]

    def epoch(e):
        clock[0] += 0.3
        return {"loss": 1.0}

    calls = window.drive_epochs(epoch, 5, 1.0, now=lambda: clock[0])
    assert [c.epoch for c in calls] == [5, 6, 7]
    assert calls[-1].end <= 1.0 + 1e-9


# ---------------------------------------------------------------- reduction

def test_reduction_on_intervals_made_by_hand():
    ops = [[("while", 0, 100), ("a", 0, 30), ("b", 40, 70), ("c", 150, 200)]]
    spans = [("bench:window", 0, 300), ("bench:step", 0, 120),
             ("bench:sleep", 120, 145), ("bench:step", 145, 210)]
    s = tr.summarize(ops, spans)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(300e-9)
    assert s.busy_s == pytest.approx(150e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert s.op_seconds["while"] == pytest.approx(40e-9)   # own time
    assert s.idle_seconds == pytest.approx(
        {"step": 35e-9, "sleep": 25e-9, "(no span)": 90e-9})
    part = tr.summarize(ops, spans, offsets_s=(100e-9, 200e-9))
    assert part.busy_s == pytest.approx(50e-9)
    assert part.idle_share == pytest.approx(0.5)


@pytest.mark.parametrize("spans", [
    [("bench:step", 0, 120)],                      # no window span at all
    [("bench:window", 10**6, 10**6 + 300)],        # on another clock
], ids=["no-window-span", "another-clock"])
def test_a_trace_whose_spans_are_not_aligned_is_an_error(spans):
    ops = [[("a", 0, 30), ("b", 40, 70)]]
    with pytest.raises(ValueError, match="not aligned"):
        tr.summarize(ops, spans)


def test_reduction_on_the_recorded_trace():
    """``recorded_v5e.xplane.pb``: one v5e chip, eight 4096^3 bf16 matmul
    dispatches 30 ms apart under ``bench:step`` with a ``bench:sleep``
    between (benchmarks/trace/record.py wrote it on the chip)."""
    path = os.path.join(HERE, "recorded_v5e.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept here")
    s = tr.reduce_file(path)
    assert s.n_devices == 1
    want = json.load(open(os.path.join(HERE, "recorded_v5e.json")))
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    # by hand: eight 30 ms sleeps, and eight 2 * 4096^3-FLOP products that
    # the chip cannot finish faster than ~5 ms together at its peaks
    assert 8 * 0.030 < s.window_s < 8 * 0.040
    assert 8 * 0.030 <= s.idle_seconds["sleep"] < s.window_s
    assert 0.004 < s.busy_s < 0.008
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert sum(s.idle_seconds.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s, rel=1e-3)
    assert any("fusion" in k or "dot" in k or "convolution" in k
               for k in s.op_seconds)


# ----------------------------------------------------------------- formulas

def test_lm_flops_per_token_matches_a_hand_count():
    from benchmarks.families.lm_trainer import flops_per_token

    sizes = dict(d_model=2048, num_layers=8, mlp_dim=8192, vocab_size=50257)
    per_layer = 2048 * 3 * 2048 + 2048 * 2048 + 2 * 2048 * 8192   # 50,331,648
    matrices = 8 * per_layer + 2048 * 50257
    assert flops_per_token(sizes, 2048) == 6 * matrices + 6 * 8 * 2048 * 2048
    assert flops_per_token(sizes, 2048) == pytest.approx(3.2348e9, rel=1e-3)


def test_resnet50_flops_match_the_published_count():
    from benchmarks.families.image_trainer import flops_per_image

    sizes = dict(base_width=64, expansion=4, stage_sizes=[3, 4, 6, 3],
                 image_channels=3)
    # torchvision documents 4.09 GFLOPs (multiply-adds) for resnet50 at 224
    macs = flops_per_image(dict(sizes, image_size=224, num_classes=1000)) / 6
    assert macs == pytest.approx(4.09e9, rel=0.01)
    # 32x32: stem 16x16, stages at 8, 4, 2, 1: the stem by hand
    cifar = flops_per_image(dict(sizes, image_size=32, num_classes=10))
    assert cifar == pytest.approx(0.5006e9, rel=1e-3)
    assert cifar > 3 * 2 * 16 * 16 * 49 * 3 * 64


def test_flash_attention_costs_match_a_hand_count():
    from benchmarks.kernels import flash_attention as fa

    f, b = fa.forward(4, 2048, 16, 128), fa.backward(4, 2048, 16, 128)
    assert f["flops"] == 2 * 4 * 16 * 2048 * 2048 * 128
    assert b["flops"] == 2 * f["flops"]
    assert f["bytes"] == 4 * (4 * 2048 * 16 * 128) * 2 + 4 * 4 * 16 * 2048
    least = fa.least_seconds(f, device.peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(f["flops"] / 197e12)


def test_an_unlisted_device_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v9")


def test_worst_leaf_gap_rule():
    ref = {"a": 1.0, "b": 2.0, "c": 0.0, "d": 0.0, "e": 0.0}
    gap, where = check.worst_leaf_gap({**ref, "b": 2.2, "c": 1e-9}, ref)
    assert where == "b" and gap == pytest.approx(0.1)
    # a leaf that is all but zero is measured against the median leaf
    gap, where = check.worst_leaf_gap({**ref, "c": 0.3}, ref)
    assert where == "c" and gap == pytest.approx(0.3 / 1.5)
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, ref)
    bad = check.Comparison("x", float("nan"), 1.0)
    assert not bad.ok


# ------------------------------------------------------- the files fit together

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_fits_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    cells_n = 24
    assert (2 + 14 * cells_n) * (SPEC["run_seconds"] + 60) \
        + cells_n * 2 * 90 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in SPEC[k]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_cell_names_files_that_exist_and_metrics_it_can_report():
    from benchmarks.harness import cell as cells

    used = set()
    for w in SPEC["workloads"]:
        c = cells.load_cell(REPO, w["name"])
        used.add(c.config_name)
        assert os.path.exists(os.path.join(c.bench_dir, "families",
                                           c.family + ".py"))
        assert c.traffic["kind"] in ("epochs", "open_loop")
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
        for m in c.per_layer:
            assert os.path.exists(os.path.join(
                c.bench_dir, "layer_metrics", m["name"] + ".py")), m["name"]
        for key in c.config.get("reduced", []):
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
        assert "limits" in c.workload["check"] and "control" in c.workload
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        body = json.load(open(os.path.join(REPO, c["file"])))
        assert body["reduced"] == c["reduced"]


def test_the_command_refuses_to_run_without_the_chip():
    """Under ``JAX_PLATFORMS=cpu`` the command exits non-zero and prints no
    result line; no option of it turns the device requirement off."""
    cell = SPEC["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "refusing" in proc.stderr
