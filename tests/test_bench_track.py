"""Bench regression tracker (tools/bench_track.py) — no jax.

Covers: a five-round BENCH_r*.json history parsing into its trend (the
shape the driver's old per-round records had; the values are a fixture,
not measurements), the threshold check against an
injected-regression fixture (nonzero exit — the acceptance bar),
--headline appending the run under test, --json output shape, and
malformed/non-bench files being skipped rather than crashing."""

import json
import os

import pytest

from tools.bench_track import load_points, main, track

HEADLINE = "cifar10_resnet50_images_per_sec_per_chip"


def _write_round(dirpath, n, value, metric=HEADLINE, **parsed_extra):
    doc = {"n": n, "cmd": "python headline.py", "rc": 0,
           "parsed": {"metric": metric, "value": value,
                      "unit": "images/sec/chip", **parsed_extra}}
    path = os.path.join(dirpath, f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


# a flat four rounds and a fifth that is the trailing best
_HISTORY = (140821.2, 141819.6, 141109.8, 140270.5, 174621.9)


def _write_history(dirpath):
    for n, value in enumerate(_HISTORY, start=1):
        _write_round(dirpath, n, value)


def test_checked_in_history_reports_trend(tmp_path, capsys):
    """A five-round history parses into its trajectory and passes the
    gate (r05 is the trailing best)."""
    _write_history(str(tmp_path))
    assert main(["--dir", str(tmp_path), "--check"]) == 0
    out = capsys.readouterr().out
    assert HEADLINE in out
    assert "140,821.2" in out and "174,621.9" in out  # first -> latest
    assert "ok: latest" in out


def test_injected_regression_exits_nonzero(tmp_path, capsys):
    """ACCEPTANCE: a fabricated regressed round fails --check."""
    _write_history(str(tmp_path))
    _write_round(str(tmp_path), 6, 100000.0)  # -42.7% vs r05's 174.6k
    assert main(["--dir", str(tmp_path), "--check"]) == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and HEADLINE in err
    # without --check the report still renders, exit stays 0
    assert main(["--dir", str(tmp_path)]) == 0
    assert "REGRESSED 42.7%" in capsys.readouterr().out


def test_threshold_and_variant_metrics_track_independently(tmp_path):
    d = str(tmp_path)
    _write_round(d, 1, 1000.0)
    _write_round(d, 2, 960.0)  # -4% vs best: inside the default 5%
    points = load_points([os.path.join(d, f) for f in sorted(os.listdir(d))])
    report = track(points, threshold_pct=5.0)
    m = report["metrics"][HEADLINE]
    assert report["ok"] and not m["regressed"]
    assert m["drop_pct"] == pytest.approx(4.0)
    # a quant-variant metric regressing does not implicate the headline
    doc = {"n": 3, "rc": 0, "parsed": {"metric": "lm_int8_tok_s",
                                       "value": 50.0, "unit": "tok/s"}}
    p3 = os.path.join(d, "BENCH_r03.json")
    json.dump(doc, open(p3, "w"))
    _write_round(d, 4, 970.0)
    doc["n"] = 5
    doc["parsed"]["value"] = 10.0  # -80% on the variant only
    json.dump(doc, open(os.path.join(d, "BENCH_r05.json"), "w"))
    points = load_points([os.path.join(d, f) for f in sorted(os.listdir(d))])
    report = track(points, threshold_pct=5.0)
    assert report["metrics"]["lm_int8_tok_s"]["regressed"]
    assert not report["metrics"][HEADLINE]["regressed"]
    assert not report["ok"]


def test_headline_file_is_newest_point_and_gates(tmp_path, capsys):
    d = str(tmp_path)
    _write_round(d, 1, 1000.0)
    _write_round(d, 2, 1100.0)
    head = os.path.join(d, "head.json")
    json.dump({"metric": HEADLINE, "value": 900.0,
               "unit": "images/sec/chip"}, open(head, "w"))
    # --headline implies the gate: 900 vs best 1100 = -18% -> fail
    assert main(["--dir", d, "--headline", head]) == 1
    capsys.readouterr()
    json.dump({"metric": HEADLINE, "value": 1200.0,
               "unit": "images/sec/chip"}, open(head, "w"))
    assert main(["--dir", d, "--headline", head]) == 0
    capsys.readouterr()
    # a missing or unusable run-under-test must FAIL the gate, not
    # silently judge only the history
    assert main(["--dir", d, "--headline",
                 os.path.join(d, "nope.json")]) == 2
    with open(head, "w") as f:
        f.write("{truncated")
    assert main(["--dir", d, "--headline", head]) == 2
    assert "cannot be judged" in capsys.readouterr().err


def test_json_output_and_skipped_files(tmp_path, capsys):
    d = str(tmp_path)
    _write_round(d, 1, 1000.0, mfu=0.30)
    _write_round(d, 2, 1050.0, mfu=0.33)
    # a MULTICHIP-style file (no parsed metric), a corrupt file, and a
    # crashed round's value:null — all skipped with a note, never a crash
    json.dump({"n_devices": 8, "ok": True},
              open(os.path.join(d, "BENCH_r03.json"), "w"))
    with open(os.path.join(d, "BENCH_r04.json"), "w") as f:
        f.write("{not json")
    json.dump({"n": 5, "rc": 1, "parsed": {"metric": HEADLINE,
                                           "value": None}},
              open(os.path.join(d, "BENCH_r05.json"), "w"))
    assert main(["--dir", d, "--json"]) == 0
    cap = capsys.readouterr()
    report = json.loads(cap.out)
    assert "skipping" in cap.err
    m = report["metrics"][HEADLINE]
    assert [r["value"] for r in m["rounds"]] == [1000.0, 1050.0]
    assert m["rounds"][1]["delta_pct"] == pytest.approx(5.0)
    assert m["rounds"][1]["mfu"] == 0.33
    assert report["ok"] is True


def test_no_usable_points_is_distinct_error(tmp_path):
    assert main(["--dir", str(tmp_path)]) == 2


def test_fleet_goodput_gates_and_abstains_on_pre_fleet_history(tmp_path):
    """The round-14 fleet gate: `fleet.goodput_ratio` is judged like the
    headline (higher is better, threshold_pct) against the best prior
    point CARRYING a fleet block — the pre-fleet BENCH history abstains,
    exactly the data_s / serving.requests_per_tick convention."""
    d = str(tmp_path)
    _write_round(d, 1, 1000.0)                      # pre-fleet: no block
    _write_round(d, 2, 1000.0, fleet={"goodput_ratio": 0.40})
    paths = [os.path.join(d, f) for f in sorted(os.listdir(d))]
    points = load_points(paths)
    assert [p["fleet_goodput"] for p in points] == [None, 0.40]
    m = track(points, threshold_pct=5.0)["metrics"][HEADLINE]
    # one fleet point: nothing prior to judge against — abstain, ok
    assert m["fleet_latest"] == 0.40 and m["fleet_best_prior"] is None
    assert not m["fleet_regressed"]
    # a regressed ratio fails the gate even with the headline value flat
    _write_round(d, 3, 1000.0, fleet={"goodput_ratio": 0.30})  # -25%
    report = track(load_points(paths + [os.path.join(d, "BENCH_r03.json")]),
                   threshold_pct=5.0)
    m = report["metrics"][HEADLINE]
    assert m["fleet_regressed"] and not report["ok"]
    assert main(["--dir", d, "--check"]) == 1
    # inside the threshold: ok again
    _write_round(d, 3, 1000.0, fleet={"goodput_ratio": 0.395})  # -1.3%
    assert main(["--dir", d, "--check"]) == 0


def test_fleet_headline_from_the_sim_runner_shape(tmp_path, capsys):
    """The runner's headline.json (metric fleet_sim_goodput + fleet
    block) loads as a first point and renders the no-history abstention."""
    d = str(tmp_path)
    path = os.path.join(d, "headline.json")
    with open(path, "w") as f:
        json.dump({"metric": "fleet_sim_goodput", "value": 0.31,
                   "unit": "ratio",
                   "fleet": {"goodput_ratio": 0.31, "slo_breaches": 4,
                             "hosts": 3}}, f)
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "fleet_sim_goodput" in out
    assert "no prior fleet history" in out
