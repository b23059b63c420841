#!/usr/bin/env python
"""Bench regression tracker over the checked-in BENCH_r*.json history.

    python tools/bench_track.py                    # trend table (repo root)
    python tools/bench_track.py --check            # CI gate: nonzero on drop
    python tools/bench_track.py --json             # machine-readable
    python tools/bench_track.py --headline out.json  # + this run's headline

Every round of this repo drops a ``BENCH_r<N>.json`` (the bench driver's
wrapper: ``{"n", "cmd", "rc", "tail", "parsed": {metric, value, unit,
mfu, ...}}``) — five rounds of history that, until now, nothing read. This
tool turns them into a guarded trajectory: a per-metric trend table
(value, Δ%, MFU per round) and a threshold check that FAILS when the
newest point drops more than ``--threshold-pct`` below the trailing best
of its metric — the reference cookbook's apex ``data_prefetcher`` bug
(PAPER.md) was exactly a silent per-round regression this would have
caught at review time.

Accepted inputs per file (positional args override the default glob):
the wrapper format above, or a raw headline JSON object (``{"metric",
"value", ...}``) via ``--headline`` for the
run-under-test. Different metric names track independently (quant/tp_impl
variants publish their own names by design), so a variant run
never gates the bf16 headline. Stdlib only: runs in CI, on a login host,
anywhere.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_points(paths: List[str], out_err=None) -> List[dict]:
    """[{metric, value, round, file, unit, mfu, vs_baseline}] from wrapper
    and raw-headline files alike; files with no parseable metric (failed
    rounds, MULTICHIP dryruns) are skipped with a note."""
    out_err = out_err or (lambda s: print(s, file=sys.stderr))
    points = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            out_err(f"bench_track: skipping {path}: {e}")
            continue
        if not isinstance(doc, dict):
            out_err(f"bench_track: skipping {path}: not a JSON object")
            continue
        parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
            else (doc if "metric" in doc else None)
        if parsed is not None and "value" not in parsed \
                and "kv_cache" in parsed:
            # decode_bench headline: the kv-cache tok/s IS the value (and
            # round 11's serving replay block rides the same object). The
            # long-context replay (round 19) skips the one-shot sections
            # entirely (kv_cache: null) — its completed-requests-per-tick
            # is the value, under its own metric name
            v = parsed["kv_cache"]
            if v is None and isinstance(parsed.get("serving"), dict):
                v = parsed["serving"].get("requests_per_tick")
            parsed = dict(parsed, value=v)
        if not parsed or "metric" not in parsed or "value" not in parsed:
            out_err(f"bench_track: skipping {path}: no parsed metric "
                    "(failed round or non-bench file)")
            continue
        try:
            value = float(parsed["value"])
        except (TypeError, ValueError):
            # a crashed round can leave value: null — skip, don't die
            out_err(f"bench_track: skipping {path}: non-numeric value "
                    f"{parsed['value']!r}")
            continue
        rnd = doc.get("n")
        if rnd is None:
            m = re.search(r"_r0*(\d+)\.json$", os.path.basename(path))
            rnd = int(m.group(1)) if m else None
        phases = parsed.get("phases") if isinstance(parsed.get("phases"),
                                                    dict) else {}
        # pre-round-9 headlines hardcoded data_s: 0.0 (device-resident
        # bench, no measurement); a real measured wait never rounds to
        # exactly 0 — treat the placeholder as absent so the gate judges
        # measured-vs-measured, never measured-vs-synthetic
        data_s = phases.get("data_s")
        if data_s == 0:
            data_s = None
        # serving trace replay (decode_bench --trace, round 11+): the
        # deterministic completed-requests-per-tick is the gated number —
        # wall req/s rides the same block but carries machine variance
        serving = (parsed.get("serving")
                   if isinstance(parsed.get("serving"), dict) else {})
        # fleet simulation (tpu_dist.sim, round 14+): the stitched fleet
        # goodput ratio is the gated end-to-end number; history without a
        # fleet block abstains, exactly the data_s/serving convention
        fleet = (parsed.get("fleet")
                 if isinstance(parsed.get("fleet"), dict) else {})
        # tuned step plans (tpu_dist.plan, round 15+): a headline driven
        # by BENCH_PLAN carries a plan block — its metric tracks under a
        # [plan:<hash>]-tagged name so plan-tuned runs gate against THEIR
        # OWN history and pre-plan points abstain, exactly the quant/
        # tp_impl naming convention (variants never gate the bf16 line)
        plan = (parsed.get("plan")
                if isinstance(parsed.get("plan"), dict) else None)
        metric = parsed["metric"]
        if plan and plan.get("hash"):
            metric = f"{metric}[plan:{plan['hash']}]"
        points.append({
            "metric": metric,
            "value": value,
            "unit": parsed.get("unit"),
            "mfu": parsed.get("mfu"),
            "vs_baseline": parsed.get("vs_baseline"),
            "data_s": data_s,
            "serving_rpt": serving.get("requests_per_tick"),
            # round 16+: speculative acceptance (higher is better) and
            # fresh pages per request (LOWER is better — the prefix
            # cache's number); pre-spec history abstains like the rest
            "serving_apt": serving.get("accepted_per_tick"),
            "serving_ppr": serving.get("pages_per_request"),
            # round 17+: attribution coverage from the request spans —
            # the share of completed-request latency the queue/prefill/
            # decode spans account for (1.0 on any ledger that lost no
            # span); pre-span history abstains like the rest
            "serving_cov": (serving.get("tail_attribution") or {}).get(
                "coverage") if isinstance(
                serving.get("tail_attribution"), dict) else None,
            # round 19+: the long-context replay's virtual-clock tail
            # numbers, both LOWER is better — TTFT p99 of the >=threshold
            # prompts, and short-request TPOT degradation vs the
            # no-long-prompt baseline; pre-long-context history abstains
            "serving_ttfl": serving.get("ttft_long_p99"),
            "serving_tip": serving.get("tpot_interference_pct"),
            "fleet_goodput": fleet.get("goodput_ratio"),
            # round 20+: autoscale reaction time — ticks from burst onset
            # to the first up decision, LOWER is better; pre-autoscale
            # history carries no field and abstains like the rest
            "fleet_lag": fleet.get("autoscale_lag_ticks"),
            "round": rnd,
            "file": os.path.basename(path),
        })
    # order by round where known (unknown rounds sort last, in arg order —
    # the --headline run-under-test lands there as the newest point)
    points.sort(key=lambda p: (p["round"] is None, p["round"] or 0))
    return points


def track(points: List[dict], threshold_pct: float,
          data_s_slack: float = 0.05) -> dict:
    """Group points by metric and judge the newest against the trailing
    best: {'metrics': {name: {...}}, 'ok': bool}.

    Beside the headline value, the newest point's ``data_s`` (the bench's
    best-trial input wait, headline JSON ``phases.data_s``) is judged
    against the best (lowest) prior: a rise of more than ``data_s_slack``
    seconds fails the gate even when throughput still looks fine — the
    apex-prefetcher class of bug where the input pipeline silently stops
    overlapping but a compute-bound trial hides it for one more round.
    Points without phases (pre-round-6 history) abstain rather than judge.
    """
    by_metric: dict = {}
    for p in points:
        by_metric.setdefault(p["metric"], []).append(p)
    report = {"metrics": {}, "ok": True, "threshold_pct": threshold_pct,
              "data_s_slack": data_s_slack}
    for name, series in by_metric.items():
        latest = series[-1]
        prior = series[:-1]
        best_prior = max((p["value"] for p in prior), default=None)
        drop_pct = None
        regressed = False
        if best_prior:
            drop_pct = (best_prior - latest["value"]) / best_prior * 100.0
            regressed = drop_pct > threshold_pct
        prior_data = [p["data_s"] for p in prior
                      if p.get("data_s") is not None]
        data_best = min(prior_data, default=None)
        data_regressed = (data_best is not None
                          and latest.get("data_s") is not None
                          and latest["data_s"] > data_best + data_s_slack)
        # serving throughput-under-load: judged like the headline value
        # (higher is better, threshold_pct) against the best prior point
        # that CARRIES a serving block — pre-serving history abstains,
        # exactly the data_s convention
        prior_srv = [p["serving_rpt"] for p in prior
                     if p.get("serving_rpt") is not None]
        srv_best = max(prior_srv, default=None)
        srv_latest = latest.get("serving_rpt")
        srv_regressed = (srv_best is not None and srv_latest is not None
                         and (srv_best - srv_latest) / srv_best * 100.0
                         > threshold_pct)
        # speculative acceptance (round 16+): higher is better, same
        # abstention convention (pre-spec history carries no field)
        prior_apt = [p["serving_apt"] for p in prior
                     if p.get("serving_apt") is not None]
        apt_best = max(prior_apt, default=None)
        apt_latest = latest.get("serving_apt")
        apt_regressed = (apt_best is not None and apt_latest is not None
                         and (apt_best - apt_latest) / apt_best * 100.0
                         > threshold_pct)
        # fresh pages per request (round 16+): LOWER is better — the gate
        # reverses (judged against the best = lowest prior, fails on RISE)
        prior_ppr = [p["serving_ppr"] for p in prior
                     if p.get("serving_ppr") is not None]
        ppr_best = min(prior_ppr, default=None)
        ppr_latest = latest.get("serving_ppr")
        ppr_regressed = (ppr_best is not None and ppr_latest is not None
                         and ppr_best > 0
                         and (ppr_latest - ppr_best) / ppr_best * 100.0
                         > threshold_pct)
        # attribution coverage (round 17+): higher is better (1.0 means
        # every completed request's latency fully decomposes into spans);
        # a drop means the engine started losing span windows
        prior_cov = [p["serving_cov"] for p in prior
                     if p.get("serving_cov") is not None]
        cov_best = max(prior_cov, default=None)
        cov_latest = latest.get("serving_cov")
        cov_regressed = (cov_best is not None and cov_latest is not None
                         and (cov_best - cov_latest) / cov_best * 100.0
                         > threshold_pct)
        # long-context TTFT p99 (round 19+): LOWER is better, virtual
        # token-equivalent units — judged like pages_per_request against
        # the best (lowest) prior carrying the field, fails on RISE
        prior_ttfl = [p["serving_ttfl"] for p in prior
                      if p.get("serving_ttfl") is not None]
        ttfl_best = min(prior_ttfl, default=None)
        ttfl_latest = latest.get("serving_ttfl")
        ttfl_regressed = (ttfl_best is not None and ttfl_latest is not None
                          and ttfl_best > 0
                          and (ttfl_latest - ttfl_best) / ttfl_best * 100.0
                          > threshold_pct)
        # long-context TPOT interference (round 19+): LOWER is better and
        # already a percentage — judged on ABSOLUTE percentage points
        # (threshold_pct of them), since the best prior can sit near zero
        prior_tip = [p["serving_tip"] for p in prior
                     if p.get("serving_tip") is not None]
        tip_best = min(prior_tip, default=None)
        tip_latest = latest.get("serving_tip")
        tip_regressed = (tip_best is not None and tip_latest is not None
                         and tip_latest > tip_best + threshold_pct)
        # fleet goodput ratio (tpu_dist.sim): higher is better, judged
        # against the best prior point CARRYING a fleet block — pre-fleet
        # history abstains, exactly the data_s/serving convention
        prior_fleet = [p["fleet_goodput"] for p in prior
                       if p.get("fleet_goodput") is not None]
        fleet_best = max(prior_fleet, default=None)
        fleet_latest = latest.get("fleet_goodput")
        fleet_regressed = (fleet_best is not None
                           and fleet_latest is not None
                           and (fleet_best - fleet_latest) / fleet_best
                           * 100.0 > threshold_pct)
        # autoscale reaction lag (round 20+): LOWER is better — judged
        # against the best (lowest) prior carrying the field, fails on
        # RISE; a zero best prior abstains (no relative scale to judge)
        prior_lag = [p["fleet_lag"] for p in prior
                     if p.get("fleet_lag") is not None]
        lag_best = min(prior_lag, default=None)
        lag_latest = latest.get("fleet_lag")
        lag_regressed = (lag_best is not None and lag_latest is not None
                         and lag_best > 0
                         and (lag_latest - lag_best) / lag_best * 100.0
                         > threshold_pct)
        rounds = [{"round": p["round"], "value": p["value"],
                   "mfu": p["mfu"], "file": p["file"],
                   "data_s": p.get("data_s"),
                   "delta_pct": (None if i == 0 or not series[i - 1]["value"]
                                 else (p["value"] / series[i - 1]["value"]
                                       - 1.0) * 100.0)}
                  for i, p in enumerate(series)]
        report["metrics"][name] = {
            "unit": latest["unit"], "rounds": rounds,
            "latest": latest["value"], "best_prior": best_prior,
            "drop_pct": drop_pct, "regressed": regressed,
            "data_s_latest": latest.get("data_s"),
            "data_s_best_prior": data_best,
            "data_s_regressed": data_regressed,
            "serving_latest": srv_latest,
            "serving_best_prior": srv_best,
            "serving_regressed": srv_regressed,
            "accepted_latest": apt_latest,
            "accepted_best_prior": apt_best,
            "accepted_regressed": apt_regressed,
            "pages_latest": ppr_latest,
            "pages_best_prior": ppr_best,
            "pages_regressed": ppr_regressed,
            "coverage_latest": cov_latest,
            "coverage_best_prior": cov_best,
            "coverage_regressed": cov_regressed,
            "fleet_latest": fleet_latest,
            "fleet_best_prior": fleet_best,
            "fleet_regressed": fleet_regressed,
            "autoscale_lag_latest": lag_latest,
            "autoscale_lag_best_prior": lag_best,
            "autoscale_lag_regressed": lag_regressed,
            "ttft_long_latest": ttfl_latest,
            "ttft_long_best_prior": ttfl_best,
            "ttft_long_regressed": ttfl_regressed,
            "interference_latest": tip_latest,
            "interference_best_prior": tip_best,
            "interference_regressed": tip_regressed,
        }
        if (regressed or data_regressed or srv_regressed or apt_regressed
                or ppr_regressed or cov_regressed or fleet_regressed
                or ttfl_regressed or tip_regressed or lag_regressed):
            report["ok"] = False
    return report


def render(report: dict, out=print) -> None:
    for name, m in sorted(report["metrics"].items()):
        out(f"{name} ({m['unit'] or '?'}):")
        for r in m["rounds"]:
            rnd = f"r{r['round']:02d}" if r["round"] is not None else "head"
            out(f"  {rnd}  {r['value']:>12,.1f}"
                + (f"  {r['delta_pct']:+6.1f}%" if r["delta_pct"] is not None
                   else "   " + " " * 6)
                + (f"  MFU {r['mfu'] * 100:.1f}%" if r.get("mfu") else "")
                + f"  [{r['file']}]")
        if m["best_prior"] is not None:
            verdict = (f"REGRESSED {m['drop_pct']:.1f}% below trailing best "
                       f"{m['best_prior']:,.1f} (threshold "
                       f"{report['threshold_pct']:g}%)"
                       if m["regressed"] else
                       f"ok: latest {m['latest']:,.1f} vs trailing best "
                       f"{m['best_prior']:,.1f} "
                       f"({-m['drop_pct']:+.1f}%)")
            out(f"  -> {verdict}")
        else:
            out("  -> single point; nothing to judge")
        if m.get("data_s_best_prior") is not None \
                and m.get("data_s_latest") is not None:
            verdict = ("DATA_S REGRESSED" if m["data_s_regressed"] else "ok")
            out(f"  -> data_s {verdict}: latest {m['data_s_latest']:.4f}s "
                f"vs best prior {m['data_s_best_prior']:.4f}s (slack "
                f"{report['data_s_slack']:g}s)")
        if m.get("serving_latest") is not None:
            if m.get("serving_best_prior") is not None:
                verdict = ("SERVING REGRESSED" if m["serving_regressed"]
                           else "ok")
                out(f"  -> serving {verdict}: latest "
                    f"{m['serving_latest']:.4f} req/tick vs best prior "
                    f"{m['serving_best_prior']:.4f} (threshold "
                    f"{report['threshold_pct']:g}%)")
            else:
                out(f"  -> serving: {m['serving_latest']:.4f} req/tick "
                    "(no prior serving history; nothing to judge)")
        if m.get("accepted_latest") is not None:
            if m.get("accepted_best_prior") is not None:
                verdict = ("ACCEPTANCE REGRESSED"
                           if m["accepted_regressed"] else "ok")
                out(f"  -> spec {verdict}: {m['accepted_latest']:.4f} "
                    f"accepted/tick vs best prior "
                    f"{m['accepted_best_prior']:.4f} (threshold "
                    f"{report['threshold_pct']:g}%)")
            else:
                out(f"  -> spec: {m['accepted_latest']:.4f} accepted/tick "
                    "(no prior speculative history; nothing to judge)")
        if m.get("pages_latest") is not None:
            if m.get("pages_best_prior") is not None:
                verdict = ("PAGES REGRESSED" if m["pages_regressed"]
                           else "ok")
                out(f"  -> pages {verdict}: {m['pages_latest']:.4f} "
                    f"fresh pages/request vs best (lowest) prior "
                    f"{m['pages_best_prior']:.4f} (threshold "
                    f"{report['threshold_pct']:g}%, lower is better)")
            else:
                out(f"  -> pages: {m['pages_latest']:.4f} fresh "
                    "pages/request (no prior prefix-cache history; "
                    "nothing to judge)")
        if m.get("coverage_latest") is not None:
            if m.get("coverage_best_prior") is not None:
                verdict = ("COVERAGE REGRESSED"
                           if m["coverage_regressed"] else "ok")
                out(f"  -> attribution {verdict}: coverage "
                    f"{m['coverage_latest']:.4f} vs best prior "
                    f"{m['coverage_best_prior']:.4f} (threshold "
                    f"{report['threshold_pct']:g}%)")
            else:
                out(f"  -> attribution: coverage "
                    f"{m['coverage_latest']:.4f} (no prior span history; "
                    "nothing to judge)")
        if m.get("ttft_long_latest") is not None:
            if m.get("ttft_long_best_prior") is not None:
                verdict = ("TTFT-LONG REGRESSED"
                           if m["ttft_long_regressed"] else "ok")
                out(f"  -> ttft-long {verdict}: p99 "
                    f"{m['ttft_long_latest']:,.1f} virtual tok-equiv vs "
                    f"best (lowest) prior {m['ttft_long_best_prior']:,.1f} "
                    f"(threshold {report['threshold_pct']:g}%, lower is "
                    "better)")
            else:
                out(f"  -> ttft-long: p99 {m['ttft_long_latest']:,.1f} "
                    "virtual tok-equiv (no prior long-context history; "
                    "nothing to judge)")
        if m.get("interference_latest") is not None:
            if m.get("interference_best_prior") is not None:
                verdict = ("INTERFERENCE REGRESSED"
                           if m["interference_regressed"] else "ok")
                out(f"  -> interference {verdict}: short-TPOT "
                    f"{m['interference_latest']:+.2f}% vs best (lowest) "
                    f"prior {m['interference_best_prior']:+.2f}% "
                    f"(slack {report['threshold_pct']:g} percentage "
                    "points, lower is better)")
            else:
                out(f"  -> interference: short-TPOT "
                    f"{m['interference_latest']:+.2f}% (no prior "
                    "long-context history; nothing to judge)")
        if m.get("fleet_latest") is not None:
            if m.get("fleet_best_prior") is not None:
                verdict = ("FLEET REGRESSED" if m["fleet_regressed"]
                           else "ok")
                out(f"  -> fleet {verdict}: goodput ratio "
                    f"{m['fleet_latest']:.4f} vs best prior "
                    f"{m['fleet_best_prior']:.4f} (threshold "
                    f"{report['threshold_pct']:g}%)")
            else:
                out(f"  -> fleet: goodput ratio {m['fleet_latest']:.4f} "
                    "(no prior fleet history; nothing to judge)")
        if m.get("autoscale_lag_latest") is not None:
            if m.get("autoscale_lag_best_prior") is not None:
                verdict = ("AUTOSCALE-LAG REGRESSED"
                           if m["autoscale_lag_regressed"] else "ok")
                out(f"  -> autoscale {verdict}: lag "
                    f"{m['autoscale_lag_latest']:.1f} tick(s) vs best "
                    f"(lowest) prior {m['autoscale_lag_best_prior']:.1f} "
                    f"(threshold {report['threshold_pct']:g}%, lower is "
                    "better)")
            else:
                out(f"  -> autoscale: lag {m['autoscale_lag_latest']:.1f} "
                    "tick(s) (no prior autoscale history; nothing to "
                    "judge)")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="bench JSONs (default: <repo>/BENCH_r*.json)")
    ap.add_argument("--dir", default=ROOT,
                    help="directory holding BENCH_r*.json (default: repo "
                    "root)")
    ap.add_argument("--headline", default="",
                    help="a raw headline JSON for the run under "
                    "test, appended as the newest point")
    ap.add_argument("--threshold-pct", type=float, default=5.0,
                    help="fail when the newest point drops more than this "
                    "%% below the metric's trailing best (default 5)")
    ap.add_argument("--data-s-slack", type=float, default=0.05,
                    help="fail when the newest point's phases.data_s rises "
                    "more than this many seconds above the metric's best "
                    "prior (input-pipeline regression gate; default 0.05)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any regressed metric (the CI gate; "
                    "implied by --headline)")
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON object on stdout")
    args = ap.parse_args(argv)

    files = list(args.files) or sorted(
        glob.glob(os.path.join(args.dir, "BENCH_r*.json")))
    if args.headline:
        files.append(args.headline)
    if not files:
        print(f"bench_track: no BENCH_r*.json under {args.dir} and no "
              "files given", file=sys.stderr)
        return 2
    points = load_points(files)
    if not points:
        print("bench_track: no usable bench points", file=sys.stderr)
        return 2
    if args.headline and not any(p["file"] == os.path.basename(args.headline)
                                 for p in points):
        # the gate --headline implies must never silently judge only the
        # history: a missing/corrupt run-under-test is itself a failure
        print(f"bench_track: headline {args.headline} yielded no usable "
              "point — the run under test cannot be judged", file=sys.stderr)
        return 2
    report = track(points, args.threshold_pct,
                   data_s_slack=args.data_s_slack)
    if args.json:
        print(json.dumps(report))
    else:
        render(report)
    if (args.check or args.headline) and not report["ok"]:
        bad = [k for k, m in report["metrics"].items()
               if m["regressed"] or m.get("data_s_regressed")
               or m.get("serving_regressed") or m.get("accepted_regressed")
               or m.get("pages_regressed") or m.get("coverage_regressed")
               or m.get("fleet_regressed") or m.get("ttft_long_regressed")
               or m.get("interference_regressed")
               or m.get("autoscale_lag_regressed")]
        print(f"bench_track: REGRESSION in {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
