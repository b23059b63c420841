#!/usr/bin/env python3
"""Per-request waterfalls, tail-latency attribution, SLO-breach exemplars.

The request observatory's reading side (obs.reqtrace is the writing
side): point it at one serving ledger or a fleet directory and it answers
"where did THIS request's latency go" —

* **waterfalls**: the span tree of a trace rendered as per-phase bars,
  cross-host traces showing every host-attempt that touched the rid;
* **tail attribution**: each completed request's admit->finish latency
  decomposed into the named categories (queue / prefill / decode) with a
  goodput-style sum-check — attributed seconds + residue == measured
  latency, per request — and the TTFT/TPOT percentiles decomposed by
  their nearest-rank exemplar request, so "p99 TTFT is queue" is a
  statement about a concrete rid, not a vibe;
* **exemplar index**: every ``slo`` breach event bound to the concrete
  worst-offender traces inside its breach window (wall-clock emit
  timestamps — the one clock comparable across hosts), so a breach is a
  link to evidence, not just a counter bump.

Usage::

    python tools/request_report.py out/serve.jsonl
    python tools/request_report.py out/fleet_dir --json
    python tools/request_report.py out/fleet_dir --waterfalls 5

Stdlib-only and deterministic: the same ledger bytes produce the same
report bytes (scripts/lint.sh gates on it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tpu_dist.obs import reqtrace                              # noqa: E402
from tpu_dist.obs.goodput import load_job_records              # noqa: E402

# per-request sum-check tolerance: span endpoints are rounded to 1e-6
# before emit, so a request's tiling can drift by ~n_spans * 0.5e-6 —
# 1e-4 passes every honest ledger and still catches a lost span window
SUM_TOL = 1e-4
# exemplar window around a breach's wall timestamp: spans admitted during
# the breach close (and emit) shortly AFTER the slo record, sheds shortly
# before — symmetric slack covers both without reaching across the run
EXEMPLAR_WINDOW_S = 30.0
EXEMPLARS_PER_BREACH = 3
_BAR_W = 32

LABELS = {
    "queue": "admission backlog (queue span: submit -> prefill start)",
    "prefill": "prompt processing (bucket pad, page writes, first token)",
    "decode": "token generation (windowed decode ticks, spec rounds)",
    "residue": "unattributed (lost spans / torn ledger)",
}


def _pctl(xs: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of a sorted list (the repo convention —
    tools/ledger_report._pctl; local copy keeps this tool import-light)."""
    if not xs:
        return None
    return xs[min(int(round(q / 100.0 * (len(xs) - 1))), len(xs) - 1)]


def _dur(span: dict) -> float:
    return float(span.get("end") or 0.0) - float(span.get("start") or 0.0)


# -- attribution ------------------------------------------------------------

def attribute_root(root: dict, trace: dict) -> dict:
    """One completed host-attempt view of a request, decomposed: category
    seconds summed from the root's child spans, residue = measured
    latency minus attributed. The ``queue``/``prefill``/``decode`` spans
    tile admit->finish by construction (engine.serve), so residue ~ 0 on
    a healthy ledger and the sum-check is an identity that a LOST span
    breaks — exactly the goodput ``sum_check`` discipline per request."""
    kids = [s for s in trace["spans"]
            if s.get("parent_id") == root["span_id"]]
    cats = {c: 0.0 for c in reqtrace.CATEGORIES}
    for s in kids:
        if s.get("name") in cats:
            cats[s["name"]] += _dur(s)
    latency = _dur(root)
    attributed = sum(cats.values())
    residue = latency - attributed
    tokens = root.get("tokens")
    decode_s = cats["decode"]
    row = {
        "trace_id": trace["trace_id"], "rid": root.get("rid"),
        "job_id": root.get("job_id"), "attempt": root.get("attempt"),
        "host": root.get("host"),
        "latency_s": round(latency, 6),
        "queue_s": round(cats["queue"], 6),
        "prefill_s": round(cats["prefill"], 6),
        "decode_s": round(decode_s, 6),
        "residue_s": round(residue, 6),
        "ttft_s": root.get("ttft_s"),
        "tokens": tokens,
        "tpot_s": (round(decode_s / tokens, 6) if tokens else None),
        # of the decode time: seconds behind OTHER requests' admissions and
        # inside garbage collections (engine.serve's per-request counters;
        # None on a ledger written before them)
        "behind_prefill_s": root.get("behind_prefill_s"),
        "behind_gc_s": root.get("behind_gc_s"),
        "spans": len(kids),
        "sum_check_ok": abs(residue) <= SUM_TOL,
        "ts": root.get("ts"),
    }
    return row


def request_rows(traces: Dict[str, dict]) -> List[dict]:
    """Every completed (root-emitting) host-attempt of every trace, in a
    deterministic order: by rid, then job identity."""
    rows = []
    for tid in sorted(traces):
        tr = traces[tid]
        for root in tr["roots"]:
            rows.append(attribute_root(root, tr))
    rows.sort(key=lambda r: (r["rid"] if r["rid"] is not None else -1,
                             str(r["job_id"]), r["attempt"] or 0))
    return rows


def _tail_point(rows: List[dict], metric: str, parts) -> Dict[str, dict]:
    """p50/p90/p99 of ``metric`` with the nearest-rank request's named
    decomposition attached — the percentile IS a concrete request here,
    so its split is an attribution, not an average that matches nobody."""
    pool = sorted((r for r in rows if r.get(metric) is not None),
                  key=lambda r: (r[metric], str(r["trace_id"])))
    out = {}
    for q in (50, 90, 99):
        r = _pctl(pool, q)
        if r is None:
            out[f"p{q}"] = None
            continue
        out[f"p{q}"] = {metric: r[metric], "rid": r["rid"],
                        "trace_id": r["trace_id"],
                        **{p: r[p] for p in parts}}
    return out


def decode_split(rows: List[dict]) -> Optional[dict]:
    """What held the next token back between a request's first and last:
    its decode seconds split into decode ticks, other requests' admissions
    (``behind_prefill_s``) and garbage collections (``behind_gc_s``), over
    all requests that carry the counters and over those at or above the
    95th percentile of the mean gap between tokens, the ones a gap SLO is
    judged on. None on a ledger without the counters."""
    pool = [r for r in rows if r.get("behind_prefill_s") is not None
            and r.get("behind_gc_s") is not None and (r["tokens"] or 0) > 1]
    if not pool:
        return None
    gap = lambda r: r["decode_s"] / (r["tokens"] - 1)

    def block(sel: List[dict]) -> dict:
        decode = sum(r["decode_s"] for r in sel)
        prefill = sum(r["behind_prefill_s"] for r in sel)
        pauses = sum(r["behind_gc_s"] for r in sel)
        return {"requests": len(sel),
                "mean_gap_s": round(decode / sum(r["tokens"] - 1
                                                 for r in sel), 6),
                "decode_s": round(decode, 6),
                "ticks_s": round(decode - prefill - pauses, 6),
                "behind_prefill_s": round(prefill, 6),
                "behind_gc_s": round(pauses, 6)}

    p95 = _pctl(sorted(gap(r) for r in pool), 95)
    return {"all": block(pool), "p95_mean_gap_s": round(p95, 6),
            "at_or_above_p95": block([r for r in pool if gap(r) >= p95])}


def tail_attribution(rows: List[dict]) -> dict:
    """The headline block: TTFT decomposes into queue+prefill, TPOT into
    decode-per-token; ``shares`` are the fleet-wide category fractions of
    total latency; ``coverage`` the attributed share (1.0 minus residue)
    — ~1.0 by construction on any ledger that didn't lose spans."""
    total = sum(r["latency_s"] for r in rows)
    shares = {}
    for cat in (*reqtrace.CATEGORIES, "residue"):
        secs = sum(r[f"{cat}_s"] for r in rows)
        shares[cat] = {"seconds": round(secs, 6),
                       "share": round(secs / total, 6) if total else None,
                       "label": LABELS[cat]}
    attributed = sum(shares[c]["seconds"] for c in reqtrace.CATEGORIES)
    return {
        "requests": len(rows),
        "ttft": _tail_point(rows, "ttft_s", ("queue_s", "prefill_s")),
        "tpot": _tail_point(rows, "tpot_s", ("decode_s", "tokens")),
        "decode_split": decode_split(rows),
        "shares": shares,
        "coverage": round(attributed / total, 6) if total else None,
        "sum_check": {
            "ok": all(r["sum_check_ok"] for r in rows),
            "requests": len(rows),
            "failed": [r["trace_id"] for r in rows
                       if not r["sum_check_ok"]],
            "max_residue_s": (round(max(abs(r["residue_s"]) for r in rows),
                                    6) if rows else 0.0),
            "tolerance_s": SUM_TOL,
        },
    }


# -- SLO-breach exemplars ---------------------------------------------------

def _candidates(records, traces: Dict[str, dict]) -> List[dict]:
    """Everything a breach can point at: completed request roots (scored
    by their category seconds) and shed spans (a shed IS the overload's
    victim). Wall ``ts`` (emit time) is the clock — the only one
    comparable to the slo record's own stamp."""
    out = []
    for tid in sorted(traces):
        tr = traces[tid]
        for root in tr["roots"]:
            row = attribute_root(root, tr)
            if row["ts"] is not None:
                out.append({"kind": "request", **row})
        for s in tr["spans"]:
            if s.get("name") == "shed" and s.get("ts") is not None:
                out.append({"kind": "shed", "trace_id": tid,
                            "rid": s.get("rid"), "host": s.get("host"),
                            "job_id": s.get("job_id"),
                            "queue_s": round(_dur(s), 6),
                            "latency_s": round(_dur(s), 6),
                            "reason": s.get("reason"), "ts": s["ts"]})
    return out


def slo_exemplars(records, traces: Dict[str, dict]) -> List[dict]:
    """Bind every ``slo`` breach event to its worst-offender traces: the
    top candidates by the breach-relevant score (queue seconds for
    queue_wait breaches, whole latency otherwise) inside the wall-clock
    breach window, same host first. A breach with an empty window falls
    back to the nearest candidate in time — a breach that resolves to NO
    evidence is a report bug, not a tolerable gap (the fleet_ci
    acceptance asserts >= 1 exemplar per breach)."""
    cands = _candidates(records, traces)
    out = []
    for rec in records:
        if rec.get("event") != "slo" or rec.get("ts") is None:
            continue
        kind = rec.get("kind")
        score_key = "queue_s" if kind == "queue_wait" else "latency_s"
        host = rec.get("host")
        same_host = [c for c in cands
                     if host is None or c.get("host") == host]
        pool = same_host or cands
        windowed = [c for c in pool
                    if abs(c["ts"] - rec["ts"]) <= EXEMPLAR_WINDOW_S]
        chosen = sorted(
            windowed,
            key=lambda c: (-(c.get(score_key) or 0.0),
                           str(c["trace_id"])))[:EXEMPLARS_PER_BREACH]
        if not chosen and pool:
            chosen = sorted(
                pool, key=lambda c: (abs(c["ts"] - rec["ts"]),
                                     str(c["trace_id"])))[:1]
        out.append({
            "kind": kind, "host": host, "value": rec.get("value"),
            "floor": rec.get("floor"), "step": rec.get("step"),
            "exemplars": [
                {"trace_id": c["trace_id"], "rid": c["rid"],
                 "kind": c["kind"], "job_id": c.get("job_id"),
                 "score_s": round(c.get(score_key) or 0.0, 6),
                 "dt_s": round(c["ts"] - rec["ts"], 3)}
                for c in chosen],
        })
    return out


# -- waterfalls -------------------------------------------------------------

def waterfall_lines(trace: dict) -> List[str]:
    """One trace as indented bars. Each host-attempt renders against its
    OWN engine clock (per-process axes don't compare); the trace header
    carries the cross-host identity that ties them together."""
    rows = [attribute_root(root, trace) for root in trace["roots"]]
    latency = max((r["latency_s"] for r in rows), default=0.0)
    hosts = ",".join(str(h) for h in trace["hosts"]) or "-"
    lines = [f"trace {trace['trace_id']}  rid={trace['rid']}  "
             f"hosts=[{hosts}]  attempts={len(trace['roots'])}  "
             f"latency={latency:.6g}s"]
    by_parent = reqtrace.children_of(trace)
    orphans = [s for s in trace["spans"]
               if s.get("parent_id") is not None
               and s["parent_id"] not in {r["span_id"]
                                          for r in trace["roots"]}]
    for root in trace["roots"]:
        t0, t1 = float(root["start"]), float(root["end"])
        width = max(t1 - t0, 1e-9)
        lines.append(f"  [{root.get('job_id')} a{root.get('attempt')}] "
                     f"request {t0:.6g} -> {t1:.6g}  ({t1 - t0:.6g}s)")
        for s in by_parent.get(root["span_id"], ()):
            off = int(_BAR_W * (float(s["start"]) - t0) / width)
            n = max(int(_BAR_W * _dur(s) / width), 1)
            off = min(off, _BAR_W - 1)
            n = min(n, _BAR_W - off)
            bar = "." * off + "#" * n + "." * (_BAR_W - off - n)
            extra = ""
            if s.get("name") == "prefill":
                extra = (f"  bucket={s.get('bucket')} "
                         f"shared={s.get('pages_shared')}")
            elif s.get("name") == "decode":
                extra = (f"  ticks={s.get('ticks')} "
                         f"tokens={s.get('tokens')}")
            elif s.get("name") in ("shed", "readmit"):
                extra = f"  reason={s.get('reason')}"
            lines.append(f"    {s.get('name'):<10} |{bar}| "
                         f"{_dur(s):.6g}s{extra}")
    for s in orphans:
        lines.append(f"  [{s.get('job_id')} a{s.get('attempt')}] "
                     f"{s.get('name'):<10} (no root: attempt never "
                     f"completed it)  {_dur(s):.6g}s  "
                     f"reason={s.get('reason')}")
    return lines


def slowest_traces(traces: Dict[str, dict], n: int) -> List[dict]:
    """The n slowest traces by their worst completed attempt, slowest
    first (trace_id tie-break keeps the order reproducible)."""
    scored = []
    for tid in sorted(traces):
        tr = traces[tid]
        if not tr["roots"]:
            continue
        worst = max(_dur(r) for r in tr["roots"])
        scored.append((worst, tid, tr))
    scored.sort(key=lambda x: (-x[0], x[1]))
    return [tr for _w, _tid, tr in scored[:n]]


# -- the report -------------------------------------------------------------

def requests_summary(records) -> dict:
    """The one machine-readable dict (``--json`` prints it verbatim; the
    fleet_ci acceptance asserts into it)."""
    traces = reqtrace.traces(records)
    rows = request_rows(traces)
    sheds = sum(1 for t in traces.values()
                for s in t["spans"] if s.get("name") == "shed")
    readmits = sum(1 for t in traces.values()
                   for s in t["spans"] if s.get("name") == "readmit")
    return {
        "traces": len(traces),
        "completed_requests": len(rows),
        "cross_host_traces": sum(1 for t in traces.values()
                                 if len(t["hosts"]) > 1),
        "sheds": sheds,
        "readmits": readmits,
        "per_request": rows,
        "tail_attribution": tail_attribution(rows) if rows else None,
        "slo_exemplars": slo_exemplars(records, traces),
        "slowest": [t["trace_id"] for t in slowest_traces(traces, 5)],
    }


def render(summary: dict, records, out=print, waterfalls: int = 3) -> None:
    out("== requests (per-request traces: obs.reqtrace) ==")
    out(f"  traces {summary['traces']}  completed "
        f"{summary['completed_requests']}  cross-host "
        f"{summary['cross_host_traces']}  sheds {summary['sheds']}  "
        f"readmits {summary['readmits']}")
    ta = summary.get("tail_attribution")
    if ta:
        sc = ta["sum_check"]
        out(f"  sum-check: {'OK' if sc['ok'] else 'FAILED'} over "
            f"{sc['requests']} requests (max residue "
            f"{sc['max_residue_s']:.6g}s, tol {sc['tolerance_s']:g})")
        out(f"  coverage: {ta['coverage']} of latency attributed")
        out("  where the seconds went:")
        for cat, row in ta["shares"].items():
            share = "-" if row["share"] is None else f"{row['share']:.1%}"
            out(f"    {cat:<8} {row['seconds']:>10.6g}s  {share:>7}  "
                f"{row['label']}")
        for metric, parts in (("ttft", ("queue_s", "prefill_s")),
                              ("tpot", ("decode_s", "tokens"))):
            out(f"  {metric} percentiles (nearest-rank exemplar request):")
            for q in ("p50", "p90", "p99"):
                p = ta[metric][q]
                if p is None:
                    out(f"    {q}: no data")
                    continue
                split = "  ".join(f"{k}={p[k]}" for k in parts)
                out(f"    {q}: {p[metric + '_s']:.6g}s  rid={p['rid']}  "
                    f"{split}")
        split = ta.get("decode_split")
        if split:
            out("  decode time, first token -> finish, by what held the "
                "next token back:")
            for label, b in (
                    ("all requests", split["all"]),
                    (f"mean gap >= p95 ({split['p95_mean_gap_s']:.6g}s)",
                     split["at_or_above_p95"])):
                share = (lambda x: f" ({x / b['decode_s']:.1%})"
                         if b["decode_s"] else "")
                parts = "  ".join(
                    f"{name} {b[key]:.6g}s{share(b[key])}"
                    for name, key in (("ticks", "ticks_s"),
                                      ("behind admissions",
                                       "behind_prefill_s"),
                                      ("in collections", "behind_gc_s")))
                out(f"    {label} ({b['requests']}): mean gap "
                    f"{b['mean_gap_s']:.6g}s  {parts}")
    if summary["slo_exemplars"]:
        out("  slo breaches -> exemplar traces:")
        for b in summary["slo_exemplars"]:
            host = "-" if b["host"] is None else b["host"]
            ex = ", ".join(
                f"rid={e['rid']} {e['kind']} {e['score_s']:.6g}s "
                f"({e['trace_id'][:8]})" for e in b["exemplars"]) or "NONE"
            out(f"    [{b['kind']} host={host} value={b['value']}] {ex}")
    if waterfalls > 0:
        traces = reqtrace.traces(records)
        slow = slowest_traces(traces, waterfalls)
        if slow:
            out(f"  {len(slow)} slowest request waterfalls:")
            for tr in slow:
                for line in waterfall_lines(tr):
                    out("    " + line)


def load_records(path: str, discover: bool = True) -> List[dict]:
    """A ledger file loads as one job (attempt family + sup sibling); a
    directory loads as a fleet (host*/ subtrees, host stamped on every
    record — the cross-host exemplar index needs it)."""
    if os.path.isdir(path):
        from tpu_dist.sim.fleet import FleetLedger

        return FleetLedger.discover(path).merged()
    return load_job_records(path, discover=discover)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-request waterfalls, tail-latency attribution "
                    "and SLO-breach exemplars from span ledger events")
    ap.add_argument("path", help="serving ledger (.jsonl) or fleet dir")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the machine-readable summary")
    ap.add_argument("--waterfalls", type=int, default=3,
                    help="N slowest request waterfalls in human output")
    ap.add_argument("--no-discover", action="store_true",
                    help="read exactly this file, no attempt-family glob")
    args = ap.parse_args(argv)
    records = load_records(args.path, discover=not args.no_discover)
    summary = requests_summary(records)
    if args.as_json:
        print(json.dumps(summary, default=str))
    else:
        render(summary, records, out=print, waterfalls=args.waterfalls)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
