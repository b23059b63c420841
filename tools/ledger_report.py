#!/usr/bin/env python
"""Summarize a tpu_dist run ledger (obs.ledger JSONL) from the CLI.

    python tools/ledger_report.py run.jsonl            # summary
    python tools/ledger_report.py run.jsonl --tail 20  # + last N step lines
    python tools/ledger_report.py run.jsonl --json     # machine-readable

Renders: run identity (kind/mesh/devices/processes), per-phase time share
(data wait vs dispatch vs device block across every step record), the
goodput section (obs.goodput: wall-clock partitioned into goodput and the
badput categories — startup/compile, data wait, dispatch, eval, ckpt,
stalls, health-skipped steps, idle residue, restart gaps — summing to
100% of the stitched wall), the
roofline section (obs.attr cost-model buckets vs measured device/comm
seconds and MFU — where the non-MFU time goes), MFU and throughput trend
(first/middle/last thirds), the epoch table, the decode/serving section
(per-request latency p50/p99 + tok/s over `decode` events),
cross-host skew/straggler
summary, numerical-health trips (obs.health), flight-recorder diagnosis
bundles (obs.flightrec), and any watchdog stall dumps; multi-process runs
get a pointer at the merged Chrome trace (tools/trace_merge.py).
Restart-attempt sibling ledgers (``run.a1.jsonl``, ... — obs.goodput run
lineage) are auto-discovered and stitched into one job timeline, with the
between-attempt gaps charged as ``restart_gap`` badput (``--no-discover``
reads only the given file). ``--json``
prints the same summary as one JSON object (the stable input for
dashboards and the ROADMAP auto-tuner). Corrupt/truncated trailing lines —
crashed runs are exactly the ones inspected here — are skipped with a
warning, never a crash. Pure stdlib + the ledger module — safe to run on
a login host with no jax installed (obs.ledger imports nothing heavy).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_dist.obs.ledger import ProgressSink, phase_totals  # noqa: E402


def _mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def _fmt_mfu(x):
    return f"{x * 100:.1f}%" if x is not None else "n/a"


def _num(v, spec):
    """None-tolerant numeric cell ('?' for a schema-legal null)."""
    return f"{v:{spec}}" if v is not None else "?"


def _thirds(xs):
    """(first, middle, last) third means — the cheap trend view."""
    if not xs:
        return None, None, None
    n = max(len(xs) // 3, 1)
    return _mean(xs[:n]), _mean(xs[len(xs) // 2 - n // 2:
                                   len(xs) // 2 - n // 2 + n]), _mean(xs[-n:])


def _si(x, unit=""):
    """Engineering-format a count (1.23 G, 45.6 M ...)."""
    if x is None:
        return "?"
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(x) >= div:
            return f"{x / div:.2f} {suf}{unit}"
    return f"{x:.0f} {unit}" if unit else f"{x:.0f}"


def roofline(cost_models, hot, mfu_mean=None, out=print):
    """The cost-model-vs-measured section: per-category flop/byte shares
    with ideal (roofline) seconds per optimizer step, against the
    measured per-step device block and comm estimate. ``hot`` is the
    warm-excluded step list summarize() already built (one filtering
    rule, not two). Returns the machine-readable dict (also embedded in
    --json output).

    The cost model counts a scan (window) body ONCE, so its totals read
    as one optimizer step — the same per-step units the measured side is
    divided down to."""
    if not cost_models:
        return None
    cm = cost_models[-1]  # the last compile is the program that trained
    buckets = cm.get("buckets") or {}
    if not buckets:
        return None
    peak_tf = cm.get("peak_tflops") or 0.0
    peak_gb = cm.get("peak_gbps") or 0.0
    tot_f = cm.get("total_flops") or sum(
        b.get("flops") or 0 for b in buckets.values())
    tot_b = cm.get("total_bytes") or sum(
        b.get("bytes") or 0 for b in buckets.values())
    nominal = bool(cm.get("peak_is_nominal"))

    def ideal_s(flops, nbytes):
        t_c = flops / (peak_tf * 1e12) if peak_tf else None
        t_m = nbytes / (peak_gb * 1e9) if peak_gb else None
        if t_c is None and t_m is None:
            return None, "?"
        if (t_c or 0) >= (t_m or 0):
            return t_c, "compute"
        return t_m, "memory"

    n_opt = sum(r.get("steps_in_dispatch") or 1 for r in hot) or 1
    dev_s = sum(r.get("device_s") or 0 for r in hot) / n_opt
    comm_s = sum(r.get("comm_s") or 0 for r in hot) / n_opt
    mfu = mfu_mean

    out(f"\nroofline (cost model vs measured, program "
        f"{cm.get('program')!r}"
        + (", NOMINAL peaks" if nominal else "") + "):")
    out(f"  {'category':<26} {'flops%':>7} {'bytes%':>7} "
        f"{'ideal s/step':>13}  bound")
    rows = {}
    for cat in sorted(buckets, key=lambda c: -(buckets[c].get("flops") or 0)):
        b = buckets[cat]
        f, by = b.get("flops") or 0.0, b.get("bytes") or 0.0
        t, bound = ideal_s(f, by)
        if cat.startswith("collective:"):
            bound = "comm"
        rows[cat] = {"flops": f, "bytes": by, "flops_share":
                     f / tot_f if tot_f else None,
                     "bytes_share": by / tot_b if tot_b else None,
                     "ideal_s": t, "bound": bound}
        out(f"  {cat:<26} {f / tot_f * 100 if tot_f else 0:6.1f}% "
            f"{by / tot_b * 100 if tot_b else 0:6.1f}% "
            + (f"{t:13.3g}" if t is not None else f"{'?':>13}")
            + f"  {bound}")
    ideal_total, _ = ideal_s(tot_f, tot_b)
    coll_b = sum(b.get("bytes") or 0 for c, b in buckets.items()
                 if c.startswith("collective:"))
    out(f"  model total {_si(tot_f, 'FLOP')} + {_si(tot_b, 'B')} per step"
        + (f" -> ideal {ideal_total:.3g} s/step" if ideal_total else ""))
    gap = dev_s / ideal_total if ideal_total and dev_s else None
    if dev_s:
        out(f"  measured: device {dev_s:.3g} s/step"
            + ((f" = {gap:,.0f}x ideal" if gap >= 10 else
                f" = {gap:.2f}x ideal") if gap else "")
            + (f"; MFU {_fmt_mfu(mfu)} (mean)" if mfu is not None else ""))
    if comm_s and coll_b:
        out(f"  comm: measured {comm_s:.3g} s/step vs {_si(coll_b, 'B')} "
            f"collective -> {coll_b / comm_s / 1e9:.2f} GB/s effective")
    return {"program": cm.get("program"), "categories": rows,
            "total_flops": tot_f, "total_bytes": tot_b,
            "collective_bytes": coll_b, "ideal_s_per_step": ideal_total,
            "measured_device_s_per_step": dev_s or None,
            "measured_comm_s_per_step": comm_s or None,
            "gap_vs_ideal": gap, "mfu_mean": mfu,
            "peak_tflops": peak_tf or None, "peak_gbps": peak_gb or None,
            "peak_is_nominal": nominal}


def _pctl(xs, q):
    """Nearest-rank percentile of a sorted list (stdlib-only)."""
    if not xs:
        return None
    return xs[min(int(round(q / 100.0 * (len(xs) - 1))), len(xs) - 1)]


GOODPUT_LABELS = {"startup": "startup/compile", "data_wait": "data wait",
                  "dispatch": "dispatch", "eval": "eval",
                  "ckpt": "checkpoint", "stall": "watchdog stall",
                  "skipped": "health-skipped", "idle": "idle/drain",
                  "restart_gap": "restart gap"}


def goodput_section(records, out=print):
    """The accounting section (obs.goodput): goodput + badput categories
    over the (possibly multi-attempt) stitched wall-clock. Returns the
    machine-readable dict (rides in --json)."""
    from tpu_dist.obs.goodput import job_accounting, split_attempts

    attempts = split_attempts(records)
    gp = job_accounting(attempts)
    if gp is None or not gp["wall_s"]:
        return gp
    slo_events = [r for r in records if r["event"] == "slo"]
    gp["slo_breaches"] = len(slo_events)
    n_att = len(gp["attempts"])
    wall = gp["wall_s"]
    out(f"\ngoodput ({n_att} attempt(s), stitched wall {wall:.1f}s):")
    rows = [("goodput", gp["goodput_s"])] + [
        (cat, gp["categories"].get(cat, 0.0))
        for cat in GOODPUT_LABELS]
    for cat, secs in rows:
        if cat != "goodput" and not secs:
            continue  # only non-zero badput rows earn a line
        out(f"  {GOODPUT_LABELS.get(cat, cat):<16} {secs:9.3f}s  "
            f"{secs / wall * 100:5.1f}%")
    out(f"  goodput ratio {gp['ratio']:.3f} over {gp['opt_steps']} "
        f"optimizer steps"
        + (f"; OVERRUN {gp['overrun_s']:.3f}s double-attributed"
           if gp["overrun_s"] else ""))
    if n_att > 1:
        for a in gp["attempts"]:
            out(f"  attempt {a['attempt']}: {a['wall_s']:.1f}s wall, "
                f"{a['goodput_s']:.1f}s goodput, status "
                f"{a['status'] or 'MISSING run_end (killed?)'}"
                + (f", restart gap {a['restart_gap_s']:.1f}s before it"
                   if a["restart_gap_s"] else ""))
    if slo_events:
        last = slo_events[-1]
        out(f"  SLO: {len(slo_events)} breach(es); last: "
            f"{last.get('kind')} {last.get('value')} < floor "
            f"{last.get('floor')} at step {last.get('step')}")
    return gp


def restarts_section(records, out=print, crash_loop_k=3):
    """The remediation view of a stitched multi-attempt job: per-attempt
    failure classification (parallel.supervisor.classify_attempt over
    ``run_end`` status + ``fault``/``stall`` evidence), injected-vs-organic
    fault counts, and a crash-loop banner when the trailing
    ``crash_loop_k`` attempts all died before their first step. Rendered
    only when there is something to say (restarts or injections)."""
    from tpu_dist.obs.goodput import split_attempts
    from tpu_dist.parallel.supervisor import classify_attempt

    fault_events = [r for r in records if r["event"] == "fault"]
    attempts = split_attempts(records)
    if len(attempts) <= 1 and not fault_events:
        return None
    rows = []
    for recs in attempts:
        starts = [r for r in recs if r.get("event") == "run_start"]
        ordinal = (starts[0].get("attempt")
                   if starts and starts[0].get("attempt") is not None
                   else len(rows))
        rows.append({
            "attempt": ordinal,
            "class": classify_attempt(recs),
            "steps": sum(1 for r in recs if r.get("event") == "step"),
            "degraded": bool(starts and starts[0].get("degraded")),
            "processes": starts[0].get("process_count") if starts else None,
            "injected": [str(r.get("site") or "?") for r in recs
                         if r.get("event") == "fault"]})
    organic = sum(1 for r in rows
                  if r["class"] != "clean" and not r["injected"])
    out(f"\nrestarts ({len(rows)} attempt(s), "
        f"{len(rows) - 1} restart(s)):")
    for r in rows:
        out(f"  attempt {r['attempt']}: {r['class']}, "
            f"{r['steps']} step record(s)"
            + (f" [degraded mesh, {r['processes']} proc]" if r["degraded"]
               else "")
            + (f"; injected fault(s): {', '.join(r['injected'])}"
               if r["injected"] else ""))
    trailing_dead = 0
    for r in reversed(rows):
        if r["steps"] or r["class"] == "clean":
            break
        trailing_dead += 1
    crash_loop = trailing_dead >= crash_loop_k
    if crash_loop:
        out(f"  CRASH LOOP: the last {trailing_dead} attempts died before "
            "their first step — the failure is deterministic; fix the run "
            "instead of restarting it")
    out(f"  faults: {len(fault_events)} injected (obs.faults), "
        f"{organic} organic failure(s)")
    return {"attempts": rows, "injected_faults": len(fault_events),
            "organic_failures": organic, "crash_loop": crash_loop}


_SCALE_LABELS = {"shrink": "mesh shrink", "expand": "mesh re-expansion",
                 "preempt_snapshot": "preemption snapshot",
                 "peer_restore": "peer state restore",
                 "drain": "serve drain"}


def elasticity_section(records, out=print):
    """The elastic-capacity timeline (round 13): ``scale`` events — the
    supervisor consensus' shrink/re-expansion decisions (stitched in from
    the ``<stem>.sup.jsonl`` sibling), the engines' coordinated preemption
    snapshots and peer state restores, and serving drains — rendered in
    wall order so a shrink -> degraded attempts -> re-expansion cycle
    reads as one story beside the goodput/restarts sections."""
    scales = sorted((r for r in records if r["event"] == "scale"),
                    key=lambda r: r.get("ts") or 0.0)
    if not scales:
        return None
    # wall anchor: the earliest timestamp anywhere (the supervisor's
    # sibling records are APPENDED to the stream, not ts-interleaved —
    # interleaving would split pseudo-attempts into the goodput math)
    t0 = min((r.get("ts") for r in records if r.get("ts") is not None),
             default=0)
    out(f"\nelasticity ({len(scales)} scale event(s)):")
    rows = []
    for r in scales:
        dt = (r.get("ts") or t0) - t0
        action = str(r.get("action") or "?")
        extras = []
        if r.get("world_from") is not None:
            extras.append(f"{r['world_from']} -> {r.get('processes')} "
                          "process(es)")
        elif r.get("processes") is not None:
            extras.append(f"{r['processes']} process(es)")
        if r.get("hosts") is not None:
            extras.append(f"hosts {r['hosts']}")
        if r.get("step") is not None:
            extras.append(f"step {r['step']}")
        if r.get("shed") is not None:
            extras.append(f"{r['shed']} request(s) shed")
        out(f"  +{dt:8.1f}s  {_SCALE_LABELS.get(action, action):<22}"
            + (f" epoch {r['epoch']}" if r.get("epoch") is not None else "")
            + ("  (" + ", ".join(extras) + ")" if extras else ""))
        rows.append({k: r.get(k) for k in
                     ("action", "processes", "epoch", "hosts", "step",
                      "world_from", "shed", "ts")})
    return rows


def decisions_section(records, out=print):
    """The autoscaling audit (round 20, obs.autoscale): every
    ``scale_decision`` the capacity monitor emitted (a fleet ledger read
    directly, or any stream carrying them) and every ``applied``
    follow-up the supervisor stamped after re-tuning at the new world
    size — rendered in wall order so decision -> rescale -> new plan hash
    reads as one story. ``None`` when the stream has neither."""
    rows = sorted((r for r in records
                   if r["event"] in ("scale_decision", "applied")),
                  key=lambda r: r.get("ts") or 0.0)
    if not rows:
        return None
    t0 = min((r.get("ts") for r in records if r.get("ts") is not None),
             default=0)
    n_dec = sum(1 for r in rows if r["event"] == "scale_decision")
    out(f"\nautoscale decisions ({n_dec} decision(s), "
        f"{len(rows) - n_dec} applied):")
    summary = []
    for r in rows:
        dt = (r.get("ts") or t0) - t0
        if r["event"] == "scale_decision":
            out(f"  +{dt:8.1f}s  {r.get('decision')}: {r.get('direction')} "
                f"{r.get('hosts_from')} -> {r.get('target_hosts')} host(s) "
                f"— {r.get('signal')}={r.get('value')} vs "
                f"{r.get('threshold')} over {r.get('window_ticks')} tick(s)"
                + (f", bundle {r['bundle']}" if r.get("bundle") else ""))
            summary.append({k: r.get(k) for k in
                            ("decision", "direction", "hosts_from",
                             "target_hosts", "signal", "value", "threshold",
                             "window_ticks", "bundle", "ts")})
        else:
            out(f"  +{dt:8.1f}s  {r.get('decision') or '(organic)'} "
                f"applied: {r.get('action')} -> {r.get('processes')} "
                f"process(es) epoch {r.get('epoch')}, plan hash "
                f"{r.get('plan_hash')}")
            summary.append({k: r.get(k) for k in
                            ("decision", "action", "processes", "epoch",
                             "plan_hash", "ts")})
    return summary


def decode_section(records, out=print):
    """The serving-SLO section: per-request latency percentiles and tok/s
    over the `decode` events (engine.generate), plus
    the continuous-batching view over `request`/`admit`/`kv_cache` events
    (engine.serve): queue-wait and TTFT percentiles, admission rejections,
    and batch occupancy from the pool-pressure snapshots."""
    decodes = [r for r in records if r["event"] == "decode"]
    requests = [r for r in records if r["event"] == "request"]
    admits = [r for r in records if r["event"] == "admit"]
    kv = [r for r in records if r["event"] == "kv_cache"]
    if not decodes and not requests and not admits:
        return None
    d = {}
    if decodes:
        secs = sorted(r["seconds"] for r in decodes
                      if r.get("seconds") is not None)
        toks = sum(r.get("tokens") or 0 for r in decodes)
        total_s = sum(secs)
        p50, p99 = _pctl(secs, 50), _pctl(secs, 99)
        d = {"requests": len(decodes), "tokens": toks,
             "tokens_per_sec": round(toks / total_s, 1) if total_s else None,
             "latency_s": {"p50": p50, "p99": p99}}
        out(f"\ndecode: {d['requests']} request(s), {_si(toks, 'tok')}"
            + (f", {d['tokens_per_sec']:,.0f} tok/s" if total_s else "")
            + (f"; latency p50 {p50 * 1e3:.1f}ms / p99 {p99 * 1e3:.1f}ms"
               if p50 is not None else ""))
    if requests or admits:
        waits = sorted(r["queue_wait_s"] for r in requests
                       if r.get("queue_wait_s") is not None)
        ttfts = sorted(r["ttft_s"] for r in requests
                       if r.get("ttft_s") is not None)
        toks = sum(r.get("tokens") or 0 for r in requests)
        rejected = sum(1 for r in admits if not r.get("accepted"))
        srv = {"completed": len(requests), "tokens": toks,
               "rejected": rejected,
               "queue_wait_s": {"p50": _pctl(waits, 50),
                                "p99": _pctl(waits, 99)},
               "ttft_s": {"p50": _pctl(ttfts, 50), "p99": _pctl(ttfts, 99)}}
        if kv:
            # occupancy from the pool snapshots: active slots over capacity
            occ = [r["active_seqs"] / r["slots"] for r in kv
                   if r.get("active_seqs") is not None and r.get("slots")]
            srv["occupancy"] = round(_mean(occ), 4) if occ else None
            last = kv[-1]
            srv["pages_free_last"] = last.get("pages_free")
            srv["high_water_used"] = last.get("high_water_used")
            # round 16: speculative-acceptance and prefix-hit TRENDS over
            # the periodic snapshots (counters are cumulative, so per-
            # window rates come from consecutive deltas: first -> last)
            srv["spec_acceptance"] = _counter_trend(
                kv, "spec_emitted", "spec_slot_ticks")
            srv["prefix_hits_last"] = last.get("prefix_hits")
            srv["cow_copies_last"] = last.get("cow_copies")
            srv["shared_pages_last"] = last.get("shared_pages")
            # round 19: the long-context serving plane — chunk-prefill
            # occupancy (share of scheduler steps that ran a prefill
            # chunk, from the cumulative chunk_ticks/tick counters) and
            # the chunk-queue depth gauge (pending chunks across parked
            # slots: max = worst backlog, last = drained or not)
            co = _counter_trend(kv, "chunk_ticks", "tick")
            # tick always advances, so the trend is 0.0 (not None) on a
            # run that never chunked — treat that as absent
            srv["chunk_occupancy"] = co if co and co["overall"] else None
            depths = [r["chunks_pending"] for r in kv
                      if r.get("chunks_pending") is not None]
            srv["chunks_pending_max"] = max(depths) if depths else None
            srv["chunks_pending_last"] = depths[-1] if depths else None
            srv["sharded_devices"] = last.get("sharded_devices")
            # per-slot recurrent state beside the pages (models whose
            # cache_layout has slot-state layers): bytes allocated, and
            # prefills that wrote a slot's state
            srv["state_bytes"] = last.get("state_bytes")
            srv["state_bytes_per_slot"] = last.get("state_bytes_per_slot")
            srv["state_writes_last"] = last.get("state_writes")
            # a model with routed expert layers: assignments that landed on
            # the experts held here, and held experts hit a layer and tick
            srv["expert_rows_last"] = last.get("expert_rows")
            srv["experts_hit_mean"] = last.get("experts_hit_mean")
            # what a sequence costs in cache: K and V bytes a token over the
            # layers that keep pages, and what the window rings hold (all
            # slots; 0 for a model without a window layer)
            srv["kv_bytes_per_token"] = last.get("kv_bytes_per_token")
            srv["window_bytes"] = last.get("window_bytes")
            # what stood between a decoding request and its next tick: the
            # admissions' own time (prefills apart from the tick they
            # queued behind) and the process's garbage collections
            srv["prefill_own_s"] = last.get("prefill_own_s")
            srv["gc_pause_s"] = last.get("gc_pause_s")
            # prefills whose program was called while the prefill before
            # them, of the same step, was still unread
            srv["prefills_ahead_last"] = last.get("prefills_ahead")
            # the decode tick one ahead of the host: ticks dispatched
            # while the tick before them was unread, and tokens computed
            # for a slot that had already ended on eos_id (dropped)
            srv["ticks_last"] = last.get("tick")
            srv["ticks_ahead_last"] = last.get("ticks_ahead")
            srv["overrun_tokens_last"] = last.get("overrun_tokens")
        d["serving"] = srv
        out(f"\nserving: {srv['completed']} completed, {rejected} rejected"
            + (f", occupancy {srv['occupancy'] * 100:.0f}%"
               if srv.get("occupancy") is not None else "")
            + (f"; queue wait p50 {srv['queue_wait_s']['p50'] * 1e3:.1f}ms"
               f" / p99 {srv['queue_wait_s']['p99'] * 1e3:.1f}ms"
               if waits else "")
            + (f"; TTFT p50 {srv['ttft_s']['p50'] * 1e3:.1f}ms"
               f" / p99 {srv['ttft_s']['p99'] * 1e3:.1f}ms"
               if ttfts else ""))
        sa = srv.get("spec_acceptance")
        if sa is not None:
            out("  speculative acceptance: "
                + f"{sa['overall']:.2f} tokens/slot-tick overall"
                + (f" (first window {sa['first']:.2f} -> last "
                   f"{sa['last']:.2f})"
                   if sa.get("first") is not None else ""))
        if srv.get("prefix_hits_last"):
            out(f"  prefix cache: {srv['prefix_hits_last']} page hits, "
                f"{srv['cow_copies_last'] or 0} CoW forks, "
                f"{srv['shared_pages_last'] or 0} pages shared at last "
                "snapshot")
        co = srv.get("chunk_occupancy")
        if co is not None:
            out("  chunked prefill: "
                + f"{co['overall'] * 100:.0f}% of steps ran a chunk"
                + (f" (first window {co['first'] * 100:.0f}% -> last "
                   f"{co['last'] * 100:.0f}%)"
                   if co.get("first") is not None else "")
                + (f"; queue depth max {srv['chunks_pending_max']}, "
                   f"last {srv['chunks_pending_last']}"
                   if srv.get("chunks_pending_max") is not None else ""))
        if srv.get("state_bytes"):
            out(f"  slot state: {_si(srv['state_bytes'], 'B')} allocated"
                + (f" ({_si(srv['state_bytes_per_slot'], 'B')} a slot)"
                   if srv.get("state_bytes_per_slot") else "")
                + f", {srv['state_writes_last'] or 0} prefills wrote a "
                "slot's state")
        if srv.get("experts_hit_mean") is not None:
            out(f"  experts: {srv['expert_rows_last']} assignments landed on "
                f"held experts; {srv['experts_hit_mean']:.1f} held experts "
                "hit a routed layer and tick")
        if srv.get("kv_bytes_per_token"):
            out(f"  KV cache: {_si(srv['kv_bytes_per_token'], 'B')} a token "
                "in pages"
                + (f", {_si(srv['window_bytes'], 'B')} of window rings"
                   if srv.get("window_bytes") else "")
                + (f"; admissions held the decoding slots "
                   f"{srv['prefill_own_s']:.3f}s"
                   + (f" ({srv['prefills_ahead_last']} prefills issued "
                      "ahead of the last one's read)"
                      if srv.get("prefills_ahead_last") is not None else "")
                   + f", garbage collections {srv['gc_pause_s']:.3f}s"
                   if srv.get("prefill_own_s") is not None else ""))
        if srv.get("ticks_ahead_last"):
            out(f"  decode tick: {srv['ticks_ahead_last']} of "
                f"{srv['ticks_last']} ticks dispatched ahead of the host's "
                f"read, {srv['overrun_tokens_last'] or 0} overrun tokens "
                "dropped")
        if (srv.get("sharded_devices") or 0) > 1:
            out(f"  sp-sharded KV pool: {srv['sharded_devices']} devices")
    return d


def _counter_trend(kv, num_key, den_key):
    """Overall + first/last per-window rate of two CUMULATIVE counters
    across the periodic ``kv_cache`` snapshots (None when the counters
    never moved — plain non-speculative serving)."""
    pts = [(r.get(num_key), r.get(den_key)) for r in kv
           if r.get(num_key) is not None and r.get(den_key) is not None]
    if not pts or not pts[-1][1]:
        return None
    trend = {"overall": round(pts[-1][0] / pts[-1][1], 4),
             "first": None, "last": None}
    deltas = []
    prev = (0, 0)
    for num, den in pts:
        dn, dd = num - prev[0], den - prev[1]
        if dd > 0:
            deltas.append(dn / dd)
        prev = (num, den)
    if deltas:
        trend["first"] = round(deltas[0], 4)
        trend["last"] = round(deltas[-1], 4)
    return trend


def audit_section(records, out=print):
    """Program-audit rollup (``audit`` events — analysis.proglint via
    plan.compile): per-program unwaivered/waived finding counts and the
    check ids involved. None when the run predates the audit knob or
    ran with audit=none."""
    audits = [r for r in records if r["event"] == "audit"]
    if not audits:
        return None
    progs = {}
    for r in audits:
        p = progs.setdefault(r.get("program") or "?",
                             {"events": 0, "findings": 0, "waived": 0,
                              "checks": []})
        p["events"] += 1
        p["findings"] += r.get("findings") or 0
        p["waived"] += r.get("waived") or 0
        for d in (r.get("detail") or ()):
            c = d.get("check")
            if c and c not in p["checks"]:
                p["checks"].append(c)
    for p in progs.values():
        p["checks"].sort()
    total = sum(p["findings"] for p in progs.values())
    waived = sum(p["waived"] for p in progs.values())
    mode = audits[-1].get("mode") or "record"
    out(f"\naudit ({mode}): {len(progs)} program(s), {total} unwaivered "
        f"finding(s), {waived} waived")
    for name in sorted(progs):
        p = progs[name]
        if p["findings"] or p["waived"]:
            out(f"  {name}: {p['findings']} finding(s)"
                + (f" + {p['waived']} waived" if p["waived"] else "")
                + (f" [{', '.join(p['checks'])}]" if p["checks"] else ""))
    return {"mode": mode, "programs": {n: progs[n] for n in sorted(progs)},
            "findings": total, "waived": waived}


def requests_section(records, out=print):
    """Per-request tracing rollup (obs.reqtrace ``span`` events): the
    waterfall summary, the tail-latency attribution table with its
    per-request sum-check, and the SLO-breach exemplar pointers — all
    delegated to tools/request_report (the span model's reading side) so
    this CLI and that one render the same math. None when the ledger
    predates spans (pre-PR-17 history stays renderable)."""
    if not any(r.get("event") == "span" for r in records):
        return None
    from tools.request_report import render as render_requests
    from tools.request_report import requests_summary

    summary = requests_summary(records)
    out("")
    render_requests(summary, records, out=out, waterfalls=1)
    return summary


def summarize(records, out=print):
    """Render the summary through ``out`` and return the machine-readable
    dict (--json prints it verbatim; the legacy count keys ride along)."""
    runs = [r for r in records if r["event"] == "run_start"]
    steps = [r for r in records if r["event"] == "step"]
    epochs = [r for r in records if r["event"] == "epoch"]
    evals = [r for r in records if r["event"] == "eval"]
    skews = [r for r in records if r["event"] == "skew"
             and r.get("spread_s") is not None]
    stalls = [r for r in records if r["event"] == "stall"]
    healths = [r for r in records if r["event"] == "health"]
    diags = [r for r in records if r["event"] == "diagnosis"]
    cost_models = [r for r in records if r["event"] == "cost_model"]
    ends = [r for r in records if r["event"] == "run_end"]
    summary = {"steps": len(steps), "epochs": len(epochs),
               "skews": len(skews), "stalls": len(stalls),
               "health": len(healths), "diagnosis": len(diags)}

    for r in runs:
        out(f"run: kind={r['kind']} devices={r.get('devices')} "
            f"mesh={r.get('mesh')} processes={r.get('process_count')}"
            + (" (MFU vs NOMINAL peak)" if r.get("peak_is_nominal") else ""))
        summary["run"] = {k: r.get(k) for k in
                          ("kind", "devices", "mesh", "process_count",
                           "peak_tflops", "peak_is_nominal", "jax_version",
                           "plan_hash", "plan_source", "plan_knobs")}
        # what the engine's constructor cost before this record (the image
        # Trainer's train.build span and its parts) and how many backend
        # compilations it made: a handful, or an eager init is back
        if r.get("build_s"):
            parts = dict(r["build_s"])
            total = parts.pop("total", None)
            out("build: "
                + (f"{total:.1f}s" if total is not None else "?s") + " ("
                + ", ".join(f"{k} {v:.1f}s" for k, v in parts.items())
                + f"), {r.get('build_compiles')} backend compilations")
            summary["run"].update(build_s=r["build_s"],
                                  build_compiles=r.get("build_compiles"))
    # resolved step plan (tpu_dist.plan): which tuned/loaded plan drove the
    # step compilation — the tuner's measured-refinement loop reads this
    # back (tools/tune.py --ledger-summary keys trials on run.plan_hash)
    plans = [r for r in records if r["event"] == "plan"]
    for r in plans[-1:]:
        out(f"plan: {r.get('plan_hash')} from {r.get('source')}"
            + (f" (device {r['device_kind']})" if r.get("device_kind")
               else "")
            + (f"\n  knobs: {r.get('knobs')}" if r.get("knobs") else ""))
        summary["plan"] = {k: r.get(k) for k in
                           ("source", "plan_hash", "knobs", "device_kind")}
    # auto-tuner invocations appended to this ledger (tools/tune.py)
    tunes = [r for r in records if r["event"] == "tune"]
    if tunes:
        for r in tunes:
            out(f"tune: {r.get('device_kind')}: best {r.get('best_hash')} "
                f"over {r.get('candidates')} candidate(s)"
                + (" [measured]" if r.get("measured") else " [analytic]"))
        summary["tune"] = [{k: r.get(k) for k in
                            ("device_kind", "candidates", "best_hash",
                             "best_step_s", "measured")} for r in tunes]
    if ends:
        secs = ends[-1]["seconds"]
        status = ends[-1].get("status") or "ok"
        summary["run_end"] = {"status": status, "steps": ends[-1]["steps"],
                              "seconds": secs}
        out(f"{'CRASHED' if status == 'crashed' else 'PREEMPTED (snapshotted)' if status == 'preempted' else 'completed'}: "
            f"{ends[-1]['steps']} steps in "
            + (f"{secs:.1f}s" if secs is not None else "?s")
            + "".join(f" {k}={v}" for k, v in ends[-1].items()
                      if k not in ("event", "ts", "pid", "steps", "seconds",
                                   "error", "metrics"))
            + (f"\n  error: {ends[-1]['error'].strip().splitlines()[-1]}"
               if ends[-1].get("error") else ""))
    elif records:
        out("NO run_end record: the writer died mid-run (crash/SIGKILL) — "
            "the events below are everything that reached disk")

    # wall-clock accounting (obs.goodput) — attempts stitched, gaps charged
    summary["goodput"] = goodput_section(records, out=out)
    # remediation view (parallel.supervisor lineage): per-attempt failure
    # classes, injected-vs-organic faults, crash-loop banner
    summary["restarts"] = restarts_section(records, out=out)
    # elastic-capacity timeline (round 13): shrink -> degraded attempts ->
    # re-expansion, preemption snapshots, peer restores, serve drains
    summary["elasticity"] = elasticity_section(records, out=out)
    # autoscaling audit (round 20): scale_decision + applied follow-ups
    summary["autoscale"] = decisions_section(records, out=out)

    if steps:
        # warm records carry the XLA compile in dispatch_s; exclude them
        # from shares/trends (the loops' own warm-excluded tok/s
        # convention) — the compile cost lives in the 'compile' event
        warm_n = sum(1 for r in steps if r.get("warm"))
        hot = [r for r in steps if not r.get("warm")] or steps
        tot = phase_totals(hot)
        # comm_s OVERLAPS device_s (obs.ledger schema note): it reports
        # beside the share table, never inside its denominator
        total = tot["data_s"] + tot["dispatch_s"] + tot["device_s"] or 1.0
        summary["phase_totals"] = tot
        out(f"\nsteps: {sum(r.get('steps_in_dispatch') or 1 for r in steps)} "
            f"optimizer steps in {len(steps)} records"
            + (f" ({warm_n} warm/compile record(s) excluded from shares)"
               if warm_n and hot is not steps else ""))
        out("phase time share (host-measured):")
        for k, label in (("data_s", "data wait"), ("dispatch_s", "dispatch"),
                         ("device_s", "device block")):
            out(f"  {label:<13} {tot[k]:9.3f}s  {tot[k] / total * 100:5.1f}%")
        if tot.get("comm_s"):
            dev = tot["device_s"] or 1e-9
            out(f"  comm          {tot['comm_s']:9.3f}s  "
                f"{tot['comm_s'] / dev * 100:5.1f}% of the device block "
                "(unoverlapped-cost estimate; overlap shows as device_s "
                "growing LESS than comm_s when buckets/rings land)")
        tp = [r["throughput"] for r in hot if r["throughput"] is not None]
        mfu = [r["mfu"] for r in hot if r["mfu"] is not None]
        summary["roofline"] = roofline(cost_models, hot,
                                       mfu_mean=_mean(mfu), out=out)
        a, b, c = _thirds(tp)
        if a is not None:
            out(f"throughput ({hot[0]['unit']}): first/mid/last thirds "
                f"{a:,.0f} / {b:,.0f} / {c:,.0f}")
            summary["throughput"] = {"unit": hot[0]["unit"], "thirds":
                                     [a, b, c], "mean": _mean(tp)}
        a, b, c = _thirds(mfu)
        if a is not None:
            out(f"MFU trend: {_fmt_mfu(a)} -> {_fmt_mfu(b)} -> {_fmt_mfu(c)}"
                f"  (mean {_fmt_mfu(_mean(mfu))})")
            summary["mfu"] = {"thirds": [a, b, c], "mean": _mean(mfu)}
        ds = [r["data_s"] for r in hot if r.get("data_s") is not None]
        a, b, c = _thirds(ds)
        if a is not None:
            out(f"data wait trend: {a:.4f}s -> {b:.4f}s -> {c:.4f}s per "
                f"record  (mean {_mean(ds):.4f}s; ~0 means the prefetcher "
                "hid the host->device copies)")
            summary["data_s"] = {"thirds": [a, b, c], "mean": _mean(ds)}
        # fused-kernel attribution: records carrying the boolean `fused`
        # extra (engines + bench since round 9) split on it, so an MFU
        # delta is attributable to the fused int8 Pallas kernel from the
        # ledger alone — no side-channel config needed
        flagged = [r for r in hot if r.get("fused") is not None]
        if flagged:
            groups = {}
            for r in flagged:
                groups.setdefault(bool(r["fused"]), []).append(r)
            split = {}
            for flag, rs in sorted(groups.items()):
                split["fused" if flag else "unfused"] = {
                    "records": len(rs),
                    "throughput_mean": _mean(
                        r["throughput"] for r in rs
                        if r.get("throughput") is not None),
                    "mfu_mean": _mean(r["mfu"] for r in rs
                                      if r.get("mfu") is not None)}
            summary["fused_split"] = split
            if len(split) == 2:
                mf, mu = (split["fused"]["mfu_mean"],
                          split["unfused"]["mfu_mean"])
                out("fused int8 kernel: "
                    f"{split['fused']['records']} fused record(s) at MFU "
                    f"{_fmt_mfu(mf)} vs {split['unfused']['records']} "
                    f"unfused at {_fmt_mfu(mu)}"
                    + (f" -> delta {_fmt_mfu(mf - mu)}"
                       if mf is not None and mu is not None else ""))
            else:
                only = next(iter(split))
                s = split[only]
                out(f"fused int8 kernel: all {s['records']} flagged "
                    f"record(s) {only}"
                    + (f" (MFU mean {_fmt_mfu(s['mfu_mean'])})"
                       if s["mfu_mean"] is not None else ""))

    if epochs:
        out("\nepochs:")
        summary["epoch_table"] = []
        for r in epochs:
            # schema-legal None values render as '?' (presence, not
            # non-nullness, is what the schema pins)
            out(f"  [{r['epoch']}] loss=" + _num(r["loss"], ".4f")
                + f" {_num(r['throughput'], ',.0f')} {r['unit']} "
                f"({_num(r['seconds'], '.1f')}s)"
                + (f" ppl={r['ppl']:.2f}" if r.get("ppl") else "")
                + (f" acc1={r['acc1'] * 100:.2f}%" if r.get("acc1") is not None
                   else ""))
            summary["epoch_table"].append(
                {k: r.get(k) for k in ("epoch", "loss", "throughput", "unit",
                                       "seconds", "ppl", "acc1")})
    if evals:
        last = evals[-1]
        out("last eval: loss=" + _num(last["loss"], ".4f")
            + (f" ppl={last['ppl']:.2f}" if last.get("ppl") else "")
            + (f" acc1={last['acc1'] * 100:.2f}%"
               if last.get("acc1") is not None else ""))
        summary["last_eval"] = {k: last.get(k)
                                for k in ("epoch", "loss", "ppl", "acc1")}

    # serving-SLO view over decode events (engine.generate)
    summary["decode"] = decode_section(records, out=out)
    summary["requests"] = requests_section(records, out=out)
    # program-audit verdicts (analysis.proglint): which step/serve
    # programs were audited and what survived the waiver file
    summary["audit"] = audit_section(records, out=out)

    if skews:
        worst = max(skews, key=lambda r: r["spread_s"])
        hist = {}
        for r in skews:
            hist[r["straggler"]] = hist.get(r["straggler"], 0) + 1
        out(f"\nskew: {len(skews)} samples; worst spread "
            f"{worst['spread_s'] * 1e3:.1f}ms at step {worst['step']} "
            f"(straggler process {worst['straggler']}); "
            f"p50 {worst['p50_s'] * 1e3:.1f}ms p99 {worst['p99_s'] * 1e3:.1f}ms")
        out(f"straggler histogram (process: samples): {hist}")
        summary["skew"] = {"worst_spread_s": worst["spread_s"],
                           "straggler_histogram":
                           {str(k): v for k, v in hist.items()}}

    if healths:
        kinds = {}
        for r in healths:
            kinds[r.get("kind")] = kinds.get(r.get("kind"), 0) + 1
        out(f"\nHEALTH TRIPS: {len(healths)} "
            f"({', '.join(f'{k}: {n}' for k, n in sorted(kinds.items()))}; "
            f"policy {healths[-1].get('policy')})")
        for r in healths[-5:]:
            out(f"  step {r.get('step')}: {r.get('kind')} "
                f"value={r.get('value')} loss={r.get('loss')} "
                f"-> {r.get('action')}")
        summary["health_kinds"] = kinds

    if diags:
        out(f"\nDIAGNOSIS BUNDLES: {len(diags)} (obs.flightrec)")
        summary["diagnosis_bundles"] = []
        for r in diags:
            out(f"  [{r.get('reason')}] step {r.get('step')} -> "
                f"{r.get('bundle')} (trace: {r.get('trace')})"
                + (f" — {r['note']}" if r.get("note") else ""))
            summary["diagnosis_bundles"].append(
                {k: r.get(k) for k in ("reason", "step", "bundle", "trace",
                                       "note")})

    if stalls:
        out(f"\nWATCHDOG STALLS: {len(stalls)}")
        for r in stalls:
            out(f"  idle {_num(r['idle_s'], '.1f')}s (threshold "
                f"{_num(r['threshold_s'], '.1f')}s) — first stack lines:")
            for line in (r.get("stacks") or "").splitlines()[:6]:
                out(f"    {line}")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="ledger JSONL (obs.ledger)")
    ap.add_argument("--tail", type=int, default=0,
                    help="also render the last N step records as lines")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object on stdout "
                    "(human render suppressed)")
    ap.add_argument("--no-discover", action="store_true",
                    help="read only the given file (no .aN restart-attempt "
                    "sibling stitching)")
    args = ap.parse_args(argv)
    # restart lineage (obs.goodput): stitch every attempt of the job —
    # plus the supervisor's .sup.jsonl scale-event sibling, APPENDED,
    # never ts-interleaved — so the goodput section sees crash->restart
    # gaps. load_job_records is THE job-loading rule (the fleet stitcher
    # tpu_dist.sim.fleet runs it once per host); torn trailing lines and
    # unreadable files warn instead of raising, because a crashed run is
    # exactly the one being inspected.
    from tpu_dist.obs.goodput import discover_attempt_paths, load_job_records

    if not args.no_discover and not args.json:
        paths = discover_attempt_paths(args.path) or [args.path]
        if len(paths) > 1:
            print(f"stitching {len(paths)} attempt ledgers: "
                  f"{[os.path.basename(p) for p in paths]}")
    records = load_job_records(args.path, discover=not args.no_discover)
    if not records:
        print(f"{args.path}: empty ledger", file=sys.stderr)
        return 1
    if args.json:
        summary = summarize(records, out=lambda s: None)
        print(json.dumps(summary, default=str))
        return 0
    summarize(records)
    if args.tail:
        print(f"\nlast {args.tail} step records:")
        sink = ProgressSink()
        for r in [r for r in records if r["event"] == "step"][-args.tail:]:
            sink(r)
    import glob

    root, ext = os.path.splitext(args.path)
    if glob.glob(f"{glob.escape(root)}.p*{ext}"):
        print(f"\nper-process sibling ledgers found — merge the lanes into "
              f"one Chrome trace with: python tools/trace_merge.py "
              f"{args.path}")
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except BrokenPipeError:
        # `ledger_report run.jsonl | head` closing the pipe is normal use
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        raise SystemExit(0)
