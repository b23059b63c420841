#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip:

    python3 benchmarks/sweep.py --workload <cell> --rates 2,4,6,8,10 --seconds 20 --seed 1

One process builds and warms the engine, then offers the cell's traffic at
each rate of the ladder in turn (draining between rates). The knee is the
highest rate at which no request was rejected and the mean queue wait of
the last third of the window's requests is no more than twice that of the
first third (waits under ``--floor-ms`` count as flat: twice nothing is
nothing). The cell's traffic file then states 0.8 of it as a number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness import device  # noqa: E402
from benchmarks.harness.stats import percentile  # noqa: E402


def thirds(fam, win) -> dict:
    """Queue-wait thirds, rejections and tails of one rate's window."""
    rows = fam._rows(win)
    res = win["result"]
    n = len(rows)
    rejected = sum(not res.accepted[r["i"]] for r in rows)
    waits = [(r["due"], r["c"].start_ts - r["due"]) for r in rows if r["ok"]]
    waits.sort()
    k = max(1, len(waits) // 3)
    first = statistics.mean(w for _, w in waits[:k])
    last = statistics.mean(w for _, w in waits[-k:])
    e2e = fam.end_to_end(win)
    ttft = [1e3 * (r["c"].first_token_ts - r["due"]) for r in rows if r["ok"]]
    e2e["ttft_p95_ms"] = percentile(ttft, 95, len(rows) - len(ttft))
    toks = sum(r["c"].n_generated for r in rows if r["ok"])
    span = max(r["c"].finish_ts for r in rows if r["ok"]) - res.t_open
    return {"rate_per_s": win["rate_per_s"], "due": n, "rejected": rejected,
            "unserved": sum(not r["ok"] for r in rows),
            "wait_first_third_ms": 1e3 * first,
            "wait_last_third_ms": 1e3 * last,
            "tokens_per_s": toks / span, **e2e}


def sweep(cell, rates, seconds: float, seed: int, devices, floor_ms: float):
    from tpu_dist.runtime import enable_compile_cache

    enable_compile_cache()
    with tempfile.TemporaryDirectory(prefix="bench_sweep_") as workdir:
        fam = cells.load_family(cell).Family(cell, seed, devices, workdir)
        fam.build()
        fam.warm()
        table = []
        for rate in rates:
            mix = dict(cell.traffic, rate_per_s=float(rate))
            row = thirds(fam, fam.run_window(seconds, mix))
            row["sustained"] = bool(
                row["rejected"] == 0 and row["unserved"] == 0 and (
                    row["wait_last_third_ms"] <= max(
                        2.0 * row["wait_first_third_ms"], floor_ms)))
            print("sweep " + json.dumps(row), flush=True)
            table.append(row)
    ok = [r["rate_per_s"] for r in table if r["sustained"]]
    return {"table": table, "knee_per_s": max(ok) if ok else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--floor-ms", type=float, default=50.0)
    args = ap.parse_args()
    cell = cells.load_cell(ROOT, args.workload)
    devices = device.require_tpu(cell.chips)
    out = sweep(cell, [float(r) for r in args.rates.split(",")],
                args.seconds, args.seed, devices, args.floor_ms)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
