#!/usr/bin/env python3
"""A bursty serving cell's knee under STEADY arrivals, once, on the chip:

    python3 benchmarks/sweep_steady.py --workload jamba2-3b.serve-chat-burst --rates 6,8,10,12,14,16 --seconds 20 --seed 1

``benchmarks/sweep.py``'s own sweep and rule, on the cell's traffic with
its ``burst`` group taken out: Poisson arrivals with the cell's lengths
(the family reads a mix without the group through ``harness/traffic.py``).
The cell's traffic file states its mean rate as a share of this knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import sweep  # noqa: E402
from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness import device  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--floor-ms", type=float, default=50.0)
    args = ap.parse_args()
    cell = cells.load_cell(ROOT, args.workload)
    cell.traffic = {k: v for k, v in cell.traffic.items() if k != "burst"}
    devices = device.require_tpu(cell.chips)
    out = sweep.sweep(cell, [float(r) for r in args.rates.split(",")],
                      args.seconds, args.seed, devices, args.floor_ms)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
