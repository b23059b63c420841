#!/usr/bin/env python3
"""Show that each cell's output check fails in the nearest precision below
the one its configuration states, and read what its limits are set from:

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3] [--seconds 8]

One process. For every seed it reads the SOUND numbers (the program as the
cell runs it against the plain float32 reference) and, for the control
seeds, the CONTROL's. The cell's workload file names the control:

* ``control.engine`` / ``control.serve``: the program's own lower-precision
  path switched on (training: ``quant``; serving: each entry of the list is
  one set of ``ServeConfig`` fields, such as ``kv_quant``), run as the cell
  runs it and held to the cell's limits;
* ``control.reference_quant``: the reference itself computed in int8 and
  put in the program's place (where the program has no such path);
* ``control.window_faults``: faults of the K-step window program, emulated
  on the single-step side of that comparison.

It prints every number and, at the end, the largest sound and the smallest
control reading of each, which the limits are set between. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell as cells  # noqa: E402
from benchmarks.harness import check, device  # noqa: E402


def _numbers(comparisons) -> dict:
    return {c.name: c.value for c in comparisons}


def _training_program(fam, seed, pristine, faults=()):
    """What one seed's first steps (and warm epoch, where the family ties
    its window program there) leave to be compared."""
    if pristine is not None:
        fam.reseed(seed, pristine)
    fam.window_faults = faults
    fam.first_steps()
    if hasattr(fam, "window_program_readings"):
        fam.warm()
    return {"seed": seed, "fed": fam.fed, "program": fam.program,
            "faults": getattr(fam, "fault_readings", {})}


def drive_training(fam, pristine, seeds) -> list:
    """The built family driven through every seed's first steps (and,
    where it has a window program to tie, through that program's emulated
    faults: they cost a few steps each)."""
    faults = () if fam.control else \
        fam.cell.workload["control"].get("window_faults", ())
    return [_training_program(fam, seed, pristine, faults) for seed in seeds]


def compare_training(fam, runs, control_seeds) -> dict:
    """{seed: {'sound': {number: value}, 'control': {...}}} of what
    ``drive_training`` kept: against the plain reference; for the control
    seeds also the reference itself in the lower precision, put in the
    program's place, and each emulated fault of the window program."""
    ctl = fam.cell.workload["control"]
    limits = fam.cell.workload["check"]["limits"]
    out = {}
    for run in runs:
        fam.seed, fam.fed, fam.program = run["seed"], run["fed"], run["program"]
        got = out[run["seed"]] = {"sound": _numbers(fam.verify(None))}
        low = {}
        if run["seed"] in control_seeds and "reference_quant" in ctl:
            low.update(_numbers(check.training_comparisons(
                fam.reference_steps(fam.fed, ctl["reference_quant"]),
                fam.reference, limits)))
        for fault, readings in run["faults"].items():
            low.update({f"{k}[{fault}]": v for k, v in _numbers(
                check.window_program_comparisons(readings, limits)).items()})
        if low:
            got["control"] = low
        print(f"control seed {run['seed']}: " + json.dumps(got), flush=True)
    return out


def read_training(cell, seeds, control_seeds, devices) -> dict:
    """A family that can ``reseed`` keeps one engine object for every seed
    (its set-up is the long part), another gets one a seed; the references
    run once the program's state is freed."""
    mod = cells.load_family(cell)

    def read(control: bool, which) -> dict:
        out = {}
        with tempfile.TemporaryDirectory(prefix="bench_ctl_") as workdir:
            make = lambda seed: mod.Family(cell, seed, devices, workdir,
                                           control=control)
            if hasattr(mod.Family, "reseed"):
                fam = make(which[0])
                fam.build()
                runs = drive_training(fam, fam._copy(fam.tr.state), which)
                fam.release()
                return compare_training(fam, runs,
                                        () if control else control_seeds)
            for seed in which:
                fam = make(seed)
                fam.build()
                runs = drive_training(fam, None, [seed])
                fam.release()
                out.update(compare_training(
                    fam, runs, () if control else control_seeds))
        return out

    out = read(False, seeds)
    if "engine" in cell.workload["control"]:
        # the program's own lower-precision path switched on
        print("the same, with the control's engine fields:", flush=True)
        for seed, got in read(True, [s for s in seeds
                                     if s in control_seeds]).items():
            out[seed].setdefault("control", {}).update(got["sound"])
    return out


def read_serving(cell, seeds, control_seeds, devices, seconds: float,
                 sound: bool = True) -> dict:
    """One engine for the sound program and one for each of the control's
    ``serve`` variants; every seed gets its own weights and traffic, a
    short window at the cell's own load, and the cell's comparison."""
    mod = cells.load_family(cell)
    variants = ([("sound", None)] if sound else []) + [
        ("control", v) for v in cell.workload["control"]["serve"]]
    out = {}
    for kind, fields in variants:
        which = seeds if fields is None else [s for s in seeds
                                             if s in control_seeds]
        if not which:
            continue
        with tempfile.TemporaryDirectory(prefix="bench_ctl_") as workdir:
            fam = mod.Family(cell, which[0], devices, workdir, control=fields)
            fam.build()
            fam.warm()
            wins = []
            for seed in which:
                if seed != fam.seed:
                    fam.reseed(seed)
                wins.append((seed, fam.run_window(seconds)))
            fam.release()
            for seed, win in wins:
                fam.seed = seed
                numbers = _numbers(fam.verify(win))
                print(f"control seed {seed} {fields or 'sound'}: "
                      + json.dumps(numbers), flush=True)
                got = out.setdefault(seed, {})
                if fields is None:
                    got["sound"] = numbers
                else:
                    tag = ",".join(f"{k}={v}" for k, v in fields.items())
                    got.setdefault("control", {}).update(
                        {f"{k}[{tag}]": v for k, v in numbers.items()})
    return out


def read_seeds(cell, seeds, control_seeds, devices, seconds: float = 8.0,
               sound: bool = True) -> dict:
    if cells.load_family(cell).Family.kind == "serve":
        return read_serving(cell, seeds, control_seeds, devices, seconds,
                            sound)
    return read_training(cell, seeds, control_seeds, devices)


def main() -> int:
    from tpu_dist.runtime import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-only", action="store_true",
                    help="serving: skip the sound engine")
    args = ap.parse_args()
    cell = cells.load_cell(ROOT, args.workload)
    devices = device.require_tpu(cell.chips)
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    sound, control = {}, {}
    for got in read_seeds(cell, seeds, ctl_seeds, devices, args.seconds,
                          not args.control_only).values():
        for k, v in got.get("sound", {}).items():
            sound.setdefault(k, []).append(v)
        for k, v in got.get("control", {}).items():
            control.setdefault(k, []).append(v)
    print("control summary: " + json.dumps({
        "sound": {k: {"max": max(v), "n": len(v)} for k, v in sound.items()},
        "control": {k: {"min": min(v), "n": len(v)}
                    for k, v in control.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
