"""How ``correct`` is decided: numbers compared with the plain reference,
each beside a limit of its own (PERF.md section 2 gives the readings every
limit was set from). Every run prints every comparison.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Tuple


@dataclasses.dataclass
class Comparison:
    name: str
    value: float
    limit: float
    note: str = ""

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def report(comparisons: List[Comparison]) -> bool:
    """Print each number beside its limit; True when all hold."""
    for c in comparisons:
        print(f"check {c.name}: {c.value:.6g} (limit {c.limit:.6g}) "
              f"{'ok' if c.ok else 'FAILED'}{' ' + c.note if c.note else ''}",
              flush=True)
    return all(c.ok for c in comparisons)


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]
                   ) -> Tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, as a share of the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    # the median of the leaves that have a gradient at all: with each
    # block's last BatchNorm gain at zero, most of a ResNet's first
    # gradients are exactly zero
    floor = statistics.median([v for v in reference.values() if v > 0.0]
                              or [0.0])
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor, 1e-30)
        if not gap <= worst:      # keeps a nan
            worst, where = gap, name
    return worst, where


SAMPLE = 4096     # elements of a leaf that the element-wise reading keeps


def leaf_sample(x):
    """Up to SAMPLE elements of one leaf at a fixed stride: the same
    elements whoever computed the leaf, and a few KB to keep on the host
    while the window runs."""
    flat = x.reshape(-1)
    return flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]


def median_sample_error(program: Dict[str, "np.ndarray"],
                        reference: Dict[str, "np.ndarray"]) -> float:
    """Median, over the leaves that have a gradient at all, of
    |program - reference| / |reference| on each leaf's sampled elements.
    The norms above see a lower precision only in second order (zero-mean
    rounding leaves a norm where it was); this sees it in first order,
    and the first gradient, from seeded weights on one batch, has no
    chaotic history that could swamp it."""
    import numpy as np

    errs = []
    for name, ref in reference.items():
        scale = float(np.linalg.norm(ref))
        if scale > 0.0:
            errs.append(float(np.linalg.norm(
                np.asarray(program[name], np.float32) - ref)) / scale)
    return statistics.median(errs) if errs else math.nan


def training_comparisons(program: dict, reference: dict, limits: dict
                         ) -> List[Comparison]:
    """The training cells' numbers: each of the first steps' losses, the
    first gradient's norm as the optimizer got it and the parameters'
    change after those steps, both by the worst leaf. ``program`` and
    ``reference`` hold ``losses`` (list), ``grad_norms`` and
    ``update_norms`` (leaf name -> norm) and ``grad_samples`` (leaf name ->
    ``leaf_sample`` of the first gradient)."""
    out = []
    each = limits["loss_rel_gap"]        # one limit, or one for each step
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        out.append(Comparison(f"loss_step{i}_rel_gap", abs(p - r) / abs(r),
                              each[i] if isinstance(each, list) else each,
                              f"program {p:.6f} reference {r:.6f}"))
    g, where = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    out.append(Comparison("first_grad_norm_worst_leaf_gap", g,
                          limits["grad_norm_gap"], f"at {where}"))
    out.append(Comparison(
        "first_grad_sample_median_rel_err",
        median_sample_error(program["grad_samples"],
                            reference["grad_samples"]),
        limits["grad_sample_rel_err"]))
    u, where = worst_leaf_gap(program["update_norms"],
                              reference["update_norms"])
    out.append(Comparison("update_norm_worst_leaf_gap", u,
                          limits["update_norm_gap"], f"at {where}"))
    return out


def window_program_readings(loss_window: float, losses_single: List[float],
                            update_window: dict, update_single: dict) -> dict:
    """The timed K-step dispatch against the engine's own single-step
    program driven over the same rows from the same state. ``update_*``
    hold ``norms`` (leaf -> norm of the parameters' change over the K
    steps) and ``samples`` (leaf -> ``leaf_sample`` of that change)."""
    mean = sum(losses_single) / len(losses_single)
    gap, where = worst_leaf_gap(update_window["norms"], update_single["norms"])
    over = f"over {len(losses_single)} steps"
    return {"loss_gap": abs(loss_window - mean) / abs(mean),
            "update_norm_gap": gap,
            "update_sample_rel_err": median_sample_error(
                update_window["samples"], update_single["samples"]),
            "notes": {"loss_gap": f"window {loss_window:.6f} single steps "
                                  f"{mean:.6f} {over}",
                      "update_norm_gap": f"at {where} {over}",
                      "update_sample_rel_err": over}}


def window_program_comparisons(readings: dict, limits: dict
                               ) -> List[Comparison]:
    """One comparison per reading of ``window_program_readings`` that the
    cell's file gives a limit (``window_program_<reading>``)."""
    return [Comparison(f"window_program_{k}", readings[k],
                       limits[f"window_program_{k}"], note)
            for k, note in readings["notes"].items()
            if f"window_program_{k}" in limits]


def count_nonfinite(records: List[dict]) -> Tuple[int, int]:
    """(attempted, failed) of a training window from the engine's ``step``
    records: optimizer steps, and those of records whose loss is not
    finite."""
    steps = lambda rs: sum(r["steps_in_dispatch"] for r in rs)
    bad = [r for r in records
           if r["loss"] is None or not math.isfinite(r["loss"])]
    return steps(records), steps(bad)
