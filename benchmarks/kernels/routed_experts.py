"""The routed products of an expert layer
(``tpu_dist/ops/routed_experts.py:routed_experts``): counts -> the bytes and
operations the algorithm needs, and the least time a chip could take for
them.

**A decode tick (the masked dense form).** A tick's routed layer multiplies
each live row's latent by the two matrices of every held expert that some
row chose. What it has to move: the weights of the experts HIT (``2 * latent
* width`` elements each, ``itemsize`` bytes: 11.01 MB for 1024 -> 2688 ->
1024 in bfloat16), once however many rows chose them; each live row's latent
in (``latent * itemsize``) and its mixed result out (``latent`` float32).
Nothing padded is counted: not the held experts that no row chose (the dense
form reads them all the same), not the slots that sit out, not the ``[held,
rows, width]`` intermediate. So the share reads low by what the program
moves beyond that, never high. The floor is the HBM one: at 64 rows the hit
experts' products are 2 * 2 * 64 * 1024 * 2688 = 0.70 GFLOP an expert
against 11.01 MB, 64 FLOP a byte, under the v5e's 240.

**A prefill above the dense form's row limit (the sorted form: two calls of
the megablox ``gmm`` kernel a layer).** The same weights of the experts hit,
once; for each ASSIGNMENT that landed on a held expert its latent row in,
its ``width`` hidden values out of the first product and into the second,
its result out in float32; and ``2 * 2 * latent * width`` operations. The
floor is the larger of the two times (it is the weights' read up to about
240 rows an expert); the parked assignments, which the kernel does not
visit, are not counted.
"""

from __future__ import annotations

import re

#: a device event of the grouped product: Pallas names the custom call after
#: the kernel's function, ``%gmm.N = ... custom-call(...)``
GMM_CALL = re.compile(r"^%?gmm[\w.\-]* = .*\bcustom-call\(")


def tick(experts_hit: float, rows: float, latent: int, width: int,
         itemsize: int = 2) -> dict:
    """``experts_hit`` (hit experts summed over the expert layers, and over
    ticks) and ``rows`` (live rows summed likewise)."""
    return {"bytes": experts_hit * 2.0 * latent * width * itemsize
            + rows * latent * (itemsize + 4.0)}


def prefill(experts_hit: float, assignments: float, latent: int, width: int,
            itemsize: int = 2) -> dict:
    """``experts_hit`` and ``assignments`` (those that landed on held
    experts), both summed over the expert layers and the prefills."""
    return {"bytes": experts_hit * 2.0 * latent * width * itemsize
            + assignments * (latent * (itemsize + 4.0)
                             + 2.0 * width * itemsize),
            "flops": assignments * 4.0 * latent * width}


def least_seconds(cost: dict, peaks: dict) -> dict:
    by_memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    by_compute = cost.get("flops", 0.0) / peaks["bf16_flops"]
    return {"seconds": max(by_memory, by_compute),
            "bound": "memory" if by_memory >= by_compute else "compute"}
