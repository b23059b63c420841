"""The selective scan of a Mamba-1 mixer: shapes -> the operations and
bytes the algorithm needs for one call, and the least time a chip could
take for them.

For ``u`` of shape (B, L, C) in ``itemsize``-byte elements and N states a
channel (``tpu_dist/ops/selective_scan.py``):

* bytes: reads ``u`` and writes ``y`` (B*L*C elements each, ``itemsize``
  bytes), reads ``delta`` (float32, B*L*C), ``B`` and ``C`` (float32,
  B*L*N each), ``A`` (C*N float32) and ``D`` (C), reads the state it starts
  from and writes the one it ends in (B*N*C float32 each). The [L, C, N]
  products are never in HBM: that is the point of the kernel;
* operations, per step, channel and state: ``delta*A``, one exponential,
  ``dA*s``, ``(delta*u)*B``, an add, ``C*s`` and its sum over the states:
  6 float32 operations and 1 exponential; per step and channel 3 more
  (``delta*u``, ``D*u`` and its add).

The floor is the HBM one: the matrix unit has no part in the scan, and the
benchmark's device table (``harness/device.py``) holds no peak for the
vector or the transcendental unit, so it cannot state a compute floor. A
scan that the vector unit bounds therefore reads LOW against this floor
(by the ratio of its vector time to its HBM time), never high: the share
cannot pass 100% for a kernel that moves these bytes at all.
"""

from __future__ import annotations

import re

#: a device event of the kernel: Pallas names the custom call after the
#: innermost scope, ``%selective_scan.N = ... custom-call(...)``
CALL = re.compile(r"^%?selective_scan[\w.\-]* = .*\bcustom-call\(")


def scan(b: int, l: int, c: int, n: int, itemsize: int = 2) -> dict:
    stream = b * l * c
    return {"vector_ops": (6.0 * n + 3.0) * stream,
            "exponentials": 1.0 * n * stream,
            "bytes": (2.0 * itemsize + 4.0) * stream + 8.0 * b * l * n
                     + 4.0 * c * n + 4.0 * c + 8.0 * b * n * c}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """The roofline's floor for one call: the HBM time (see the module's
    text for why no compute floor is stated)."""
    return {"seconds": cost["bytes"] / peaks["hbm_bytes_per_s"],
            "bound": "memory"}
