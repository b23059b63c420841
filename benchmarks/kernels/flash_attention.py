"""Causal flash attention: shapes -> the operations and bytes the algorithm
needs, and the least time a chip could take for them.

For q, k, v of shape (B, L, H, D) in ``itemsize``-byte elements:

* forward: QK^T and PV over the causal half of the L x L square,
  2 * B*H*L*L*D FLOPs; reads q, k, v and writes o (4 tensors) plus the
  float32 log-sum-exp row (B*H*L*4 bytes);
* backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q over the same
  half, 4 * B*H*L*L*D FLOPs. The score recomputation FlashAttention-2
  also executes is NOT counted: it is recomputed work. Reads q, k, v, o,
  dO and the log-sum-exp, writes dq, dk, dv (8 tensors).

Softmax's exponentials are not counted (they are not matrix work).
"""

from __future__ import annotations


def forward(b: int, l: int, h: int, d: int, itemsize: int = 2) -> dict:
    return {"flops": 2.0 * b * h * l * l * d,
            "bytes": 4.0 * b * l * h * d * itemsize + 4.0 * b * h * l}


def backward(b: int, l: int, h: int, d: int, itemsize: int = 2) -> dict:
    return {"flops": 4.0 * b * h * l * l * d,
            "bytes": 8.0 * b * l * h * d * itemsize + 4.0 * b * h * l}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """The roofline's floor for one call and which bound sets it."""
    compute = cost["flops"] / peaks["bf16_flops"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
