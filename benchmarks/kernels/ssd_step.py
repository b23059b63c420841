"""The one-step Mamba-2 state update of a decode tick
(``tpu_dist/ops/ssd.py``: ``ssd_step`` and the kernel over the live rows,
``ssd_step_live``): counts -> the bytes the recurrence needs, and the least
time a chip could take for them.

A live row's step reads its state ``[H, P, N]`` float32 and writes it back
(``2 * H * P * N * 4`` bytes: 8.39 MB at the published 128 heads of 64
channels and 128 states), and beside it moves the row's ``x`` in and ``y``
out (``H * P`` activations each), its ``dt`` (``H`` float32) and its ``B``
and ``C`` (``G * N`` activations each). Nothing padded is counted and no
slot that sits out: the plain form, which passes over every slot's state
whatever it holds, stays under 100% by the share of slots that decode. The
floor is the HBM one: a state element costs three multiplications and two
additions for eight bytes moved, far under the v5e's 240 FLOP a byte.
"""

from __future__ import annotations


def step(rows: float, heads: int, head_dim: int, d_state: int, groups: int,
         itemsize: int = 2) -> dict:
    """``rows``: live rows summed over the Mamba-2 layers (and over ticks);
    ``itemsize`` of the activations (the state is float32 whatever they
    are)."""
    return {"bytes": rows * (2.0 * heads * head_dim * d_state * 4
                             + 2.0 * heads * head_dim * itemsize
                             + heads * 4.0
                             + 2.0 * groups * d_state * itemsize)}


def least_seconds(cost: dict, peaks: dict) -> dict:
    return {"seconds": cost["bytes"] / peaks["hbm_bytes_per_s"],
            "bound": "memory"}
