"""The grouped in-place decode read of a rows-layout KV layer
(``tpu_dist/ops/paged_attention.py:paged_grouped_decode_attention``): shapes
-> the bytes the algorithm needs for one call, and the least time a chip
could take for them.

One query a row over a row's LIVE tokens: the call reads each live token's K
and V row once (``kv_heads * head_dim`` elements each, ``itemsize`` bytes),
and the queries and the outputs, which are nothing beside them (``H *
head_dim`` a row). The floor counts the live tokens' rows and NOTHING padded:
not the rest of a token's last page, which the kernel fetches whole, not a
ring's page that has left the window, not the other heads' lanes of the
block-diagonal products. So the share reads low by what the kernel fetches or
multiplies beyond the live rows, never high.

The floor is the HBM one. The products are ``2 * 2 * H * kv_heads * head_dim``
a live token as the kernel lays them out (every head against every KV head's
lanes), at 40 heads over 10 of 128: 0.2 MFLOP against 5 KB, or 40 FLOP a
byte, under the v5e's 240: still memory-bound on paper, so the HBM floor is
the roofline's.
"""

from __future__ import annotations

import re

#: a device event of the kernel by the scope its call sits in: Pallas names
#: the custom call after the innermost scope, ``%shared_kv_read.N = ...
#: custom-call(...)`` (a layer's pages that other layers read too) or
#: ``%window_read.N`` (a window ring)
SHARED_CALL = re.compile(r"^%?shared_kv_read[\w.\-]* = .*\bcustom-call\(")
WINDOW_CALL = re.compile(r"^%?window_read[\w.\-]* = .*\bcustom-call\(")


def read(live_tokens: float, kv_heads: int, head_dim: int,
         itemsize: int = 2) -> dict:
    """One layer's read of ``live_tokens`` rows (summed over the call's
    batch rows): K and V."""
    return {"bytes": 2.0 * live_tokens * kv_heads * head_dim * itemsize}


def least_seconds(cost: dict, peaks: dict) -> dict:
    return {"seconds": cost["bytes"] / peaks["hbm_bytes_per_s"],
            "bound": "memory"}


def roofline_share(obs: dict, call, live_tokens: float, calls: int,
                   what: str):
    """Percent of the HBM floor of ``calls`` reads of ``live_tokens`` rows in
    all over the traced window's time in the device events ``call``
    matches, at the cell's configuration and precision; None where the
    trace shows no such event."""
    from benchmarks.harness import device

    spent = sum(v for k, v in obs["trace"].op_seconds.items()
                if call.search(k))
    if spent <= 0 or not calls:
        return None
    s = obs["cell"].config
    itemsize = {"bf16": 2, "fp32": 4}[
        obs["cell"].workload["engine"]["precision"]]
    least = least_seconds(
        read(live_tokens, s["num_key_value_heads"], s["head_dim"], itemsize),
        device.peaks(obs["device_kind"]))["seconds"]
    print(f"{what}: {calls} calls, {1e6 * spent / calls:.1f} us a call in "
          f"the trace, HBM floor {1e6 * least / calls:.1f} us a call",
          flush=True)
    return 100.0 * least / spent
