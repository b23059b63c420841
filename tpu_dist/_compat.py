"""The one import site for the two JAX entry points every layer shares.

The repo targets the installed jax (0.9: top-level ``jax.shard_map`` with
``check_vma``, the ``jax_num_cpu_devices`` option). Call sites import from
here so the spelling lives once.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True, **kwargs):
    """``jax.shard_map`` with keyword mesh/specs; ``axis_names`` = the
    MANUAL axes (the rest stay GSPMD auto axes)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def set_cpu_device_count(n: int) -> None:
    """Request ``n`` virtual CPU devices. Must run before the backend
    initializes (conftest / driver entry time)."""
    jax.config.update("jax_num_cpu_devices", n)
