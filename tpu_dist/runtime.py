"""Process-level choices every entry point makes the same way.

Two rules live here so that no call site carries its own copy:

* :func:`pallas_interpret` — Pallas kernels compile on platform ``tpu``
  and run in the interpreter everywhere else (the CPU tests);
* :func:`enable_compile_cache` — where JAX's persistent compilation cache
  lives: wherever ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed
  directory inside the checkout (the path is part of the cache key, so a
  directory that moves never hits).
"""

from __future__ import annotations

import os
from typing import Optional

#: the in-checkout cache directory used when the environment names none
#: (git-ignored; built at run time)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in interpreter mode: an explicit
    ``interpret`` wins; otherwise compiled on ``tpu``, interpreted on any
    other backend."""
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads it itself and this
    sets no directory in code. Safe to call repeatedly."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the default 1 s floor would leave the small serving programs (one
    # per prefill bucket) to recompile on every cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    # an executable read back from the cache carries the op_names of the
    # program that WROTE it, and JAX's default leaves names out of the key:
    # a commit that only renames a jax.named_scope would run, and be traced,
    # under its parent's names (found on the chip in PR 24: the decode tick
    # came back without its paged_read scope). With the names in the key a
    # trace's readers join on this checkout's own scopes; the price is that
    # entries are per checkout path and per edit of a traced file
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
