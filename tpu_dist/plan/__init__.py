"""tpu_dist.plan — step-plan IR, compiler, and hardware auto-tuner.

Lazy (PEP 562) like ``tpu_dist.parallel``: ``plan.ir`` and ``plan.tune``
are stdlib-only and must import under the scripts/lint.sh jax-import
blocker; ``plan.compile`` (the lowerer) pulls jax and is resolved only
when asked for.
"""

from __future__ import annotations

import importlib

# the submodules themselves resolve FIRST (``from tpu_dist.plan import
# tune`` must yield the module, not the re-exported tune() function —
# the import machinery's _handle_fromlist getattr would otherwise recurse)
_SUBMODULES = ("ir", "tune", "compile")

_IR = ("Plan", "PlanError", "plan_hash", "load_plan_file",
       "plan_for_device", "apply_plan_to_config", "plan_from_config",
       "plan_knob_summary", "KNOWN_AXES")
_TUNE = ("search", "default_space", "device_peaks",
         "estimate_step_seconds", "emit_plan_file")
_COMPILE = ("compile_train_step", "compile_eval_step", "Bindings",
            "activate_plan", "resolve_config_plan")

__all__ = list(_IR + _TUNE + _COMPILE)


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"tpu_dist.plan.{name}")
    if name in _IR:
        return getattr(importlib.import_module("tpu_dist.plan.ir"), name)
    if name in _TUNE:
        return getattr(importlib.import_module("tpu_dist.plan.tune"), name)
    if name in _COMPILE:
        return getattr(importlib.import_module("tpu_dist.plan.compile"),
                       name)
    raise AttributeError(f"module 'tpu_dist.plan' has no attribute {name!r}")
