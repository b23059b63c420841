"""Plan compiler: lower a :class:`tpu_dist.plan.ir.Plan` to step callables.

The trainers derive a Plan (``plan.ir.plan_from_config``) and hand it here
with their :class:`Bindings`; :func:`compile_train_step` and
:func:`compile_eval_step` are the one way from a plan to a step program:

1. **validate** — :meth:`Plan.validate` + the mesh-axis check (the mode
   exclusion rules' one home);
2. **template** — pick the engine's pure step function (the ONE step
   template per engine: ``engine/steps.py:_train_step_fn`` for images,
   ``engine/lm_steps.py:_lm_step_fn`` and its explicit/ring/sp per-device
   flavors for tokens — the templates stay in the engine modules, the
   compiler composes them);
3. **window** — optionally wrap the template in a ``lax.scan`` dispatch
   window (host-fed stacked batches, or HBM-resident indexed gathers with
   the engine's gather prelude);
4. **partition** — ``jit`` with GSPMD shardings (``sync='gspmd'``) or
   ``shard_map`` + ``jit`` with explicit specs (``sync='explicit'`` /
   ``layout='sp'``).

Each lowering's docstring states the signature of the program it returns.

``activate_plan`` applies a plan's global trace-time switches (fused
int8 kernel, Pallas block sizes) and ``resolve_config_plan`` implements
the configs' ``plan: auto|<path>|none`` knob for both engines.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist._compat import shard_map
from tpu_dist.plan.ir import (Plan, PlanError, apply_plan_to_config,
                              plan_hash, plan_knob_summary)

if TYPE_CHECKING:   # annotations only: the engine package imports this module
    from tpu_dist.engine.state import TrainState


@dataclass
class Bindings:
    """What a plan lowers AGAINST: the run's concrete objects. The model
    binding must already embody the plan's quant (flax modules bake it in
    at construction — the engines build them from the same config the
    plan was derived from). It is the PLAIN model under ``tp_impl='ring'``
    too: init, eval and checkpoints use it as it is, and the train
    lowerings take their ring twin from it (:func:`_train_model`)."""

    mesh: Mesh
    model: Any = None                 # flax module (non-sp paths)
    model_ctor: Optional[Callable] = None  # sp: ctor(attn_fn=...) -> model
    tx: Any = None                    # optimizer (optax or fused protocol)
    transform: Optional[Callable] = None       # image train transform
    eval_transform: Optional[Callable] = None  # image eval transform
    image_shape: Optional[Tuple[int, int, int]] = None  # indexed image paths


def compile_train_step(plan: Plan, binds: Bindings) -> Callable:
    """Validate the plan against the bindings' mesh and lower its train
    step: ``(state, <batch or window>, rng) -> (state, metric sums)``, the
    batch arguments as the lowering that :func:`_lower_train` picks
    documents them."""
    _pass_validate(plan, binds)
    return _lower_train(plan, binds)


def compile_eval_step(plan: Plan, binds: Bindings) -> Callable:
    """Validate + lower the plan's eval step (forward only, metric sums);
    a windowed plan gives the whole-set scan."""
    _pass_validate(plan, binds)
    return _lower_eval(plan, binds)


# ---- pass 1: validate -----------------------------------------------------

def _pass_validate(plan: Plan, binds: Bindings) -> None:
    plan.validate()
    if binds.mesh is None:
        raise PlanError("Bindings.mesh is required")
    plan.validate_against_mesh(dict(binds.mesh.shape))
    if plan.layout == "sp" and binds.model_ctor is None:
        raise PlanError("layout='sp' lowers a model_ctor(attn_fn=...) — "
                        "the ring attention binds per seq axis")
    if plan.engine == "image" and binds.model is not None \
            and binds.transform is None and binds.tx is not None:
        raise PlanError("the image templates need a transform binding")


# ---- program audit (tpu_dist.analysis.proglint) ---------------------------
# A module-level switch in the activate_plan mold: the engines arm it from
# cfg.audit before their first dispatch, the partition helpers below
# REGISTER every program they mint as a side effect (never a wrapper: the
# callers hold the jitted callable itself and ``.lower()`` it), and the
# engines run
# the compile-time pass at the same first-dispatch probe that already
# lowers the program for telemetry. The runtime half (the recompile
# sentry) is a host-only counter read at the drain boundaries.

AUDIT_MODES = ("none", "record", "halt")

_AUDIT = {"mode": "none", "ledger": None, "sentry": None}


def set_audit(mode: str, ledger=None) -> None:
    """Arm (or disarm) the program audit for this process. ``record``
    emits ``audit`` ledger events; ``halt`` additionally raises
    :class:`~tpu_dist.analysis.proglint.AuditError` on any unwaivered
    finding. A fresh sentry per call: each run watches its own caches."""
    mode = mode or "none"
    if mode not in AUDIT_MODES:
        raise ValueError(f"audit={mode!r}: pick one of {AUDIT_MODES}")
    _AUDIT["mode"], _AUDIT["ledger"] = mode, ledger
    if mode == "none":
        _AUDIT["sentry"] = None
    else:
        from tpu_dist.analysis.proglint import RecompileSentry

        _AUDIT["sentry"] = RecompileSentry()


def audit_mode() -> str:
    return _AUDIT["mode"]


def register_audit_program(program: str, fn, allowed: int = 1) -> None:
    """Put a jitted program under the recompile sentry (PL005).
    ``allowed`` is its legal trace-cache size — 1 for fixed-shape step
    programs, the bucket count for deliberately shape-specializing ones
    (serve prefill). No-op when the audit is off."""
    if _AUDIT["sentry"] is not None:
        _AUDIT["sentry"].register(program, fn, allowed)


def _emit_audit(program: str, findings) -> None:
    led = _AUDIT["ledger"]
    if led is not None:
        led.emit("audit", program=program, mode=_AUDIT["mode"],
                 findings=len([f for f in findings if not f.waived]),
                 waived=len([f for f in findings if f.waived]),
                 detail=[f.to_json() for f in findings] or None)


def audit_program(program: str, fn, *args, hlo=None, precision=None,
                  allowed: int = 1):
    """The compile-time pass over ONE program: retrace abstractly
    (make_jaxpr — no compile, no execution), run the jaxpr checks, check
    donation against the caller's already-compiled HLO text (the
    telemetry.program_stats artifact — zero extra lowering), register
    the program with the sentry, and emit exactly one ``audit`` ledger
    event. Returns the (waiver-applied) findings; raises AuditError
    under ``halt`` when any survive."""
    if _AUDIT["mode"] == "none":
        return []
    from tpu_dist.analysis import proglint

    register_audit_program(program, fn, allowed)
    closed = jax.make_jaxpr(fn)(*args)
    findings = proglint.audit_jaxpr(program, closed,
                                    precision=precision, hlo=hlo)
    waivers, meta = proglint.load_waivers()
    findings = proglint.apply_waivers(findings, waivers) + meta
    _emit_audit(program, findings)
    bad = proglint.unwaivered(findings)
    if bad and _AUDIT["mode"] == "halt":
        raise proglint.AuditError(
            "audit=halt: " + "; ".join(f.render() for f in bad))
    return findings


def check_audit_sentry() -> None:
    """The drain-boundary PL005 check: one host-side ``_cache_size``
    read per registered program (no device sync — DL002 stays clean).
    Findings latch per program, so ``record`` emits exactly one
    ``audit`` event per offender; ``halt`` raises on unwaivered ones."""
    sentry = _AUDIT["sentry"]
    if sentry is None:
        return
    findings = sentry.check()
    if not findings:
        return
    from tpu_dist.analysis import proglint

    waivers, _ = proglint.load_waivers()
    findings = proglint.apply_waivers(findings, waivers)
    for f in findings:
        _emit_audit(f.program, [f])
    bad = proglint.unwaivered(findings)
    if bad and _AUDIT["mode"] == "halt":
        raise proglint.AuditError(
            "audit=halt: " + "; ".join(f.render() for f in bad))


# ---- pass 4 helpers: partition --------------------------------------------

def _jit_gspmd(fn, in_shardings, out_shardings, donate: bool):
    jf = jax.jit(fn, in_shardings=in_shardings,
                 out_shardings=out_shardings,
                 donate_argnums=(0,) if donate else ())
    register_audit_program(getattr(fn, "__name__", "step"), jf)
    return jf


def _shard_map_jit(fn, mesh, in_specs, out_specs, donate: bool):
    sharded = shard_map(fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    jf = jax.jit(sharded, donate_argnums=(0,) if donate else ())
    register_audit_program(getattr(fn, "__name__", "step"), jf)
    return jf


# ---- image lowerings ------------------------------------------------------

def _train_model(plan: Plan, b: Bindings):
    """The model the train templates apply: under ``tp_impl='ring'`` the
    ring collective-matmul CLONE of the bound model (identical params —
    parallel.overlap), made here and nowhere else."""
    return b.model.clone(tp_impl="ring") if plan.tp_impl == "ring" \
        else b.model


def _image_accum_train(plan: Plan, b: Bindings) -> Callable:
    """ONE optimizer step from K microbatches (gradient accumulation).

    signature: (state, images_u8 (K,B,...), labels (K,B), rng) -> (state,
    metrics summed over microbatches), the microbatches sharded
    (None, data). Grads are averaged over the K microbatches inside a
    lax.scan, then applied once — the standard recipe for global batches
    that exceed device memory. K is read from the leading dim at trace
    time (any ``grad_accum_steps > 1`` selects this template). BN
    statistics advance per microbatch (torch accumulation-loop semantics).
    """
    from tpu_dist.engine.steps import _apply_update, _loss_and_metrics

    mesh, model, tx, transform = b.mesh, b.model, b.tx, b.transform
    health = plan.health
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(None, plan.data_axis))

    def step(state: TrainState, images_u8, labels, rng):
        k = images_u8.shape[0]
        dropout_rng, aug_rng = jax.random.split(
            jax.random.fold_in(rng, state.step))

        def micro(carry, batch):
            grads_acc, stats, i = carry
            imgs, lbls = batch
            d_rng = jax.random.fold_in(dropout_rng, i)
            a_rng = jax.random.fold_in(aug_rng, i)
            grad_fn = jax.value_and_grad(
                lambda p: _loss_and_metrics(model, transform, p, stats,
                                            imgs, lbls, d_rng, a_rng,
                                            state.loss_scale, True),
                has_aux=True)
            (_, (new_stats, metrics)), grads = grad_fn(state.params)
            grads_acc = jax.tree.map(lambda a, g: a + g / k, grads_acc,
                                     grads)
            return (grads_acc, new_stats, i + 1), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             state.params)
        (grads, new_stats, _), metrics_k = jax.lax.scan(
            micro, (zeros, state.batch_stats, jnp.int32(0)),
            (images_u8, labels))
        metrics = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)
        return _apply_update(tx, state, grads, new_stats, metrics, health)

    return _jit_gspmd(step, (None, batch_sh, batch_sh, repl), (None, repl),
                      plan.donate)


def _image_explicit_train(plan: Plan, b: Bindings) -> Callable:
    """Explicit-collective image step (horovod-equivalent, reference
    variant 5): one program per device via shard_map, same signature as
    the plain jit step — (state, images_u8 (B,...), labels (B,), rng),
    batch sharded on ``data``.

    Gradient averaging is an explicit psum with optional bf16 payload
    compression (reference 5.horovod_distributed.py:123-125) and horovod's
    gradient_predivide_factor placement (pre-scale before summation,
    post-scale after; reference 5.2...py:185). ``adasum`` replaces the mean
    by the Adasum recursive-halving operator (hvd.Adasum —
    parallel.collectives.adasum_reduce); predivide/compression are
    mean-path knobs and do not apply to it. ``grad_bucket_mb > 0`` replaces
    the tree-wide psum with DDP-style size-targeted bucket collectives
    (parallel.overlap.bucketed_grad_sync), the decomposition XLA's
    scheduler can overlap. Under ``tp_impl='ring'`` the model's collectives
    run over ``model_axis`` inside this same program, compute is replicated
    across it per data shard, and the grads of the (replicated) params are
    additionally pmean'd over it. BatchNorm stats stay per replica and are
    pmean'd (horovod's local BN), where the jit step's are global-batch."""
    from tpu_dist.engine.steps import _apply_update, _loss_and_metrics
    from tpu_dist.parallel.collectives import compress_grads

    mesh, tx, transform = b.mesh, b.tx, b.transform
    model = _train_model(plan, b)
    data_axis = plan.data_axis
    health = plan.health
    grad_compression = plan.grad_compression
    predivide_factor = plan.predivide_factor
    adasum = plan.adasum
    grad_bucket_mb = plan.grad_bucket_mb
    model_axis = plan.model_axis if plan.tp_impl == "ring" else None
    nrep = mesh.shape[data_axis]

    def per_device(state: TrainState, images_u8, labels, rng):
        dropout_rng, aug_rng = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(rng, state.step),
                               jax.lax.axis_index(data_axis)))
        grad_fn = jax.value_and_grad(
            lambda p: _loss_and_metrics(model, transform, p,
                                        state.batch_stats, images_u8,
                                        labels, dropout_rng, aug_rng,
                                        state.loss_scale, True),
            has_aux=True)
        (_, (new_stats, metrics)), grads = grad_fn(state.params)
        if model_axis is not None:
            # ring TP: params are replicated over the model axis while the
            # per-device losses are identical across it — the mean restores
            # the single-loss gradient (overlap.py scaling note)
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, model_axis), grads)
        if adasum:
            from tpu_dist.parallel.collectives import adasum_reduce
            grads = adasum_reduce(grads, data_axis, nrep)
        else:
            # horovod allreduce: predivide -> (compress) -> psum -> postdivide
            pre = predivide_factor if predivide_factor != 1.0 else nrep
            grads = jax.tree.map(lambda g: g / pre, grads)
            down, up = compress_grads(grads, grad_compression)
            if grad_bucket_mb > 0:
                from tpu_dist.parallel.overlap import bucketed_grad_sync
                down = bucketed_grad_sync(down, data_axis, grad_bucket_mb,
                                          mean=False, axis_size=nrep)
            else:
                down = jax.tree.map(lambda g: jax.lax.psum(g, data_axis),
                                    down)
            grads = up(down)
            if predivide_factor != 1.0:
                grads = jax.tree.map(lambda g: g * (predivide_factor / nrep),
                                     grads)
        # per-replica BN stats -> pmean (≈ horovod local BN + periodic sync)
        new_stats = jax.tree.map(lambda s: jax.lax.pmean(s, data_axis),
                                 new_stats)
        metrics = jax.tree.map(lambda m: jax.lax.psum(m, data_axis), metrics)
        return _apply_update(tx, state, grads, new_stats, metrics, health)

    return _shard_map_jit(per_device, mesh,
                          (P(), P(data_axis), P(data_axis), P()),
                          (P(), P()), plan.donate)


def _image_train(plan: Plan, b: Bindings) -> Callable:
    """The gspmd image train lowerings around ONE template
    (engine.steps._train_step_fn), batch sharded on ``data``, params
    replicated; XLA inserts the gradient all-reduce (DDP-equivalent) and
    BatchNorm statistics are over the GLOBAL batch.

    * ``window='none'``: (state, images_u8 (B,...), labels (B,), rng).
    * ``window='stacked'``: K optimizer steps in ONE dispatch, a lax.scan
      over host-fed stacked batches — (state, images_u8 (K,B,...), labels
      (K,B), rng) -> (state, metrics summed over the K steps). K is a
      trace-time constant (leading dim); the window runs on the device
      with zero host round-trips.
    * ``window='indexed'``: K steps reading a DEVICE-RESIDENT data set by
      index — (state, images_all REPLICATED (packed by
      steps.pack_images_for_device: (N,HWC/4) i32, or (N,H,W,C) u8),
      labels_all (N,) REPLICATED, idx (K,B) i32 sharded (None, data),
      rng). The arrays live in HBM once, each scan iteration gathers its
      batch at HBM bandwidth, and the host sends only the index window: a
      few KB a dispatch, not ~3 KB an image.

    Both windows are identical math to K sequential single steps (same
    per-step rng fold)."""
    from tpu_dist.engine.steps import _train_step_fn

    mesh = b.mesh
    data_axis = plan.data_axis
    repl = NamedSharding(mesh, P())
    step = _train_step_fn(b.model, b.tx, b.transform, plan.health)

    if plan.window == "none":
        batch_sh = NamedSharding(mesh, P(data_axis))
        return _jit_gspmd(step, (None, batch_sh, batch_sh, repl),
                          (None, repl), plan.donate)

    if plan.window == "stacked":
        batch_sh = NamedSharding(mesh, P(None, data_axis))

        def multi(state: TrainState, images_u8, labels, rng):
            def body(st, batch):
                imgs, lbls = batch
                st, metrics = step(st, imgs, lbls, rng)
                return st, metrics
            state, metrics_k = jax.lax.scan(body, state,
                                            (images_u8, labels))
            return state, jax.tree.map(lambda m: jnp.sum(m, axis=0),
                                       metrics_k)

        return _jit_gspmd(multi, (None, batch_sh, batch_sh, repl),
                          (None, repl), plan.donate)

    # window == "indexed": device-resident dataset, (K, B) index windows
    if b.image_shape is None:
        raise PlanError("the image indexed window needs an image_shape "
                        "binding")
    h, w, c = b.image_shape
    idx_sh = NamedSharding(mesh, P(None, data_axis))

    def multi(state: TrainState, images_all, labels_all, idx, rng):
        def body(st, idx_b):
            rows = jnp.take(images_all, idx_b, axis=0)
            if rows.dtype == jnp.int32:  # packed: bitcast words back to bytes
                rows = jax.lax.bitcast_convert_type(rows, jnp.uint8)
            imgs = rows.reshape(-1, h, w, c)
            lbls = jnp.take(labels_all, idx_b, axis=0)
            return step(st, imgs, lbls, rng)
        state, metrics_k = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)

    return _jit_gspmd(multi, (None, repl, repl, idx_sh, repl), (None, repl),
                      plan.donate)


def _image_eval(plan: Plan, b: Bindings) -> Callable:
    """Image eval lowerings (C15): metric sums on the global sharded
    batch — (params, batch_stats, images_u8, labels, valid (B,) f32) — or,
    for ``window='indexed'``, the whole validation set in ONE dispatch from
    HBM-resident data: (params, batch_stats, images_all (packed,
    REPLICATED), labels_all, idx (K,B) i32 sharded (None, data), valid
    (K,B) f32 same sharding) -> sums over all K batches. ``valid`` masks
    sampler padding per sample in both."""
    from tpu_dist.engine.steps import _metric_sums, cross_entropy_sum

    mesh = b.mesh
    model = b.model
    transform = b.eval_transform or b.transform
    data_axis = plan.data_axis
    repl = NamedSharding(mesh, P())

    if plan.window != "indexed":
        batch_sh = NamedSharding(mesh, P(data_axis))

        def step(params, batch_stats, images_u8, labels, valid):
            x = transform(images_u8, None)
            logits = model.apply({"params": params,
                                  "batch_stats": batch_stats}, x,
                                 train=False)
            return _metric_sums(logits, labels,
                                cross_entropy_sum(logits, labels, valid),
                                valid)

        return jax.jit(step, in_shardings=(None, None, batch_sh, batch_sh,
                                           batch_sh),
                       out_shardings=repl)

    if b.image_shape is None:
        raise PlanError("the image indexed eval needs an image_shape "
                        "binding")
    h, w, c = b.image_shape
    idx_sh = NamedSharding(mesh, P(None, data_axis))

    def step(params, batch_stats, images_all, labels_all, idx, valid):
        def body(sums, blk):
            idx_b, valid_b = blk
            rows = jnp.take(images_all, idx_b, axis=0)
            if rows.dtype == jnp.int32:
                rows = jax.lax.bitcast_convert_type(rows, jnp.uint8)
            x = transform(rows.reshape(-1, h, w, c), None)
            labels = jnp.take(labels_all, idx_b, axis=0)
            logits = model.apply({"params": params,
                                  "batch_stats": batch_stats}, x,
                                 train=False)
            m = _metric_sums(logits, labels,
                             cross_entropy_sum(logits, labels, valid_b),
                             valid_b)
            return jax.tree.map(jnp.add, sums, m), None

        zeros = {k: jnp.float32(0.0)
                 for k in ("loss_sum", "correct1", "correct5", "count")}
        sums, _ = jax.lax.scan(body, zeros, (idx, valid))
        return sums

    return jax.jit(step, in_shardings=(None, None, repl, repl, idx_sh,
                                       idx_sh),
                   out_shardings=repl)


# ---- lm lowerings ---------------------------------------------------------

def _lm_accum_train(plan: Plan, b: Bindings) -> Callable:
    """ONE LM optimizer step from K microbatches: (state, inputs
    (K,B,L), targets (K,B,L), rng) -> (state, metric sums over
    microbatches). Equal microbatch sizes make the average of per-micro
    means the full-batch mean; dropout folds a per-microbatch index on top
    of the usual state.step fold."""
    from tpu_dist.engine.lm_steps import _lm_grads_and_metrics
    from tpu_dist.engine.steps import _apply_update

    mesh, model, tx = b.mesh, b.model, b.tx
    aux_weight, loss_chunk, health = (plan.aux_weight, plan.loss_chunk,
                                      plan.health)
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(None, plan.data_axis))

    def step(state: TrainState, inputs, targets, rng):
        k = inputs.shape[0]
        dropout_rng = jax.random.fold_in(rng, state.step)

        def micro(carry, batch):
            grads_acc, i = carry
            mb_in, mb_tg = batch
            grads, metrics = _lm_grads_and_metrics(
                model, aux_weight, state.params, mb_in, mb_tg,
                jax.random.fold_in(dropout_rng, i), loss_chunk)
            grads_acc = jax.tree.map(lambda a, g: a + g / k, grads_acc,
                                     grads)
            return (grads_acc, i + 1), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             state.params)
        (grads, _), metrics_k = jax.lax.scan(
            micro, (zeros, jnp.int32(0)), (inputs, targets))
        metrics = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)
        return _apply_update(tx, state, grads, {}, metrics, health)

    return _jit_gspmd(step, (None, batch_sh, batch_sh, repl), (None, repl),
                      plan.donate)


def _lm_explicit_template(plan: Plan, b: Bindings) -> Callable:
    """The explicit per-device LM step the plan names, from the engine
    templates: ring-TP (shard_map over (data, model), the ring clone's
    ppermute rings riding ``model``; params replicated) or explicit dp
    with ``grad_bucket_mb`` bucket reduce-scatters (<= 0: one pmean)."""
    from tpu_dist.engine.lm_steps import (_lm_explicit_dp_step_fn,
                                          _lm_tp_ring_step_fn)

    if plan.tp_impl == "ring":
        return _lm_tp_ring_step_fn(
            _train_model(plan, b), b.tx, plan.aux_weight, plan.data_axis,
            plan.model_axis, b.mesh.shape[plan.model_axis],
            plan.loss_chunk, plan.health)
    return _lm_explicit_dp_step_fn(
        b.model, b.tx, plan.aux_weight, plan.data_axis,
        b.mesh.shape[plan.data_axis], plan.grad_bucket_mb,
        plan.loss_chunk, plan.health)


def _lm_explicit_train(plan: Plan, b: Bindings) -> Callable:
    """Partition an explicit per-device LM step: single-batch shard_map,
    or for ``window='indexed'`` a lax.scan over (K, B) index windows INSIDE
    the shard_map program, gathering rows from the HBM-resident (N, L+1)
    matrix and shifting on device — :func:`_lm_train`'s signatures."""
    step_fn = _lm_explicit_template(plan, b)
    mesh = b.mesh
    data_axis = plan.data_axis

    if plan.window == "none":
        return _shard_map_jit(step_fn, mesh,
                              (P(), P(data_axis), P(data_axis), P()),
                              (P(), P()), plan.donate)

    def per_device(state: TrainState, rows_all, idx, rng):
        def body(st, idx_b):
            rows = jnp.take(rows_all, idx_b, axis=0)     # (B_local, L+1)
            return step_fn(st, rows[:, :-1], rows[:, 1:], rng)
        state, metrics_k = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)

    return _shard_map_jit(per_device, mesh,
                          (P(), P(), P(None, data_axis), P()),
                          (P(), P()), plan.donate)


def _lm_sp_train(plan: Plan, b: Bindings) -> Callable:
    """Sequence-parallel LM lowerings: batch on ``data``, sequence on
    ``seq``, ring attention inside shard_map. ``model_ctor(attn_fn=...)``
    builds the model so the ring binds per axis. Single-batch — tokens
    sharded (data, seq) — or the indexed window, :func:`_lm_train`'s
    signature: each scan iteration gathers its (B/data, L+1) rows and takes
    this device's sequence shard with a device-side shift, so the
    long-context mode too pays one dispatch per K steps."""
    from tpu_dist.engine.lm_steps import _lm_sp_step_fn, _sp_window_slices
    from tpu_dist.parallel.ring_attention import ring_attention_fn

    mesh = b.mesh
    data_axis, seq_axis = plan.data_axis, plan.seq_axis
    model = b.model_ctor(attn_fn=ring_attention_fn(seq_axis))
    one_step = _lm_sp_step_fn(model, b.tx, plan.aux_weight, data_axis,
                              seq_axis, plan.loss_chunk, plan.health)

    if plan.window == "none":
        return _shard_map_jit(
            one_step, mesh,
            (P(), P(data_axis, seq_axis), P(data_axis, seq_axis), P()),
            (P(), P()), plan.donate)

    n_seq = mesh.shape[seq_axis]

    def per_device(state: TrainState, rows_all, idx, rng):
        shard_len = (rows_all.shape[1] - 1) // n_seq
        seq_idx = jax.lax.axis_index(seq_axis)

        def body(st, idx_b):
            rows = jnp.take(rows_all, idx_b, axis=0)
            inputs, targets = _sp_window_slices(rows, seq_idx, shard_len)
            return one_step(st, inputs, targets, rng)

        state, metrics_k = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)

    return _shard_map_jit(per_device, mesh,
                          (P(), P(), P(None, data_axis), P()),
                          (P(), P()), plan.donate)


def _lm_train(plan: Plan, b: Bindings) -> Callable:
    """The gspmd LM train lowerings around the ONE template
    (engine.lm_steps._lm_step_fn): dp, and dp x tp / fsdp / ep when the
    TrainState was placed with the matching sharding helper (GSPMD
    propagates the param layout and emits the collectives).

    * ``window='none'``: (state, inputs (B,L), targets (B,L), rng), batch
      sharded on ``data``.
    * ``window='indexed'``: K optimizer steps per dispatch from an
      HBM-RESIDENT token corpus — (state, rows_all (N, L+1) i32
      REPLICATED, idx (K, B) i32 sharded (None, data), rng) -> (state,
      metrics summed over K steps). Each scan iteration gathers its
      (B, L+1) rows and shifts inputs/targets ON DEVICE; the host sends
      only the index window. Identical math to K sequential single steps
      (same per-step rng fold)."""
    from tpu_dist.engine.lm_steps import _lm_step_fn

    mesh = b.mesh
    data_axis = plan.data_axis
    repl = NamedSharding(mesh, P())
    one_step = _lm_step_fn(b.model, b.tx, plan.aux_weight, plan.loss_chunk,
                           plan.health)

    if plan.window == "none":
        batch_sh = NamedSharding(mesh, P(data_axis))
        # With TP the state arrives pre-sharded (parallel.tp
        # shard_lm_params) and in_shardings=None lets GSPMD propagate that
        # layout through the step; pure DP states arrive replicated — the
        # same jit serves both. out_shardings=None likewise.
        return jax.jit(one_step,
                       in_shardings=(None, batch_sh, batch_sh, repl),
                       out_shardings=None,
                       donate_argnums=(0,) if plan.donate else ())

    idx_sh = NamedSharding(mesh, P(None, data_axis))

    def multi(state: TrainState, rows_all, idx, rng):
        def body(st, idx_b):
            rows = jnp.take(rows_all, idx_b, axis=0)     # (B, L+1)
            return one_step(st, rows[:, :-1], rows[:, 1:], rng)
        state, metrics_k = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics_k)

    return _jit_gspmd(multi, (None, repl, idx_sh, repl), (None, repl),
                      plan.donate)


def _lm_sp_eval(plan: Plan, b: Bindings) -> Callable:
    """Held-out eval under sequence parallelism, :func:`_lm_eval`'s two
    signatures with (data, seq)-sharded tokens, ring attention, and metric
    sums psum'd over BOTH axes."""
    from tpu_dist.engine.lm_steps import (_lm_eval_metrics,
                                          _sp_window_slices,
                                          zeros_lm_metrics)
    from tpu_dist.parallel.ring_attention import ring_attention_fn

    mesh = b.mesh
    data_axis, seq_axis = plan.data_axis, plan.seq_axis
    loss_chunk = plan.loss_chunk
    model = b.model_ctor(attn_fn=ring_attention_fn(seq_axis))

    if plan.window != "indexed":
        def per_device(params, inputs, targets, valid):
            seq_idx = jax.lax.axis_index(seq_axis)
            pos_offset = seq_idx * inputs.shape[1]
            mask = jnp.broadcast_to(valid[:, None], targets.shape).astype(
                jnp.float32)
            metrics = _lm_eval_metrics(model, params, inputs, targets,
                                       mask, loss_chunk, pos_offset)
            return jax.tree.map(
                lambda m: jax.lax.psum(jax.lax.psum(m, seq_axis),
                                       data_axis), metrics)

        sharded = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(data_axis, seq_axis), P(data_axis, seq_axis),
                      P(data_axis)),
            out_specs=P(), check_vma=False)
        return jax.jit(sharded)

    n_seq = mesh.shape[seq_axis]

    def per_device(params, rows_all, idx, valid):
        shard_len = (rows_all.shape[1] - 1) // n_seq
        seq_idx = jax.lax.axis_index(seq_axis)
        pos_offset = seq_idx * shard_len

        def body(sums, blk):
            idx_b, valid_b = blk
            rows = jnp.take(rows_all, idx_b, axis=0)
            inputs, targets = _sp_window_slices(rows, seq_idx, shard_len)
            mask = jnp.broadcast_to(valid_b[:, None], targets.shape).astype(
                jnp.float32)
            m = _lm_eval_metrics(model, params, inputs, targets, mask,
                                 loss_chunk, pos_offset)
            return jax.tree.map(jnp.add, sums, m), None

        sums, _ = jax.lax.scan(body, zeros_lm_metrics(), (idx, valid))
        return jax.tree.map(
            lambda m: jax.lax.psum(jax.lax.psum(m, seq_axis), data_axis),
            sums)

    sharded = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(), P(None, data_axis), P(None, data_axis)),
        out_specs=P(), check_vma=False)
    return jax.jit(sharded)


def _lm_eval(plan: Plan, b: Bindings) -> Callable:
    """GSPMD LM eval lowerings, for any placement the params carry (dp /
    fsdp / tp / ep): (params, inputs, targets, valid (B,)) -> {loss_sum,
    correct1, count}, or for ``window='indexed'`` whole-val-set perplexity
    in ONE dispatch — (params, rows_all (N, L+1) REPLICATED, idx (K, B) i32
    sharded (None, data), valid (K, B) f32 same sharding). ``valid`` 0/1
    excludes sampler wrap-padding rows, so perplexity is exact."""
    from tpu_dist.engine.lm_steps import _lm_eval_metrics, zeros_lm_metrics

    mesh = b.mesh
    model = b.model
    data_axis = plan.data_axis
    loss_chunk = plan.loss_chunk
    repl = NamedSharding(mesh, P())

    if plan.window != "indexed":
        batch_sh = NamedSharding(mesh, P(data_axis))

        def step(params, inputs, targets, valid):
            mask = jnp.broadcast_to(valid[:, None], targets.shape).astype(
                jnp.float32)
            return _lm_eval_metrics(model, params, inputs, targets, mask,
                                    loss_chunk)

        return jax.jit(step, in_shardings=(None, batch_sh, batch_sh,
                                           batch_sh),
                       out_shardings=NamedSharding(mesh, P()))

    idx_sh = NamedSharding(mesh, P(None, data_axis))

    def step(params, rows_all, idx, valid):
        def body(sums, blk):
            idx_b, valid_b = blk
            rows = jnp.take(rows_all, idx_b, axis=0)
            inputs, targets = rows[:, :-1], rows[:, 1:]
            mask = jnp.broadcast_to(valid_b[:, None], targets.shape).astype(
                jnp.float32)
            m = _lm_eval_metrics(model, params, inputs, targets, mask,
                                 loss_chunk)
            return jax.tree.map(jnp.add, sums, m), None

        sums, _ = jax.lax.scan(body, zeros_lm_metrics(), (idx, valid))
        return sums

    return jax.jit(step, in_shardings=(None, repl, idx_sh, idx_sh),
                   out_shardings=repl)


# ---- dispatch -------------------------------------------------------------

def _lower_train(plan: Plan, b: Bindings) -> Callable:
    if plan.engine == "image":
        if plan.grad_accum_steps > 1:
            return _image_accum_train(plan, b)
        if plan.sync == "explicit":
            return _image_explicit_train(plan, b)
        return _image_train(plan, b)
    if plan.grad_accum_steps > 1:
        return _lm_accum_train(plan, b)
    if plan.layout == "sp":
        return _lm_sp_train(plan, b)
    if plan.sync == "explicit":
        return _lm_explicit_train(plan, b)
    return _lm_train(plan, b)


def _lower_eval(plan: Plan, b: Bindings) -> Callable:
    if plan.engine == "image":
        return _image_eval(plan, b)
    if plan.layout == "sp":
        return _lm_sp_eval(plan, b)
    return _lm_eval(plan, b)


# ---- plan activation + the config knob ------------------------------------

def activate_plan(plan: Plan) -> None:
    """Apply the plan's global TRACE-TIME switches: the fused int8 Pallas
    kernel dispatch (ops.quant.set_fused_quant) and the searchable Pallas
    block sizes (ops.pallas_quant / pallas_sgd / pallas_adamw). Call
    BEFORE building step functions — these are read at trace time."""
    from tpu_dist.ops import pallas_adamw, pallas_quant, pallas_sgd
    from tpu_dist.ops.quant import set_fused_quant

    set_fused_quant({"auto": None, "on": True, "off": False}[
        plan.fused_quant])
    pallas_quant.set_quant_blocks(*plan.quant_block)
    pallas_sgd.set_block_rows(plan.opt_block_rows)
    pallas_adamw.set_block_rows(plan.opt_block_rows)


def _auto_workload(cfg, engine: str) -> dict:
    """A tuner workload from a config (the 'auto' knob's input): crude
    param counts are fine — the search ranks knobs, it does not predict
    wall time."""
    if engine == "lm":
        n = (cfg.vocab_size * cfg.d_model
             + cfg.num_layers * 12 * cfg.d_model * cfg.d_model)
        toks = cfg.batch_size * cfg.seq_len
    else:
        n = 25_000_000                       # resnet50-scale placeholder
        toks = cfg.batch_size
    return {"engine": engine, "n_params": float(n),
            "tokens_per_step": float(toks),
            "devices": jax.device_count()}


def _auto_filter(cfg, engine: str):
    """Prune 'auto' candidates to what THIS config can legally run (an
    explicit plan file is applied as-is and may fail loudly; auto must
    never break a working config)."""
    mesh_shape = getattr(cfg, "mesh_shape", None) or ()
    mesh_axes = tuple(getattr(cfg, "mesh_axes", ("data",)))
    multi = {a for a, s in zip(mesh_axes, mesh_shape) if a != "data"
             and (s is None or s > 1)}
    pure_dp = not multi and not getattr(cfg, "fsdp", False)
    accum = getattr(cfg, "grad_accum_steps", 1) > 1
    host_data = getattr(cfg, "data_placement", "auto") == "host"
    quant_ok = (engine == "lm"
                or getattr(cfg, "arch", "").startswith("vit"))

    def keep(plan: Plan) -> bool:
        if plan.quant != "none" and not quant_ok:
            return False
        if plan.grad_bucket_mb > 0 and not (pure_dp and not accum):
            return False
        if plan.sync == "explicit" and not pure_dp:
            return False
        if plan.window != "none" and (host_data or accum):
            return False
        if plan.window != "none" and engine == "image" \
                and getattr(cfg, "dataset", "") == "imagenet":
            # imagefolder datasets are not HBM-resident ArrayDatasets;
            # the indexed window would refuse at Trainer init
            return False
        return True

    return keep


def resolve_config_plan(cfg):
    """Implement the configs' ``plan`` knob: ``''``/``'none'`` -> no-op;
    a path -> load the (per-device-kind) plan file; ``'auto'`` -> run the
    tuner's analytic search for this device kind, pruned to what the
    config can run. Returns ``(new_cfg, plan_info | None)`` where
    plan_info is the {'source', 'hash', 'knobs', 'plan'} record the
    engines stamp into run_start + the ``plan`` ledger event. Applies the
    plan's trace-time switches (:func:`activate_plan`) as a side effect.
    """
    spec = getattr(cfg, "plan", "") or ""
    if spec in ("", "none"):
        return cfg, None
    from tpu_dist.plan import ir

    engine = "image" if any(f.name == "variant"
                            for f in dataclasses.fields(type(cfg))) \
        else "lm"
    device_kind = getattr(jax.devices()[0], "device_kind", "unknown")
    if spec == "auto":
        from tpu_dist.plan import tune as tune_mod
        keep = _auto_filter(cfg, engine)
        # knobs the auto space does NOT search are carried from the
        # config, never reset to Plan defaults — 'auto' tunes what it
        # explores and must leave the rest of a working config alone
        # (precision/bf16, grad accumulation, chunked CE, health policy,
        # tp_impl all stay the user's choice)
        carried = {k: getattr(cfg, k) for k in
                   ("precision", "grad_accum_steps", "health", "tp_impl")
                   if hasattr(cfg, k)}
        if engine == "lm":
            carried["loss_chunk"] = getattr(cfg, "loss_chunk", 0)
        space = []
        for p in tune_mod.default_space(engine, jax.device_count()):
            try:
                p = dataclasses.replace(p, **carried).validate()
            except PlanError:
                continue   # carried knobs make this candidate illegal
            if keep(p):
                space.append(p)
        if not space:
            # abstaining must be LOUD: "the tuner found nothing legal for
            # this config" (e.g. tp_impl='ring' — outside the searched
            # space) is different from "the tuner never ran"
            import sys
            print("plan=auto: no legal candidate plans for this config "
                  "(its knobs fall outside the searched space); running "
                  "with the hand-set knobs", file=sys.stderr)
            return cfg, None
        result = tune_mod.search(workload=_auto_workload(cfg, engine),
                                 device_kind=device_kind, space=space)
        if result["best"] is None:
            return cfg, None
        plan = result["best"]["plan"]
        source = "auto"
    else:
        plans = ir.load_plan_file(spec)
        plan = ir.plan_for_device(plans, device_kind)
        source = spec
    if plan.engine != engine:
        raise PlanError(f"plan engine {plan.engine!r} does not drive the "
                        f"{engine} engine (plan source: {source})")
    new_cfg = apply_plan_to_config(cfg, plan)
    activate_plan(plan)
    info = {"source": source, "hash": plan_hash(plan),
            "knobs": plan_knob_summary(plan), "plan": plan,
            "device_kind": device_kind}
    return new_cfg, info
