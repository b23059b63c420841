"""Trainer: epoch loop, distributed eval, metering, CSV logs (C14/C15/C17/C21).

Orchestrates the reference's train()/validate()/checkpoint skeleton (reference
2.distributed.py:166-189) around the fused TPU step functions. Differences by
design:

* metric tensors are NOT pulled to host every batch (the reference's
  per-batch barrier+allreduce serialized the step — SURVEY.md §3.2 note);
  device metrics are fetched only at print-frequency boundaries, so the TPU
  queue stays full (JAX async dispatch);
* printing/logging is process-0-only (the reference printed on every rank —
  duplicated output, 2.distributed.py:238-239);
* per-epoch CSV timing matches reference format [wall_start, seconds]
  (reference 1.dataparallel.py:187-190).
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist import configs
from tpu_dist.data import (DataLoader, DistributedSampler, assemble_global,
                           load_dataset, make_transform, prefetch_to_device)
from tpu_dist.engine import checkpoint as ckpt
from tpu_dist.engine.state import TrainState, init_model
from tpu_dist.engine.steps import pack_images_for_device
from tpu_dist.models import create_model
from tpu_dist.obs import (HealthError, RunObs, faults, profile_session,
                          step_annotation, trace)
from tpu_dist.ops import LossScaleState, make_optimizer, make_policy, step_decay_schedule
from tpu_dist.parallel.mesh import batch_sharding, make_mesh, replicated
from tpu_dist.parallel.supervisor import PREEMPT_SNAPSHOT_RC
from tpu_dist.plan.compile import (Bindings, compile_eval_step,
                                   compile_train_step)
from tpu_dist.plan.ir import plan_from_config
from tpu_dist.runtime import pallas_interpret
from tpu_dist.utils.meters import MeterBank


class Trainer:
    """One engine for all cookbook variants; flavor picked by config.

    ``cfg.variant``: 'jit' (compiler-partitioned, DDP-equiv) or 'shard_map'
    (explicit psum, horovod-equiv). Which step programs that means is ONE
    decision, ``self.plan`` (``plan.ir.plan_from_config`` of the config,
    the mesh and where the data lives), compiled in :meth:`_build_steps`.
    Multi-host vs single-host is decided by how the process was launched
    (tpu_dist.parallel.launch), not here.
    """

    def __init__(self, cfg: configs.TrainConfig, mesh=None):
        # construction is one span with its three heavy parts beneath it
        # (train.build > build.data, build.init, build.place); fit()'s
        # run_start record carries their seconds and how many backend
        # compilations the constructor made (a handful: an eager init
        # creeping back in reads in the hundreds)
        compiles = trace.backend_compiles()
        trace.gc_seconds()       # full collections are host.gc spans from here
        self._build_tracer = trace.StepTracer("build.")
        with trace.ring().span("train.build") as built:
            self._build(cfg, mesh)
        self.build_info = {
            "build_s": {"total": round(built.seconds, 3),
                        **{k: round(v, 3)
                           for k, v in self._build_tracer.pop().items()}},
            "build_compiles": trace.backend_compiles() - compiles}

    def _build(self, cfg: configs.TrainConfig, mesh) -> None:
        # step plan (tpu_dist.plan): the `plan` knob rewrites the
        # plan-owned config fields (incl. variant) and flips the
        # trace-time kernel switches BEFORE anything below reads them
        from tpu_dist.plan.compile import resolve_config_plan
        cfg, self._plan_info = resolve_config_plan(cfg)
        self.cfg = cfg
        # fail fast on bad config, before device/model setup
        if cfg.resume and not os.path.exists(cfg.resume):
            raise FileNotFoundError(f"--resume checkpoint not found: {cfg.resume}")
        if cfg.pretrained and not os.path.exists(cfg.pretrained):
            raise FileNotFoundError(
                f"--pretrained checkpoint not found: {cfg.pretrained}")
        if cfg.optimizer not in ("sgd", "fused_sgd", "adamw"):
            raise ValueError(f"unknown optimizer {cfg.optimizer!r} "
                             "(sgd|fused_sgd|adamw)")
        from tpu_dist.models.registry import model_kind
        if model_kind(cfg.arch) != "image":
            raise ValueError(
                f"--arch {cfg.arch} is a language model; this trainer drives "
                "image classifiers — use scripts/8.lm_longcontext.py")
        if cfg.tp_impl == "ring" and not cfg.arch.startswith("vit"):
            # ring collective-matmul TP (parallel.overlap) is for the
            # transformer-family image archs
            raise ValueError(
                f"--tp-impl ring applies to the transformer-family "
                f"image archs (vit_*); arch {cfg.arch!r} has no "
                "column/row-parallel projections")
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh_shape, cfg.mesh_axes)
        # the step plan of this config on this mesh, BEFORE any build:
        # variant/health/tp_impl spellings and every mode exclusion
        # (Plan.validate) fail here; the window kind joins it once the
        # data set has been measured (_build_steps)
        self.plan = plan_from_config(cfg, dict(self.mesh.shape))
        self.policy = make_policy(cfg.precision)
        with self._build_tracer.span("data"):
            self.train_ds, self.val_ds = load_dataset(
                cfg.dataset, cfg.data, cfg.synth_train_size,
                cfg.synth_val_size,
                seed=cfg.seed if cfg.seed is not None else 1234)
        self.num_classes = self.train_ds.num_classes

        nprocs = jax.process_count()
        # global batch divided per process (reference 2.distributed.py:113);
        # then further split per device by the mesh sharding.
        ndev = self.mesh.devices.size
        # nprocs always divides ndev (equal local devices per process), so
        # batch % ndev == 0 also guarantees an integral per-process batch
        if cfg.batch_size % ndev:
            raise ValueError(
                f"global batch {cfg.batch_size} not divisible by device count "
                f"{ndev} ({nprocs} processes x {ndev // nprocs} local devices)")
        self.local_batch = cfg.batch_size // nprocs

        model_kw = {}
        if cfg.norm:
            model_kw["norm"] = cfg.norm
        if cfg.norm_dtype:
            if cfg.norm_dtype not in ("bf16", "fp32"):
                raise ValueError(f"--norm-dtype {cfg.norm_dtype!r} "
                                 "(bf16|fp32)")
            if cfg.norm_dtype == "bf16":
                model_kw["norm_dtype"] = jnp.bfloat16
        if cfg.stem:
            model_kw["stem"] = cfg.stem
        if model_kw and not cfg.arch.startswith(("resnet", "resnext",
                                                 "wide_resnet")):
            raise ValueError(
                f"--norm/--norm-dtype/--stem are ResNet-family knobs; "
                f"arch {cfg.arch!r} does not take them")
        if cfg.quant and cfg.quant != "none":
            from tpu_dist.ops.quant import validate_quant
            validate_quant(cfg.quant)
            if not cfg.arch.startswith("vit"):
                # int8 matmuls live in the transformer families (ops.quant);
                # conv stacks would need a quantized-conv path this repo
                # does not carry yet — refuse rather than silently ignore
                raise ValueError(
                    f"--quant {cfg.quant} applies to the transformer-family "
                    f"image archs (vit_*); arch {cfg.arch!r} does not take it")
            model_kw["quant"] = cfg.quant
        self.model = create_model(
            cfg.arch, num_classes=self.num_classes,
            dtype=self.policy.compute_dtype, pretrained=cfg.pretrained,
            warmstart_handled=True,  # grafted below (registry guard)
            **model_kw)
        if cfg.tp_impl == "ring":
            # config-time twin of the LMTrainer check: each shard's qkv
            # slice must hold whole heads (vit_tiny's 3 heads cannot split
            # over a 2-wide model axis)
            tp = self.mesh.shape["model"]
            heads = getattr(self.model, "num_heads", 0)
            if heads % tp:
                raise ValueError(
                    f"--tp-impl ring shards attention heads: num_heads "
                    f"{heads} of {cfg.arch!r} must divide by the 'model' "
                    f"axis ({tp})")

        seed = cfg.seed if cfg.seed is not None else 0
        self.rng = jax.random.PRNGKey(seed)
        h, w, c = self.train_ds.image_shape

        # ceil: the sampler pads to full batches, so an epoch really runs
        # ceil(N/batch) optimizer steps — floor would fire LR decay early
        self.steps_per_epoch = max(1, -(-len(self.train_ds) // cfg.batch_size))
        self.schedule = step_decay_schedule(
            cfg.scaled_lr(jax.device_count() if cfg.lr_scale_by_world else 1),
            self.steps_per_epoch, cfg.lr_step_epochs)
        if cfg.optimizer == "fused_sgd":  # validated at __init__ entry
            from tpu_dist.ops.pallas_sgd import FusedSGD
            self.tx = FusedSGD(self.schedule, cfg.momentum, cfg.weight_decay,
                               interpret=pallas_interpret())
        else:
            self.tx = make_optimizer(
                cfg.lr, cfg.momentum, cfg.weight_decay, self.steps_per_epoch,
                cfg.lr_step_epochs, schedule=self.schedule,
                kind=cfg.optimizer, b1=cfg.adam_b1, b2=cfg.adam_b2,
                eps=cfg.adam_eps)

        def make_state(key):
            params, batch_stats = init_model(self.model, key, (2, h, w, c))
            return TrainState.create(
                self.policy.cast_params_for_storage(params), batch_stats,
                self.tx, (LossScaleState.create(cfg.loss_scale)
                          if cfg.loss_scale else None))

        with self._build_tracer.span("init"):
            # the whole state (parameters, batch statistics, the storage
            # cast, tx.init, the loss scale) is born in ONE compiled
            # program of the PRNG key, replicated over the mesh where it
            # comes out. Eager, the same work was some 230 one-operation
            # compilations for ResNet-50, then a copy of the whole tree
            # from the default device. What equals the eager values, on
            # the CPU and on the chip: init_model's docstring, which
            # LMTrainer's jitted init (lm_loop.py) shares.
            self.state = jax.jit(
                make_state, out_shardings=replicated(self.mesh))(self.rng)
            if cfg.pretrained:  # existence checked first-line in __init__
                self._graft_pretrained()
            jax.block_until_ready(self.state)

        augment = self.train_ds.name.startswith(("imagenet", "synth-imagenet"))
        self.transform = make_transform(
            self.train_ds.mean, self.train_ds.std, augment=augment,
            dtype=self.policy.compute_dtype)
        eval_transform = make_transform(
            self.val_ds.mean, self.val_ds.std, augment=False,
            dtype=self.policy.compute_dtype)

        # gradient accumulation: split each global batch into N sequential
        # microbatches whose grads average into ONE optimizer step — for
        # global batches beyond HBM
        self.accum = cfg.grad_accum_steps
        if self.accum > 1 and cfg.batch_size % (self.accum * ndev):
            raise ValueError(
                f"global batch {cfg.batch_size} not divisible by "
                f"grad_accum_steps x device count ({self.accum} x {ndev})")

        # K-steps-per-dispatch window. Math is identical to K sequential
        # dispatches; only the host round-trip count changes.
        self.k = cfg.steps_per_dispatch
        if cfg.data_placement not in ("auto", "host", "device"):
            raise ValueError(f"unknown data_placement {cfg.data_placement!r}")
        in_memory = isinstance(getattr(self.train_ds, "images", None), np.ndarray)
        if cfg.data_placement == "device" and not in_memory:
            raise ValueError("data_placement='device' needs an in-memory "
                             "(ArrayDataset) training set")
        # budget covers BOTH splits when the val set can ride along into HBM
        # (in-memory, same image shape — the upload gate below)
        val_rides = (in_memory and
                     isinstance(getattr(self.val_ds, "images", None),
                                np.ndarray)
                     and self.val_ds.image_shape == self.train_ds.image_shape)
        data_bytes = (self.train_ds.images.nbytes
                      + (self.val_ds.images.nbytes if val_rides else 0)
                      ) if in_memory else 0
        fits_hbm = (in_memory and data_bytes
                    <= int(os.environ.get("TPU_DIST_DEVICE_DATA_MAX",
                                          str(1 << 30))))
        self.device_data = (cfg.data_placement == "device" or
                            (cfg.data_placement == "auto" and fits_hbm
                             and self.k > 1))
        self._build_steps(eval_transform, val_rides)
        self._train_data_dev = None
        self._val_data_dev = None
        self._prefetched_windows = None  # (epoch, [(n, device idx window)])
        self._dispatched_windows = set()  # window lengths dispatched once
        if self.device_data:
            # whole training set resident in HBM (rows packed into i32 words
            # for native 32-bit gathers), replicated per chip; per-step
            # batches are gathered on device from an index window
            with self._build_tracer.span("place"):
                self._train_data_dev = (
                    jax.device_put(
                        pack_images_for_device(self.train_ds.images),
                        replicated(self.mesh)),
                    jax.device_put(self.train_ds.labels.astype(np.int32),
                                   replicated(self.mesh)))
                # the val set rides along in HBM too (same placement
                # rules): the whole distributed eval becomes ONE dispatch
                # per epoch
                if val_rides:
                    self._val_data_dev = (
                        jax.device_put(
                            pack_images_for_device(self.val_ds.images),
                            replicated(self.mesh)),
                        jax.device_put(self.val_ds.labels.astype(np.int32),
                                       replicated(self.mesh)))
                # the span ends when the rows have landed
                jax.block_until_ready(
                    (self._train_data_dev, self._val_data_dev))

        self.batch_sharding = batch_sharding(self.mesh)
        self.best_acc1 = 0.0
        self.start_epoch = cfg.start_epoch
        self._skip_batches = 0
        self.is_main = jax.process_index() == 0
        # geometry stamped into every checkpoint: resume math (step ->
        # epoch/skip mapping, LR schedule) is only valid against the same
        # steps_per_epoch, and the blob only loads correctly into the same
        # model/dataset shapes (flax from_bytes does NOT validate them) —
        # mismatches must not pass silently
        self._run_meta = {"steps_per_epoch": self.steps_per_epoch,
                          "batch_size": cfg.batch_size,
                          "dataset_len": len(self.train_ds),
                          "arch": cfg.arch,
                          "dataset": self.train_ds.name,
                          "num_classes": self.num_classes,
                          "image_shape": list(self.train_ds.image_shape)}

        if cfg.resume:
            # hard geometry first, from the meta header alone: a wrong-arch
            # blob fails inside flax from_bytes with an opaque structure
            # mismatch, so the clear error must fire BEFORE deserialization
            pre = ckpt.read_checkpoint_meta(cfg.resume)
            hard_pre = {k: (pre[k], v) for k, v in self._run_meta.items()
                        if k in ("arch", "num_classes", "image_shape")
                        and k in pre and pre[k] != v}
            if hard_pre:
                raise ValueError(
                    "--resume checkpoint is from a different model geometry ("
                    + ", ".join(f"{k}: checkpoint {a} vs run {b}"
                                for k, (a, b) in hard_pre.items()) + ")")
            self.state, meta = ckpt.load_checkpoint(cfg.resume, self.state)
            self.state = jax.device_put(self.state, replicated(self.mesh))
            self.start_epoch = meta.get("epoch", 0)
            self.best_acc1 = meta.get("best_acc1", 0.0)
            self.log(f"=> resumed from {cfg.resume} (epoch {self.start_epoch})")
            mismatch = {k: (meta[k], v) for k, v in self._run_meta.items()
                        if k in meta and meta[k] != v}
            detail = ", ".join(f"{k}: checkpoint {a} vs run {b}"
                               for k, (a, b) in mismatch.items())
            # model/input identity: the blob would load into wrong-shaped
            # arrays without any error from flax (or train a wrong-width
            # head) — always fatal
            hard = {"arch", "num_classes", "image_shape"} & mismatch.keys()
            if hard:
                raise ValueError(
                    f"--resume checkpoint is from a different model geometry "
                    f"({detail})")
            if mismatch:
                if meta.get("mid_epoch"):
                    # the skip count below would misplace the resume point:
                    # double-applied or skipped batches + LR-schedule drift
                    raise ValueError(
                        "mid-epoch resume requires the checkpoint's data/"
                        f"batch geometry ({detail})")
                self.log(f"warning: resume with changed geometry ({detail}); "
                         "the LR schedule will not line up with the original run")
            # mid-epoch (interrupt) checkpoint: the sampler's per-epoch
            # permutation is deterministic, so resume is STEP-exact — derive
            # the true epoch from the step counter and skip the batches whose
            # updates are already in the state (no double-applied gradients,
            # no LR-schedule drift). Covers interrupts during validation too
            # (training complete -> next epoch, zero skips). The reference
            # had no resume at all.
            if meta.get("mid_epoch"):
                step_done = int(jax.device_get(self.state.step))
                self.start_epoch = step_done // self.steps_per_epoch
                self._skip_batches = step_done % self.steps_per_epoch
                if self._skip_batches:
                    self.log(f"=> mid-epoch checkpoint: resuming epoch "
                             f"{self.start_epoch}, skipping "
                             f"{self._skip_batches} already-applied batches")
        # checkpoint-less dp-pure recovery (round 13): on a supervisor
        # mesh re-expansion (TPU_DIST_PEER_RESUME), adopt a survivor's
        # live replicated state over a broadcast collective — the joining
        # host has no local checkpoint, and the consensus renumbering
        # keeps process 0 a survivor. Replicated (pure-dp) layouts only.
        self._dp_pure = all(s == 1 for n, s in self.mesh.shape.items()
                            if n != "data")
        self._peer_restored = False
        if os.environ.get("TPU_DIST_PEER_RESUME") == "1" and self._dp_pure:
            host_state, did = ckpt.peer_restore_state(self.state)
            if did:
                self._peer_restored = True
                self.state = jax.device_put(host_state,
                                            replicated(self.mesh))
                # epoch/skip re-derive from the adopted step counter —
                # the same math as a mid-epoch resume
                step_done = int(np.asarray(host_state.step))
                self.start_epoch = step_done // self.steps_per_epoch
                self._skip_batches = step_done % self.steps_per_epoch
                self.log(f"=> peer-restored state from a survivor at step "
                         f"{step_done} (no disk round-trip); resuming "
                         f"epoch {self.start_epoch}")
        self._epoch_in_progress = self.start_epoch
        self._program_hbm = None    # post-dispatch probe (telemetry contract)
        self._program_flops = None  # per-device step FLOPs (XLA cost model)
        # run observability: ledger + step tracer + skew monitor + hang
        # watchdog, wired from cfg (obs.RunObs); a pathless ledger is free
        self.obs = RunObs("image", cfg, self.mesh, unit="img/s",
                          plan_info=self._plan_info)
        # program audit (tpu_dist.analysis.proglint via plan.compile):
        # armed here so the compile-time pass and the drain-boundary
        # recompile sentry see every program this run builds
        from tpu_dist.plan.compile import set_audit
        set_audit(cfg.audit, self.obs.ledger)
        # whether int8 matmuls (vit_* quant archs) route through the fused
        # Pallas kernel — trace-time static; stamped into step records so
        # ledger_report can attribute MFU deltas (LMTrainer twin)
        from tpu_dist.ops.quant import fused_quant_active
        self._fused_quant = cfg.quant == "int8" and fused_quant_active()

    # ------------------------------------------------------------------
    def _graft_pretrained(self) -> None:
        """Warm start: the donor's tensors over the fresh state's where
        path and shape match (fresh optimizer state: no ``tx.init`` here
        depends on a parameter's value)."""
        cfg = self.cfg
        pre_params, pre_stats, pre_meta = ckpt.load_warmstart(cfg.pretrained)
        params, n_p, skipped = ckpt.graft_params(self.state.params,
                                                 pre_params)
        batch_stats, n_s, _ = ckpt.graft_params(self.state.batch_stats,
                                                pre_stats)
        if n_p == 0:
            raise ValueError(
                f"--pretrained {cfg.pretrained} (arch "
                f"{pre_meta.get('arch', '?')!r}) shares no tensors with "
                f"{cfg.arch!r} — wrong checkpoint?")
        self.log(f"=> warm-started {n_p} param tensors (+{n_s} BN stats)"
                 f" from {cfg.pretrained}"
                 + (f"; fresh init kept for {skipped}" if skipped else ""))
        self.state = jax.device_put(
            self.state.replace(params=params, batch_stats=batch_stats),
            replicated(self.mesh))

    def _build_steps(self, eval_transform, val_rides: bool) -> None:
        """THE place the step programs come from: the config's plan with
        the window kind the data allows, compiled against this run's
        objects. ``train_step``/``eval_step`` take one host-fed batch;
        ``window_step`` takes K steps a dispatch (device-resident rows by
        index, or host-fed stacked batches), ``window_eval_step`` the whole
        resident validation set."""
        window = ("indexed" if self.device_data
                  else "stacked" if self.k > 1 else "none")
        self.plan = dataclasses.replace(self.plan, window=window)
        binds = Bindings(mesh=self.mesh, model=self.model, tx=self.tx,
                         transform=self.transform,
                         eval_transform=eval_transform,
                         image_shape=self.train_ds.image_shape)
        per_batch = dataclasses.replace(self.plan, window="none")
        self.train_step = compile_train_step(per_batch, binds)
        self.eval_step = compile_eval_step(per_batch, binds)
        if window != "none":
            self.window_step = compile_train_step(self.plan, binds)
        if window == "indexed" and val_rides:
            self.window_eval_step = compile_eval_step(self.plan, binds)

    def log(self, *a, **k):
        # getattr: log is callable from __init__ before is_main is set
        if getattr(self, "is_main", jax.process_index() == 0):
            print(*a, **k, flush=True)

    def _sampler(self, ds, train: bool, epoch: int) -> DistributedSampler:
        sampler = DistributedSampler(
            len(ds), num_replicas=jax.process_count(),
            rank=jax.process_index(), shuffle=train,
            seed=(self.cfg.seed or 0) + (17 if not train else 0),
            batch_size=self.local_batch)
        sampler.set_epoch(epoch)
        return sampler

    def _loader(self, ds, train: bool, epoch: int) -> DataLoader:
        return DataLoader(ds, self._sampler(ds, train, epoch), self.local_batch,
                          workers=self.cfg.workers, emit_valid=not train)

    def _drain(self, pending, meters) -> None:
        """Pull queued device metric sums into the meter bank (ONE blocking
        transfer per print window — the async-dispatch sync point) and emit
        one ledger ``step`` record per drained entry: the device-block time
        of the transfer is apportioned across the window's steps, so every
        record carries the full data/dispatch/device phase breakdown. The
        fused health probes (obs.health) ride the same fetch; the sentry
        consumes them here — under ``skip`` a non-finite record is kept
        out of the meter averages (its update was already zeroed on
        device), and under ``halt`` the sentry raises out of the loop."""
        tr = self.obs.tracer
        with tr.span("wait"):
            # distlint: disable=DL002 -- THE drain boundary: the one sanctioned fetch point of the loop
            fetched = jax.device_get([m for m, _ in pending])
        device_s = tr.pop().get("wait", 0.0)
        total_steps = sum(info["n_steps"] for _, info in pending) or 1
        # everything from the transfer's return to this function's: the
        # step records and their fan-out to the ledger's sinks, health,
        # heartbeat (what the observability costs a drain)
        with tr.span("emit", step=pending[-1][1]["step"], steps=total_steps):
            self._emit_records(fetched, pending, meters, device_s,
                               total_steps)

    def _emit_records(self, fetched, pending, meters, device_s: float,
                      total_steps: int) -> None:
        import math

        from tpu_dist.utils.telemetry import device_memory_stats
        hbm = device_memory_stats()
        for m, (_, info) in zip(fetched, pending):
            cnt = float(m["count"])
            loss = float(m["loss_sum"]) / cnt
            acc1 = float(m["correct1"]) / cnt
            # under 'skip' the non-finite step's update was zeroed on
            # device, so its NaN loss must not poison the epoch averages;
            # under 'record'/'halt' the NaN flows through — divergence
            # should be VISIBLE in the printed loss, as it always was
            if math.isfinite(loss) or self.obs.health.policy != "skip":
                meters.update("Loss", loss, int(cnt))
                meters.update("Acc@1", acc1, int(cnt))
                meters.update("Acc@5", float(m["correct5"]) / cnt, int(cnt))
            n = info["n_steps"]
            share = device_s * n / total_steps
            gn = float(m["grad_norm"]) / n
            nf = float(m["nonfinite_count"])
            un = float(m["update_norm"]) / n
            self.obs.step(
                info["step"], loss, info["n_items"],
                wall_s=info["data_s"] + info["dispatch_s"] + share,
                data_s=info["data_s"], dispatch_s=info["dispatch_s"],
                device_s=share, device_flops=self._program_flops,
                steps_in_dispatch=n,
                warm=info.get("warm", False), fused=self._fused_quant,
                acc1=acc1,
                grad_norm=gn, nonfinite_count=nf, update_norm=un,
                hbm_bytes_in_use=hbm.get("bytes_in_use"),
                hbm_peak_bytes=hbm.get("peak_bytes_in_use"))
            self.obs.health.observe(info["step"], loss, nonfinite=nf,
                                    grad_norm=gn, update_norm=un, n_steps=n)
        pending.clear()
        self.obs.heartbeat()  # watchdog: device progress proven at this sync
        # recompile sentry (PL005): a host-only trace-cache counter read
        # at the sanctioned boundary — no device sync rides on it
        from tpu_dist.plan.compile import check_audit_sentry
        check_audit_sentry()

    def _apply_nan_fault(self) -> None:
        """The ``nan_batch`` injection effect (obs.faults): pixel inputs
        are uint8, so the numeric fault lands on the param tree — the next
        step's loss/grads go non-finite exactly as a NaN batch would make
        them, and the health sentry/policy takes it from there."""
        self.state = self.state.replace(
            params=faults.poison_params(self.state.params))

    def _preempt_snapshot(self, pending=None, meters=None) -> None:
        """Coordinated snapshot on preemption (round 13): the drain blocks
        until the in-flight dispatched steps land, then a consistent
        checkpoint commits through the CRC/keep-K container (the
        collective gather inside save_checkpoint is the cross-host
        barrier for sharded state) and the process exits ``PREEMPT_SNAPSHOT_RC`` — the supervisor
        classifies ``preemption_snapshotted`` and the restart resumes
        from THIS step, not the last periodic checkpoint."""
        cfg = self.cfg
        if pending:
            self._drain(pending, meters)
        self.obs.pause()  # the snapshot write is not a stall
        # distlint: disable=DL002 -- preemption boundary: one scalar fetch after the final drain
        step_done = int(jax.device_get(self.state.step))
        try:
            mesh_epoch = int(os.environ.get("TPU_DIST_MESH_EPOCH", "0") or 0)
        except ValueError:
            mesh_epoch = 0
        if cfg.checkpoint_dir:
            # cross-host consistency comes from save_checkpoint itself:
            # sharded states gather via a COLLECTIVE (every live host
            # blocks in it — the barrier), replicated dp state is in
            # per-step lockstep so process 0's replica IS the global cut.
            # No explicit sync_global_devices here: on a shrink-triggered
            # SIGTERM the lost host would never arrive and the barrier
            # would hang every survivor into its SIGKILL deadline.
            t0_ck = time.time()
            ckpt.save_checkpoint(
                cfg.checkpoint_dir, self.state, self._epoch_in_progress,
                self.best_acc1, cfg.arch, is_best=False,
                extra_meta={"mid_epoch": True, "preempt": True,
                            **self._run_meta},
                keep=cfg.keep_checkpoints)
            self.obs.ledger.emit(
                "ckpt", epoch=self._epoch_in_progress,
                path=cfg.checkpoint_dir, is_best=False,
                seconds=round(time.time() - t0_ck, 6), preempt=True)
        self.obs.ledger.emit(
            "scale", action="preempt_snapshot",
            processes=jax.process_count(), epoch=mesh_epoch, step=step_done)
        self.log(f"preempted ({self.obs.preempt_source}, deadline "
                 f"{self.obs.preempt_deadline_s}s): snapshot at step "
                 f"{step_done} — exiting for supervised resume")
        self.obs.run_end(status="preempted", snapshot_step=step_done,
                         best_acc1=self.best_acc1)
        raise SystemExit(PREEMPT_SNAPSHOT_RC)

    # ------------------------------------------------------------------
    def train_epoch(self, epoch: int) -> Dict[str, float]:
        with trace.ring().span("train.epoch", epoch=epoch):
            if self.k > 1 or self.device_data:
                return self._train_epoch_windowed(epoch)
            return self._train_epoch_batched(epoch)

    def _train_epoch_batched(self, epoch: int) -> Dict[str, float]:
        cfg = self.cfg
        loader = self._loader(self.train_ds, True, epoch)
        nb = len(loader)
        meters = MeterBank(nb, [("Time", "6.3f"), ("Data", "6.3f"),
                                ("Loss", ".4e"), ("Acc@1", "6.3f"),
                                ("Acc@5", "6.3f")],
                           prefix=f"Epoch: [{epoch}]")
        skip = self._skip_batches
        self._skip_batches = 0
        self.obs.resume()  # watchdog watches from epoch entry
        pending = []
        end = time.time()
        if self.accum > 1:
            # host-side split into (N, B/N, ...) microbatches; sharded
            # (None, 'data') so every microbatch spans all devices
            n = self.accum

            def split(b):
                imgs, lbls = b
                return (imgs.reshape(n, -1, *imgs.shape[1:]),
                        lbls.reshape(n, -1))

            micro_sh = NamedSharding(self.mesh, P(None, "data"))
            it = prefetch_to_device(map(split, iter(loader)), micro_sh)
        else:
            it = prefetch_to_device(iter(loader), self.batch_sharding)
        tr = self.obs.tracer
        for i, (images, labels) in enumerate(tr.timed_iter("data", it)):
            if i < skip:  # step-exact resume of a mid-epoch checkpoint
                end = time.time()
                continue
            data_s = time.time() - end
            meters.update("Data", data_s)
            gstep = epoch * self.steps_per_epoch + i
            effects = self.obs.fire_step_faults(gstep)
            if "nan_batch" in effects:
                self._apply_nan_fault()
            if "preempt_deadline" in effects:
                self.obs.request_preemption(
                    deadline_s=effects["preempt_deadline"].args.get("secs"),
                    source="fault")
            if self.obs.preempt_pending():
                self._preempt_snapshot(pending, meters)  # raises SystemExit
            was_cold = self._program_hbm is None  # this dispatch compiles
            with step_annotation(gstep), \
                    tr.span("dispatch", step=gstep, first_call=was_cold):
                self.state, metrics = self.train_step(
                    self.state, images, labels, self.rng)
            dispatch_s = tr.pop().get("dispatch", 0.0)
            if self._program_hbm is None:
                # static per-program peak + step FLOPs (CSV column / MFU;
                # lower() is abstract, so donation is untouched). Probed
                # AFTER the dispatch just above: the AOT compile would not
                # seed jit's dispatch cache, so probing first would compile
                # the step twice (utils.telemetry.program_stats contract) —
                # and probing post-dispatch in the SAME iteration means
                # even a single-dispatch run still records the column
                from tpu_dist.plan.compile import audit_mode, audit_program
                from tpu_dist.utils.telemetry import program_stats
                st = program_stats(self.train_step, self.state, images,
                                   labels, self.rng,
                                   with_hlo=bool(self.obs.ledger.path)
                                   or audit_mode() != "none")
                self._program_hbm = st["hbm_bytes"] or False
                self._program_flops = st["flops"]
                self.obs.ledger.emit("compile", program="train_step",
                                     hbm_bytes=st["hbm_bytes"],
                                     flops=st["flops"])
                # compile-time audit pass against the SAME lowered
                # artifact (plan.compile.audit_program) — a no-op under
                # audit=none, one 'audit' ledger event per program else
                audit_program("train_step", self.train_step, self.state,
                              images, labels, self.rng, hlo=st.get("hlo"),
                              precision=cfg.precision)
                if st.get("hlo"):
                    # static cost attribution of the same executable (one
                    # lower for hbm/flops/buckets — obs.attr); feeds the
                    # ledger_report roofline section
                    from tpu_dist.obs.attr import emit_cost_model
                    emit_cost_model(self.obs.ledger, "train_step",
                                    st["hlo"], xla_flops=st["flops"])
            pending.append((metrics, {
                "step": gstep, "n_steps": 1, "n_items": cfg.batch_size,
                "data_s": data_s, "dispatch_s": dispatch_s,
                "warm": was_cold}))
            boundary = i % cfg.print_freq == 0 or i == nb - 1
            if boundary:
                self._drain(pending, meters)
            # every iteration, so avg(Time) = wall/batches; under async
            # dispatch the device wait lands on boundary iterations (the
            # device_get above) and non-boundary Time is dispatch-only
            meters.update("Time", time.time() - end)
            if boundary and self.is_main:
                meters.display(i)
            end = time.time()
        self.obs.pause()  # eval/ckpt follow: step completions stop by design
        snap = meters.snapshot()  # ONE read feeds printer, ledger, and return
        return {"loss": snap["Loss"]["avg"], "top1": snap["Acc@1"]["avg"],
                "top5": snap["Acc@5"]["avg"], "batches": nb - skip}

    def _host_windows(self, loader, skip: int):
        """Yield (n_batches, (imgs (K,B,...), lbls (K,B))) host-stacked
        windows, skipping the first ``skip`` batches (step-exact resume). A
        short tail yields a smaller window (jit retraces once per K)."""
        it = iter(loader)
        for _ in range(skip):
            next(it)
        while True:
            stack = []
            for batch in it:
                stack.append(batch)
                if len(stack) == self.k:
                    break
            if not stack:
                return
            imgs = np.stack([b[0] for b in stack])
            lbls = np.stack([b[1] for b in stack])
            yield len(stack), (imgs, lbls)

    def _epoch_indices(self, ds, train: bool, epoch: int):
        """THE sampler->(nb, local_batch) index layout shared by the windowed
        train path and the one-dispatch eval (they must never diverge: the
        sampler's batch-blocked ordering is load-bearing for N-process
        bit-exactness). Returns (idx (nb,B) i32, valid (nb,B) f32)."""
        sampler = self._sampler(ds, train, epoch)
        idx, valid = sampler.indices_with_valid()
        nb = sampler.num_samples // self.local_batch
        n = nb * self.local_batch
        shape = (nb, self.local_batch)
        return (np.asarray(idx[:n], np.int32).reshape(shape),
                np.asarray(valid[:n], np.float32).reshape(shape))

    def _streamed_host_windows(self, loader, skip: int, put):
        """(n, device window) items via a BOUNDED background pipeline
        (tpu_dist.data.loader.stream_prefetch): the producer thread
        assembles window w+1's uint8 batches and dispatches their
        host->device upload while window w trains — the epoch-prefetch
        trick (device mode's index uploads) applied to pixel windows, for
        datasets too large for HBM residency (ImageNet-224 scale)."""
        from tpu_dist.data.loader import stream_prefetch

        return stream_prefetch(
            (n, put(p)) for n, p in self._host_windows(loader, skip))

    def _device_windows(self, epoch: int, skip: int, put):
        """(K,B) index windows for the HBM-resident dataset, already ON
        device. The transfers are dispatched asynchronously here, so calling
        this for epoch e+1 while epoch e's validation runs hides the
        host->device index upload entirely (epoch-granularity prefetch)."""
        batches, _ = self._epoch_indices(self.train_ds, True, epoch)
        batches = batches[skip:]
        return [(len(w), put(np.ascontiguousarray(w)))
                for w in (batches[i:i + self.k]
                          for i in range(0, len(batches), self.k))]

    def _train_epoch_windowed(self, epoch: int) -> Dict[str, float]:
        """K-steps-per-dispatch epoch: same math as the
        per-batch loop, ~1/K the host round-trips, and (device mode) only
        index windows cross the host->device link."""
        cfg = self.cfg
        nb = self.steps_per_epoch  # == len(loader): sampler pads to batches
        meters = MeterBank(nb, [("Time", "6.3f"), ("Data", "6.3f"),
                                ("Loss", ".4e"), ("Acc@1", "6.3f"),
                                ("Acc@5", "6.3f")],
                           prefix=f"Epoch: [{epoch}]")
        skip = self._skip_batches
        self._skip_batches = 0
        self.obs.resume()  # watchdog watches from epoch entry
        win_sh = NamedSharding(self.mesh, P(None, "data"))
        put = partial(assemble_global, win_sh)
        if self.device_data:
            def dispatch(state, dev_payload):
                return self.window_step(state, *self._train_data_dev,
                                        dev_payload, self.rng)

            cached = self._prefetched_windows
            self._prefetched_windows = None
            if cached is not None and cached[0] == epoch and skip == 0:
                windows = cached[1]
            else:
                windows = self._device_windows(epoch, skip, put)
        else:
            def dispatch(state, dev_payload):
                return self.window_step(state, *dev_payload, self.rng)

            loader = self._loader(self.train_ds, True, epoch)
            windows = self._streamed_host_windows(loader, skip, put)

        pending = []  # window metric sums awaiting the next print boundary
        done = skip
        last_print = skip - 1
        tr = self.obs.tracer
        end = time.time()
        for n, dev_payload in tr.timed_iter("data", windows):
            # per-BATCH seconds (window seconds / n, weighted n) so the
            # printed avg keeps the per-batch path's meaning:
            # avg(Time) = wall / batches in both paths
            data_s = time.time() - end
            meters.update("Data", data_s / n, n)
            effects = self.obs.fire_step_faults(
                epoch * self.steps_per_epoch + done)
            if "nan_batch" in effects:
                self._apply_nan_fault()
            if "preempt_deadline" in effects:
                self.obs.request_preemption(
                    deadline_s=effects["preempt_deadline"].args.get("secs"),
                    source="fault")
            if self.obs.preempt_pending():
                self._preempt_snapshot(pending, meters)  # raises SystemExit
            was_cold = self._program_hbm is None  # this dispatch compiles
            # each window length is a compiled program of its own
            first_call = n not in self._dispatched_windows
            self._dispatched_windows.add(n)
            gstep = epoch * self.steps_per_epoch + done
            with step_annotation(gstep), \
                    tr.span("dispatch", step=gstep, first_call=first_call):
                self.state, metrics = dispatch(self.state, dev_payload)
            dispatch_s = tr.pop().get("dispatch", 0.0)
            if self._program_hbm is None:
                # post-dispatch probe (same iteration, so single-window
                # runs record it too): see telemetry.program_stats; the
                # cost model counts the scan body once, so flops ~= ONE
                # optimizer step of the window program
                from tpu_dist.plan.compile import audit_mode, audit_program
                from tpu_dist.utils.telemetry import program_stats
                args = ((*self._train_data_dev, dev_payload, self.rng)
                        if self.device_data else (*dev_payload, self.rng))
                st = program_stats(self.window_step, self.state, *args,
                                   with_hlo=bool(self.obs.ledger.path)
                                   or audit_mode() != "none")
                self._program_hbm = st["hbm_bytes"] or False
                self._program_flops = st["flops"]
                self.obs.ledger.emit("compile", program="window_step",
                                     hbm_bytes=st["hbm_bytes"],
                                     flops=st["flops"])
                # same-artifact compile-time audit (plan.compile)
                audit_program("window_step", self.window_step, self.state,
                              *args, hlo=st.get("hlo"),
                              precision=cfg.precision)
                if st.get("hlo"):
                    # static cost attribution (obs.attr), same executable
                    from tpu_dist.obs.attr import emit_cost_model
                    emit_cost_model(self.obs.ledger, "window_step",
                                    st["hlo"], xla_flops=st["flops"])
            done += n
            pending.append((metrics, {
                "step": epoch * self.steps_per_epoch + done - 1,
                "n_steps": n, "n_items": n * cfg.batch_size,
                "data_s": data_s, "dispatch_s": dispatch_s,
                "warm": was_cold}))
            boundary = (done - 1) - last_print >= cfg.print_freq or done == nb
            if boundary and done == nb and self.device_data \
                    and epoch + 1 < cfg.epochs:
                # queue next epoch's index uploads BEFORE blocking on this
                # epoch's metrics: they land during drain/validate/checkpoint
                self._prefetched_windows = (
                    epoch + 1, self._device_windows(epoch + 1, 0, put))
            if boundary:
                self._drain(pending, meters)
                last_print = done - 1
            meters.update("Time", (time.time() - end) / n, n)
            if boundary and self.is_main:
                meters.display(done - 1)
            end = time.time()
        self.obs.pause()  # eval/ckpt follow: step completions stop by design
        snap = meters.snapshot()
        return {"loss": snap["Loss"]["avg"], "top1": snap["Acc@1"]["avg"],
                "top5": snap["Acc@5"]["avg"], "batches": nb - skip}

    def validate(self, epoch: int = 0) -> float:
        """Distributed eval (C15): metric sums psum'd across replicas, padding
        masked out, exact division by the true sample count. device_get
        happens ONCE after the loop so eval batches pipeline (async dispatch),
        unlike the reference's per-batch barrier+allreduce. With an
        HBM-resident val set the whole eval is ONE dispatch."""
        t0_eval = time.time()  # exact eval badput for the goodput ledger
        if self._val_data_dev is not None:
            idx, valid = self._epoch_indices(self.val_ds, False, epoch)
            win_sh = NamedSharding(self.mesh, P(None, "data"))
            idx_d = assemble_global(win_sh, np.ascontiguousarray(idx))
            valid_d = assemble_global(win_sh, np.ascontiguousarray(valid))
            # distlint: disable=DL002 -- one-dispatch eval: the eval drain boundary
            m = jax.device_get(self.window_eval_step(
                self.state.params, self.state.batch_stats,
                *self._val_data_dev, idx_d, valid_d))
            sums = {k: float(m[k]) for k in
                    ("loss_sum", "correct1", "correct5", "count")}
        else:
            loader = self._loader(self.val_ds, False, epoch)
            pending = []
            it = prefetch_to_device(iter(loader), self.batch_sharding)
            for images, labels, valid in it:
                pending.append(self.eval_step(
                    self.state.params, self.state.batch_stats, images, labels,
                    valid))
            sums = {"loss_sum": 0.0, "correct1": 0.0, "correct5": 0.0,
                    "count": 0.0}
            # distlint: disable=DL002 -- eval drain boundary: pending eval metrics fetched in one batch
            for m in jax.device_get(pending):
                for k in sums:
                    sums[k] += float(m[k])
        n = max(sums["count"], 1.0)
        acc1 = sums["correct1"] / n
        acc5 = sums["correct5"] / n
        self.obs.ledger.emit("eval", epoch=epoch, loss=sums["loss_sum"] / n,
                             acc1=acc1, acc5=acc5, count=int(sums["count"]),
                             seconds=round(time.time() - t0_eval, 6))
        self.log(f" * Acc@1 {acc1 * 100:.3f} Acc@5 {acc5 * 100:.3f} "
                 f"Loss {sums['loss_sum'] / n:.4f}")
        return acc1

    # ------------------------------------------------------------------
    def fit(self) -> float:
        cfg = self.cfg
        # SIGTERM becomes a snapshot request this loop drains at its next
        # step boundary (the coordinated-preemption contract)
        self.obs.enable_preempt_snapshot()
        self.obs.run_start(**self.build_info)
        if self._peer_restored:
            try:
                mesh_epoch = int(
                    os.environ.get("TPU_DIST_MESH_EPOCH", "0") or 0)
            except ValueError:
                mesh_epoch = 0
            self.obs.ledger.emit(
                "scale", action="peer_restore",
                processes=jax.process_count(), epoch=mesh_epoch)
        if cfg.evaluate:
            try:
                return self.validate()
            finally:
                self.obs.run_end(best_acc1=self.best_acc1)
        stop_telemetry = None
        if cfg.telemetry_csv:
            # EVERY process samples (multi-host skew forensics need the
            # straggler's memory timeline too); non-main paths are
            # .pN-suffixed so files never clobber (obs.per_process_path)
            from tpu_dist.obs import per_process_path
            from tpu_dist.utils.telemetry import start_hbm_sampler
            stop_telemetry = start_hbm_sampler(
                per_process_path(cfg.telemetry_csv, jax.process_index()),
                ledger=self.obs.ledger)
        try:
            # device tracing (reference's only profiling was wall-clock CSVs
            # + nvidia-smi sampling, statistics.sh:1-4; the TPU-native answer
            # is a real XLA trace — obs.profile_session flushes it even on
            # OOM/interrupt: a failing run is exactly the one worth
            # profiling)
            with profile_session(cfg.profile_dir, self.obs.profiling):
                self._fit_epochs()
        except HealthError:
            # a halt must never abandon an in-flight async write: join this
            # dir's writer before re-raising, surfacing any write failure
            # as a warning rather than masking the halt itself
            try:
                ckpt.wait_for_async_save(cfg.checkpoint_dir or None)
            except RuntimeError as we:
                self.log(f"warning: async checkpoint write failed during "
                         f"health halt: {we}")
            raise
        except KeyboardInterrupt:
            self.obs.pause()  # slow interrupt-save is not a stall
            # strictly better than the reference (no try/except around its
            # training at all, SURVEY.md §5 'Failure detection'): an interrupt
            # leaves a resumable checkpoint instead of losing the run
            ckpt.save_checkpoint(cfg.checkpoint_dir, self.state,
                                 self._epoch_in_progress, self.best_acc1,
                                 cfg.arch, is_best=False,
                                 extra_meta={"mid_epoch": True,
                                             **self._run_meta},
                                 keep=cfg.keep_checkpoints)
            self.log(f"interrupted — checkpoint saved at epoch "
                     f"{self._epoch_in_progress}; resume with --resume")
            raise
        finally:
            if stop_telemetry is not None:
                stop_telemetry()
            ckpt.wait_for_async_save()  # never exit with a write in flight
            self.obs.run_end(best_acc1=self.best_acc1)
        return self.best_acc1

    def _fit_epochs(self) -> None:
        cfg = self.cfg
        for epoch in range(self.start_epoch, cfg.epochs):
            self._epoch_in_progress = epoch
            if self.obs.preempt_pending():
                # SIGTERM landed during the previous eval/checkpoint span
                self._preempt_snapshot()
            t0 = time.time()
            train_metrics = self.train_epoch(epoch)
            train_secs = time.time() - t0
            acc1 = self.validate(epoch)
            epoch_secs = time.time() - t0
            # end-to-end train-phase rate (loader + dispatch + device), the
            # number the bench's device rate is compared against; counts
            # only batches actually trained (a resumed mid-epoch runs
            # fewer than steps_per_epoch)
            train_imgs = train_metrics.get(
                "batches", self.steps_per_epoch) * cfg.batch_size
            train_ips = train_imgs / max(train_secs, 1e-9)
            is_best = acc1 > self.best_acc1
            self.best_acc1 = max(acc1, self.best_acc1)
            # the epoch record; the legacy CSV row (reference format
            # [wall start, epoch seconds] + train-img/s and peak-HBM
            # columns) renders from THIS event via the
            # EpochCsvSink the obs layer registered — one source of truth.
            # hbm: allocator truth when the backend exposes it, else XLA's
            # static per-program analysis (empty when neither exists)
            from tpu_dist.utils.telemetry import peak_hbm_bytes
            self.obs.ledger.emit(
                "epoch", epoch=epoch, start_ts=t0, seconds=epoch_secs,
                throughput=train_ips, unit="img/s",
                loss=train_metrics["loss"], acc1=acc1,
                hbm_bytes=peak_hbm_bytes() or self._program_hbm or None,
                batches=train_metrics.get("batches"))
            # async: serialization + disk write overlap the next epoch (the
            # device->host gather stays on the critical path by necessity);
            # the goodput ledger charges only the blocking share
            t0_ck = time.time()
            ckpt.save_checkpoint(cfg.checkpoint_dir, self.state, epoch + 1,
                                 self.best_acc1, cfg.arch, is_best,
                                 extra_meta=self._run_meta, async_write=True,
                                 keep=cfg.keep_checkpoints)
            self.obs.ledger.emit(
                "ckpt", epoch=epoch + 1, path=cfg.checkpoint_dir,
                is_best=is_best, seconds=round(time.time() - t0_ck, 6))
            self.log(f"Epoch {epoch}: train_loss={train_metrics['loss']:.4f} "
                     f"val_acc1={acc1 * 100:.3f} best={self.best_acc1 * 100:.3f} "
                     f"({epoch_secs:.1f}s, train {train_ips:,.0f} img/s)")
