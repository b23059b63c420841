"""LMTrainer: the language-model twin of engine.loop.Trainer.

Round 2 drove the LM parallelism surface (dp/tp/sp/pp/ep/fsdp, flash, remat)
from a fixed-batch demo loop in scripts/8; this module gives the LM family
the SAME orchestration the image family has — epochs over a real token
corpus (tpu_dist.data.tokens), DistributedSampler rows with N-process
bit-exactness, K-steps-per-dispatch windows from an HBM-resident row matrix,
MeterBank progress lines + per-epoch CSV, exact held-out perplexity in EVERY
parallelism mode (sp and pp included), step-exact mid-epoch resume, and
tokens/sec with MFU from XLA's cost model.

Mode selection is by mesh axes, exactly like scripts/8:
  data=N                      pure DP (jit; GSPMD allreduce)
  data=N  + fsdp=True         ZeRO-3 param+opt sharding, same step
  data=N,model=M              tensor parallel (Megatron shardings via GSPMD)
  data=N,expert=M             MoE expert parallelism (GShard dispatch)
  data=N,seq=M                sequence parallel (ring attention, shard_map)
  data=N,stage=M              pipeline parallel (GPipe microbatches)
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist.configs import LMConfig
from tpu_dist.data import DistributedSampler, assemble_global
from tpu_dist.data.tokens import load_token_dataset
from tpu_dist.engine import checkpoint as ckpt
from tpu_dist.engine.lm_steps import LM_METRIC_KEYS, make_lm_batches
from tpu_dist.engine.state import TrainState
from tpu_dist.obs import (HealthError, RunObs, faults, profile_session,
                          step_annotation, trace)
from tpu_dist.ops import lm_lr_schedule, make_optimizer, make_policy
from tpu_dist.parallel.mesh import make_mesh, replicated
from tpu_dist.parallel.supervisor import PREEMPT_SNAPSHOT_RC
from tpu_dist.plan.compile import (Bindings, compile_eval_step,
                                   compile_train_step)
from tpu_dist.plan.ir import plan_from_config
from tpu_dist.runtime import pallas_interpret
from tpu_dist.utils.meters import MeterBank


class LMTrainer:
    """One engine for every LM parallelism flavor; mode picked by the mesh.

    Which step programs a run gets is ONE decision, ``self.plan``
    (``plan.ir.plan_from_config`` of the config, the mesh's axis sizes and
    where the rows live), compiled in :meth:`_build_steps`. Pipeline
    parallelism is the one mode a Plan cannot name yet: a ``stage`` axis
    takes ``parallel/pp.py``'s own builders there."""

    def __init__(self, cfg: LMConfig, mesh=None):
        # step plan (tpu_dist.plan): the `plan` knob rewrites the
        # plan-owned config fields and flips the trace-time kernel
        # switches BEFORE anything below reads them; run_start + a
        # 'plan' ledger event record the resolved hash
        from tpu_dist.plan.compile import resolve_config_plan
        cfg, self._plan_info = resolve_config_plan(cfg)
        self.cfg = cfg
        trace.gc_seconds()       # full collections are host.gc spans from here
        if cfg.resume and not os.path.exists(cfg.resume):
            raise FileNotFoundError(f"--resume checkpoint not found: {cfg.resume}")
        if cfg.pretrained and not os.path.exists(cfg.pretrained):
            raise FileNotFoundError(
                f"--pretrained checkpoint not found: {cfg.pretrained}")
        if cfg.optimizer not in ("sgd", "adamw", "fused_adamw"):
            # fail fast, BEFORE corpus/model setup (the image Trainer's
            # contract; fused_sgd is image-only — its Pallas kernel assumes
            # the SGD update form)
            raise ValueError(f"unknown optimizer {cfg.optimizer!r} "
                             "(sgd|adamw|fused_adamw)")
        mesh_shape = cfg.mesh_shape or (jax.device_count(),)
        self.mesh = mesh if mesh is not None else make_mesh(
            tuple(mesh_shape), tuple(cfg.mesh_axes))
        self.policy = make_policy(cfg.precision)

        # ---- corpus ----
        seed = cfg.seed if cfg.seed is not None else 0
        self.train_ds, self.val_ds = load_token_dataset(
            cfg.data, cfg.seq_len, cfg.vocab_size, val_frac=cfg.val_frac,
            synth_tokens=cfg.synth_tokens, seed=seed, val_data=cfg.val_data)
        self.vocab_size = self.train_ds.vocab_size

        # ---- mode ----
        names = self.mesh.axis_names
        shape = self.mesh.shape
        self.use_sp = "seq" in names and shape["seq"] > 1
        self.use_tp = "model" in names and shape["model"] > 1
        self.use_ep = "expert" in names and shape["expert"] > 1
        self.use_pp = "stage" in names and shape["stage"] > 1
        self._validate_mode()
        # the step plan of this config on this mesh, BEFORE corpus and
        # model: health/tp_impl spellings and every mode exclusion
        # (Plan.validate) fail here; the window kind joins it once the
        # corpus has been measured
        self.plan = plan_from_config(cfg, dict(shape))
        self.use_ring = self.plan.tp_impl == "ring"
        self.use_bucket = self.plan.grad_bucket_mb > 0
        self.mode = (f"pp-{cfg.pp_schedule}"
                     + ("+tp" if self.use_pp and self.use_tp else "")
                     if self.use_pp else
                     "sp-ring" if self.use_sp else
                     ("ep-moe" + ("+tp" if self.use_tp else ""))
                     if self.use_ep else
                     ("tp-ring" if self.use_ring else "tp") if self.use_tp
                     else
                     "fsdp" if cfg.fsdp else
                     ("dp-moe" if cfg.num_experts else "dp")
                     + ("-bucketed" if self.use_bucket else ""))

        # ---- batch geometry ----
        nprocs = jax.process_count()
        d_size = shape.get("data", 1)
        if cfg.batch_size % max(d_size, nprocs):
            raise ValueError(
                f"global batch {cfg.batch_size} (sequences) must divide by "
                f"the data axis ({d_size}) and process count ({nprocs})")
        if self.use_sp and cfg.seq_len % shape["seq"]:
            raise ValueError(f"seq_len {cfg.seq_len} not divisible by the "
                             f"seq axis ({shape['seq']})")
        if self.use_pp and (cfg.batch_size // d_size) % cfg.pp_microbatches:
            raise ValueError(
                f"per-data-shard batch {cfg.batch_size // d_size} not "
                f"divisible by {cfg.pp_microbatches} microbatches")
        self.local_batch = cfg.batch_size // nprocs

        # ---- model ----
        self.model, self._model_ctor, self.decode_model = \
            self._build_model()
        # init through the single-device twin (a one-row dummy batch does
        # not divide over a mesh-bound attention kernel's data axis), as ONE
        # compiled program: eager flax init dispatches some sixty tiny
        # programs, which on the chip is most of a cold start's compile
        # count (87 -> 11 in the smoke's LM phase). On the CPU backend the
        # values are bitwise the eager ones (as Trainer's: engine/state.py
        # init_model) but for leaves drawn as random.normal times a constant
        # deviation, here the two embeddings: one program rounds the product
        # of the normal's own sqrt(2) and the deviation once where eager
        # calls round twice, a last bit in part of the leaf. On the chip
        # they differ in the last bits, as any fused program may.
        params = jax.jit(lambda key: self.decode_model.init(
            {"params": key}, np.zeros((1, cfg.seq_len), np.int32),
            train=False)["params"])(jax.random.PRNGKey(seed))
        if cfg.pretrained:
            # warm-start BEFORE any pipeline stacking, so the donor must be
            # an UNSTACKED (per-block) param tree. Non-pp runs save exactly
            # that; a pp run's checkpoint keeps its stage-stacked blocks
            # (resume needs the stacked layout) and is therefore NOT a
            # valid --pretrained donor as-is — convert it first with
            # parallel.pp.unstack_pipeline_params. The stamped pp_stages
            # meta makes the mismatch detectable, so refuse loudly instead
            # of letting graft_params silently keep fresh init for every
            # block. (Shape-matched graft, fresh optimizer state; --resume
            # is the continue-a-run path; existence checked first-line in
            # __init__.)
            pre_params, _, pre_meta = ckpt.load_warmstart(cfg.pretrained)
            if pre_meta.get("pp_stages"):
                raise ValueError(
                    f"--pretrained {cfg.pretrained} was saved by a "
                    f"pipeline-parallel run ({pre_meta['pp_stages']} stages):"
                    " its blocks are stage-stacked and would not graft onto "
                    "a fresh model. Unstack it first (parallel.pp."
                    "unstack_pipeline_params) and re-save, or warm-start "
                    "from a non-pp checkpoint.")
            params, n_p, skipped = ckpt.graft_params(params, pre_params)
            if n_p == 0:
                raise ValueError(
                    f"--pretrained {cfg.pretrained} (arch "
                    f"{pre_meta.get('arch', '?')!r}) shares no tensors with "
                    f"this model — wrong checkpoint?")
            self.log(f"=> warm-started {n_p} param tensors from "
                     f"{cfg.pretrained}"
                     + (f"; fresh init kept for {skipped}" if skipped else ""))
        self.steps_per_epoch = max(
            1, -(-len(self.train_ds) // cfg.batch_size))
        # warmup + constant/cosine/step LR as a pure function of the step
        # count inside the jitted update; the count lives in
        # the checkpointed optax state, so --resume continues the trajectory
        total_steps = (cfg.lr_decay_steps or cfg.max_steps
                       or cfg.epochs * self.steps_per_epoch)
        self.lr_schedule = lm_lr_schedule(
            cfg.lr, cfg.lr_schedule, warmup_steps=cfg.warmup_steps,
            total_steps=total_steps, steps_per_epoch=self.steps_per_epoch,
            step_epochs=cfg.lr_step_epochs, min_frac=cfg.lr_min_frac)
        # pp clips inside the step by the cross-stage global norm
        # (parallel.pp._clip_pp_grads), so its optax chain carries no clip
        # of its own — which also keeps the opt_state pytree structure
        # independent of the --grad-clip flag under pp
        if cfg.optimizer == "fused_adamw":
            # Pallas fused update (ops.pallas_adamw): engine steps dispatch
            # on the apply() protocol, pp included. grad_clip fuses INTO
            # the kernel (the scalar-row clip slot) for the non-pp modes;
            # under pp the step clips by the cross-stage global norm
            # BEFORE _apply_update, so the kernel-side clip stays off —
            # exactly the optax-chain split above
            from tpu_dist.ops.pallas_adamw import FusedAdamW
            self.tx = FusedAdamW(self.lr_schedule, b1=cfg.adam_b1,
                                 b2=cfg.adam_b2, eps=cfg.adam_eps,
                                 weight_decay=cfg.weight_decay,
                                 clip_norm=0.0 if self.use_pp
                                 else cfg.grad_clip,
                                 interpret=pallas_interpret())
        else:
            self.tx = make_optimizer(cfg.lr, cfg.momentum, cfg.weight_decay,
                                     schedule=self.lr_schedule,
                                     kind=cfg.optimizer, b1=cfg.adam_b1,
                                     b2=cfg.adam_b2, eps=cfg.adam_eps,
                                     grad_clip=0.0 if self.use_pp
                                     else cfg.grad_clip)
        if self.use_pp:
            from tpu_dist.parallel.pp import stack_pipeline_params
            params = stack_pipeline_params(params, shape["stage"])
        state = TrainState.create(params, {}, self.tx)

        # ---- windows / device-resident rows ----
        self.rng = jax.random.PRNGKey(seed + 1)
        self.k = cfg.steps_per_dispatch
        if cfg.data_placement not in ("auto", "host", "device"):
            raise ValueError(f"unknown data_placement {cfg.data_placement!r}")
        # gradient accumulation (jit modes): N sequential microbatches per
        # optimizer step
        self.accum = cfg.grad_accum_steps
        if self.accum > 1:
            if self.use_pp:
                raise ValueError("grad_accum_steps > 1 supports the jit "
                                 "modes (dp/fsdp/tp/ep); pp already "
                                 "microbatches via --pp-microbatches")
            if cfg.batch_size % (self.accum * d_size):
                raise ValueError(
                    f"global batch {cfg.batch_size} not divisible by "
                    f"grad_accum_steps x data axis ({self.accum} x {d_size})")
        rows_bytes = (len(self.train_ds) + len(self.val_ds)) * \
            (cfg.seq_len + 1) * 4
        fits = rows_bytes <= int(os.environ.get("TPU_DIST_DEVICE_DATA_MAX",
                                                str(1 << 30)))
        self.device_data = (cfg.data_placement == "device" or
                            (cfg.data_placement == "auto" and fits
                             and self.k > 1))
        if self.k > 1 and not self.device_data:
            raise ValueError(
                "steps_per_dispatch > 1 needs the device-resident row path "
                "(corpus too large for TPU_DIST_DEVICE_DATA_MAX, or "
                "data_placement='host')")
        self._build_steps()
        self._train_rows_dev = None
        self._val_rows_dev = None
        self._prefetched_windows = None
        self._dispatched_windows = set()  # window lengths dispatched once
        if self.device_data:
            # distlint: disable=DL008 -- one-time whole-dataset HBM residency at init; per-step uploads don't exist in this mode
            self._train_rows_dev = jax.device_put(
                self.train_ds.rows_array(), replicated(self.mesh))
            # distlint: disable=DL008 -- one-time whole-dataset HBM residency at init (see _train_rows_dev)
            self._val_rows_dev = jax.device_put(
                self.val_ds.rows_array(), replicated(self.mesh))

        # ---- geometry meta / resume ----
        self._run_meta = {
            "vocab_size": self.vocab_size, "num_layers": cfg.num_layers,
            "d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "seq_len": cfg.seq_len, "num_experts": cfg.num_experts,
            "pp_stages": shape["stage"] if self.use_pp else 0,
            "steps_per_epoch": self.steps_per_epoch,
            "batch_size": cfg.batch_size, "dataset_len": len(self.train_ds),
            "mode": self.mode,
        }
        self.start_epoch = 0
        self._skip_batches = 0
        self.best_ppl = float("inf")
        self.is_main = jax.process_index() == 0
        if cfg.resume:
            hard = ("vocab_size", "num_layers", "d_model", "num_heads",
                    "seq_len", "num_experts", "pp_stages")
            pre = ckpt.read_checkpoint_meta(cfg.resume)
            bad = {k: (pre[k], self._run_meta[k]) for k in hard
                   if k in pre and pre[k] != self._run_meta[k]}
            if bad:
                raise ValueError(
                    "--resume checkpoint has different model geometry: " +
                    ", ".join(f"{k}: checkpoint {a} vs run {b}"
                              for k, (a, b) in bad.items()))
            state, meta = ckpt.load_checkpoint(cfg.resume, state)
            self.start_epoch = meta.get("epoch", 0)
            self.best_ppl = meta.get("best_ppl", float("inf"))
            soft = {k: (meta[k], self._run_meta[k])
                    for k in ("steps_per_epoch", "batch_size", "dataset_len")
                    if k in meta and meta[k] != self._run_meta[k]}
            if meta.get("mid_epoch"):
                if soft:
                    raise ValueError(
                        "mid-epoch resume requires the checkpoint's data/"
                        "batch geometry (" + ", ".join(
                            f"{k}: checkpoint {a} vs run {b}"
                            for k, (a, b) in soft.items()) + ")")
                step_done = int(np.asarray(state.step))
                self.start_epoch = step_done // self.steps_per_epoch
                self._skip_batches = step_done % self.steps_per_epoch
                if self._skip_batches:
                    self.log(f"=> mid-epoch checkpoint: resuming epoch "
                             f"{self.start_epoch}, skipping "
                             f"{self._skip_batches} already-applied batches")
            self.log(f"=> resumed from {cfg.resume} "
                     f"(epoch {self.start_epoch})")
        # checkpoint-less dp-pure recovery (round 13): on a supervisor
        # mesh re-expansion (TPU_DIST_PEER_RESUME), adopt a survivor's
        # live replicated state over a broadcast collective — the
        # returning host has no local checkpoint, and the consensus
        # renumbering keeps process 0 a survivor. Replicated layouts
        # only; sharded modes take the disk path above.
        self._dp_pure = not (self.use_sp or self.use_tp or self.use_ep
                             or self.use_pp or cfg.fsdp)
        self._peer_restored = False
        if os.environ.get("TPU_DIST_PEER_RESUME") == "1" and self._dp_pure:
            state, did = ckpt.peer_restore_state(state)
            if did:
                self._peer_restored = True
                # epoch/skip re-derive from the adopted step counter, the
                # same math as a mid-epoch resume (best_ppl is the one
                # piece a joiner cannot recover — it only gates is_best)
                step_done = int(np.asarray(state.step))
                self.start_epoch = step_done // self.steps_per_epoch
                self._skip_batches = step_done % self.steps_per_epoch
                self.log(f"=> peer-restored state from a survivor at step "
                         f"{step_done} (no disk round-trip); resuming "
                         f"epoch {self.start_epoch}")
        self.state = self._place(state)
        self._epoch_in_progress = self.start_epoch
        self._flops_per_step = None  # analytical, lazily (utils.mfu)
        self._program_hbm = None     # post-dispatch probe (telemetry contract)
        self.last_tok_s = 0.0        # last epoch's train-phase tokens/sec
        self._warmed = False         # first dispatch carries XLA compile;
                                     # its wall time is excluded from tok/s
        # run observability: ledger + tracer + skew monitor + hang watchdog
        # (obs.RunObs) — the LM engine's step records carry tok/s + MFU
        self.obs = RunObs("lm", cfg, self.mesh, unit="tok/s",
                          plan_info=self._plan_info)
        # program audit (tpu_dist.analysis.proglint via plan.compile):
        # armed here so the compile-time pass and the drain-boundary
        # recompile sentry see every program this run builds
        from tpu_dist.plan.compile import set_audit
        set_audit(cfg.audit, self.obs.ledger)
        # whether the int8 matmuls route through the fused Pallas kernel
        # (ops.pallas_quant) — trace-time static, so ONE read here is the
        # truth for every step record; ledger_report attributes MFU deltas
        # to the kernel by splitting records on this flag
        from tpu_dist.ops.quant import fused_quant_active
        self._fused_quant = cfg.quant == "int8" and fused_quant_active()
        # comm phase for the step ledger records: when grad sync is an
        # explicit decomposed collective (grad_bucket_mb), time the sync
        # alone once — the UNOVERLAPPED per-step comm cost readers compare
        # device_s against (tools/ledger_report renders the share). Ring
        # TP's comm interleaves with the matmul chunks by construction and
        # cannot be isolated post-fusion, so its records carry comm_s=None.
        self._comm_probe_s = (self._measure_comm_probe()
                              if self.use_bucket else None)

    # ------------------------------------------------------------------
    def _validate_mode(self):
        cfg = self.cfg
        multi = [a for a in ("seq", "model", "expert", "stage")
                 if a in self.mesh.axis_names and self.mesh.shape[a] > 1]
        if len(multi) > 1 and set(multi) not in ({"stage", "model"},
                                                 {"expert", "model"}):
            raise ValueError(
                f"unsupported model-parallel axis combination {multi} "
                "(one axis at a time, stage+model for pp x tp, or "
                "expert+model for MoE x tp)")
        if self.use_pp and cfg.fsdp:
            raise ValueError("a 'stage' mesh axis does not compose with "
                             "fsdp (blocks already shard over 'stage')")
        # (--grad-clip composes with pp since round 5: the pp steps clip by
        # the cross-stage global norm — parallel.pp._clip_pp_grads — so the
        # optax chain must NOT carry its own per-device clip. MoE composes
        # with both pp schedules and with pp x tp: GPipe carries the router
        # aux through autodiff, 1f1b threads it as an explicit vjp
        # cotangent, and pp_tp_placement_specs shards the stacked expert
        # kernels Megatron-style over 'model'.)
        if self.use_ep and not cfg.num_experts:
            raise ValueError("an 'expert' mesh axis requires num_experts > 0")
        # (MoE composes with a 'seq' axis: experts are replicated and the
        # GShard dispatch is group-local math, so it runs unchanged inside
        # the sp shard_map — router groups become shard-local; a
        # --moe-group-size dividing the shard keeps routing dp-identical)
        if (self.use_tp and cfg.num_experts
                and not (self.use_ep or self.use_pp)):
            raise ValueError("MoE + pure tensor parallelism not supported: "
                             "use data=N,expert=M[,model=K] or "
                             "data=N,stage=S,model=K")
        if cfg.fsdp and (self.use_sp or self.use_tp or self.use_ep):
            self.log("warning: fsdp applies to the pure data-parallel "
                     "layout; ignored with a seq/model/expert mesh axis")
        if self.use_tp and cfg.tp_impl == "ring":
            tp = self.mesh.shape["model"]
            if self.use_pp or self.use_ep:
                raise ValueError("tp_impl='ring' drives the pure "
                                 "data x model layout; pp/ep compositions "
                                 "ride the GSPMD impl")
            if cfg.seq_len % tp:
                raise ValueError(f"tp_impl='ring' seq-shards the residual: "
                                 f"seq_len {cfg.seq_len} must divide by the "
                                 f"model axis ({tp})")
            if cfg.num_heads % tp:
                raise ValueError(f"tp_impl='ring' shards heads: num_heads "
                                 f"{cfg.num_heads} must divide by the model "
                                 f"axis ({tp})")
        if cfg.grad_bucket_mb > 0 and (self.use_pp or self.use_ep
                                       or cfg.fsdp):
            # (a model or seq axis beside it is the plan's to refuse)
            raise ValueError(
                "grad_bucket_mb > 0 decomposes the pure-dp gradient "
                "allreduce (replicated params); fsdp/pp/ep keep their "
                "GSPMD-scheduled sync")

    def _build_model(self):
        cfg = self.cfg
        import jax.numpy as jnp

        train_attn_fn = None  # set where training needs a mesh-bound twin
        if cfg.attn == "blockwise":
            from tpu_dist.ops.flash_attention import blockwise_attention_fn
            attn_fn = blockwise_attention_fn(cfg.attn_block)
        elif cfg.attn == "flash":
            from tpu_dist.ops.flash_attention import flash_attention_fn
            attn_fn = flash_attention_fn(block_k=cfg.attn_block)
            if not (self.use_sp or self.use_pp or self.use_ring
                    or self.use_bucket):
                # the compiler-partitioned modes (dp/fsdp/tp/ep): a Mosaic
                # kernel cannot be partitioned by GSPMD, so on a multi-
                # device mesh the kernel runs per shard — batch rows over
                # 'data', heads over 'model'. The manual modes above
                # already call it from inside their own shard_map.
                train_attn_fn = flash_attention_fn(
                    block_k=cfg.attn_block, mesh=self.mesh,
                    spec=P("data", None,
                           "model" if self.use_tp else None, None))
        elif cfg.attn == "full":
            from tpu_dist.models.transformer import full_attention
            attn_fn = full_attention
        else:
            raise ValueError(f"unknown attn {cfg.attn!r}")
        if self.use_sp and cfg.attn != "full":
            self.log(f"warning: a 'seq' mesh axis uses ring attention; "
                     f"attn={cfg.attn} ignored")
        from tpu_dist.ops.quant import validate_quant
        validate_quant(cfg.quant)
        lm_kw = dict(vocab_size=self.vocab_size, num_layers=cfg.num_layers,
                     d_model=cfg.d_model, num_heads=cfg.num_heads,
                     max_len=cfg.seq_len, dtype=self.policy.compute_dtype,
                     attn_fn=train_attn_fn or attn_fn, remat=cfg.remat,
                     quant=cfg.quant)
        if cfg.num_experts:
            from tpu_dist.models.moe import MoETransformerLM
            # the MoE knobs ride in the ctor kwargs so EVERY mode (jit, sp
            # rebind, windowed) builds the identical model from ONE dict
            lm_kw = dict(lm_kw, num_experts=cfg.num_experts,
                         router_top_k=cfg.router_top_k,
                         group_size=cfg.moe_group_size,
                         capacity_factor=cfg.moe_capacity_factor)
            model_cls = MoETransformerLM
        else:
            from tpu_dist.models.transformer import tiny_lm
            model_cls = tiny_lm
        model = model_cls(**lm_kw)
        # generate/serve apply these weights on ONE device: same attention
        # math, no training mesh bound into the kernel call
        decode_model = (model if train_attn_fn is None
                        else model.clone(attn_fn=attn_fn))
        # the sp lowerings bind ring attention per seq axis themselves:
        # they take the constructor (``ctor(attn_fn=...)``), not the module
        ctor = partial(model_cls, **{k: v for k, v in lm_kw.items()
                                     if k != "attn_fn"})
        return model, ctor, decode_model

    def _build_steps(self):
        """THE place the step programs come from: the config's plan with
        the window kind the corpus allows, compiled against this run's
        objects. ``train_step``/``eval_step`` take one host-fed batch;
        ``window_step``/``window_eval_step`` scan (K, B) index windows
        over the HBM-resident row matrix, in every mode."""
        cfg = self.cfg
        self.valid_spec = P("data")
        if self.use_pp:
            from tpu_dist.parallel import pp
            if cfg.pp_schedule not in ("gpipe", "1f1b"):
                raise ValueError(f"unknown pp_schedule {cfg.pp_schedule!r} "
                                 "(gpipe|1f1b)")
            train_kw = dict(loss_chunk=cfg.loss_chunk,
                            aux_weight=cfg.moe_aux_weight,
                            grad_clip=cfg.grad_clip, health=cfg.health)
            maker = (pp.make_lm_pp_1f1b_train_step
                     if cfg.pp_schedule == "1f1b"
                     else pp.make_lm_pp_train_step)
            self.train_step = maker(self.model, self.tx, self.mesh,
                                    cfg.pp_microbatches, **train_kw)
            self.eval_step = pp.make_lm_pp_eval_step(
                self.model, self.mesh, cfg.pp_microbatches,
                loss_chunk=cfg.loss_chunk)
            if self.device_data:
                self.window_step = pp.make_lm_pp_indexed_multi_train_step(
                    self.model, self.tx, self.mesh, cfg.pp_microbatches,
                    schedule=cfg.pp_schedule, **train_kw)
                self.window_eval_step = pp.make_lm_pp_indexed_eval_step(
                    self.model, self.mesh, cfg.pp_microbatches,
                    loss_chunk=cfg.loss_chunk)
            self.data_spec = P("data", None)
            return
        sp = self.plan.layout == "sp"
        if sp:
            # sp's training model closes over mesh axis names (ring
            # attention); decode with the full-attention equivalent
            self.decode_model = self._model_ctor()
        self.data_spec = P("data", "seq") if sp else P("data")
        self.plan = dataclasses.replace(
            self.plan, window="indexed" if self.device_data else "none")
        binds = Bindings(mesh=self.mesh, model=self.model,
                         model_ctor=self._model_ctor, tx=self.tx)
        per_batch = dataclasses.replace(self.plan, window="none")
        self.train_step = compile_train_step(per_batch, binds)
        self.eval_step = compile_eval_step(per_batch, binds)
        if self.device_data:
            self.window_step = compile_train_step(self.plan, binds)
            self.window_eval_step = compile_eval_step(self.plan, binds)

    def _place(self, st):
        """Apply the mode's parameter sharding (also re-places resumes)."""
        cfg = self.cfg
        if self.use_pp:
            from tpu_dist.parallel.pp import shard_state_pp
            return shard_state_pp(self.mesh, st)
        if self.use_ep:
            from tpu_dist.parallel.ep import shard_state_ep
            return shard_state_ep(self.mesh, st)
        if self.use_ring:
            # ring TP keeps params replicated (each device slices its
            # column/row shard at use — parallel.overlap design note)
            # distlint: disable=DL008 -- state placement at init/resume, not a per-step input upload
            return jax.device_put(st, replicated(self.mesh))
        if self.use_tp:
            from tpu_dist.parallel.tp import shard_lm_params
            # distlint: disable=DL008 -- state placement at init/resume, not a per-step input upload
            return TrainState(
                step=jax.device_put(st.step, NamedSharding(self.mesh, P())),
                params=shard_lm_params(self.mesh, st.params), batch_stats={},
                opt_state=jax.device_put(st.opt_state,
                                         NamedSharding(self.mesh, P())),
                loss_scale=None)
        if cfg.fsdp and not (self.use_sp or self.use_pp):
            from tpu_dist.parallel.fsdp import shard_state_fsdp
            return shard_state_fsdp(self.mesh, st)
        # distlint: disable=DL008 -- state placement at init/resume, not a per-step input upload
        return jax.device_put(st, replicated(self.mesh))

    # ------------------------------------------------------------------
    def _measure_comm_probe(self) -> float:
        """Wall seconds of ONE standalone bucketed grad sync at this run's
        exact bucket geometry (zeros in the params' shapes) — the comm_s
        estimate stamped on step ledger records. One extra tiny compile,
        paid only when grad_bucket_mb > 0."""
        import jax.numpy as jnp
        from tpu_dist._compat import shard_map
        from tpu_dist.parallel.overlap import bucketed_grad_sync

        n = self.mesh.shape["data"]
        mb = self.cfg.grad_bucket_mb
        sync = jax.jit(shard_map(
            lambda g: bucketed_grad_sync(g, "data", mb, mean=True,
                                         axis_size=n),
            mesh=self.mesh, in_specs=P(), out_specs=P(), check_vma=False))
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             self.state.params)
        # distlint: disable=DL002 -- comm probe: compile+warm barrier, measures the sync on purpose
        jax.block_until_ready(sync(zeros))  # compile + warm
        t0 = time.time()
        # distlint: disable=DL002 -- comm probe: the measured barrier itself
        jax.block_until_ready(sync(zeros))
        return time.time() - t0

    def log(self, *a, **kw):
        if getattr(self, "is_main", jax.process_index() == 0):
            print(*a, **kw, flush=True)

    def _sampler(self, ds, train: bool, epoch: int) -> DistributedSampler:
        sampler = DistributedSampler(
            len(ds), num_replicas=jax.process_count(),
            rank=jax.process_index(), shuffle=train,
            seed=(self.cfg.seed or 0) + (17 if not train else 0),
            batch_size=self.local_batch)
        sampler.set_epoch(epoch)
        return sampler

    def _epoch_indices(self, ds, train: bool, epoch: int):
        """(idx (nb, B), valid (nb, B)) — the SAME batch-blocked layout as
        the image Trainer (load-bearing for N-process bit-exactness)."""
        sampler = self._sampler(ds, train, epoch)
        idx, valid = sampler.indices_with_valid()
        nb = sampler.num_samples // self.local_batch
        n = nb * self.local_batch
        shape = (nb, self.local_batch)
        return (np.asarray(idx[:n], np.int32).reshape(shape),
                np.asarray(valid[:n], np.float32).reshape(shape))

    def _drain(self, pending, meters) -> None:
        """One blocking transfer per print window (the async-dispatch sync
        point), then one ledger ``step`` record per drained entry with the
        transfer's device-block time apportioned across the window. The
        fused health probes ride the same fetch (obs.health): under
        ``skip`` a non-finite record stays out of the meter averages (its
        update was already zeroed on device), and under ``halt`` the
        sentry raises out of the loop."""
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
            # under 'skip' the non-finite step's update was zeroed on
            # device, so its NaN loss must not poison the epoch averages;
            # under 'record'/'halt' the NaN flows through — divergence
            # should be VISIBLE in the printed loss, as it always was
            if math.isfinite(loss) or self.obs.health.policy != "skip":
                meters.update("Loss", loss, int(cnt))
                meters.update("Acc", float(m["correct1"]) / cnt, int(cnt))
            # MoE router health: mean per-token combine mass (1.0 = no
            # capacity drops; the dropped fraction is ~(1 - RMass) for
            # top-2, and (1 - RMass/avg_gate) for top-1)
            n = float(m.get("router_mass_n", 0.0))
            if n > 0:
                meters.update("RMass", float(m["router_mass_sum"]) / n,
                              int(n))
            k = info["n_steps"]
            share = device_s * k / total_steps
            gn = float(m["grad_norm"]) / k
            nf = float(m["nonfinite_count"])
            un = float(m["update_norm"]) / k
            self.obs.step(
                info["step"], loss, info["n_items"],
                wall_s=info["data_s"] + info["dispatch_s"] + share,
                data_s=info["data_s"], dispatch_s=info["dispatch_s"],
                device_s=share, device_flops=self._device_step_flops(),
                steps_in_dispatch=k,
                warm=info.get("warm", False),
                comm_s=(self._comm_probe_s * k
                        if self._comm_probe_s else None),
                fused=self._fused_quant,
                grad_norm=gn, nonfinite_count=nf, update_norm=un,
                hbm_bytes_in_use=hbm.get("bytes_in_use"),
                hbm_peak_bytes=hbm.get("peak_bytes_in_use"))
            self.obs.health.observe(info["step"], loss, nonfinite=nf,
                                    grad_norm=gn, update_norm=un, n_steps=k)
        pending.clear()
        self.obs.heartbeat()  # watchdog: device progress proven at this sync
        # recompile sentry (PL005): a host-only trace-cache counter read
        # at the sanctioned boundary — no device sync rides on it
        from tpu_dist.plan.compile import check_audit_sentry
        check_audit_sentry()

    def _meter_fields(self):
        fields = [("Time", "6.3f"), ("Data", "6.3f"), ("Loss", ".4e"),
                  ("Acc", "6.3f")]
        if self.cfg.num_experts:
            fields.append(("RMass", "5.3f"))
        return fields

    # ------------------------------------------------------------------
    def train_epoch(self, epoch: int) -> Dict[str, float]:
        with trace.ring().span("train.epoch", epoch=epoch):
            if self.device_data:
                return self._train_epoch_windowed(epoch)
            return self._train_epoch_batched(epoch)

    def _train_epoch_batched(self, epoch: int) -> Dict[str, float]:
        cfg = self.cfg
        idx, _ = self._epoch_indices(self.train_ds, True, epoch)
        nb = len(idx)
        meters = MeterBank(nb, self._meter_fields(),
                           prefix=f"Epoch: [{epoch}]")
        skip = self._skip_batches
        self._skip_batches = 0
        self.obs.resume()  # watchdog watches from epoch entry
        if self.accum > 1:
            # host-side split into (N, B/N, L) microbatches, sharded
            # (None, 'data') so every microbatch spans all devices
            sh = NamedSharding(self.mesh, P(None, "data"))
            shape = lambda a: a.reshape(self.accum, -1, a.shape[-1])
        else:
            sh = NamedSharding(self.mesh, self.data_spec)
            shape = lambda a: a

        def batches():
            # row gather + shift + upload dispatch, run in the prefetch
            # thread so assembly never stalls the dispatch loop
            for j in range(skip, nb):
                rows = self.train_ds.get_rows(idx[j])
                inputs, targets = make_lm_batches(rows)
                yield (j,
                       assemble_global(sh, np.ascontiguousarray(
                           shape(inputs))),
                       assemble_global(sh, np.ascontiguousarray(
                           shape(targets))))

        from tpu_dist.data.loader import stream_prefetch
        pending = []
        warm_secs, warm_batches = 0.0, 0
        i = skip - 1
        tokens_per_batch = cfg.batch_size * cfg.seq_len
        tr = self.obs.tracer
        end = time.time()
        for i, inputs_d, targets_d in tr.timed_iter(
                "data", stream_prefetch(batches())):
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
            was_cold = not self._warmed  # this dispatch carries the compile
            with step_annotation(gstep), \
                    tr.span("dispatch", step=gstep, first_call=was_cold):
                self.state, metrics = self.train_step(
                    self.state, inputs_d, targets_d, self.rng)
            dispatch_s = tr.pop().get("dispatch", 0.0)
            if not self._warmed:
                # compile + first step, to the wall — a deliberate one-time
                # block so warm_secs excludes XLA compile from tok/s
                # distlint: disable=DL002 -- intentional single sync on the run's first dispatch (compile-wall measurement)
                jax.device_get(metrics)
                self._warmed = True
                warm_secs = time.time() - end
                warm_batches = 1
            if self._program_hbm is None:
                # probe AFTER the dispatch (and after the warm-timing
                # device_get, so warm_secs stays honest): the AOT lower/
                # compile would not seed jit's dispatch cache, so probing
                # first would compile the step twice (telemetry.py
                # contract); same-iteration probing keeps the column on
                # single-dispatch runs
                from tpu_dist.plan.compile import audit_mode, audit_program
                from tpu_dist.utils.telemetry import program_stats
                st = program_stats(self.train_step, self.state, inputs_d,
                                   targets_d, self.rng,
                                   with_hlo=bool(self.obs.ledger.path)
                                   or audit_mode() != "none")
                self._program_hbm = st["hbm_bytes"] or False
                self.obs.ledger.emit(
                    "compile", program="train_step",
                    seconds=warm_secs or None,
                    hbm_bytes=st["hbm_bytes"], flops=st["flops"])
                # compile-time audit pass against the SAME lowered
                # artifact (plan.compile.audit_program) — a no-op under
                # audit=none, one 'audit' ledger event per program else
                audit_program("train_step", self.train_step, self.state,
                              inputs_d, targets_d, self.rng,
                              hlo=st.get("hlo"), precision=cfg.precision)
                if st.get("hlo"):
                    # static cost attribution of the same executable (one
                    # lower for hbm/flops/buckets — obs.attr roofline)
                    from tpu_dist.obs.attr import emit_cost_model
                    emit_cost_model(self.obs.ledger, "train_step",
                                    st["hlo"], xla_flops=st["flops"])
            pending.append((metrics, {
                "step": gstep, "n_steps": 1, "n_items": tokens_per_batch,
                "data_s": data_s, "dispatch_s": dispatch_s,
                "warm": was_cold}))
            boundary = i % cfg.print_freq == 0 or i == nb - 1
            if boundary:
                self._drain(pending, meters)
            meters.update("Time", time.time() - end)
            if boundary and self.is_main:
                meters.display(i)
            end = time.time()
            if self._step_cap_hit(epoch, i + 1):
                break
        if pending:  # a max_steps break can land between print boundaries
            self._drain(pending, meters)
        self.obs.pause()  # eval/ckpt follow: steps stop completing by design
        done = i + 1 - skip if nb else 0
        snap = meters.snapshot()  # ONE read feeds printer, ledger, and return
        out = {"loss": snap["Loss"]["avg"], "acc": snap["Acc"]["avg"],
               "batches": done, "warmup_secs": warm_secs,
               "warmup_batches": warm_batches}
        if self.cfg.num_experts:
            out["rmass"] = snap["RMass"]["avg"]
        return out

    def _device_windows(self, epoch: int, skip: int, put):
        batches, _ = self._epoch_indices(self.train_ds, True, epoch)
        batches = batches[skip:]
        if self.cfg.max_steps:
            # a K-step dispatch is atomic, so clip the window list to the
            # remaining step budget — otherwise the windowed path would
            # overshoot max_steps by up to K-1 optimizer steps
            remaining = self.cfg.max_steps - \
                (epoch * self.steps_per_epoch + skip)
            batches = batches[:max(remaining, 0)]
        return [(len(w), put(np.ascontiguousarray(w)))
                for w in (batches[i:i + self.k]
                          for i in range(0, len(batches), self.k))]

    def _train_epoch_windowed(self, epoch: int) -> Dict[str, float]:
        """K optimizer steps per dispatch over HBM-resident rows: the host
        sends only (K, B) int32 index windows (the image Trainer's indexed
        path, loop.py, applied to tokens)."""
        cfg = self.cfg
        nb = self.steps_per_epoch
        meters = MeterBank(nb, self._meter_fields(),
                           prefix=f"Epoch: [{epoch}]")
        skip = self._skip_batches
        self._skip_batches = 0
        self.obs.resume()  # watchdog watches from epoch entry
        win_sh = NamedSharding(self.mesh, P(None, "data"))
        put = partial(assemble_global, win_sh)
        cached = self._prefetched_windows
        self._prefetched_windows = None
        if cached is not None and cached[0] == epoch and skip == 0:
            windows = cached[1]
        else:
            windows = self._device_windows(epoch, skip, put)
        pending = []
        done = skip
        last_print = skip - 1
        warm_secs, warm_batches = 0.0, 0
        tokens_per_batch = cfg.batch_size * cfg.seq_len
        tr = self.obs.tracer
        end = time.time()
        for n, idx_dev in tr.timed_iter("data", windows):
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
            was_cold = not self._warmed  # this dispatch carries the compile
            # each window length is a compiled program of its own
            first_call = n not in self._dispatched_windows
            self._dispatched_windows.add(n)
            gstep = epoch * self.steps_per_epoch + done
            with step_annotation(gstep), \
                    tr.span("dispatch", step=gstep, first_call=first_call):
                self.state, metrics = self.window_step(
                    self.state, self._train_rows_dev, idx_dev, self.rng)
            dispatch_s = tr.pop().get("dispatch", 0.0)
            if not self._warmed:
                # compile + first window, to the wall (see train_epoch)
                # distlint: disable=DL002 -- intentional single sync on the run's first dispatch (compile-wall measurement)
                jax.device_get(metrics)
                self._warmed = True
                warm_secs = time.time() - end
                warm_batches = n
            if self._program_hbm is None:
                # post-dispatch probe (same iteration, so single-window
                # runs record it too): see telemetry.program_stats
                from tpu_dist.plan.compile import audit_mode, audit_program
                from tpu_dist.utils.telemetry import program_stats
                st = program_stats(self.window_step, self.state,
                                   self._train_rows_dev, idx_dev, self.rng,
                                   with_hlo=bool(self.obs.ledger.path)
                                   or audit_mode() != "none")
                self._program_hbm = st["hbm_bytes"] or False
                self.obs.ledger.emit(
                    "compile", program="window_step",
                    seconds=warm_secs or None,
                    hbm_bytes=st["hbm_bytes"], flops=st["flops"])
                # same-artifact compile-time audit (plan.compile)
                audit_program("window_step", self.window_step, self.state,
                              self._train_rows_dev, idx_dev, self.rng,
                              hlo=st.get("hlo"), precision=cfg.precision)
                if st.get("hlo"):
                    # static cost attribution (obs.attr), same executable
                    from tpu_dist.obs.attr import emit_cost_model
                    emit_cost_model(self.obs.ledger, "window_step",
                                    st["hlo"], xla_flops=st["flops"])
            done += n
            pending.append((metrics, {
                "step": epoch * self.steps_per_epoch + done - 1,
                "n_steps": n, "n_items": n * tokens_per_batch,
                "data_s": data_s, "dispatch_s": dispatch_s,
                "warm": was_cold}))
            boundary = (done - 1) - last_print >= cfg.print_freq or done == nb
            if boundary and done == nb and epoch + 1 < cfg.epochs:
                # queue next epoch's index uploads before blocking on metrics
                self._prefetched_windows = (
                    epoch + 1, self._device_windows(epoch + 1, 0, put))
            if boundary:
                self._drain(pending, meters)
                last_print = done - 1
            meters.update("Time", (time.time() - end) / n, n)
            if boundary and self.is_main:
                meters.display(done - 1)
            end = time.time()
            if self._step_cap_hit(epoch, done):
                break
        if pending:  # a max_steps break can land between print boundaries
            self._drain(pending, meters)
        self.obs.pause()  # eval/ckpt follow: steps stop completing by design
        snap = meters.snapshot()
        out = {"loss": snap["Loss"]["avg"], "acc": snap["Acc"]["avg"],
               "batches": done - skip, "warmup_secs": warm_secs,
               "warmup_batches": warm_batches}
        if self.cfg.num_experts:
            out["rmass"] = snap["RMass"]["avg"]
        return out

    def _step_cap_hit(self, epoch: int, batches_done: int) -> bool:
        cap = self.cfg.max_steps
        return bool(cap) and epoch * self.steps_per_epoch + batches_done >= cap

    def _apply_nan_fault(self) -> None:
        """The ``nan_batch`` injection effect (obs.faults): token inputs
        are integers, so the numeric fault lands on the param tree — the
        next step's loss/grads go non-finite exactly as a NaN batch would
        make them, and the health sentry/policy takes it from there."""
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
                0.0, "lm", is_best=False,
                extra_meta={"mid_epoch": True, "preempt": True,
                            "best_ppl": self.best_ppl, **self._run_meta},
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
                         best_ppl=self.best_ppl)
        raise SystemExit(PREEMPT_SNAPSHOT_RC)

    # ------------------------------------------------------------------
    def validate(self, epoch: int = 0):
        """Exact held-out metrics in EVERY mode: (loss, ppl, acc).
        Sampler wrap-padding is masked per row; sums divide by the true
        token count (the image Trainer's C15 contract, for tokens)."""
        t0_eval = time.time()  # exact eval badput for the goodput ledger
        idx, valid = self._epoch_indices(self.val_ds, False, epoch)
        if self._val_rows_dev is not None:
            win_sh = NamedSharding(self.mesh, P(None, "data"))
            # distlint: disable=DL002 -- one-dispatch eval: the eval drain boundary
            m = jax.device_get(self.window_eval_step(
                self.state.params, self._val_rows_dev,
                assemble_global(win_sh, np.ascontiguousarray(idx)),
                assemble_global(win_sh, np.ascontiguousarray(valid))))
            sums = {k: float(m[k]) for k in LM_METRIC_KEYS}
        else:
            sh = NamedSharding(self.mesh, self.data_spec)
            vsh = NamedSharding(self.mesh, self.valid_spec)
            pending = []
            for i in range(len(idx)):
                rows = self.val_ds.get_rows(idx[i])
                inputs, targets = make_lm_batches(rows)
                pending.append(self.eval_step(
                    self.state.params,
                    assemble_global(sh, np.ascontiguousarray(inputs)),
                    assemble_global(sh, np.ascontiguousarray(targets)),
                    assemble_global(vsh, np.ascontiguousarray(valid[i]))))
            sums = {k: 0.0 for k in LM_METRIC_KEYS}
            # distlint: disable=DL002 -- eval drain boundary: pending eval metrics fetched in one batch
            for m in jax.device_get(pending):
                for k in sums:
                    sums[k] += float(m[k])
        n = max(sums["count"], 1.0)
        loss = sums["loss_sum"] / n
        ppl = float(np.exp(min(loss, 30.0)))
        acc = sums["correct1"] / n
        self.obs.ledger.emit("eval", epoch=epoch, loss=loss, ppl=ppl,
                             acc=acc, count=int(sums["count"]),
                             seconds=round(time.time() - t0_eval, 6))
        self.log(f" * val_loss {loss:.4f} ppl {ppl:.2f} acc {acc:.3f}")
        return loss, ppl, acc

    # ------------------------------------------------------------------
    def _device_step_flops(self):
        """Per-device-program share of ONE optimizer step's model FLOPs
        (analytical — utils.mfu; computed once, lazily). Feeds both the
        epoch-line MFU (:meth:`_mfu`) and the per-step ledger records."""
        cfg = self.cfg
        if self._flops_per_step is None:
            from tpu_dist.utils.mfu import (lm_flops_per_token,
                                            moe_lm_flops_per_token)
            if cfg.num_experts:
                per_token = moe_lm_flops_per_token(
                    self.state.params, cfg.num_layers, cfg.seq_len,
                    cfg.d_model, cfg.num_experts, cfg.router_top_k,
                    total_tokens=cfg.batch_size * cfg.seq_len,
                    group_size=cfg.moe_group_size,
                    capacity_factor=cfg.moe_capacity_factor)
            else:
                per_token = lm_flops_per_token(
                    self.state.params, cfg.num_layers, cfg.seq_len,
                    cfg.d_model)
            ndev = self.mesh.devices.size
            self._flops_per_step = per_token * cfg.batch_size * \
                cfg.seq_len / ndev
        return self._flops_per_step or None

    def _mfu(self, tok_per_sec: float):
        """(tflops, mfu). ANALYTICAL model-FLOPs accounting for dense
        (6*N_non-embed + 6*layers*L*d, fwd+bwd, causal) AND MoE (dense part
        + top_k-activated expert params + the GShard dispatch/combine
        einsums) — XLA's cost model counts scan bodies once and cannot cost
        Pallas custom calls, so it understates flash runs, and it cannot
        see how many experts a token activates."""
        from tpu_dist.utils.mfu import peak_tflops_for
        if not self._device_step_flops():
            return None, None
        # per-device program FLOPs over the tokens IT processes per step
        tokens_per_step = self.cfg.batch_size * self.cfg.seq_len
        ndev = self.mesh.devices.size
        flops_per_token = self._flops_per_step / (tokens_per_step / ndev)
        tflops = (tok_per_sec / ndev) * flops_per_token / 1e12
        peak = peak_tflops_for(jax.devices()[0])
        return tflops, (tflops / peak if peak else None)

    # ------------------------------------------------------------------
    def fit(self) -> float:
        """Returns best val perplexity."""
        cfg = self.cfg
        # SIGTERM becomes a snapshot request this loop drains at its next
        # step boundary (the coordinated-preemption contract)
        self.obs.enable_preempt_snapshot()
        self.obs.run_start()
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
                return self.validate(0)[1]
            finally:
                self.obs.run_end(best_ppl=self.best_ppl)
        stop_telemetry = None
        if cfg.telemetry_csv:
            # EVERY process samples; non-main paths are .pN-suffixed so
            # multi-host runs never clobber one file (obs.per_process_path)
            from tpu_dist.obs import per_process_path
            from tpu_dist.utils.telemetry import start_hbm_sampler
            stop_telemetry = start_hbm_sampler(
                per_process_path(cfg.telemetry_csv, jax.process_index()),
                ledger=self.obs.ledger)
        try:
            # real XLA trace (per-op device time, HBM, MXU utilization) —
            # the same C22 hook the image Trainer has; obs.profile_session
            # flushes it even on OOM/interrupt
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
            if cfg.checkpoint_dir:
                ckpt.save_checkpoint(cfg.checkpoint_dir, self.state,
                                     self._epoch_in_progress,
                                     0.0, "lm", is_best=False,
                                     extra_meta={"mid_epoch": True,
                                                 "best_ppl": self.best_ppl,
                                                 **self._run_meta},
                                     keep=cfg.keep_checkpoints)
                self.log(f"interrupted — checkpoint saved at epoch "
                         f"{self._epoch_in_progress}; resume with --resume")
            else:
                self.log("interrupted — no checkpoint_dir, nothing saved")
            raise
        finally:
            if stop_telemetry is not None:
                stop_telemetry()
            ckpt.wait_for_async_save()
            self.obs.run_end(best_ppl=self.best_ppl)
        return self.best_ppl

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
            loss, ppl, acc = self.validate(epoch)
            epoch_secs = time.time() - t0
            # throughput excludes the first dispatch of the RUN (XLA compile
            # rides on it — the old scripts/8 loop's 'first step compiles'
            # exclusion, kept through the Trainer rewrite)
            w_secs = train_metrics.get("warmup_secs", 0.0)
            w_batches = train_metrics.get("warmup_batches", 0)
            timed_batches = train_metrics["batches"] - w_batches
            if timed_batches > 0:
                tok_s = (timed_batches * cfg.batch_size * cfg.seq_len
                         / max(train_secs - w_secs, 1e-9))
            else:  # single-dispatch epoch: report the compile-laden rate
                tok_s = (train_metrics["batches"] * cfg.batch_size
                         * cfg.seq_len / max(train_secs, 1e-9))
            self.last_tok_s = tok_s
            tflops, mfu = self._mfu(tok_s)
            is_best = ppl < self.best_ppl
            self.best_ppl = min(ppl, self.best_ppl)
            # the epoch record; the legacy per-epoch CSV row renders from
            # THIS event via the obs layer's EpochCsvSink — one source
            from tpu_dist.utils.telemetry import peak_hbm_bytes
            self.obs.ledger.emit(
                "epoch", epoch=epoch, start_ts=t0, seconds=epoch_secs,
                throughput=tok_s, unit="tok/s",
                loss=train_metrics["loss"], ppl=ppl, mfu=mfu, tflops=tflops,
                hbm_bytes=peak_hbm_bytes() or self._program_hbm or None,
                batches=train_metrics.get("batches"))
            if cfg.checkpoint_dir:
                t0_ck = time.time()  # sync-path save cost (async writes
                # overlap the next epoch; the goodput ledger charges only
                # what actually blocked the loop)
                ckpt.save_checkpoint(
                    cfg.checkpoint_dir, self.state, epoch + 1, 0.0, "lm",
                    is_best, extra_meta={"best_ppl": self.best_ppl,
                                         **self._run_meta},
                    async_write=True, keep=cfg.keep_checkpoints)
                self.obs.ledger.emit(
                    "ckpt", epoch=epoch + 1, path=cfg.checkpoint_dir,
                    is_best=is_best,
                    seconds=round(time.time() - t0_ck, 6))
            # LR actually applied by the LAST update of this epoch (the
            # schedule is evaluated at the pre-increment step counter)
            # distlint: disable=DL002 -- epoch boundary: validate() just drained the device queue, one scalar fetch is free
            step_done = int(jax.device_get(self.state.step))
            lr_now = float(self.lr_schedule(max(step_done - 1, 0)))
            self.log(
                f"Epoch {epoch} [{self.mode}]: "
                f"train_loss={train_metrics['loss']:.4f} "
                f"val_ppl={ppl:.2f} best={self.best_ppl:.2f} "
                f"lr={lr_now:.3g} "
                f"({epoch_secs:.1f}s, train {tok_s:,.0f} tok/s"
                + (f", {tflops:.1f} TF/s/chip" if tflops else "")
                + (f", MFU {mfu * 100:.1f}%" if mfu else "") + ")")
            if self._step_cap_hit(epoch, self.steps_per_epoch):
                self.log(f"max_steps={cfg.max_steps} reached")
                return
