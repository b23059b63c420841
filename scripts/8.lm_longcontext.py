#!/usr/bin/env python
"""Variant 8 — long-context transformer LM over a dp x sp / tp / ep / pp mesh.

Beyond the reference (which is DP-only over image CNNs, SURVEY.md §2c):
trains a causal LM on a REAL token corpus through the shared LM engine
(tpu_dist.engine.lm_loop.LMTrainer) — epochs, distributed sampler rows,
K-steps-per-dispatch windows from HBM-resident rows, exact held-out
perplexity in every mode, mid-epoch resume — with the parallelism picked by
flags:

  --mesh data=8                 pure data parallel (jit)
  --mesh data=2,seq=4           sequence parallel: ring attention over 'seq'
  --mesh data=4,model=2         tensor parallel: Megatron shardings via GSPMD
  --mesh data=2,expert=4        MoE expert parallelism (with --num-experts)
  --mesh data=2,stage=4         pipeline parallel (--pp-schedule gpipe|1f1b)
  --mesh data=2,stage=2,model=2 pipeline x tensor parallel (Megatron inside
                                each stage via a GSPMD auto axis)

Data: --data points at a token file (.bin uint16 / .npy, nanoGPT-style);
absent, a deterministic synthetic affine corpus is generated so the loss
curve is meaningful without downloads. --steps N caps optimizer steps
(smoke runs); otherwise --epochs governs. Same multi-host launch story as
every other variant (tpu_dist.parallel.launch).
"""

import argparse
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np


def parse_mesh(s):
    shape, axes = [], []
    for part in s.split(","):
        name, n = part.split("=")
        axes.append(name.strip())
        shape.append(int(n))
    return tuple(shape), tuple(axes)


def main():
    from tpu_dist.configs import LMConfig, add_args

    ap = argparse.ArgumentParser(description=__doc__)
    add_args(ap, LMConfig())
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    help="e.g. data=2,seq=4 | data=4,model=2 | data=8 "
                         "(overrides --mesh-shape/--mesh-axes)")
    ap.add_argument("--steps", type=int, default=0,
                    help="alias for --max-steps (cookbook compat)")
    ap.add_argument("--generate", type=int, default=0,
                    help="after training, greedy-decode N tokens from the "
                         "trained model and report how often they follow "
                         "the synthetic affine rule")
    ap.add_argument("--serve", type=int, default=0,
                    help="after training, stand up the long-context "
                         "ServeEngine (chunked prefill + sp-sharded paged "
                         "KV pool) on the SAME devices the sequence axis "
                         "trained on and serve N short requests plus one "
                         "long prompt: chunked when training ran without "
                         "sp, sequence-parallel prefill into the sharded "
                         "pool when it did")
    args = ap.parse_args()

    from tpu_dist.parallel import launch
    info = launch.initialize()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = LMConfig(**{f.name: getattr(args, f.name)
                      for f in dataclasses.fields(LMConfig)})
    if args.mesh:
        cfg = dataclasses.replace(cfg, mesh_shape=args.mesh[0],
                                  mesh_axes=args.mesh[1])
    if args.steps:
        cfg = dataclasses.replace(cfg, max_steps=args.steps)

    trainer = LMTrainer(cfg)
    if jax.process_index() == 0:
        print(f"[proc {info.process_id}/{info.num_processes}] "
              f"mesh={dict(trainer.mesh.shape)} mode={trainer.mode} "
              f"corpus={trainer.train_ds.name} rows={len(trainer.train_ds)} "
              f"tokens/step={cfg.batch_size * cfg.seq_len}")
    if cfg.max_restarts > 0:
        # in-process self-healing (parallel.supervisor): halts/crashes
        # rebuild the trainer with attempt lineage + newest-valid resume.
        # The prebuilt trainer serves attempt 0 (avoids a second compile);
        # restarts rebuild, and the --generate path below must decode the
        # LAST attempt's state, so the factory tracks it. Process-killing
        # faults need the subprocess flavor:
        # python -m tpu_dist.supervise -- python scripts/8...
        from tpu_dist.parallel.supervisor import run_supervised
        current = {"trainer": trainer, "used": False}

        def build(run_cfg):
            if current["used"]:
                # drop the dead attempt's trainer BEFORE constructing the
                # replacement: its params/opt-state must be collectable
                # while the rebuild re-allocates them (HBM headroom)
                current["trainer"] = None
                current["trainer"] = LMTrainer(run_cfg)
            current["used"] = True  # one-shot: attempt 0 and ONLY attempt
            # 0 gets the prebuilt trainer, even when it died pre-step
            return current["trainer"]

        best_ppl = run_supervised(build, cfg)
        trainer = current["trainer"]
    else:
        best_ppl = trainer.fit()
    if jax.process_index() == 0 and not cfg.evaluate:
        print(f"throughput {trainer.last_tok_s:,.0f} tokens/sec "
              f"({trainer.mode}) best_ppl {best_ppl:.2f}")

    if args.generate or args.serve:
        # decode on host-replicated params; the gather is a COLLECTIVE for
        # cross-host sharded modes, so EVERY process enters it — only the
        # decode itself is process-0-only. pp's stacked layout is restored
        # to the dense tree first.
        from tpu_dist.engine.checkpoint import gather_to_host
        from tpu_dist.engine.generate import generate
        host_params = gather_to_host(trainer.state.params)
    if args.generate and jax.process_index() == 0:
        if trainer.use_pp:
            from tpu_dist.parallel.pp import unstack_pipeline_params
            host_params = unstack_pipeline_params(host_params)
        n = min(args.generate, cfg.seq_len - 2)
        seed = 3
        prompt = jnp.asarray([[seed, (seed * 5 + 7) % trainer.vocab_size]],
                             jnp.int32)
        # trainer.decode_model: the trained weights' single-device twin
        # (sp's ring attention and the mesh-bound flash kernel both stay
        # behind in training). Dense AND MoE models decode through the KV
        # cache (models.transformer.attend_maybe_cached is shared).
        gen_model = trainer.decode_model
        out = np.asarray(generate(gen_model, host_params, prompt, steps=n,
                                  use_cache=True))
        follows = sum(int(out[0, i + 1])
                      == (int(out[0, i]) * 5 + 7) % trainer.vocab_size
                      for i in range(1, n + 1))
        print(f"generated {n} tokens, {follows}/{n} follow the affine rule: "
              f"{out[0].tolist()}")

    if args.serve and jax.process_index() == 0:
        # the serving half of the long-context story: the engine stands up
        # on the SAME local devices the model's sequence axis trained on.
        # With --mesh ...,seq=N the KV pool shards its page arenas over an
        # ('sp',) submesh of those N devices and long prompts prefill
        # sequence-parallel (ring attention, scattered KV writes); without
        # sp the long prompt goes through chunked prefill instead — either
        # way short requests keep decoding in between.
        from tpu_dist.engine.serve import (DecodeRequest, ServeConfig,
                                           ServeEngine)
        from tpu_dist.parallel.mesh import SEQ_AXIS, SP_AXIS, make_mesh

        if trainer.use_pp:
            print("--serve: pipeline-stacked params don't decode through "
                  "the serving engine (use --generate's dense restore)")
            return
        sp_n = (int(trainer.mesh.shape[SEQ_AXIS])
                if trainer.use_sp else 1)
        sp_n = min(sp_n, len(jax.local_devices()))
        page_size = 8
        if sp_n > 1 and cfg.seq_len < 2 * sp_n * page_size:
            print(f"--serve: seq_len {cfg.seq_len} too short for a "
                  f"{sp_n}-device sp pool; serving chunked on one device")
            sp_n = 1
        step = sp_n * page_size
        serve_len = (cfg.seq_len // step) * step
        serve_model = trainer.decode_model
        mesh = (make_mesh((sp_n,), (SP_AXIS,),
                          devices=jax.local_devices()[:sp_n])
                if sp_n > 1 else None)
        thresh = serve_len // 2
        scfg = ServeConfig(
            max_slots=4, page_size=page_size,
            num_pages=4 * (serve_len // page_size), max_len=serve_len,
            prefill_chunk=2 * page_size,
            sp_prefill_threshold=thresh if mesh is not None else 0)
        eng = ServeEngine(serve_model, host_params, scfg, mesh=mesh)

        def affine(seed, n):
            toks = [seed % trainer.vocab_size]
            for _ in range(n - 1):
                toks.append((toks[-1] * 5 + 7) % trainer.vocab_size)
            return np.asarray(toks, np.int32)

        long_len = thresh if mesh is not None else serve_len // 2
        reqs = [DecodeRequest(0, affine(3, long_len), 8)]
        reqs += [DecodeRequest(i + 1, affine(3 + i, 6), 8)
                 for i in range(args.serve)]
        comps = eng.run(reqs)
        follows = total = 0
        for c in comps:
            toks = [int(t) for t in c.tokens]
            gen0 = c.prompt_len  # first generated index
            follows += sum(toks[i + 1] == (toks[i] * 5 + 7)
                           % trainer.vocab_size
                           for i in range(gen0 - 1, len(toks) - 1))
            total += len(toks) - gen0
        st = eng.stats()
        print(f"served {len(comps)}/{len(reqs)} requests "
              f"(1 long {long_len}-token prompt + {args.serve} short) on "
              f"{sp_n} device(s): {st['sp_prefills']} sp prefills, "
              f"{st['chunk_ticks']} chunk ticks, occupancy "
              f"{st['occupancy'] * 100:.0f}%, {follows}/{total} generated "
              "tokens follow the affine rule")
        if cfg.ledger_path:
            # the program's spans (train.* and now serve.*), beside the
            # ledger as RunObs.run_end left them after training
            from tpu_dist.obs import SPANS_SUFFIX, trace
            n = trace.ring().dump(cfg.ledger_path + SPANS_SUFFIX)
            print(f"{n} program spans -> {cfg.ledger_path + SPANS_SUFFIX}")


if __name__ == "__main__":
    main()
