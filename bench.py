#!/usr/bin/env python
"""Headline benchmark: CIFAR10 ResNet-50 training throughput per chip + MFU.

The reference publishes no numbers; this repo establishes the baseline
(images/sec/chip on the flagship config, scripts/7.jax_tpu.py: ResNet-50,
bf16 compute, fused on-device input pipeline, donated state).

Methodology: K training steps per dispatch (lax.scan multi-step,
tpu_dist.engine.steps.make_multi_train_step) so per-dispatch host latency
is amortized out of the device-rate measurement; best window of several
trials is reported (median and all trials inform stderr diagnostics).

MFU accounting: per-step FLOPs come from XLA's own cost model
(compiled.cost_analysis()), peak from the device kind
(utils.mfu.PEAK_TFLOPS; an unlisted TPU is an error). Set BENCH_SWEEP=1 for a stderr table over per-chip batch
sizes and both ResNet stems (the 7x7/s2+maxpool ImageNet stem shrinks 32x32
inputs to 8x8 before stage 1 and starves the MXU; `cifar_stem=True` is the
standard 3x3/s1 CIFAR variant).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"tflops", "flops_per_img"}. vs_baseline is vs BASELINE.json's published
number when present, else 1.0 (this run IS the baseline).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_dist.runtime import enable_compile_cache, pallas_interpret
from tpu_dist.utils.mfu import peak_tflops_for

IMG = int(os.environ.get("BENCH_IMAGE_SIZE", "32"))       # 224 = ImageNet
ARCH = os.environ.get("BENCH_ARCH", "resnet50")
NUM_CLASSES = int(os.environ.get("BENCH_NUM_CLASSES", "10"))


def bench_ledger(kind: str, config: dict):
    """(ledger, path, goodput_acc) when BENCH_LEDGER names a JSONL path,
    else (None, None, None): the bench feeds the SAME obs.ledger event
    stream the engines write — run_start with the BENCH_* geometry, one
    'step' per timed trial with the dispatch/device phase split, run_end
    — so bench runs are queryable with tools/ledger_report.py like any
    training run. A GoodputAccumulator rides as a sink so the headline
    JSON carries the run's wall-clock partition (the 'goodput' block).
    The LM bench emits live (plus a 'compile' event for the warm
    dispatch); the image path constructs the ledger only after measure()
    returns and emits its trial records retrospectively, so its 'ts'
    stamps are end-of-run and it carries no 'compile' event."""
    path = os.environ.get("BENCH_LEDGER", "")
    if not path:
        return None, None, None
    import jax

    from tpu_dist.obs import GoodputAccumulator, Ledger, effective_peak_tflops

    eff_peak, nominal = effective_peak_tflops()
    ledger = Ledger(path)
    acc = GoodputAccumulator()
    ledger.add_sink(acc.add)
    ledger.emit("run_start", kind=kind, config=config, mesh=None,
                devices=sorted({d.device_kind for d in jax.local_devices()}),
                process_count=jax.process_count(),
                device_count=jax.device_count(),
                peak_tflops=eff_peak, peak_is_nominal=nominal)
    return ledger, path, acc


def goodput_block(acc):
    """Headline-JSON goodput block from the bench ledger's accumulator.
    None without BENCH_LEDGER (no partition without an event stream) AND
    on the image bench's retrospective path: its records are all emitted
    after measure() returns, so the timestamp span is milliseconds while
    the itemized phase seconds are real — the overrun guard below refuses
    to publish that nonsense ratio rather than hide it."""
    part = acc.finalize() if acc is not None else None
    if not part:
        return None
    if part["overrun_s"] > 0.5 * part["wall_s"]:
        return None
    return {"ratio": part["ratio"], "wall_s": part["wall_s"],
            "goodput_s": part["goodput_s"],
            "overrun_s": part["overrun_s"],
            "categories": part["categories"]}


def lm_geometry():
    """(env-derived) LM bench geometry — THE single parse of the BENCH_*
    geometry knobs, shared by lm_build and profile_lm's parse-only path so
    trace renormalization can never drift from the capture."""
    import jax

    n_chips = jax.device_count()
    return dict(
        n_chips=n_chips,
        L=int(os.environ.get("BENCH_SEQ_LEN", "2048")),
        d_model=int(os.environ.get("BENCH_D_MODEL", "1024")),
        layers=int(os.environ.get("BENCH_LAYERS", "8")),
        heads=int(os.environ.get("BENCH_HEADS", "8")),
        vocab=int(os.environ.get("BENCH_VOCAB", "32000")),
        batch=int(os.environ.get("BENCH_LM_BATCH", "8")) * n_chips,
        attn_kind=os.environ.get("BENCH_ATTN", "flash"),
        k=int(os.environ.get("BENCH_STEPS_PER_WINDOW",
                             os.environ.get("BENCH_STEPS", "20"))),
        loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "0")),
        quant=os.environ.get("BENCH_QUANT") or "none",
        tp_impl=os.environ.get("BENCH_TP_IMPL") or "gspmd",
        tp=int(os.environ.get("BENCH_TP_DEGREE", "2")),
        grad_bucket_mb=float(os.environ.get("BENCH_GRAD_BUCKET_MB", "0")))


_PLAN_BLOCK = None   # set by apply_bench_plan; rides in every headline JSON


def apply_bench_plan():
    """BENCH_PLAN=<plan JSON path>: drive this bench run from a tuned step
    plan (tools/tune.py output, selected for this device kind) instead of
    hand-set BENCH_* knobs. The plan's knobs are written INTO the BENCH_*
    env (plan wins — that is the point) so the one geometry parse
    (lm_geometry) stays the single source; the Pallas block sizes / fused
    switch apply via plan.compile.activate_plan. The headline JSON gains a
    'plan' block ({source, hash, knobs}) and tools/bench_track.py tracks
    plan-tagged headlines independently. Returns the block (or None)."""
    global _PLAN_BLOCK
    spec = os.environ.get("BENCH_PLAN", "")
    if not spec:
        return None
    import jax

    from tpu_dist.models.registry import model_kind
    from tpu_dist.plan.compile import activate_plan
    from tpu_dist.plan.ir import (load_plan_file, plan_for_device,
                                  plan_hash, plan_knob_summary)

    kind = getattr(jax.devices()[0], "device_kind", "unknown")
    plan = plan_for_device(load_plan_file(spec), kind)
    engine = "lm" if model_kind(ARCH) == "lm" else "image"
    if plan.engine != engine:
        raise SystemExit(f"BENCH_PLAN={spec}: plan engine {plan.engine!r} "
                         f"does not drive BENCH_ARCH={ARCH} ({engine})")
    # the bench has no knob for these plan dimensions; silently dropping
    # them while stamping the FULL plan hash would make bench_track gate
    # a [plan:<hash>] series on numbers the plan did not produce — refuse
    unmappable = {k: v for k, v in (
        ("precision", plan.precision), ("health", plan.health),
        ("grad_accum_steps", plan.grad_accum_steps),
        ("window", plan.window if plan.window == "stacked" else "none"),
    ) if v not in ("fp32", "record", 1, "none")}
    if unmappable:
        raise SystemExit(
            f"BENCH_PLAN={spec}: plan {sorted(unmappable)} have no BENCH_* "
            "knob — the headline would carry a plan hash the run did not "
            "execute; re-emit the plan without them for benching")
    os.environ["BENCH_QUANT"] = plan.quant
    os.environ["BENCH_TP_IMPL"] = plan.tp_impl
    os.environ["BENCH_GRAD_BUCKET_MB"] = str(plan.grad_bucket_mb)
    if engine == "lm":
        os.environ["BENCH_LOSS_CHUNK"] = str(plan.loss_chunk)
    # plan wins over PRE-EXPORTED knobs too: a stale BENCH_STEPS_PER_WINDOW
    # or BENCH_FUSED_QUANT from an earlier sweep must never leak into a
    # plan-tagged headline (bench_track gates the [plan:<hash>] series on
    # these numbers). window='none' / fused_quant='auto' mean "the bench's
    # own default / the auto dispatch", so the env overrides are CLEARED
    if plan.window != "none":
        os.environ["BENCH_STEPS_PER_WINDOW"] = str(plan.steps_per_dispatch)
    else:
        os.environ.pop("BENCH_STEPS_PER_WINDOW", None)
        os.environ.pop("BENCH_STEPS", None)
    if plan.fused_quant != "auto":
        os.environ["BENCH_FUSED_QUANT"] = (
            "1" if plan.fused_quant == "on" else "0")
    else:
        os.environ.pop("BENCH_FUSED_QUANT", None)
    activate_plan(plan)
    _PLAN_BLOCK = {"source": spec, "hash": plan_hash(plan),
                   "device_kind": kind, "knobs": plan_knob_summary(plan)}
    print(f"bench plan: {_PLAN_BLOCK['hash']} from {spec} "
          f"(device {kind}): {_PLAN_BLOCK['knobs']}", file=sys.stderr)
    return _PLAN_BLOCK


def apply_fused_quant_knob():
    """BENCH_FUSED_QUANT=1/0 forces the fused Pallas int8 kernel on/off
    (ops.quant.set_fused_quant; unset = auto: fused on TPU). Must run
    BEFORE any step function is built — the dispatch is trace-time static.
    Returns the active state for the config block."""
    knob = os.environ.get("BENCH_FUSED_QUANT", "")
    from tpu_dist.ops.quant import fused_quant_active, set_fused_quant
    if knob != "":
        set_fused_quant(knob == "1")
    return fused_quant_active()


def prefetch_enabled() -> bool:
    """BENCH_PREFETCH=1: stream each trial's batch host->device through
    data.loader.DevicePrefetcher instead of pre-placing it in HBM, so the
    step records carry a MEASURED data_s (the consumer's queue wait —
    ~0 when staging overlaps the previous trial's compute) and the
    headline JSON a 'prefetch' overlap block."""
    return os.environ.get("BENCH_PREFETCH") == "1"



def health_block(metrics, k: int) -> dict:
    """Headline-JSON numerical-health block from the fused step probes
    (obs.health riding the window's metric sums) — shared by both benches
    so the two JSON schemas cannot drift."""
    import jax

    # distlint: disable=DL002 -- bench health gate: deliberate drain to act on probe values
    hm = jax.device_get({kk: metrics[kk] for kk in
                         ("grad_norm", "nonfinite_count", "update_norm")})
    return {"nonfinite_leaves": float(hm["nonfinite_count"]),
            "grad_norm_per_step": round(float(hm["grad_norm"]) / k, 4),
            "update_norm_per_step": round(float(hm["update_norm"]) / k, 4)}


def lm_build():
    """THE windowed-LM-step builder shared by lm_bench and
    tools/profile_lm.py (the profiler must capture the SAME program the
    bench times — a hand-copied setup drifts; ADVICE/code-review r5).
    Reads the BENCH_* env knobs (lm_geometry) and returns a dict with the
    compiled-input pieces plus the geometry the callers report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.engine.lm_steps import make_lm_indexed_multi_train_step
    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import TransformerLM, full_attention
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.mesh import make_mesh, replicated

    g = lm_geometry()
    n_chips, L, d_model = g["n_chips"], g["L"], g["d_model"]
    layers, heads, vocab = g["layers"], g["heads"], g["vocab"]
    batch, attn_kind, k = g["batch"], g["attn_kind"], g["k"]
    loss_chunk = g["loss_chunk"]
    from tpu_dist.ops.quant import validate_quant
    quant = validate_quant(g["quant"])
    from tpu_dist.parallel.overlap import validate_tp_impl
    tp_impl = validate_tp_impl(g["tp_impl"])
    grad_bucket_mb = g["grad_bucket_mb"]
    if tp_impl == "ring" and grad_bucket_mb > 0:
        raise SystemExit("BENCH_TP_IMPL=ring and BENCH_GRAD_BUCKET_MB are "
                         "separate overlap paths (ring TP vs dp bucketed "
                         "sync); set one per run so the headline is "
                         "attributable")

    if attn_kind == "flash":
        from tpu_dist.ops.flash_attention import flash_attention_fn
        attn_fn = flash_attention_fn()
    elif attn_kind == "blockwise":
        from tpu_dist.ops.flash_attention import blockwise_attention_fn
        attn_fn = blockwise_attention_fn(512)
    else:
        attn_fn = full_attention
    if tp_impl == "ring":
        tp = g["tp"]
        if n_chips % tp or heads % tp or L % tp:
            raise SystemExit(
                f"BENCH_TP_IMPL=ring needs BENCH_TP_DEGREE ({tp}) dividing "
                f"the chip count ({n_chips}), BENCH_HEADS ({heads}) and "
                f"BENCH_SEQ_LEN ({L})")
        mesh = make_mesh((-1, tp), ("data", "model"))
    else:
        mesh = make_mesh()
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, d_model=d_model,
        num_heads=heads, max_len=L, dtype=jnp.bfloat16, attn_fn=attn_fn,
        remat=os.environ.get("BENCH_REMAT") == "1", quant=quant)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, L), np.int32), train=False)["params"]
    opt = os.environ.get("BENCH_OPTIMIZER", "sgd")
    if opt == "fused_adamw":  # Pallas single-pass update (ops.pallas_adamw)
        from tpu_dist.ops.pallas_adamw import FusedAdamW
        tx = FusedAdamW(lambda s: 1e-3,
                        interpret=pallas_interpret())
    elif opt == "adamw":
        tx = make_optimizer(1e-3, weight_decay=0.1, kind="adamw",
                            schedule=lambda s: 1e-3)
    elif opt == "sgd":
        tx = make_optimizer(1e-3, 0.9, 0.0, steps_per_epoch=10 ** 6)
    else:
        raise SystemExit(f"BENCH_OPTIMIZER={opt}: sgd|adamw|fused_adamw")
    state = jax.device_put(TrainState.create(params, {}, tx),
                           replicated(mesh))
    if tp_impl == "ring":
        # ring collective-matmul TP (parallel.overlap): K-step windows scan
        # inside the explicit shard_map program; params stay replicated
        from tpu_dist.engine.lm_steps import (
            _lm_tp_ring_step_fn, make_lm_explicit_indexed_multi_train_step)
        ring_step = _lm_tp_ring_step_fn(
            model.clone(tp_impl="ring"), tx, 0.01, "data", "model",
            mesh.shape["model"], loss_chunk=loss_chunk)
        window = make_lm_explicit_indexed_multi_train_step(ring_step, mesh)
    elif grad_bucket_mb > 0:
        from tpu_dist.engine.lm_steps import (
            _lm_explicit_dp_step_fn, make_lm_explicit_indexed_multi_train_step)
        dp_step = _lm_explicit_dp_step_fn(
            model, tx, 0.01, "data", mesh.shape["data"], grad_bucket_mb,
            loss_chunk=loss_chunk)
        window = make_lm_explicit_indexed_multi_train_step(dp_step, mesh)
    else:
        window = make_lm_indexed_multi_train_step(model, tx, mesh,
                                                  loss_chunk=loss_chunk)

    rng = np.random.default_rng(0)
    rows = rng.integers(0, vocab, (batch, L + 1)).astype(np.int32)
    rows_dev = jax.device_put(rows, replicated(mesh))
    idx = np.tile(np.arange(batch, dtype=np.int32), (k, 1))
    idx_dev = jax.device_put(idx, NamedSharding(mesh, P(None, "data")))
    key = jax.random.PRNGKey(1)
    return dict(window=window, state=state, rows_dev=rows_dev,
                idx_dev=idx_dev, key=key, params=params, mesh=mesh,
                rows_host=rows, idx_host=idx,
                n_chips=n_chips, L=L, d_model=d_model, layers=layers,
                batch=batch, k=k, attn_kind=attn_kind,
                loss_chunk=loss_chunk, quant=quant, tp_impl=tp_impl,
                grad_bucket_mb=grad_bucket_mb)


def lm_bench():
    """BENCH_ARCH=transformer_lm: tokens/sec/chip + MFU for the LM engine.

    Drives the SAME windowed HBM-resident path LMTrainer trains with
    (make_lm_indexed_multi_train_step): K optimizer steps per dispatch over
    device-resident rows, bf16 compute, flash attention. Knobs:
    BENCH_SEQ_LEN (2048), BENCH_D_MODEL (1024), BENCH_LAYERS (8),
    BENCH_HEADS (8), BENCH_VOCAB (32000), BENCH_LM_BATCH per chip (8),
    BENCH_ATTN full|blockwise|flash (flash), BENCH_REMAT=1,
    BENCH_OPTIMIZER sgd|adamw|fused_adamw, BENCH_LOSS_CHUNK,
    BENCH_FUSED_QUANT 1|0 (force the fused Pallas int8 kernel on/off;
    unset = auto), BENCH_PREFETCH=1 (stream trial batches host->device
    through data.loader.DevicePrefetcher — data_s becomes measured).
    Each timed window ends in a device_get of its metric sums: the values
    cannot reach the host before the program has finished, so the fetch is
    the completion barrier.
    """
    import jax
    from tpu_dist.utils.mfu import lm_flops_per_token

    if ARCH != "transformer_lm":
        raise SystemExit(
            f"BENCH_ARCH={ARCH}: the LM bench drives the dense "
            "TransformerLM only (its analytical MFU accounting assumes "
            "dense); use BENCH_ARCH=transformer_lm with BENCH_* geometry "
            "knobs")

    if os.environ.get("BENCH_FUSED_QUANT", "") != "" \
            and (os.environ.get("BENCH_QUANT") or "none") != "int8":
        # same refuse-rather-than-mislead rule as the conv-arch guard:
        # forcing the fused kernel with no int8 matmuls in the program
        # would publish a plain bf16 number under a fused-int8 intent
        raise SystemExit(
            "BENCH_FUSED_QUANT only means something with BENCH_QUANT=int8 "
            f"(got BENCH_QUANT={os.environ.get('BENCH_QUANT') or 'none'}); "
            "unset it or set BENCH_QUANT=int8")
    fused_quant = apply_fused_quant_knob()  # BEFORE lm_build traces steps
    b = lm_build()
    window, state = b["window"], b["state"]
    rows_dev, idx_dev, key = b["rows_dev"], b["idx_dev"], b["key"]
    n_chips, L, batch, k = b["n_chips"], b["L"], b["batch"], b["k"]
    layers, d_model = b["layers"], b["d_model"]
    attn_kind, loss_chunk, quant = b["attn_kind"], b["loss_chunk"], b["quant"]
    tp_impl, grad_bucket_mb = b["tp_impl"], b["grad_bucket_mb"]
    trials = int(os.environ.get("BENCH_TRIALS", "3"))
    prefetcher = None
    if prefetch_enabled():
        # stream each trial's (rows, idx) host->device on the prefetcher's
        # producer thread; the consumer wait IS the step record's data_s
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tpu_dist.data.loader import DevicePrefetcher
        from tpu_dist.parallel.mesh import replicated
        mesh = b["mesh"]
        idx_sh = NamedSharding(mesh, P(None, "data"))

        def stage(batch_pair):
            r, ix = batch_pair
            return (jax.device_put(r, replicated(mesh)),
                    jax.device_put(ix, idx_sh))
        prefetcher = DevicePrefetcher(
            ((b["rows_host"], b["idx_host"]) for _ in range(trials)),
            put=stage)
        trial_batches = iter(prefetcher)

    # analytical model FLOPs (tpu_dist.utils.mfu.lm_flops_per_token; XLA's
    # cost model undercounts scan bodies and cannot cost Pallas kernels)
    flops_per_token = lm_flops_per_token(b["params"], layers, L, d_model)
    ledger, ledger_path, goodput_acc = bench_ledger(
        "bench_lm", {**lm_geometry(),
                     "fused_quant": fused_quant and quant == "int8",
                     "prefetch": prefetcher is not None})
    t_warm = time.perf_counter()
    state, m = window(state, rows_dev, idx_dev, key)           # compile+warm
    jax.device_get(m)
    if ledger:
        ledger.emit("compile", program="window_step",
                    seconds=round(time.perf_counter() - t_warm, 3))
    # probe AFTER the warm dispatch (telemetry.program_stats contract —
    # the AOT lower does not seed jit's dispatch cache, so probing first
    # would compile the window twice); one lower yields the cost-model
    # cross-check AND the HLO for cost attribution when a ledger rides
    from tpu_dist.utils.telemetry import program_stats
    st = program_stats(window, state, rows_dev, idx_dev, key,
                       with_hlo=bool(ledger))
    xla_flops = st["flops"]
    if xla_flops:
        print(f"xla cost model (diagnostic only): "
              f"{xla_flops / (batch * L / n_chips) / 1e6:.2f} MFLOP/token vs "
              f"analytical {flops_per_token / 1e6:.2f}", file=sys.stderr)
    else:
        print("xla cost model unavailable on this backend (cross-check "
              "and ledger cost attribution skipped)", file=sys.stderr)
    if ledger and st.get("hlo"):
        from tpu_dist.obs.attr import emit_cost_model
        emit_cost_model(ledger, "window_step", st["hlo"],
                        xla_flops=xla_flops)
    peak = peak_tflops_for(jax.devices()[0])
    rates, phases = [], []
    for i in range(trials):
        t0 = time.perf_counter()
        if prefetcher is not None:
            rows_dev, idx_dev = next(trial_batches)
        data_s = time.perf_counter() - t0
        state, m = window(state, rows_dev, idx_dev, key)
        disp_s = time.perf_counter() - t0 - data_s
        jax.device_get(m)  # the window's completion barrier
        dt = time.perf_counter() - t0
        rates.append(k * batch * L / dt)
        phases.append({"data_s": round(data_s, 6),
                       "dispatch_s": round(disp_s, 6),
                       "device_s": round(dt - data_s - disp_s, 6)})
        if ledger:
            # ledger MFU uses the engines' nominal-peak fallback (non-null
            # on CPU); the headline JSON's mfu stays real-peak-only
            from tpu_dist.obs import effective_peak_tflops
            t_tf = rates[-1] / n_chips * flops_per_token / 1e12
            ledger.emit("step", step=i, loss=None,
                        throughput=round(rates[-1] / n_chips, 1),
                        unit="tok/s/chip",
                        mfu=t_tf / effective_peak_tflops()[0],
                        steps_in_dispatch=k,
                        data_s=phases[-1]["data_s"],
                        dispatch_s=phases[-1]["dispatch_s"],
                        device_s=phases[-1]["device_s"],
                        comm_s=None, fused=fused_quant and quant == "int8")
    best = max(rates)
    best_phases = phases[rates.index(best)]
    prefetch_stats = None
    if prefetcher is not None:
        prefetcher.close()
        prefetch_stats = prefetcher.stats()
    # the headline carries the last trial's numerical-health block
    health = health_block(m, k)
    tok_chip = best / n_chips
    tflops = tok_chip * flops_per_token / 1e12
    mfu = tflops / peak if peak else None
    if ledger:
        ledger.emit("run_end", steps=trials * k,
                    seconds=round(time.perf_counter() - t_warm, 3))
        ledger.close()
    print(f"lm {layers}L/d{d_model} L={L} b/chip={batch // n_chips} "
          f"attn={attn_kind}"
          + (f" loss_chunk={loss_chunk}" if loss_chunk else "")
          + (f" quant={quant}" if quant != "none" else "")
          + (f" tp_impl={tp_impl}" if tp_impl != "gspmd" else "")
          + (f" grad_bucket_mb={grad_bucket_mb:g}" if grad_bucket_mb else "")
          + f": {tok_chip:,.0f} tok/s/chip, trials "
          f"{[round(r / n_chips) for r in rates]}"
          + (f", {tflops:.1f} TFLOP/s/chip" if tflops else "")
          + (f", MFU {mfu * 100:.1f}% of {peak} TF peak (bf16 peak; the "
             "int8 MXU path doubles it)" if mfu and quant == "int8" else
             f", MFU {mfu * 100:.1f}% of {peak} TF peak" if mfu else ""),
          file=sys.stderr)
    # BENCH_QUANT / BENCH_TP_IMPL publish their OWN metric names: variants
    # ride alongside the bf16 GSPMD headline, never replacing it (the
    # headline's name — and its baseline comparison — must stay
    # like-for-like), and the config block pins tp_impl/grad_bucket_mb so
    # two runs are never silently cross-compared
    quant_tag = f"_{quant}" if quant != "none" else ""
    impl_tag = (f"_{tp_impl}" if tp_impl != "gspmd" else
                "_bucketed" if grad_bucket_mb else "")
    print(json.dumps({
        "metric": f"lm_{layers}l_d{d_model}_seq{L}{quant_tag}{impl_tag}"
                  "_tokens_per_sec_per_chip",
        "value": round(tok_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
        "config": {"tp_impl": tp_impl, "grad_bucket_mb": grad_bucket_mb,
                   "quant": quant, "attn": attn_kind,
                   "loss_chunk": loss_chunk,
                   "fused_quant": fused_quant and quant == "int8",
                   "prefetch": prefetcher is not None,
                   "tp_degree": (b["mesh"].shape["model"]
                                 if tp_impl == "ring" else 1)},
        "mfu": round(mfu, 4) if mfu else None,
        "tflops": round(tflops, 2) if tflops else None,
        "phases": best_phases,
        "prefetch": prefetch_stats,
        "health": health,
        "goodput": goodput_block(goodput_acc),
        "plan": _PLAN_BLOCK,
        "ledger": ledger_path,
    }))


def build(model_kwargs, batch, k):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.data import make_transform
    from tpu_dist.data.datasets import CIFAR10_MEAN, CIFAR10_STD
    from tpu_dist.engine.state import TrainState, init_model
    from tpu_dist.engine.steps import make_multi_train_step
    from tpu_dist.models import create_model
    from tpu_dist.ops import make_optimizer
    from tpu_dist.parallel.mesh import make_mesh, replicated

    mesh = make_mesh()
    model = create_model(ARCH, num_classes=NUM_CLASSES, dtype=jnp.bfloat16,
                         **model_kwargs)
    params, batch_stats = init_model(model, jax.random.PRNGKey(0), (2, IMG, IMG, 3))
    tx = make_optimizer(0.1, 0.9, 1e-4, steps_per_epoch=100)
    # distlint: disable=DL008 -- one-time state replication at bench setup, not a per-step upload
    state = jax.device_put(TrainState.create(params, batch_stats, tx),
                           replicated(mesh))
    transform = make_transform(CIFAR10_MEAN, CIFAR10_STD, dtype=jnp.bfloat16)
    step = make_multi_train_step(model, tx, transform, mesh)

    from tpu_dist.engine.steps import make_train_step
    single = make_train_step(model, tx, transform, mesh, donate=False)

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (k, batch, IMG, IMG, 3)).astype(np.uint8)
    labels = rng.integers(0, NUM_CLASSES, (k, batch)).astype(np.int32)
    sh_img = NamedSharding(mesh, P(None, "data"))
    # distlint: disable=DL008 -- HBM-resident bench design: the whole K-step window is pre-placed before timing (BENCH_PREFETCH=1 is the streamed mode)
    images_dev = jax.device_put(images, sh_img)
    # distlint: disable=DL008 -- HBM-resident bench design: pre-placed window (see images_dev)
    labels_dev = jax.device_put(labels, sh_img)
    return (step, single, state, images_dev, labels_dev,
            (images, labels), sh_img)


def flops_per_step(single, state, images, labels, key,
                   with_hlo: bool = False) -> dict:
    """One training step's {'flops', 'hlo'} from the SINGLE-step program
    (the scan flavor's cost analysis counts its body only once, so it
    can't be trusted for per-step math; `single` is never dispatched, so
    its AOT compile is the only one it pays). ``with_hlo`` additionally
    returns the optimized HLO for cost attribution (obs.attr)."""
    from tpu_dist.utils.telemetry import program_stats

    st = program_stats(single, state, images[0], labels[0], key,
                       with_hlo=with_hlo)
    if st["flops"] is None:
        print("cost_analysis unavailable", file=sys.stderr)
    return st


def measure(model_kwargs, per_chip_batch, k, trials, with_hlo=False):
    import jax

    n_chips = jax.device_count()
    batch = per_chip_batch * n_chips
    (step, single, state, images, labels,
     host_batch, sh_img) = build(model_kwargs, batch, k)
    key = jax.random.PRNGKey(0)
    # with_hlo only on the headline run: the sweep discards everything
    # past the rate, and the optimized-HLO text can run to megabytes
    st = flops_per_step(single, state, images, labels, key,
                        with_hlo=with_hlo)
    step_flops = st["flops"]

    # warmup: compile + one full window
    state, metrics = step(state, images, labels, key)
    # distlint: disable=DL002 -- compile+warm barrier before the timed window
    jax.block_until_ready(metrics)

    prefetcher = None
    if prefetch_enabled():
        # per-trial host->device staging on the producer thread: data_s
        # below becomes a measured queue wait instead of the synthetic 0.0
        from tpu_dist.data.loader import DevicePrefetcher

        def stage(pair):
            return (jax.device_put(pair[0], sh_img),
                    jax.device_put(pair[1], sh_img))
        prefetcher = DevicePrefetcher(
            (host_batch for _ in range(trials)), put=stage)
        trial_batches = iter(prefetcher)

    rates, phases = [], []
    for _ in range(trials):
        t0 = time.perf_counter()
        if prefetcher is not None:
            images, labels = next(trial_batches)
        data_s = time.perf_counter() - t0
        state, metrics = step(state, images, labels, key)
        disp_s = time.perf_counter() - t0 - data_s
        # distlint: disable=DL002 -- the timed measurement barrier - benches measure the sync
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        rates.append(batch * k / dt)
        phases.append({"data_s": round(data_s, 6),
                       "dispatch_s": round(disp_s, 6),
                       "device_s": round(dt - data_s - disp_s, 6)})
    prefetch_stats = None
    if prefetcher is not None:
        prefetcher.close()
        prefetch_stats = prefetcher.stats()
    best_phases = phases[rates.index(max(rates))]
    return (max(rates), sorted(rates), step_flops, batch, best_phases,
            list(zip(rates, phases)),  # trials in timing order (ledger)
            health_block(metrics, k), st.get("hlo"), prefetch_stats)


def main():
    import jax
    enable_compile_cache()

    # a tuned plan (BENCH_PLAN) rewrites the BENCH_* knobs BEFORE the
    # guards/geometry below read them
    apply_bench_plan()

    from tpu_dist.models.registry import model_kind
    if model_kind(ARCH) == "lm":
        lm_bench()
        return

    if os.environ.get("BENCH_QUANT", "none") not in ("", "none") \
            or os.environ.get("BENCH_FUSED_QUANT", "") != "":
        # refuse rather than silently publish a bf16 number under the
        # user's int8 intent: the conv models have no quantized path
        raise SystemExit(
            "BENCH_QUANT/BENCH_FUSED_QUANT apply to the LM "
            f"bench only (BENCH_ARCH=transformer_lm); BENCH_ARCH={ARCH} "
            "has no quantized path")
    if os.environ.get("BENCH_TP_IMPL", "gspmd") not in ("", "gspmd") \
            or float(os.environ.get("BENCH_GRAD_BUCKET_MB", "0") or 0) > 0:
        # same guard pattern: the overlap knobs drive the LM bench; the
        # image bench's jit window has no explicit collectives to decompose
        raise SystemExit(
            "BENCH_TP_IMPL/BENCH_GRAD_BUCKET_MB apply to the LM bench only "
            f"(BENCH_ARCH=transformer_lm); BENCH_ARCH={ARCH} rides the "
            "compiler-scheduled path")

    n_chips = jax.device_count()
    per_chip_batch = int(os.environ.get("BENCH_PER_CHIP_BATCH", "1024"))
    # BENCH_STEPS kept as an alias (earlier recipe name). K=160 amortizes
    # dispatch latency to <8% of the window (device-side rate ~148k img/s/chip
    # per the XLA trace; measured wall rate 137k at K=160 vs 95k at K=20).
    k = int(os.environ.get("BENCH_STEPS_PER_WINDOW",
                           os.environ.get("BENCH_STEPS", "160")))
    trials = int(os.environ.get("BENCH_TRIALS", "4"))
    peak = peak_tflops_for(jax.devices()[0])

    def report(tag, best, rates, step_flops, batch):
        ips_chip = best / n_chips
        tflops = mfu = fpi = None
        if step_flops:
            # cost_analysis describes the per-device SPMD program, which
            # processes batch/n_chips images per step
            fpi = step_flops / (batch / n_chips)
            tflops = ips_chip * fpi / 1e12
            mfu = tflops / peak if peak else None
        print(f"{tag}: {ips_chip:,.0f} img/s/chip, trials "
              f"{[round(r / n_chips) for r in rates]}"
              + (f", {fpi / 1e9:.3f} GFLOP/img, {tflops:.1f} TFLOP/s/chip"
                 if fpi else "")
              + (f", MFU {mfu * 100:.1f}% of {peak} TF peak" if mfu else ""),
              file=sys.stderr)
        return ips_chip, tflops, mfu, fpi

    if os.environ.get("BENCH_SWEEP") == "1":
        if not ARCH.startswith("resnet"):
            raise SystemExit("BENCH_SWEEP sweeps ResNet stems; unset "
                             f"BENCH_ARCH={ARCH}")
        for stem in (False, True):
            for pcb in (1024, 2048, 4096):
                try:
                    res = measure({"cifar_stem": stem}, pcb,
                                  min(k, 40), max(2, trials // 2))
                    report(f"sweep stem={'cifar' if stem else 'imagenet'} "
                           f"b/chip={pcb} k={min(k, 40)}", *res[:4])
                except Exception as e:
                    print(f"sweep stem={stem} b={pcb}: failed {e!r}",
                          file=sys.stderr)

    # Headline defaults: bf16 normalized
    # activations (fp32 BN statistics — the MLPerf-TPU ResNet practice) and
    # the space-to-depth stem. Both are convergence-parity-verified
    # (tools/convergence.py --norm-dtype bf16 --stem s2d) and the s2d stem
    # spans exactly the 7x7/s2 function space
    # (tests/test_models.py::test_s2d_stem_spans_imagenet_stem). Opt back
    # into the round-1-4 torch-parity config with BENCH_NORM_DTYPE=fp32
    # BENCH_STEM=imagenet.
    kwargs = {}
    norm_dtype = os.environ.get("BENCH_NORM_DTYPE", "bf16")
    if norm_dtype not in ("bf16", "fp32"):
        raise SystemExit(f"BENCH_NORM_DTYPE={norm_dtype}: use bf16 "
                         "(fp32-stats/bf16-activations) or fp32")
    if norm_dtype == "bf16":
        import jax.numpy as jnp
        kwargs["norm_dtype"] = jnp.bfloat16
    if os.environ.get("BENCH_CIFAR_STEM") == "1":
        kwargs["cifar_stem"] = True  # composes with norm_dtype
        default_model = False
    else:
        stem = os.environ.get("BENCH_STEM", "s2d")
        kwargs["stem"] = stem  # imagenet|cifar|s2d (models/resnet.py)
        default_model = stem == "s2d" and norm_dtype == "bf16"
    if os.environ.get("BENCH_NORM") and os.environ["BENCH_NORM"] != "bn":
        kwargs["norm"] = os.environ["BENCH_NORM"]  # bn/empty = default
        default_model = False
    if not ARCH.startswith(("resnet", "resnext", "wide_resnet")):
        # raise only on knobs that actually ASK for something non-default
        # (BENCH_NORM=bn / BENCH_NORM_DTYPE=bf16-by-default / unset are
        # no-ops and stay accepted for wrapper-script compatibility)
        asked = (os.environ.get("BENCH_CIFAR_STEM") == "1"
                 or os.environ.get("BENCH_NORM") not in (None, "", "bn")
                 or os.environ.get("BENCH_NORM_DTYPE") == "bf16"
                 or os.environ.get("BENCH_STEM") not in (None, "", "imagenet"))
        if asked:
            raise SystemExit(
                "BENCH_CIFAR_STEM/BENCH_NORM/BENCH_NORM_DTYPE/BENCH_STEM are "
                f"ResNet knobs; unset them with BENCH_ARCH={ARCH}")
        kwargs = {}
        default_model = True
    (best, rates, window_flops, batch, phases, trial_data, health,
     step_hlo, prefetch_stats) = measure(
         kwargs, per_chip_batch, k, trials,
         with_hlo=bool(os.environ.get("BENCH_LEDGER")))
    ips_per_chip, tflops, mfu, fpi = report("headline", best, rates,
                                            window_flops, batch)
    ledger, ledger_path, goodput_acc = bench_ledger(
        "bench_image", {"arch": ARCH, "img": IMG, "classes": NUM_CLASSES,
                        "per_chip_batch": per_chip_batch, "k": k,
                        "prefetch": prefetch_stats is not None,
                        **{kk: getattr(v, "__name__", str(v))
                           for kk, v in kwargs.items()}})
    if ledger:
        # one 'step' per timed trial, in timing order — emitted
        # retrospectively (measure() ran before the ledger existed); MFU
        # vs the engines' effective peak (nominal fallback keeps it
        # non-null on CPU — run_start carries peak_is_nominal)
        from tpu_dist.obs import effective_peak_tflops
        eff_peak = effective_peak_tflops()[0]
        if step_hlo:
            # cost attribution of the single-step program (obs.attr) —
            # the ledger_report roofline reads it back beside the trials
            from tpu_dist.obs.attr import emit_cost_model
            emit_cost_model(ledger, "train_step", step_hlo,
                            xla_flops=window_flops)
        for i, (rate, ph) in enumerate(trial_data):
            r_chip = rate / n_chips
            tf = r_chip * fpi / 1e12 if fpi else None
            ledger.emit("step", step=i, loss=None,
                        throughput=round(r_chip, 1), unit="img/s/chip",
                        mfu=round(tf / eff_peak, 6) if tf else None,
                        steps_in_dispatch=k, data_s=ph["data_s"],
                        dispatch_s=ph["dispatch_s"],
                        device_s=ph["device_s"], comm_s=None)
        ledger.emit("run_end", steps=trials * k,
                    seconds=round(sum(batch * k / r for r in rates), 3))
        ledger.close()

    default_workload = (IMG == 32 and NUM_CLASSES == 10 and default_model
                        and ARCH == "resnet50")
    if not default_workload:
        # a different image size/class count/model variant is a different
        # workload: name it and do NOT compare against the CIFAR baseline
        variant = "_".join(
            f"{k}-{getattr(v, '__name__', v)}"
            for k, v in sorted(kwargs.items()))
        print(json.dumps({
            "metric": f"{ARCH}_{IMG}px"
                      + (f"_{variant}" if variant else "")
                      + "_images_per_sec_per_chip",
            "value": round(ips_per_chip, 1),
            "unit": "images/sec/chip",
            "vs_baseline": 1.0,
            "mfu": round(mfu, 4) if mfu else None,
            "tflops": round(tflops, 2) if tflops else None,
            "flops_per_img": round(fpi) if fpi else None,
            "phases": phases,
            "prefetch": prefetch_stats,
            "health": health,
            "goodput": goodput_block(goodput_acc),
            "plan": _PLAN_BLOCK,
            "ledger": ledger_path,
        }))
        return

    baseline = None
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as f:
            baseline = json.load(f).get("published", {}).get(
                "cifar10_resnet50_images_per_sec_per_chip")
    except Exception:
        pass
    vs = ips_per_chip / baseline if baseline else 1.0

    # like-for-like tagging: BASELINE.json's published number is the ROUND-1
    # config (7x7 imagenet stem, fp32 norm outputs); today's default is
    # s2d+bf16-norm. The ratio is still published (it tracks the headline's
    # drift across rounds), but both configs ride the JSON so the comparison
    # is never silently cross-config.
    active_cfg = (f"stem={kwargs.get('stem', 'imagenet')}"
                  f",norm_dtype={norm_dtype}")
    baseline_cfg = "stem=imagenet,norm_dtype=fp32"
    print(json.dumps({
        "metric": "cifar10_resnet50_images_per_sec_per_chip",
        "value": round(ips_per_chip, 1),
        "unit": "images/sec/chip",
        "config": active_cfg,
        "vs_baseline": round(vs, 3),
        "vs_baseline_config": baseline_cfg if baseline else None,
        "mfu": round(mfu, 4) if mfu else None,
        "tflops": round(tflops, 2) if tflops else None,
        "flops_per_img": round(fpi) if fpi else None,
        "phases": phases,
        "prefetch": prefetch_stats,
        "health": health,
        "goodput": goodput_block(goodput_acc),
        "plan": _PLAN_BLOCK,
        "ledger": ledger_path,
    }))


if __name__ == "__main__":
    main()
