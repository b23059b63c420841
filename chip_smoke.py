#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three main paths once, in ONE process, through the classes the
cookbook scripts use, at the full width the repo supports:

* the image trainer (``tpu_dist.engine.Trainer``, the configuration of
  scripts/7.jax_tpu.py: ResNet-50, CIFAR10 shapes, global batch 1024, bf16,
  16 steps per dispatch): two windows, the distributed eval, a checkpoint
  save and a resume;
* the LM trainer (``tpu_dist.engine.lm_loop.LMTrainer`` at the repo's
  long-standing LM geometry: 8 layers, d1024, 8 heads, L2048, V32000, batch 8, bf16, flash
  attention): a few steps, then one step each with the fused Pallas AdamW
  and with int8 matmuls;
* the server (``tpu_dist.engine.serve.ServeEngine``) on that LM's
  parameters: mixed-length requests on the default path, checked against
  ``engine.generate``, then again with int8 KV pages read by the paged
  Pallas kernel.

``python chip_smoke.py --multichip`` runs ONLY the path across four chips
and what it is compared with: dp=4 (jit and explicit psum), dp2 x tp2,
seq=4 (ring attention) and fsdp, each against the same global batch and
seed on one device of the four.

Depth and data are cut (a few steps, synthetic data and random weights made
from ``--seed``); widths are not. It needs a TPU: with no accelerator it
exits non-zero and prints no result. Rates it prints are information about
this one cold run, never a performance result. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback
from typing import Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the chip run and the CPU rehearsal of the same phases
    (tests/test_chip_smoke.py) disagree on. The defaults are the chip's."""

    # image trainer — scripts/7.jax_tpu.py:22-24
    arch: str = "resnet50"
    image_dataset: str = "synthetic-cifar10"
    image_batch: int = 1024
    steps_per_dispatch: int = 16
    image_val: int = 2048
    image_precision: str = "bf16"
    # the explicit-psum pair (--multichip) needs batch-decoupled math to be
    # comparable with one device: per-replica BatchNorm statistics differ
    # from global-batch ones BY DESIGN, GroupNorm does not
    explicit_norm: str = "gn"
    multichip_image_steps: int = 4
    # a tenth of script 7's 0.1: at 0.1 the GroupNorm model's loss climbs
    # from 2.5 to 15.5 in four steps on four chips and on one alike (my chip
    # run, PR 21), a regime that amplifies rounding instead of exposing a
    # sharding fault
    multichip_image_lr: float = 0.01
    # LM trainer — the geometry tests/test_chip_compile.py compiles for
    num_layers: int = 8
    d_model: int = 1024
    num_heads: int = 8
    seq_len: int = 2048
    vocab_size: int = 32000
    lm_batch: int = 8
    lm_precision: str = "bf16"
    lm_steps: int = 4
    multichip_lm_steps: int = 3
    # server
    max_slots: int = 8
    page_size: int = 16
    prompt_lens: Tuple[int, ...] = (64, 200, 520, 1024)  # two requests each
    new_tokens: int = 32


FULL = Sizes()

#: the comparisons of --multichip in fp32, each held to what the CPU test of
#: the same pair uses: (loss abs tol, param rtol, param atol). In bf16 the
#: bounds are counted in roundoff instead (:func:`_compare`).
MULTICHIP_TOL = {
    # tests/test_engine.py::test_single_vs_multi_device_same_update
    "image_dp_jit": (2e-4, 1e-4, 1e-6),
    # tests/test_engine.py::test_jit_and_shard_map_flavors_agree_exactly
    "image_dp_psum": (2e-4, 1e-5, 1e-7),
    # tests/test_lm.py::test_tp_matches_dp, test_lm_loop modes (2e-4, 2e-6)
    "lm_tp": (2e-4, 2e-4, 2e-6),
    # tests/test_lm.py::test_sp_ring_matches_dp
    "lm_sp": (2e-4, 2e-3, 1e-5),
    # tests/test_lm.py::test_fsdp_matches_dp_and_stays_sharded
    "lm_fsdp": (2e-4, 1e-4, 1e-6),
}


#: one unit of bf16 roundoff; what the bf16 comparisons are counted in
BF16_EPS = 2.0 ** -8


class PhaseError(AssertionError):
    """A phase ran to its end and its check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


# ---------------------------------------------------------------- accounting

class CompileMeter:
    """Counts backend compilations and the seconds they took, from JAX's own
    monitoring events; a persistent-cache hit still counts as one (its
    seconds are the retrieval) and is counted as a hit beside."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


def _hbm() -> dict:
    """Peak and live HBM over the local devices (None where the backend has
    no allocator counters: the CPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    live = [s.get("bytes_in_use") for s in stats]
    gb = lambda xs: (round(max(xs) / 2**30, 3)
                     if all(x is not None for x in xs) else None)
    return {"peak_hbm_gb": gb(peak), "live_hbm_gb": gb(live)}


class Report:
    """Runs phases, prints one line per phase, remembers which failed."""

    def __init__(self, meter: CompileMeter):
        self.meter = meter
        self.results = {}
        self.failed = []

    def phase(self, name: str, fn, *args, **kwargs):
        c0, s0, h0 = self.meter.snapshot()
        t0 = time.time()
        out = None
        try:
            out = fn(*args, **kwargs)
            line = dict(out["line"])
        except Exception as e:  # a failed phase must not hide the next one
            traceback.print_exc(file=sys.stderr)
            self.failed.append(name)
            line = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
        c1, s1, h1 = self.meter.snapshot()
        line.update(seconds=round(time.time() - t0, 2),
                    compilations=c1 - c0,
                    compile_seconds=round(s1 - s0, 2),
                    cache_hits=h1 - h0, **_hbm())
        self.results[name] = line
        gc.collect()
        print(f"phase {name}: {json.dumps(line)}", flush=True)
        return out

    @property
    def ok(self) -> bool:
        return not self.failed


def _ledger_losses(path: str):
    """(first, last, n) of the per-step training losses an engine wrote."""
    from tpu_dist.obs import read_ledger

    losses = [r["loss"] for r in read_ledger(path)
              if r.get("event") == "step" and r.get("loss") is not None]
    _check(bool(losses), f"no step records in {path}")
    return float(losses[0]), float(losses[-1]), len(losses)


def _finite(*xs: float) -> bool:
    return all(math.isfinite(x) for x in xs)


def _kernel_check(lowered, what: str) -> dict:
    """On the chip the compiled program must hold its Pallas kernels as
    ``tpu_custom_call``s; off it they run interpreted. ``lowered`` is the
    program lowered for the arguments it really ran with — the lower+compile
    the engines' telemetry probe makes, a cache hit after the dispatch."""
    from tpu_dist.runtime import pallas_interpret

    n = lowered.compile().as_text().count("tpu_custom_call")
    if pallas_interpret():
        return {"tpu_custom_calls": n, "kernels": "interpreted"}
    _check(n > 0, f"{what}: no tpu_custom_call in the compiled program — "
           "the Pallas kernel did not compile for the chip")
    return {"tpu_custom_calls": n, "kernels": "compiled"}


# ------------------------------------------------------------------- phases

def phase_runtime() -> dict:
    """What every entry point does first: launch detection, the compile
    cache, the native host library — and whether block_until_ready blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist import _native
    from tpu_dist.parallel import launch
    from tpu_dist.runtime import enable_compile_cache

    info = launch.initialize()
    _check(info.method == "local" and jax.process_count() == 1,
           f"a single host must take the local launch branch, got {info}")
    cache_dir = enable_compile_cache()
    # the host gather: native library built from csrc/ on first use, or
    # the numpy path — either must give numpy's answer
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (64, 8, 8, 3)).astype(np.uint8)
    lbls = np.arange(64, dtype=np.int32)
    idx = rng.permutation(64)[:16]
    got_i, got_l = _native.gather_batch(imgs, lbls, idx)
    _check(np.array_equal(got_i, imgs[idx]) and np.array_equal(got_l, lbls[idx]),
           "host batch gather disagrees with numpy indexing")
    # does block_until_ready block? Time a device_get AFTER it: if the work
    # were still running, the fetch would have to wait for it
    n = 4096 if jax.default_backend() == "tpu" else 256
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(a):
        for _ in range(64):
            a = (a @ a) * (1.0 / n)
        return a.astype(jnp.float32).sum()

    jax.device_get(chain(x))  # compile + warm
    t0 = time.perf_counter()
    y = chain(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    jax.device_get(y)
    t_get = time.perf_counter() - t0 - t_block
    _check(t_get < max(0.5 * t_block, 0.05),
           f"block_until_ready returned early: fetch after it took {t_get:.3f}s "
           f"of a {t_block:.3f}s program")
    return {"line": {
        "launch": info.method, "cache_dir": cache_dir,
        "cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "host_gather": "native" if _native.available() else "numpy",
        "dispatch_s": round(t_dispatch, 4),
        "block_until_ready_s": round(t_block, 4),
        "device_get_after_s": round(t_get, 4)}}


def _one_device_mesh():
    """The plain smoke's mesh: exactly one device, whatever the host holds
    (a four-chip host runs the same one-chip programs)."""
    import jax

    from tpu_dist.parallel.mesh import make_mesh

    return make_mesh((1,), ("data",), devices=jax.devices()[:1])


def _image_cfg(sizes: Sizes, seed: int, workdir: str, tag: str, **over):
    from tpu_dist.configs import TrainConfig

    k = over.get("steps_per_dispatch", sizes.steps_per_dispatch)
    base = dict(
        arch=sizes.arch, dataset=sizes.image_dataset, epochs=1,
        batch_size=sizes.image_batch, variant="jit",
        precision=sizes.image_precision, steps_per_dispatch=k,
        # two dispatch windows an epoch
        synth_train_size=2 * k * sizes.image_batch,
        synth_val_size=sizes.image_val, seed=seed, print_freq=k,
        checkpoint_dir=os.path.join(workdir, f"ck_{tag}"),
        ledger_path=os.path.join(workdir, f"{tag}.jsonl"))
    base.update(over)
    return TrainConfig(**base)


def phase_image(sizes: Sizes, seed: int, workdir: str) -> dict:
    from tpu_dist.engine import Trainer

    cfg = _image_cfg(sizes, seed, workdir, "image")
    mesh = _one_device_mesh()
    tr = Trainer(cfg, mesh=mesh)
    _check(tr.device_data and tr.k == sizes.steps_per_dispatch,
           "the windowed HBM-resident path was not taken")
    acc = tr.fit()                       # 2 windows, eval, async save
    first, last, n = _ledger_losses(cfg.ledger_path)
    step = int(tr.state.step)
    _check(step == 2 * sizes.steps_per_dispatch, f"ran {step} steps")
    ck = os.path.join(cfg.checkpoint_dir, f"{sizes.arch}-checkpoint.msgpack")
    _check(os.path.exists(ck), f"no checkpoint at {ck}")
    del tr
    gc.collect()
    # resume: one more epoch (two windows) from the saved state
    cfg2 = dataclasses.replace(
        cfg, resume=ck, epochs=2,
        ledger_path=os.path.join(workdir, "image_resume.jsonl"))
    tr2 = Trainer(cfg2, mesh=mesh)
    _check(tr2.start_epoch == 1, f"resumed at epoch {tr2.start_epoch}")
    acc2 = tr2.fit()
    _, last2, n2 = _ledger_losses(cfg2.ledger_path)
    step2 = int(tr2.state.step)
    _check(step2 == 4 * sizes.steps_per_dispatch,
           f"resume ended at step {step2}")
    _check(_finite(first, last, last2, acc, acc2), "non-finite loss")
    _check(last2 < first, f"loss did not fall: {first} -> {last2}")
    return {"line": {
        "arch": sizes.arch, "batch": sizes.image_batch,
        "windows": n, "loss_first": round(first, 4),
        "loss_last": round(last, 4), "val_acc1": round(acc, 4),
        "checkpoint": os.path.basename(ck), "resumed_windows": n2,
        "resume_loss_last": round(last2, 4), "resume_val_acc1": round(acc2, 4),
        "steps": step2}}


def _lm_cfg(sizes: Sizes, seed: int, workdir: str, tag: str, **over):
    from tpu_dist.configs import LMConfig

    steps = over.pop("max_steps", sizes.lm_steps)
    base = dict(
        num_layers=sizes.num_layers, d_model=sizes.d_model,
        num_heads=sizes.num_heads, seq_len=sizes.seq_len,
        vocab_size=sizes.vocab_size, batch_size=sizes.lm_batch,
        precision=sizes.lm_precision, attn="flash", max_steps=steps,
        # enough rows for the steps plus a two-batch held-out tail
        synth_tokens=(steps + 4) * sizes.lm_batch * (sizes.seq_len + 1),
        val_frac=2.0 / (steps + 4), seed=seed, print_freq=1,
        ledger_path=os.path.join(workdir, f"{tag}.jsonl"))
    base.update(over)
    return LMConfig(**base)


def _lm_step_lowered(tr):
    """The trainer's per-batch train step, lowered for its real shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    x = jax.ShapeDtypeStruct(
        (tr.cfg.batch_size, tr.cfg.seq_len), jnp.int32,
        sharding=NamedSharding(tr.mesh, tr.data_spec))
    return tr.train_step.lower(tr.state, x, x, tr.rng)


def _lm_run(sizes: Sizes, seed: int, workdir: str, tag: str, **over):
    """Build an LMTrainer as scripts/8 does, fit, check the kernels."""
    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = _lm_cfg(sizes, seed, workdir, tag, **over)
    tr = LMTrainer(cfg, mesh=_one_device_mesh())
    ppl = tr.fit()
    first, last, n = _ledger_losses(cfg.ledger_path)
    _check(n == cfg.max_steps, f"{tag}: ran {n} of {cfg.max_steps} steps")
    _check(_finite(first, last, ppl), f"{tag}: non-finite loss")
    line = {"steps": n, "loss_first": round(first, 4),
            "loss_last": round(last, 4), "val_ppl": round(ppl, 2),
            **_kernel_check(_lm_step_lowered(tr), tag)}
    return tr, line


def phase_lm(sizes: Sizes, seed: int, workdir: str) -> dict:
    tr, line = _lm_run(sizes, seed, workdir, "lm")
    _check(line["loss_last"] < line["loss_first"],
           f"loss did not fall: {line}")
    line.update(layers=sizes.num_layers, d_model=sizes.d_model,
                seq_len=sizes.seq_len, vocab=sizes.vocab_size,
                batch=sizes.lm_batch, attn="flash")
    # what the server phase needs, and nothing of the optimizer state
    keep = {"model": tr.decode_model, "params": tr.state.params}
    del tr
    return {"line": line, **keep}


def phase_lm_variant(sizes: Sizes, seed: int, workdir: str, tag: str,
                     **over) -> dict:
    _, line = _lm_run(sizes, seed, workdir, tag, max_steps=1, **over)
    if "quant" in over:
        from tpu_dist.ops.quant import fused_quant_active
        line["fused_quant"] = fused_quant_active()
    return {"line": {**over, **line}}


def _requests(sizes: Sizes, seed: int):
    """Two requests per prompt length, on the synthetic corpus's affine
    rule from seeded starts."""
    import numpy as np

    from tpu_dist.engine.serve import DecodeRequest

    rng = np.random.default_rng(seed + 99)
    reqs = []
    for n in sizes.prompt_lens:
        for _ in range(2):
            toks = np.empty(n, np.int64)
            toks[0] = rng.integers(0, sizes.vocab_size)
            for i in range(1, n):
                toks[i] = (toks[i - 1] * 5 + 7) % sizes.vocab_size
            reqs.append(DecodeRequest(len(reqs), toks.astype(np.int32),
                                      sizes.new_tokens))
    return reqs


def _serve(model, params, sizes: Sizes, seed: int, **cfg_over):
    from tpu_dist.engine.serve import ServeConfig, ServeEngine

    scfg = ServeConfig(
        max_slots=sizes.max_slots, page_size=sizes.page_size,
        num_pages=sizes.max_slots * (sizes.seq_len // sizes.page_size),
        max_len=sizes.seq_len, **cfg_over)
    eng = ServeEngine(model, params, scfg)
    reqs = _requests(sizes, seed)
    t0 = time.time()
    comps = eng.run(reqs)
    secs = time.time() - t0
    _check(len(comps) == len(reqs) and eng.rejected == 0,
           f"{len(comps)}/{len(reqs)} requests completed, "
           f"{eng.rejected} rejected")
    for c in comps:
        _check(c.n_generated == sizes.new_tokens
               and int(c.tokens.min()) >= 0
               and int(c.tokens.max()) < sizes.vocab_size,
               f"request {c.rid}: {c.n_generated} tokens, range "
               f"[{c.tokens.min()}, {c.tokens.max()}]")
    st = eng.stats()
    line = {"requests": f"{len(comps)}/{len(reqs)}",
            "prompt_lens": list(sizes.prompt_lens),
            "new_tokens": sizes.new_tokens, "prefills": st["prefills"],
            "ticks": st["ticks"], "occupancy": st["occupancy"],
            "read": st["read"], "live_pages": st["live_pages"],
            "tokens_per_s_cold": round(
                len(comps) * sizes.new_tokens / max(secs, 1e-9), 1)}
    return eng, {c.rid: c.tokens for c in comps}, reqs, line


def _tick_lowered(eng):
    """The engine's decode tick at max_slots, lowered for its real shapes."""
    import jax.numpy as jnp

    from tpu_dist.engine.serve import _tick_program

    n = len(eng.slots)
    tick = _tick_program(eng.model, eng.cfg.temperature, eng.cfg.top_k,
                         eng.cfg.top_p, eng.sp_mesh)
    return tick.lower(
        eng.params, eng.pool.layers(),
        jnp.zeros((n, eng.max_pages_per_seq), jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), eng._rng)


def _near_tie(model, params, prefix, tok_a: int, tok_b: int) -> dict:
    """Where two greedy decodes part ways after an identical prefix: the
    margin between the two tokens under a third computation (one full
    forward over the prefix). Three bf16 computations of one logit — the
    paged tick, the cached decode, this forward — each carry about a unit
    of accumulated roundoff, so two candidates within four units of the
    row's top logit are a tie that rounding broke, not a wrong token. (The
    one divergence seen on the chip so far was 0.85 of a unit: my chip
    run, PR 21.)"""
    import jax
    import numpy as np

    # padded to the model's length: causal attention keeps the padding out
    # of row t-1, and every divergence reuses one compiled forward
    t = prefix.size
    padded = np.zeros((1, model.max_len), np.int32)
    padded[0, :t] = prefix
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False))(
        params, padded)
    row = np.asarray(logits[0, t - 1], np.float32)
    top = float(row.max())
    tol = 4 * BF16_EPS * max(1.0, float(np.abs(row).max()))
    gaps = (top - float(row[tok_a]), top - float(row[tok_b]))
    return {"gap_ref": round(gaps[0], 5), "gap_served": round(gaps[1], 5),
            "tol": round(tol, 5), "tie": max(gaps) <= tol}


def phase_serve(sizes: Sizes, seed: int, lm: dict) -> dict:
    """Default path, greedy, against engine.generate on the same prompts
    (what tests/test_serve.py pins bit-for-bit on the CPU)."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.engine.generate import generate

    model, params = lm["model"], lm["params"]
    eng, served, reqs, line = _serve(model, params, sizes, seed)
    exact, diverged = 0, []
    for n in sizes.prompt_lens:
        group = [r for r in reqs if r.prompt.size == n]
        ref = np.asarray(generate(
            model, params, jnp.asarray(np.stack([r.prompt for r in group])),
            steps=sizes.new_tokens, use_cache=True))
        for r, want in zip(group, ref):
            got = served[r.rid]
            if np.array_equal(got, want):
                exact += 1
                continue
            t = int(np.flatnonzero(got != want)[0])
            tie = _near_tie(model, params, want[:t], int(want[t]), int(got[t]))
            diverged.append({"rid": r.rid, "prompt_len": n,
                             "first_diff_pos": t,
                             "generated_index": t - n, **tie})
    line.update(greedy_exact=f"{exact}/{len(reqs)}", diverged=diverged)
    # a divergence is a finding, printed above; it fails the phase unless
    # the reference itself calls the two tokens a tie at that position
    bad = [d for d in diverged if not d["tie"]]
    _check(not bad, f"greedy tokens differ from engine.generate beyond a "
           f"bf16 tie: {bad}")
    return {"line": line, "tokens": served}


def phase_serve_int8(sizes: Sizes, seed: int, lm: dict,
                     default_tokens: Optional[dict]) -> dict:
    """int8 KV pages read by the paged Pallas kernel."""
    import numpy as np

    eng, served, reqs, line = _serve(lm["model"], lm["params"], sizes, seed,
                                     kv_quant="int8", attn_read="flash")
    line.update(kv_quant="int8", attn_read="flash",
                **_kernel_check(_tick_lowered(eng),
                                "int8-flash serving tick"))
    if default_tokens:
        # information: quantized pages may legitimately change a token
        same = [float(np.mean(served[r.rid][r.prompt.size:]
                              == default_tokens[r.rid][r.prompt.size:]))
                for r in reqs]
        line["agreement_with_default"] = round(float(np.mean(same)), 4)
    return {"line": line}


def run_single_chip(sizes: Sizes, seed: int, workdir: str,
                    meter: Optional[CompileMeter] = None) -> Report:
    """Every phase of the one-chip smoke, in order, at ``sizes``."""
    rep = Report(meter or CompileMeter())
    rep.phase("runtime", phase_runtime)
    rep.phase("image_trainer", phase_image, sizes, seed, workdir)
    lm = rep.phase("lm_trainer", phase_lm, sizes, seed, workdir)
    rep.phase("lm_fused_adamw", phase_lm_variant, sizes, seed, workdir,
              "lm_fused_adamw", optimizer="fused_adamw", lr=1e-3)
    rep.phase("lm_int8", phase_lm_variant, sizes, seed, workdir,
              "lm_int8", quant="int8")
    if lm is None:
        rep.failed += ["serve_default", "serve_int8_flash"]
        return rep
    default = rep.phase("serve_default", phase_serve, sizes, seed, lm)
    rep.phase("serve_int8_flash", phase_serve_int8, sizes, seed, lm,
              default["tokens"] if default else None)
    return rep


# ---------------------------------------------------------------- multichip

def _flat_params(params):
    """Host float32 vector of a (possibly sharded) parameter tree."""
    import jax
    import numpy as np

    from tpu_dist.engine.checkpoint import gather_to_host

    return np.concatenate([np.asarray(x, np.float32).ravel() for x in
                           jax.tree_util.tree_leaves(gather_to_host(params))])


def _placement(params, n_devices: int, want_sharded: bool) -> dict:
    """Where the parameters really lie: every leaf spans ``n_devices``
    distinct devices and, for the sharded layouts, some leaf holds only a
    slice of itself on each."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    spans = {len({s.device for s in x.addressable_shards}) for x in leaves}
    _check(spans == {n_devices},
           f"parameters span {sorted(spans)} devices, want {n_devices} — "
           "placement collapsed onto fewer devices")
    sharded = sum(x.addressable_shards[0].data.shape != x.shape
                  for x in leaves)
    _check(bool(sharded) == want_sharded,
           f"{sharded} parameter leaves are sharded, want_sharded="
           f"{want_sharded}")
    return {"devices_in_use": n_devices, "sharded_leaves": int(sharded)}


def _compare(name: str, ref: dict, got: dict, precision: str) -> dict:
    """Hold a layout to its one-device twin. In fp32 the tolerances are the
    CPU tests' own, element by element. In bf16 the same run differs by
    rounding alone — a sharded reduction rounds partial sums the one-device
    program never forms — so the bounds are counted in bf16 roundoff: the
    first loss (same weights, same batch) to the CPU tests' absolute bound
    or an eighth of a unit of the loss, the last loss to one unit, and the
    whole update ``final - initial`` to eight units in relative L2 norm. A
    sharding fault (a replica dropped, a gradient not averaged, a shard
    misplaced) moves the update by tens of percent, not by 3."""
    import numpy as np

    loss_tol, rtol, atol = MULTICHIP_TOL[name]
    d_first = abs(got["loss_first"] - ref["loss_first"])
    d_last = abs(got["loss_last"] - ref["loss_last"])
    diff = np.abs(got["params"] - ref["params"])
    bound = atol + rtol * np.abs(ref["params"])
    update = ref["params"] - ref["params0"]
    upd_err = float(np.linalg.norm((got["params"] - got["params0"]) - update)
                    / max(float(np.linalg.norm(update)), 1e-30))
    out = {"loss_first": round(got["loss_first"], 6),
           "loss_last": round(got["loss_last"], 6),
           "ref_loss_last": round(ref["loss_last"], 6),
           "d_loss_first": float(f"{d_first:.3g}"),
           "d_loss_last": float(f"{d_last:.3g}"),
           "same_init": bool(np.array_equal(got["params0"], ref["params0"])),
           "update_rel_err": float(f"{upd_err:.3g}"),
           "param_max_abs_diff": float(f"{diff.max():.3g}"),
           "param_outside_fp32_tol": float(f"{np.mean(diff > bound):.3g}")}
    if precision == "fp32":
        out["tol"] = {"loss_abs": loss_tol, "rtol": rtol, "atol": atol}
        ok = (d_first <= loss_tol and d_last <= loss_tol
              and not np.any(diff > bound))
    else:
        tol = {"loss_first_abs": max(loss_tol, BF16_EPS / 8
                                     * abs(ref["loss_first"])),
               "loss_last_abs": BF16_EPS * abs(ref["loss_last"]),
               "update_rel_err": 8 * BF16_EPS}
        out["tol"] = {k: float(f"{v:.3g}") for k, v in tol.items()}
        ok = (d_first <= tol["loss_first_abs"]
              and d_last <= tol["loss_last_abs"]
              and upd_err <= tol["update_rel_err"])
    out["agrees"] = bool(ok and out["same_init"])
    return out


def _fit_recorded(tr, extra: Optional[dict] = None) -> dict:
    """Fit a trainer; keep its losses, its parameters before and after as
    host vectors, and the final (placed) state."""
    params0 = _flat_params(tr.state.params)
    tr.fit()
    first, last, n = _ledger_losses(tr.cfg.ledger_path)
    return {"loss_first": first, "loss_last": last, "records": n,
            "params0": params0, "params": _flat_params(tr.state.params),
            "state": tr.state, **(extra or {})}


def _image_run(sizes: Sizes, seed: int, workdir: str, tag: str, mesh,
               **over) -> dict:
    from tpu_dist.engine import Trainer

    n = sizes.multichip_image_steps
    # two dispatch windows for the compiler-partitioned variant; the
    # explicit-psum engine dispatches batch by batch
    k = max(n // 2, 1) if over.get("variant", "jit") == "jit" else 1
    cfg = _image_cfg(sizes, seed, workdir, tag, steps_per_dispatch=k,
                     print_freq=1, synth_train_size=n * sizes.image_batch,
                     lr=sizes.multichip_image_lr,
                     mesh_shape=tuple(mesh.devices.shape), **over)
    out = _fit_recorded(Trainer(cfg, mesh=mesh))
    step = int(out["state"].step)
    _check(step == n, f"{tag}: ran {step} steps")
    return out


def phase_multichip_image(sizes: Sizes, seed: int, workdir: str, name: str,
                          mesh, mesh1, **over) -> dict:
    ref = _image_run(sizes, seed, workdir, f"{name}_ref", mesh1, **over)
    ref.pop("state")
    got = _image_run(sizes, seed, workdir, name, mesh, **over)
    line = {"mesh": dict(mesh.shape), **over,
            "lr": sizes.multichip_image_lr,
            **_placement(got.pop("state").params, mesh.devices.size, False),
            **_compare(name, ref, got, sizes.image_precision)}
    _check(line["agrees"], f"{name} disagrees with one device: {line}")
    return {"line": line}


def _lm_multi_run(sizes: Sizes, seed: int, workdir: str, tag: str, mesh,
                  **over) -> dict:
    from tpu_dist.engine.lm_loop import LMTrainer

    cfg = _lm_cfg(sizes, seed, workdir, tag,
                  max_steps=sizes.multichip_lm_steps,
                  mesh_shape=tuple(mesh.devices.shape),
                  mesh_axes=tuple(mesh.axis_names), **over)
    tr = LMTrainer(cfg, mesh=mesh)
    out = _fit_recorded(tr, {"mode": tr.mode})
    _check(out["records"] == cfg.max_steps,
           f"{tag}: ran {out['records']} of {cfg.max_steps} steps")
    return out


def phase_multichip_lm(sizes: Sizes, seed: int, workdir: str, name: str,
                       mesh, ref: dict, want_sharded: bool, **over) -> dict:
    got = _lm_multi_run(sizes, seed, workdir, name, mesh, **over)
    line = {"mesh": dict(mesh.shape), "mode": got["mode"],
            **_placement(got.pop("state").params, mesh.devices.size,
                         want_sharded),
            **_compare(name, ref, got, sizes.lm_precision)}
    _check(line["agrees"], f"{name} disagrees with one device: {line}")
    return {"line": line}


def run_multichip(sizes: Sizes, seed: int, workdir: str, devices: Sequence,
                  meter: Optional[CompileMeter] = None) -> Report:
    """The path across four chips and, for each layout, the same global
    batch and seed on ONE of them."""
    from tpu_dist.parallel.mesh import make_mesh
    from tpu_dist.runtime import enable_compile_cache

    _check(len(devices) == 4, f"need four devices, got {len(devices)}")
    enable_compile_cache()
    rep = Report(meter or CompileMeter())
    one = make_mesh((1,), ("data",), devices=devices[:1])
    dp4 = make_mesh((4,), ("data",), devices=devices)
    rep.phase("image_dp_jit", phase_multichip_image, sizes, seed, workdir,
              "image_dp_jit", dp4, one, variant="jit")
    norm = {"norm": sizes.explicit_norm} if sizes.explicit_norm else {}
    rep.phase("image_dp_psum", phase_multichip_image, sizes, seed, workdir,
              "image_dp_psum", dp4, one, variant="shard_map", **norm)

    def lm_ref():
        out = _lm_multi_run(sizes, seed, workdir, "lm_ref", one)
        out.pop("state")
        return {"line": {"mode": out["mode"],
                         "loss_first": round(out["loss_first"], 6),
                         "loss_last": round(out["loss_last"], 6)},
                "ref": out}

    ref = rep.phase("lm_one_device", lm_ref)
    if ref is None:
        rep.failed += ["lm_tp", "lm_sp", "lm_fsdp"]
        return rep
    rep.phase("lm_tp", phase_multichip_lm, sizes, seed, workdir, "lm_tp",
              make_mesh((2, 2), ("data", "model"), devices=devices),
              ref["ref"], True)
    rep.phase("lm_fsdp", phase_multichip_lm, sizes, seed, workdir, "lm_fsdp",
              dp4, ref["ref"], True, fsdp=True)
    rep.phase("lm_sp", phase_multichip_lm, sizes, seed, workdir, "lm_sp",
              make_mesh((1, 4), ("data", "seq"), devices=devices),
              ref["ref"], False)
    return rep


# --------------------------------------------------------------------- main

def require_tpu(min_devices: int):
    """The device check: stop, with no result, unless JAX reports a TPU —
    and unless the program this script drives is beside it."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < min_devices:
        print(f"chip_smoke: needs {min_devices} tpu device(s), JAX reports "
              f"{len(devices)} x {devices[0].platform!r} — refusing to run "
              "on anything else", file=sys.stderr)
        raise SystemExit(2)
    try:
        import tpu_dist  # noqa: F401
    except ImportError:
        print("chip_smoke: the tpu_dist package is not importable from "
              f"{os.getcwd()} — run it from the root of a checkout",
              file=sys.stderr)
        raise SystemExit(3)
    return devices


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip path and its one-device "
                         "references (needs four tpu devices)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the synthetic data and the random weights")
    args = ap.parse_args(argv)

    t0 = time.time()
    devices = require_tpu(4 if args.multichip else 1)
    meter = CompileMeter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.multichip:
            rep = run_multichip(FULL, args.seed, workdir, devices[:4], meter)
        else:
            rep = run_single_chip(FULL, args.seed, workdir, meter)
    count, secs, hits = meter.snapshot()
    print("smoke: " + json.dumps({
        "seconds": round(time.time() - t0, 1), "compilations": count,
        "compile_seconds": round(secs, 1), "cache_hits": hits,
        "failed": rep.failed}), flush=True)
    print(json.dumps({
        "ok": rep.ok,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": 4 if args.multichip else len(devices)}}),
        flush=True)
    return 0 if rep.ok else 1


if __name__ == "__main__":
    sys.exit(main())
