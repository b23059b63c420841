"""Family ``lm_server``: a GPT-2-shaped configuration served by
``tpu_dist.engine.serve.ServeEngine`` (paged KV, continuous batching) under
an open loop of requests at a fixed rate.

The workload file gives the model's engine fields and ``ServeConfig``'s;
the traffic file the arrival rate and the length distributions.
"""

from __future__ import annotations

import gc
import math
import os
import sys
import time
from typing import Dict, List

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmarks.families.lm_trainer import ref_name  # noqa: E402
from benchmarks.harness import check, traffic, window  # noqa: E402
from benchmarks.harness.stats import percentile  # noqa: E402
from benchmarks.harness.trainers import as_engine_tree, fold_seed  # noqa: E402
from benchmarks.reference import lm as ref  # noqa: E402


class _Adapter(window.EngineAdapter):
    def __init__(self, eng, schedule):
        from tpu_dist.engine.serve import DecodeRequest

        self.eng, self.s, self._req = eng, schedule, DecodeRequest

    def submit(self, i: int) -> bool:
        return self.eng.submit(self._req(
            rid=i, prompt=self.s.prompts[i],
            max_new_tokens=int(self.s.answer_len[i])))

    def step(self):
        return self.eng.step()

    def busy(self) -> bool:
        return bool(self.eng.queue) or any(
            s is not None for s in self.eng.slots)

    def counters(self):
        return self.eng.ticks, self.eng.prefills


class Family:
    kind = "serve"

    def __init__(self, cell, seed: int, devices, workdir: str,
                 control: dict = None):
        self.cell, self.seed, self.devices = cell, int(seed), list(devices)
        self.sizes = cell.config
        self.engine = dict(cell.workload["engine"])
        # ``control``: ServeConfig fields of a lower-precision path of the
        # program's own, switched on by benchmarks/control.py alone
        self.serve = {**cell.workload["serve"], **(control or {})}
        self.eng = None

    # -- set-up -----------------------------------------------------------
    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        from tpu_dist.engine.serve import ServeConfig, ServeEngine
        from tpu_dist.models.transformer import full_attention, tiny_lm
        from tpu_dist.ops.flash_attention import flash_attention_fn

        s, e = self.sizes, self.engine
        if e["attn"] == "flash":
            attn = flash_attention_fn(block_k=int(e["attn_block"]))
        elif e["attn"] == "full":
            attn = full_attention
        else:
            raise ValueError(f"attn {e['attn']!r}: flash | full")
        dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[e["precision"]]
        model = tiny_lm(vocab_size=s["vocab_size"],
                        num_layers=s["num_layers"], d_model=s["d_model"],
                        num_heads=s["num_heads"],
                        max_len=s["max_positions"], dtype=dtype, attn_fn=attn)
        like = jax.eval_shape(
            lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32),
                                 train=False)["params"], jax.random.PRNGKey(0))
        self._weights_fn = jax.jit(
            lambda key: ref.make_weights(s, key, dtype))
        self._params = lambda: as_engine_tree(
            self._weights_fn(fold_seed(self.seed)), like, ref_name, dtype)
        self._model = model
        with jax.default_device(self.devices[0]):
            self.eng = ServeEngine(model, self._params(),
                                   ServeConfig(**self.serve))
        self.max_len = self.eng.max_len

    def reseed(self, seed: int) -> None:
        """Other weights in the same idle engine (``control.py`` reads its
        seeds in one process: the compiled programs stay)."""
        import jax

        from tpu_dist.engine.generate import _quantize_for_decode

        self.seed = int(seed)
        self.eng.params = None
        gc.collect()
        with jax.default_device(self.devices[0]):
            params = self._params()
            if self.eng.cfg.quant != "none":
                _, params = _quantize_for_decode(self._model, params,
                                                 self.eng.cfg.quant)
        self.eng.params = params

    def first_steps(self) -> None:
        """Serving has no state to read back before the window."""

    def warm(self) -> None:
        """One request through every prefill bucket the mix's prompt
        lengths can reach, then the decode tick, so nothing compiles in
        the window."""
        from tpu_dist.engine.serve import DecodeRequest

        p = self.cell.traffic["prompt"]
        rng = np.random.default_rng([self.seed, 0xca11])
        lens, lo = [], int(p["min"])
        for b in self.eng.buckets:
            if b >= lo:
                lens.append(min(b, int(p["max"])))
            if b >= int(p["max"]):
                break
        reqs = [DecodeRequest(rid=-(i + 1), prompt=rng.integers(
            0, self.sizes["vocab_size"], n).astype(np.int32),
            max_new_tokens=4) for i, n in enumerate(lens)]
        with window.annotate("warm_requests"):
            done = self.eng.run(reqs)
        if len(done) != len(reqs):
            raise RuntimeError(f"warm-up served {len(done)}/{len(reqs)}")

    def timed_program(self):
        """The decode tick at ``max_slots``, compiled for the arguments it
        runs with (a cache hit)."""
        import jax.numpy as jnp

        from tpu_dist.engine.serve import _tick_program

        eng, n = self.eng, len(self.eng.slots)
        tick = _tick_program(eng.model, eng.cfg.temperature, eng.cfg.top_k,
                             eng.cfg.top_p, eng.sp_mesh)
        return tick.lower(
            eng.params, eng.pool.layers(),
            jnp.zeros((n, eng.max_pages_per_seq), jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
            eng._rng).compile()

    # -- the window ---------------------------------------------------------
    def run_window(self, seconds: float, mix: dict = None) -> dict:
        mix = mix or self.cell.traffic
        sched = traffic.open_loop_schedule(
            mix, self.seed, seconds, self.sizes["vocab_size"], self.max_len)
        adapter = _Adapter(self.eng, sched)
        t_open = time.monotonic() + float(mix.get("preroll_s", 0.0))
        res = window.drive_open_loop(
            adapter, sched.due, t_open,
            deadline=seconds + float(mix.get("drain_limit_s", 120.0)))
        return {"schedule": sched, "result": res, "t_open": t_open,
                "t_close": t_open + seconds, "seconds": seconds,
                "rate_per_s": mix["rate_per_s"]}

    def _rows(self, win: dict) -> List[dict]:
        """One row per request that was due inside the window."""
        if "rows" in win:
            return win["rows"]
        sched, res = win["schedule"], win["result"]
        rows = win["rows"] = []
        for i in np.flatnonzero(sched.in_window):
            i = int(i)
            due = res.t_open + float(sched.due[i])
            c = res.completions.get(i)
            ok = (c is not None and c.n_generated == sched.answer_len[i]
                  and c.prompt_len == sched.prompt_len[i])
            rows.append({"i": i, "due": due, "ok": ok, "c": c,
                         "late": float(res.submit_ts[i]) - due})
        return rows

    def end_to_end(self, win: dict) -> Dict[str, float]:
        rows = self._rows(win)
        good = [r for r in rows if r["ok"]]
        missing = len(rows) - len(good)
        ttft = [1e3 * (r["c"].first_token_ts - r["due"]) for r in good]
        gap = [1e3 * (r["c"].finish_ts - r["c"].first_token_ts)
               / (r["c"].n_generated - 1) for r in good]
        res = win["result"]
        drained = max((c.finish_ts for c in res.completions.values()),
                      default=res.t_open) - win["t_close"]
        toks = sum(r["c"].n_generated for r in good)
        print(f"window: {len(rows)} requests due in {win['seconds']:.1f} s at "
              f"{win['rate_per_s']} /s, {len(good)} served, "
              f"{toks} tokens, drain {max(drained, 0.0):.3f} s after the "
              f"window, ttft p50 {percentile(ttft, 50):.3f} ms, gap p50 "
              f"{percentile(gap, 50):.3f} ms", flush=True)
        wall = lambda sel: [1e3 * (st.end - st.start) for st in res.steps
                            if sel(st)] or [math.nan]
        pre = wall(lambda st: st.prefills > 0)
        tick = wall(lambda st: st.prefills == 0 and st.ticks == 1)
        wait = [1e3 * (r["c"].start_ts - r["due"]) for r in good]
        late = [1e3 * r["late"] for r in rows]
        print(f"loop: submit lateness p50 {percentile(late, 50):.1f} p95 "
              f"{percentile(late, 95):.1f} ms, queue wait p50 "
              f"{percentile(wait, 50):.1f} p95 {percentile(wait, 95):.1f} ms, "
              f"steps with a prefill p50 {percentile(pre, 50):.1f} p95 "
              f"{percentile(pre, 95):.1f} ms ({len(pre)}), pure ticks p50 "
              f"{percentile(tick, 50):.1f} p95 {percentile(tick, 95):.1f} ms "
              f"({len(tick)})", flush=True)
        steps = sorted(res.steps, key=lambda st: st.start - st.end)[:3]
        print("longest engine steps: " + ", ".join(
            f"{1e3 * (st.end - st.start):.1f} ms at {st.start - res.t_open:.2f} s"
            f" ({st.ticks} tick, {st.prefills} prefill)" for st in steps)
            + f"; ttft p95 {percentile(ttft, 95, missing):.3f} ms",
            flush=True)
        return {"gap_p95_ms": percentile(gap, 95, missing)}

    def observations(self, win: dict) -> dict:
        res = win["result"]
        return {"engine_steps": [s for s in res.steps
                                 if s.start >= res.t_open
                                 and s.end <= win["t_close"]]}

    def attempted_failed(self, win: dict):
        rows = self._rows(win)
        return len(rows), sum(not r["ok"] for r in rows)

    # -- the comparison -------------------------------------------------------
    def release(self) -> None:
        self.eng = None
        gc.collect()

    def sample(self, win: dict) -> List[np.ndarray]:
        """The served token rows the reference reads: ``sample_requests``
        finished requests drawn from the seed, the longest among them."""
        good = [r for r in self._rows(win) if r["ok"]]
        n = int(self.cell.workload["check"]["sample_requests"])
        if not good:
            return []
        longest = max(good, key=lambda r: len(r["c"].tokens))
        rest = [r for r in good if r is not longest]
        rng = np.random.default_rng([self.seed, 0x5a3])
        pick = [rest[j] for j in rng.permutation(len(rest))[:n - 1]]
        return [(r["c"].prompt_len, np.asarray(r["c"].tokens, np.int32))
                for r in [longest] + pick]

    def token_gaps(self, sample) -> List[float]:
        """Per served token of the sampled requests, the gap by which its
        reference logit lies below the reference's best at its position
        (one full float32 forward over prompt + answer)."""
        import jax
        import jax.numpy as jnp

        heads = self.sizes["num_heads"]

        def below_best(w, x):
            # row t predicts token t + 1: everything a request needs comes
            # back as one vector of its padded width
            logits = ref.forward(w, x, heads)[0]
            return logits.max(-1) - jnp.take_along_axis(
                logits, jnp.roll(x[0], -1)[:, None], 1)[:, 0]

        served_gaps = []
        with jax.default_device(self.devices[0]):
            weights = jax.jit(ref.stack_blocks)(
                self._weights_fn(fold_seed(self.seed)))
            fwd = jax.jit(below_best)
            for plen, toks in sample:
                # padded to a power of two: causal attention keeps the
                # padding out of the rows read, and a few compiled lengths
                # serve every request
                width = min(self.max_len,
                            max(128, 1 << (len(toks) - 1).bit_length()))
                padded = np.zeros((1, width), np.int32)
                padded[0, :len(toks)] = toks
                served = jax.device_get(fwd(weights, jnp.asarray(padded)))
                # the rows that predict the answer
                served_gaps.extend(served[plen - 1:len(toks) - 1].tolist())
        return served_gaps

    def comparisons(self, gaps: List[float], note: str = ""
                    ) -> List[check.Comparison]:
        lim = self.cell.workload["check"]["limits"]
        if not gaps:
            gaps = [math.inf]
        return [check.Comparison("served_token_logit_gap_max", max(gaps),
                                 lim["served_token_gap_max"], note),
                check.Comparison("served_token_logit_gap_mean",
                                 sum(gaps) / len(gaps),
                                 lim["served_token_gap_mean"])]

    def verify(self, win: dict) -> List[check.Comparison]:
        sample = self.sample(win)
        gaps = self.token_gaps(sample) if sample else []
        n_tok = sum(len(t) - p for p, t in sample)
        return self.comparisons(
            gaps,
            f"{len(sample)} requests, {n_tok} served tokens, longest "
            f"{max((len(t) for _, t in sample), default=0)}")
