"""What the two training families share: the weights handed to the engine,
the first steps driven through the window's own call, the timed window of
whole ``train_epoch`` calls, ``train_mfu``, and the comparison with the
plain reference.

A family (``benchmarks/families/<family>.py``) subclasses ``TrainerFamily``
and supplies what belongs to its engine: how the trainer is built, how the
engine's parameter leaves are named in the reference, which calls make one
optimizer step each, how the first gradient is read from the optimizer's
state, the reference's own steps, and the FLOPs a sample requires.
"""

from __future__ import annotations

import json
from typing import Dict, List

import numpy as np

from . import check, device, window


def path_names(path) -> tuple:
    """('block0', 'qkv', 'kernel') from a jax key path."""
    return tuple(getattr(k, "key", getattr(k, "name", None)) for k in path)


def fold_seed(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def engine_seed(seed: int) -> int:
    """The seed as the engines' int32 configs can hold it."""
    return seed % (2**31 - 1)


def as_engine_tree(weights, like, ref_name, dtype=None):
    """``weights`` (reference leaf name -> array) arranged as the engine's
    parameter tree ``like``; a leaf of ``like`` that is an array gives its
    placement, a bare shape leaves the default device."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    new = []
    for path, old in leaves:
        w = weights[ref_name(path_names(path))]
        if w.shape != old.shape:
            raise ValueError(f"{path_names(path)}: engine {old.shape} "
                             f"vs reference {w.shape}")
        w = w.astype(dtype or old.dtype)
        sharding = getattr(old, "sharding", None)
        new.append(jax.device_put(w, sharding) if sharding is not None
                   and not isinstance(old, jax.ShapeDtypeStruct) else w)
    return jax.tree_util.tree_unflatten(treedef, new)


def read_step_records(ledger_path: str, first_step: int) -> List[dict]:
    """The engine's own ``step`` records (``Trainer._drain`` /
    ``LMTrainer._drain``) from ``first_step`` on."""
    out = []
    with open(ledger_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("event") == "step" and rec["step"] >= first_step:
                out.append(rec)
    return out


class TrainerFamily:
    kind = "train"

    def __init__(self, cell, seed: int, devices, workdir: str,
                 control: bool = False):
        self.cell, self.seed, self.devices = cell, int(seed), list(devices)
        self.workdir, self.control = workdir, control
        self.engine = dict(cell.workload["engine"])
        if control:
            self.engine.update(cell.workload["control"].get("engine", {}))
        self.sizes = cell.config
        self.tr = None
        self._weights_fn = None
        self.program = None       # readings of the first steps
        self.reference = None     # the reference's, once verify() has run
        self.fed = None           # what those steps were fed (host arrays)
        self.next_epoch = 0

    # -- what a family supplies ------------------------------------------
    def make_trainer(self): raise NotImplementedError
    def make_weights(self, key, dtype): raise NotImplementedError
    def ref_name(self, names: tuple) -> str: raise NotImplementedError
    def single_steps(self) -> List[dict]: raise NotImplementedError
    def fed_rows(self, epoch: int, batch: int): raise NotImplementedError
    def first_grad(self, opt_state, params0): raise NotImplementedError
    def reference_steps(self, fed, quant: str) -> dict: raise NotImplementedError
    def flops_per_sample(self) -> float: raise NotImplementedError
    samples_per_epoch: int = 0
    steps_per_epoch: int = 0

    # -- set-up -----------------------------------------------------------
    def mesh(self):
        from tpu_dist.parallel.mesh import make_mesh

        return make_mesh((len(self.devices),), ("data",),
                         devices=self.devices)

    def _weights(self):
        """The benchmark's weights, float32 on the device, one program."""
        import jax
        import jax.numpy as jnp

        if self._weights_fn is None:
            self._weights_fn = jax.jit(
                lambda key: self.make_weights(key, jnp.float32))
        return self._weights_fn(fold_seed(self.seed))

    def _as_engine_tree(self, weights, like):
        return as_engine_tree(weights, like, self.ref_name)

    def _leaf_samples(self, tree) -> Dict[str, np.ndarray]:
        import jax

        taken = jax.jit(lambda t: jax.tree_util.tree_map(
            check.leaf_sample, t))(tree)
        flat = jax.tree_util.tree_flatten_with_path(jax.device_get(taken))[0]
        return {self.ref_name(path_names(p)): np.asarray(v, np.float32)
                for p, v in flat}

    def _leaf_norms(self, tree) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            t))(tree)
        flat = jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]
        return {self.ref_name(path_names(p)): float(v) for p, v in flat}

    def build(self) -> None:
        """The engine object with the benchmark's weights in it."""
        self.tr = self.make_trainer()
        params = self._as_engine_tree(self._weights(), self.tr.state.params)
        self.tr.state = self.tr.state.replace(params=params)

    def first_steps(self) -> None:
        """Drive the engine object through its first optimizer steps (the
        cell's ``check.steps``: three, or two where three reference steps
        would outlast the window), each through ``train_epoch`` (the window's own call and feed), and
        keep what the reference will be compared with."""
        import jax

        tr = self.tr
        losses, fed = [], []
        grad_norms = grad_samples = None
        steps = self.single_steps()[:int(self.cell.workload["check"]["steps"])]
        for i, call in enumerate(steps):
            tr._skip_batches = call["skip"]
            if "max_steps" in call:
                tr.cfg.max_steps = call["max_steps"]
            fed.append(self.fed_rows(call["epoch"], call["skip"]))
            with window.annotate("first_step"):
                out = tr.train_epoch(call["epoch"])
            losses.append(float(out["loss"]))
            if i == 0:
                p0 = self._as_engine_tree(self._weights(), tr.state.params)
                grad = self.first_grad(tr.state.opt_state, p0)
                grad_norms = self._leaf_norms(grad)
                grad_samples = self._leaf_samples(grad)
                del p0, grad
        if hasattr(tr.cfg, "max_steps"):
            tr.cfg.max_steps = 0
        tr._skip_batches = 0
        p0 = self._as_engine_tree(self._weights(), tr.state.params)
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x - y, a, b))(tr.state.params, p0)
        del p0
        self.program = {"losses": losses, "grad_norms": grad_norms,
                        "grad_samples": grad_samples,
                        "update_norms": self._leaf_norms(delta)}
        self.fed = fed
        self.next_epoch = max(c["epoch"] for c in steps) + 1

    def warm(self) -> None:
        """One whole epoch: every shape of the timed window compiles here."""
        with window.annotate("warm_epoch"):
            self.warm_out = self.tr.train_epoch(self.next_epoch)
        self.next_epoch += 1

    # -- the window ---------------------------------------------------------
    def run_window(self, seconds: float) -> dict:
        first = self.next_epoch
        calls = window.drive_epochs(self.tr.train_epoch, first, seconds)
        self.next_epoch = first + len(calls)
        records = read_step_records(self.tr.cfg.ledger_path,
                                    first * self.steps_per_epoch)
        return {"calls": calls, "records": records,
                "t_open": calls[0].start, "t_close": calls[-1].end}

    def end_to_end(self, win: dict) -> Dict[str, float]:
        calls = win["calls"]
        span = calls[-1].end - calls[0].start
        rate = len(calls) * self.samples_per_epoch / span
        peak = device.peaks(self.devices[0].device_kind)["bf16_flops"]
        mfu = 100.0 * self.flops_per_sample() * rate / (
            len(self.devices) * peak)
        print(f"window: {len(calls)} whole train_epoch calls in {span:.4f} s, "
              f"{rate / len(self.devices):.1f} {self.sample_unit}/s/chip, "
              f"{self.flops_per_sample():.6g} FLOPs/{self.sample_unit}",
              flush=True)
        walls = sorted((c.end - c.start, c.epoch, c.start - calls[0].start)
                       for c in calls)
        print(f"epochs: wall median {walls[len(walls) // 2][0]:.4f} s, "
              "longest " + ", ".join(
                  f"{w:.4f} s (epoch {e}, {at:.1f} s in)"
                  for w, e, at in walls[:-4:-1]), flush=True)
        return {"train_mfu": mfu}

    def observations(self, win: dict) -> dict:
        return {"step_records": win["records"],
                "steps_per_call": self.steps_per_epoch}

    def attempted_failed(self, win: dict):
        return check.count_nonfinite(win["records"])

    # -- the comparison -------------------------------------------------------
    def release(self) -> None:
        """Free the program's state: the reference runs after it."""
        import gc

        self.tr = None
        gc.collect()

    def verify(self, win: dict) -> List[check.Comparison]:
        reference = self.reference = self.reference_steps(self.fed, "none")
        limits = self.cell.workload["check"]["limits"]
        comps = check.training_comparisons(self.program, reference, limits)
        if self.program.get("window_program"):
            comps += check.window_program_comparisons(
                self.program["window_program"], limits)
        if win is not None:
            calls = win["calls"]
            first, last = calls[0].out["loss"], calls[-1].out["loss"]
            comps.append(check.Comparison(
                "window_last_epoch_loss_over_first", last / first,
                limits["window_loss_ratio"],
                f"first {first:.6f} last {last:.6f}"))
        return comps
