"""Family ``image_trainer``: a torchvision ResNet trained by
``tpu_dist.engine.Trainer`` through ``train_epoch`` on the device-resident
windowed path (K optimizer steps per dispatch).

The cell's workload file gives the engine's fields (``TrainConfig`` names),
the traffic file the data set's size; the configuration file the sizes.
"""

from __future__ import annotations

import os
import sys
from typing import List

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmarks.harness import check  # noqa: E402
from benchmarks.harness.trainers import TrainerFamily, engine_seed  # noqa: E402
from benchmarks.reference import resnet as ref  # noqa: E402

_BLOCK = {"Conv_0": "conv1", "BatchNorm_0": "bn1", "Conv_1": "conv2",
          "BatchNorm_1": "bn2", "Conv_2": "conv3", "BatchNorm_2": "bn3",
          "downsample_conv": "down", "downsample_bn": "downbn"}
_PARAM = {"kernel": "w", "scale": "g", "bias": "b"}


def ref_name(names: tuple) -> str:
    """('layer2_block0', 'Conv_1', 'kernel') -> 'layer2.0.conv2.w'."""
    if len(names) == 2:
        return f"{names[0]}.{_PARAM[names[1]]}"
    stage, block = names[0].split("_block")
    return f"{stage}.{block}.{_BLOCK[names[1]]}.{_PARAM[names[2]]}"


def flops_per_image(sizes: dict) -> float:
    """Model FLOPs one trained image requires: two per multiply-add of
    every convolution and of the classifier in the forward pass, times
    three for forward and backward. BatchNorm, ReLU, pooling and the
    optimizer are not counted (they are not matrix work)."""
    base, classes = sizes["base_width"], sizes["num_classes"]

    def conv(side, k, cin, cout):
        return 2.0 * side * side * k * k * cin * cout

    side = -(-sizes["image_size"] // 2)            # 7x7 stride 2
    fwd = conv(side, 7, sizes["image_channels"], base)
    side = -(-side // 2)                           # 3x3 max-pool stride 2
    cin = base
    for s, blocks in enumerate(sizes["stage_sizes"]):
        width = base * 2 ** s
        cout = width * sizes["expansion"]
        for j in range(blocks):
            out = -(-side // 2) if s > 0 and j == 0 else side
            fwd += conv(side, 1, cin, width) + conv(out, 3, width, width) \
                + conv(out, 1, width, cout)
            if j == 0:
                fwd += conv(out, 1, cin, cout)     # the projection shortcut
            side, cin = out, cout
    fwd += 2.0 * cin * classes
    return 3.0 * fwd


class Family(TrainerFamily):
    sample_unit = "img"

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.batch = int(self.engine["batch_size"])
        self.k = int(self.engine["steps_per_dispatch"])
        self.samples_per_epoch = int(self.cell.traffic["train_images"])
        self.steps_per_epoch = self.samples_per_epoch // self.batch

    def flops_per_sample(self) -> float:
        return flops_per_image(self.sizes)

    def make_trainer(self):
        from tpu_dist.configs import TrainConfig
        from tpu_dist.engine import Trainer

        cfg = TrainConfig(
            arch=self.sizes["arch"], dataset=self.cell.traffic["dataset"],
            synth_train_size=self.samples_per_epoch,
            synth_val_size=self.batch, epochs=10**6, print_freq=10**6,
            seed=engine_seed(self.seed), variant="jit",
            checkpoint_dir=os.path.join(self.workdir, "ck"),
            ledger_path=os.path.join(self.workdir, "run.jsonl"),
            mesh_shape=(len(self.devices),), **self.engine)
        tr = Trainer(cfg, mesh=self.mesh())
        if not tr.device_data or tr.k != self.k \
                or tr.steps_per_epoch != self.steps_per_epoch:
            raise ValueError("the windowed device-resident path was not "
                             f"taken (k={tr.k}, device_data={tr.device_data},"
                             f" steps={tr.steps_per_epoch})")
        return tr

    def make_weights(self, key, dtype):
        return ref.make_weights(self.sizes, key, dtype)

    ref_name = staticmethod(ref_name)

    def timed_program(self):
        """The K-step window program the window dispatches, compiled for
        the arguments it runs with (a cache hit)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        tr = self.tr
        idx = jax.ShapeDtypeStruct(
            (self.k, self.batch), jnp.int32,
            sharding=NamedSharding(tr.mesh, P(None, "data")))
        return tr.window_step.lower(tr.state, *tr._train_data_dev, idx,
                                    tr.rng).compile()

    def single_steps(self) -> List[dict]:
        """The last batch of each of the first three epochs: resumed
        mid-epoch at ``steps_per_epoch - 1``, ``train_epoch`` dispatches
        one window of one step (the engine's own shape for a short last
        window), so a single optimizer step can be read back."""
        return [{"epoch": self.next_epoch + e,
                 "skip": self.steps_per_epoch - 1} for e in range(3)]

    def warm(self) -> None:
        """The warm epoch ties the TIMED program to the single-step one
        that the reference was compared with. From the state after the
        first steps, ``train_epoch`` dispatches the K-step window program
        over one whole epoch (the window's own call, feed and compiled
        program); from a copy of that state the engine's own one-step
        window program is driven over the same rows, one dispatch a step.
        Kept: the epoch's mean loss and the parameters' change over the K
        steps, both ways. The K-step dispatch's state goes on into the
        window."""
        faults = tuple(getattr(self, "window_faults", ()))   # control.py's
        before = [self._copy(self.tr.state) for _ in range(1 + len(faults))]
        p0 = self._copy(self.tr.state.params)
        epoch = self.next_epoch
        super().warm()
        self.program["window_program"] = self.window_program_readings(
            epoch, before.pop(0), p0)
        self.fault_readings = {
            f: self.window_program_readings(epoch, before.pop(0), p0, f)
            for f in faults}

    def reseed(self, seed: int, pristine) -> None:
        """Other weights in the same engine object (``control.py`` reads a
        dozen seeds in one process: the data set and the compiled programs
        stay, the epochs go on so every seed is fed other rows)."""
        self.seed = int(seed)
        params = self._as_engine_tree(self._weights(), pristine.params)
        self.tr.state = self._copy(pristine).replace(params=params)

    def _copy(self, tree):
        import jax
        import jax.numpy as jnp

        return jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(tree)

    def _change(self, params, p0) -> dict:
        import jax

        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x - y, a, b))(params, p0)
        return {"norms": self._leaf_norms(delta),
                "samples": self._leaf_samples(delta)}

    def single_step_chain(self, epoch: int, state, fault: str = "none"):
        """``state`` driven over ``epoch``'s rows by the engine's one-step
        window program, one dispatch a step (``state`` is donated).
        Returns (state after, each step's loss). ``fault`` is the control's
        (``benchmarks/control.py``): ``one_step_dropped`` hands the state
        of the middle step back unchanged, ``half_batch`` feeds every step
        the first half of its rows twice."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_dist.data import assemble_global

        tr = self.tr
        rows, _ = tr._epoch_indices(tr.train_ds, True, epoch)
        if fault == "half_batch":
            half = rows.shape[1] // 2
            rows = np.concatenate([rows[:, :half], rows[:, :half]], axis=1)
        elif fault not in ("none", "one_step_dropped"):
            raise ValueError(f"unknown fault {fault!r}")
        sharding = NamedSharding(tr.mesh, P(None, "data"))
        sums = []
        for j in range(len(rows)):
            keep = self._copy(state) if (
                fault == "one_step_dropped" and j == len(rows) // 2) else None
            state, m = tr.window_step(
                state, *tr._train_data_dev,
                assemble_global(sharding, np.ascontiguousarray(rows[j:j + 1])),
                tr.rng)
            sums.append(m)
            if keep is not None:
                state = keep
        losses = [float(m["loss_sum"]) / float(m["count"])
                  for m in jax.device_get(sums)]
        return state, losses

    def window_program_readings(self, epoch: int, before, p0,
                                fault: str = "none") -> dict:
        after, losses = self.single_step_chain(epoch, before, fault)
        single = self._change(after.params, p0)
        del after
        return check.window_program_readings(
            float(self.warm_out["loss"]), losses,
            self._change(self.tr.state.params, p0), single)

    def fed_rows(self, epoch: int, batch: int):
        tr = self.tr
        idx, _ = tr._epoch_indices(tr.train_ds, True, epoch)
        rows = idx[batch]
        if len(set(rows.tolist())) != len(rows):
            raise ValueError("a fed batch holds the same row twice")
        return (np.asarray(tr.train_ds.images[rows]),
                np.asarray(tr.train_ds.labels[rows]),
                np.asarray(tr.train_ds.mean), np.asarray(tr.train_ds.std))

    def first_grad(self, opt_state, params0):
        """torch-style SGD: after one step the momentum buffer is
        g + wd p0, so g = buffer - wd p0."""
        import jax

        trace = next(s.trace for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda s: hasattr(s, "trace"))
            if hasattr(s, "trace"))
        wd = float(self.engine.get("weight_decay", 1e-4))
        return jax.jit(lambda t, p: jax.tree_util.tree_map(
            lambda a, b: a - wd * b, t, p))(trace, params0)

    def _reference_programs(self, quant: str):
        """The reference's jitted pieces, made once for each precision (a
        process that reads many seeds compiles them once)."""
        import jax
        import jax.numpy as jnp

        made = self.__dict__.setdefault("_ref_programs", {})
        if quant not in made:
            e = self.engine
            made[quant] = (
                jax.jit(jax.value_and_grad(
                    lambda w, x, y: ref.loss_fn(w, x, y, quant))),
                jax.jit(lambda w, b, g: ref.sgd_step(
                    w, b, g, lr=float(e.get("lr", 0.1)),
                    momentum=float(e.get("momentum", 0.9)),
                    wd=float(e.get("weight_decay", 1e-4)))),
                jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                                   for k, v in t.items()}))
        return made[quant]

    def reference_steps(self, fed, quant: str) -> dict:
        import jax
        import jax.numpy as jnp

        grad, step, norms = self._reference_programs(quant)
        w0 = self._weights()
        w, buf = w0, jax.tree_util.tree_map(jnp.zeros_like, w0)
        out = {"losses": []}
        for t, (images, labels, mean, std) in enumerate(fed, start=1):
            x = ref.normalize(jnp.asarray(images), mean, std)
            loss, g = grad(w, x, jnp.asarray(labels))
            out["losses"].append(float(loss))
            if t == 1:
                out["grad_norms"] = {k: float(v) for k, v in
                                     jax.device_get(norms(g)).items()}
                out["grad_samples"] = {
                    k: np.asarray(check.leaf_sample(v), np.float32)
                    for k, v in g.items()}
            w, buf = step(w, buf, g)
        delta = {k: w[k] - w0[k] for k in w}
        out["update_norms"] = {k: float(v) for k, v in
                               jax.device_get(norms(delta)).items()}
        return out
