"""Family ``lm_trainer``: a GPT-2-shaped configuration trained by
``tpu_dist.engine.lm_loop.LMTrainer`` through ``train_epoch``.

The cell's workload file gives the engine's fields (``LMConfig`` names), the
traffic file the steps of an epoch; the configuration file gives the sizes.
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
from benchmarks.reference import lm as ref  # noqa: E402

#: the engine's (module, parameter) -> the reference's leaf name
_LEAF = {("qkv", "kernel"): "wqkv", ("proj", "kernel"): "wo",
         ("mlp_in", "kernel"): "w1", ("mlp_in", "bias"): "b1",
         ("mlp_out", "kernel"): "w2", ("mlp_out", "bias"): "b2",
         ("ln1", "scale"): "ln1_g", ("ln1", "bias"): "ln1_b",
         ("ln2", "scale"): "ln2_g", ("ln2", "bias"): "ln2_b"}
_TOP = {("tok_emb", "embedding"): "tok_emb",
        ("pos_emb", "embedding"): "pos_emb", ("ln_f", "scale"): "lnf_g",
        ("ln_f", "bias"): "lnf_b", ("lm_head", "kernel"): "head"}


def ref_name(names: tuple) -> str:
    """('block3', 'qkv', 'kernel') -> 'block3.wqkv'."""
    if names in _TOP:
        return _TOP[names]
    return f"{names[0]}.{_LEAF[names[1:]]}"


def flops_per_token(sizes: dict, seq_len: int) -> float:
    """Model FLOPs one trained token requires, forward and backward,
    nothing recomputed: 6 per matrix parameter outside the two embedding
    tables (the untied head is a matrix) plus causal attention's
    6 * layers * L * d (QK^T and PV over half the square, times three).
    Copied from ``tpu_dist/utils/mfu.py:lm_flops_per_token``."""
    d, layers = sizes["d_model"], sizes["num_layers"]
    matrices = layers * (4 * d * d + 2 * d * sizes["mlp_dim"]) \
        + d * sizes["vocab_size"]
    return 6.0 * matrices + 6.0 * layers * seq_len * d


class Family(TrainerFamily):
    sample_unit = "tokens"

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.seq_len = int(self.engine.get("seq_len",
                                           self.sizes["max_positions"]))
        self.batch = int(self.engine["batch_size"])
        self.steps_per_epoch = int(self.cell.traffic["steps_per_epoch"])
        self.samples_per_epoch = (self.steps_per_epoch * self.batch
                                  * self.seq_len)

    def flops_per_sample(self) -> float:
        return flops_per_token(self.sizes, self.seq_len)

    def make_trainer(self):
        from tpu_dist.configs import LMConfig
        from tpu_dist.engine.lm_loop import LMTrainer

        s, l = self.sizes, self.seq_len
        rows = self.steps_per_epoch * self.batch
        # the held-out tail is one row: never read, the loader demands it
        tokens = rows * l + 1 + (l + 1)
        fields = dict(self.engine)
        fields.pop("seq_len", None)
        cfg = LMConfig(
            num_layers=s["num_layers"], d_model=s["d_model"],
            num_heads=s["num_heads"], vocab_size=s["vocab_size"], seq_len=l,
            synth_tokens=tokens, val_frac=0.0, epochs=10**6,
            print_freq=10**6, seed=engine_seed(self.seed),
            ledger_path=os.path.join(self.workdir, "run.jsonl"),
            mesh_shape=(len(self.devices),), **fields)
        if s["mlp_dim"] != 4 * s["d_model"] or \
                s["head_dim"] * s["num_heads"] != s["d_model"]:
            raise ValueError("the repo's block fixes mlp_dim = 4 d_model and "
                             "head_dim = d_model / num_heads")
        tr = LMTrainer(cfg, mesh=self.mesh())
        if tr.steps_per_epoch != self.steps_per_epoch:
            raise ValueError(f"epoch of {tr.steps_per_epoch} steps, the mix "
                             f"states {self.steps_per_epoch}")
        return tr

    def make_weights(self, key, dtype):
        return ref.make_weights(self.sizes, key, dtype)

    ref_name = staticmethod(ref_name)

    def timed_program(self):
        """The train step the window dispatches, compiled for the
        arguments it runs with (a cache hit)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        tr = self.tr
        x = jax.ShapeDtypeStruct(
            (self.batch, self.seq_len), jnp.int32,
            sharding=NamedSharding(tr.mesh, tr.data_spec))
        return tr.train_step.lower(tr.state, x, x, tr.rng).compile()

    def single_steps(self) -> List[dict]:
        return [{"epoch": 0, "skip": i, "max_steps": i + 1} for i in range(3)]

    def fed_rows(self, epoch: int, batch: int):
        tr = self.tr
        idx, _ = tr._epoch_indices(tr.train_ds, True, epoch)
        rows = tr.train_ds.get_rows(idx[batch])
        if len({r.tobytes() for r in rows}) != len(rows):
            raise ValueError("a fed batch holds the same row twice")
        return np.asarray(rows[:, :-1]), np.asarray(rows[:, 1:])

    def first_grad(self, opt_state, params0):
        """AdamW's first moment after one step is (1 - b1) g."""
        import jax

        mu = next(s.mu for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu"))
        scale = 1.0 / (1.0 - float(self.engine.get("adam_b1", 0.9)))
        return jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: x * scale, t))(mu)

    def reference_steps(self, fed, quant: str = "none") -> dict:
        """The reference's own three AdamW steps on the rows the program
        was fed. One sequence at a time inside one program (float32
        activations of a whole batch would not fit beside the float32
        optimizer state), buffers handed on in place."""
        import jax
        import jax.numpy as jnp

        if quant != "none":
            raise ValueError("the LM cells' control is the program's own "
                             "quant, not a lower-precision reference")
        e, heads = self.engine, self.sizes["num_heads"]

        def batch_loss(w, x, y):
            row = jax.checkpoint(lambda xy: ref.loss_fn(
                w, xy[0][None], xy[1][None], heads))
            return jnp.mean(jax.lax.map(row, (x, y)))

        grad = jax.jit(jax.value_and_grad(batch_loss))
        step = jax.jit(lambda w, mu, nu, g, t: ref.adamw_step(
            w, mu, nu, g, t, lr=float(e["lr"]),
            b1=float(e.get("adam_b1", 0.9)), b2=float(e.get("adam_b2", 0.95)),
            eps=float(e.get("adam_eps", 1e-8)),
            wd=float(e.get("weight_decay", 0.0))),
            static_argnums=4, donate_argnums=(0, 1, 2))
        stack = jax.jit(ref.stack_blocks)
        norms = jax.jit(ref.leaf_norms)
        samples = jax.jit(lambda t: ref.leaf_samples(t, check.leaf_sample))
        host = lambda t: {k: float(v) for k, v in jax.device_get(t).items()}
        w = stack(self._weights())
        mu = jax.tree_util.tree_map(jnp.zeros_like, w)
        nu = jax.tree_util.tree_map(jnp.zeros_like, w)
        out = {"losses": []}
        for t, (x, y) in enumerate(fed, start=1):
            loss, g = grad(w, jnp.asarray(x), jnp.asarray(y))
            out["losses"].append(float(loss))
            if t == 1:
                out["grad_norms"] = host(norms(g))
                out["grad_samples"] = {
                    k: np.asarray(v, np.float32)
                    for k, v in jax.device_get(samples(g)).items()}
            w, mu, nu = step(w, mu, nu, g, t)
            del g
        del mu, nu
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b))(w, stack(self._weights()))
        out["update_norms"] = host(norms(delta))
        return out
