"""The LM families end to end on the CPU at a toy size: everything behind
``benchmarks/run.py`` but the look for a chip (build, first steps, warm-up,
window, reduction, reference check, result object)."""

import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toyroot  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toyroot.make(tmp_path_factory.mktemp("bench"))


def test_toy_pieces_are_added_as_new_files_and_appended_entries(root):
    """A configuration, a traffic mix, a cell and a per-layer metric were
    added to the copy without editing a file that was there."""
    import filecmp
    import json

    same = filecmp.dircmp(os.path.join(toyroot.REPO, "benchmarks"),
                          os.path.join(root, "benchmarks"),
                          ignore=["__pycache__"])

    def walk(d):
        assert not d.diff_files and not d.left_only, (d.diff_files,
                                                      d.left_only)
        for sub in d.subdirs.values():
            walk(sub)

    walk(same)
    base = json.load(open(os.path.join(toyroot.REPO, "BENCHMARK.json")))
    toy = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for key in ("configs", "workloads", "per_layer"):
        assert toy[key][:len(base[key])] == base[key]
        assert len(toy[key]) > len(base[key])


def test_lm_trainer_cell_runs_and_agrees_with_the_reference(root):
    res = toyroot.run_toy(root, "toy-lm.train", seed=2**31 + 5)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"train_mfu", "setup_s"}
    assert res["metrics"]["train_mfu"]["value"] > 0
    assert res["device"]["count"] == 1


def test_lm_trainer_traced_run_reports_per_layer_metrics(root):
    from benchmarks.trace import reduce as tr

    seen = {}
    real = tr.reduce_file

    def fake(path, offsets_s=None):
        # the CPU has no device plane: hand the reduction made-up device
        # operations on the recorded spans' clock, so the rest of a traced
        # run (readers, breakdown, device fields) is driven for real
        _, spans = tr.read_xplane(path)
        lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
        ops = [[("fusion.1", lo, lo + 0.25 * (hi - lo)),
                ("flash_fwd", lo + 0.5 * (hi - lo), lo + 0.75 * (hi - lo))]]
        seen["spans"] = {n for n, _, _ in spans}
        return tr.summarize(ops, spans, offsets_s)

    tr.reduce_file = fake
    try:
        res = toyroot.run_toy(root, "toy-lm.train", seed=11, trace=True)
    finally:
        tr.reduce_file = real
    assert "bench:train_epoch" in seen["spans"]
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["toy_records"]["value"] >= 2          # the toy metric, found by name
    assert 0 < m["host_share.train"]["value"] < 100
    assert m["device_idle.train"]["value"] == pytest.approx(50.0, abs=1.0)
    assert "conv_share.train" not in m             # lists another cell
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root):
    """The timed path broken underneath: the trainer's compiled step hands
    back the state it was given. The loss stays plausible; the update norm
    does not."""
    from tpu_dist.engine.lm_loop import LMTrainer

    real = LMTrainer._build_steps

    def broken(self):
        real(self)
        step = self.train_step

        def unchanged(st, x, y, rng):
            import jax
            import jax.numpy as jnp

            keep = jax.tree_util.tree_map(jnp.copy, st)   # the step donates
            return keep, step(st, x, y, rng)[1]

        unchanged.lower = step.lower
        self.train_step = unchanged

    LMTrainer._build_steps = broken
    try:
        res = toyroot.run_toy(root, "toy-lm.train", seed=4)
    finally:
        LMTrainer._build_steps = real
    assert res["correct"] is False


def test_lm_training_control_int8_fails_a_limit(root):
    """The lower precision the cell's file names (the program's own int8
    matmuls) must fail at least one of the cell's numbers."""
    import jax

    from benchmarks import control
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(root, "toy-lm.train")
    got = control.read_seeds(cell, [6], {6}, jax.devices()[:1])[6]
    limits = {"loss_step0_rel_gap": "loss_rel_gap",
              "first_grad_norm_worst_leaf_gap": "grad_norm_gap",
              "first_grad_sample_median_rel_err": "grad_sample_rel_err",
              "update_norm_worst_leaf_gap": "update_norm_gap"}
    lim = cell.workload["check"]["limits"]
    assert all(got["sound"][k] <= lim[v] for k, v in limits.items())
    assert any(got["control"][k] > lim[v] for k, v in limits.items())


def test_server_cell_runs_and_agrees_with_the_reference(root):
    res = toyroot.run_toy(root, "toy-lm.serve", seed=9, seconds=3.0)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 24                   # 8 /s for 3 s
    assert set(res["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in res["metrics"].values())


def test_a_served_token_altered_where_it_is_produced_is_not_correct(root):
    """The timed path broken underneath: the decode tick's tokens are
    shifted by one before the engine stores them."""
    from tpu_dist.engine import serve

    real = serve._tick_program

    def broken(*a, **kw):
        tick = real(*a, **kw)

        def wrapped(*args):
            nxt, layers, rng = tick(*args)
            return (nxt + 1) % toyroot.TOY_LM["vocab_size"], layers, rng

        wrapped.lower = tick.lower
        return wrapped

    serve._tick_program = broken
    try:
        res = toyroot.run_toy(root, "toy-lm.serve", seed=10, seconds=2.0)
    finally:
        serve._tick_program = real
    assert res["correct"] is False


def test_serving_control_int8_fails_the_limit(root):
    import jax

    from benchmarks import control
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(root, "toy-lm.serve")
    got = control.read_seeds(cell, [12, 13], {13}, jax.devices()[:1], 2.0)
    limit = cell.workload["check"]["limits"]["served_token_gap_mean"]
    assert all(g["sound"]["served_token_logit_gap_mean"] <= limit
               for g in got.values())
    # the program's own int8 weight-only matmuls, switched on (int8 KV
    # pages alter no token of a 2-layer model): seed 13's weights went
    # into the engine that seed 12 built
    assert "control" not in got[12]
    assert got[13]["control"][
        "served_token_logit_gap_mean[quant=int8_wo]"] > limit


def test_lm_reference_agrees_with_the_repos_model_in_fp32(root):
    import jax
    import jax.numpy as jnp

    from benchmarks.families.lm_trainer import ref_name
    from benchmarks.harness.trainers import as_engine_tree
    from benchmarks.reference import lm as ref
    from tpu_dist.models.transformer import tiny_lm

    s = toyroot.TOY_LM
    model = tiny_lm(vocab_size=s["vocab_size"], num_layers=s["num_layers"],
                    d_model=s["d_model"], num_heads=s["num_heads"],
                    max_len=s["max_positions"])
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 64)),
                       jnp.int32)
    like = jax.eval_shape(lambda k: model.init({"params": k}, toks,
                                               train=False)["params"],
                          jax.random.PRNGKey(0))
    w = ref.make_weights(s, jax.random.PRNGKey(1))
    w = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
         for i, (k, v) in enumerate(sorted(w.items()))}   # no unit/zero leaf
    want = ref.forward(ref.stack_blocks(w), toks, s["num_heads"])
    got = model.apply({"params": as_engine_tree(w, like, ref_name)}, toks,
                      train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
