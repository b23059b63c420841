"""The cells' step programs, compiled for a described TPU v5e at the sizes
the benchmark runs (nothing runs; no chip time). This is how the depth cut
N of ``cerebras-gpt-1.3b-depthcut`` and the per-chip batch of the four-chip
FSDP cell (PERF.md, Open question 1) were found, and it guards them: a
later PR that makes a step outgrow the chip fails here first.

A compile that passes is not a chip run and says nothing about times.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

GIB = 2.0 ** 30
#: what one v5e chip's 16 GB leaves a program (the runtime keeps the rest)
HBM_GIB = 15.0


def _cell(name):
    from benchmarks.harness import cell as cells

    return cells.load_cell(REPO, name)


@pytest.fixture(scope="module")
def v5e():
    """The devices of a described v5e:2x2, persistent cache off around the
    module (such a compile can be written to it, not read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _total_gib(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GIB


def _lm(sizes, engine, mesh, spec=None):
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops.flash_attention import flash_attention_fn

    bound = {} if spec is None else {"mesh": mesh, "spec": spec}
    return tiny_lm(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_layers"],
        d_model=sizes["d_model"], num_heads=sizes["num_heads"],
        max_len=sizes["max_positions"], dtype=jnp.bfloat16,
        attn_fn=flash_attention_fn(block_k=engine["attn_block"],
                                   interpret=False, **bound))


def _lm_train_step(sizes, engine, devices, batch, fsdp):
    """LMTrainer's step for the cell's engine fields, lowered for shapes."""
    from tpu_dist.engine.lm_steps import make_lm_train_step
    from tpu_dist.engine.state import TrainState
    from tpu_dist.ops.optim import lm_lr_schedule, make_optimizer
    from tpu_dist.parallel.fsdp import fsdp_shardings
    from tpu_dist.parallel.mesh import make_mesh

    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    model = _lm(sizes, engine, mesh,
                P("data", None, None, None) if len(devices) > 1 else None)
    sched = lm_lr_schedule(engine["lr"], "constant", warmup_steps=0,
                           total_steps=1000, steps_per_epoch=8,
                           step_epochs=30, min_frac=0.0)
    tx = make_optimizer(engine["lr"], 0.9, engine["weight_decay"],
                        schedule=sched, kind=engine["optimizer"],
                        b1=engine["adam_b1"], b2=engine["adam_b2"],
                        eps=engine["adam_eps"])
    seq = engine["seq_len"]
    plain = _lm(sizes, engine, mesh)      # init never binds the mesh

    def make(key):
        params = plain.init({"params": key}, jnp.zeros((1, seq), jnp.int32),
                            train=False)["params"]
        return TrainState.create(params, {}, tx)

    state = jax.eval_shape(make, jax.random.PRNGKey(0))
    repl = NamedSharding(mesh, P())
    if fsdp:
        shard = state.replace(
            step=repl, params=fsdp_shardings(mesh, state.params, "data", 1024),
            opt_state=fsdp_shardings(mesh, state.opt_state, "data", 1024))
    else:
        shard = jax.tree_util.tree_map(lambda _: repl, state)
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, shard)
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    step = make_lm_train_step(model, tx, mesh)
    return step.lower(state, x, x, rng).compile()


def test_depth_cut_train_step_fits_one_chip(v5e):
    cell = _cell("cerebras-gpt-1.3b-depthcut.train")
    e = cell.workload["engine"]
    compiled = _lm_train_step(cell.config, e, v5e[:1], e["batch_size"], False)
    assert "tpu_custom_call" in compiled.as_text()
    total = _total_gib(compiled)
    stated = cell.config["depth_cut"]["memory_analysis_gib"][
        str(cell.config["num_layers"])]
    assert total == pytest.approx(stated, abs=0.3), total
    # room for one more float32 copy of the parameters: the benchmark
    # hands its weights over while the engine still holds its own
    params_gib = stated * 0  # computed below from the program's arguments
    ma = compiled.memory_analysis()
    params_gib = ma.argument_size_in_bytes / 3 / GIB
    assert total + params_gib <= HBM_GIB, (total, params_gib)


def test_serving_tick_of_the_whole_model_fits_one_chip(v5e):
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.engine.serve import _tick_program
    from tpu_dist.parallel.mesh import make_mesh

    cell = _cell("cerebras-gpt-1.3b.serve-chat")
    s, srv = cell.config, cell.workload["serve"]
    mesh = make_mesh((1,), ("data",), devices=v5e[:1])
    chip = NamedSharding(mesh, P())
    model = _lm(s, cell.workload["engine"], mesh)
    n = srv["max_slots"]
    pages_per_seq = srv["max_len"] // srv["page_size"]

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    params = shapes(jax.eval_shape(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32),
            train=False)["params"]), jax.random.PRNGKey(0)))
    layers = shapes(jax.eval_shape(lambda: PagedKVPool(
        s["num_layers"], srv["num_pages"], srv["page_size"], s["num_heads"],
        s["head_dim"], dtype=jnp.bfloat16).layers()))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    tick = _tick_program(model, 0.0, 0, 0.0, None)
    compiled = tick.lower(params, layers, i32(n, pages_per_seq), i32(n),
                          i32(n), rng).compile()
    ma = compiled.memory_analysis()
    weights = ma.argument_size_in_bytes / GIB
    assert 8.5 < weights < 9.5        # 2.84 GB of weights + 6.44 GB of pages
    assert _total_gib(compiled) <= HBM_GIB, _total_gib(compiled)


def test_fsdp_step_of_the_whole_model_fits_four_chips(v5e):
    """Open question 1's cell: 24 layers, ``fsdp=True`` on ``data=4``, two
    2048-token sequences a chip."""
    cell = _cell("cerebras-gpt-1.3b-depthcut.train")
    whole = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "cerebras-gpt-1.3b.json")))
    compiled = _lm_train_step(whole, cell.workload["engine"], v5e, 8, True)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "reduce-scatter" in text
    assert _total_gib(compiled) <= HBM_GIB, _total_gib(compiled)
