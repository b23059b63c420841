"""The main path's Pallas kernels, compiled for a described TPU v5e.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``): nothing runs, but
what Mosaic would refuse on the chip — a block not aligned to the tiling,
more fast memory than a kernel may use — it refuses here, on the CPU, at no
chip time. Each case is a kernel the trainers or the server really call, at
the widths they call it with (chip_smoke.py's LM: 8 layers, d1024, 8 heads
of 128, L2048, V32000, batch 8; the LM training cell's 4 x 2048 x 16 heads
of 128; also 16 heads of 64 at L16384, prefills of 256 and 1024, the
serving pool's pages, the serving cell's decode read, the hybrid LM's
selective scan at 5120 channels, and the expert cell's routed products over
the hit list, its attention layer's grouped in-place read and its Mamba-2
mixers' one-step update over the live rows, alone and in the cell's whole
tick program). Interpret-mode tests cannot see any of this.

A compile that passes is not a chip run: it says nothing about results or
times (``python chip_smoke.py`` is that proof).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """The devices of a described v5e:2x2, with the persistent compilation
    cache off around the module: such a compile can be written to the cache
    but not read back without a chip."""
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


def _flash_train(b, l, h, d):
    from tpu_dist.ops.flash_attention import flash_attention_fn

    attn = flash_attention_fn(block_k=1024, interpret=False)
    qkv = [((b, l, h, d), jnp.bfloat16)] * 3

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fwd_bwd, qkv


def _flash_prefill(l):
    """The serve prefill's attention: one padded prompt, forward only."""
    from tpu_dist.ops.flash_attention import flash_attention_fn

    attn = flash_attention_fn(block_k=1024, interpret=False)
    return (lambda q, k, v: attn(q, k, v)), [((1, l, 8, 128), jnp.bfloat16)] * 3


def _paged_int8(b, l):
    """The int8-KV decode tick over gathered pages (max_pages x 16 rows)."""
    from tpu_dist.ops.paged_attention import int8kv_paged_flash_attention_fn

    attn = int8kv_paged_flash_attention_fn(interpret=False)
    h, d = 8, 128
    return attn, [((b, 1, h, d), jnp.bfloat16),
                  ((b, l, h, d), jnp.int8), ((b, l, h), jnp.float32),
                  ((b, l, h, d), jnp.int8), ((b, l, h), jnp.float32),
                  ((b,), jnp.int32)]


def _paged_decode(b, num_pages, max_pages, h, d):
    """The decode tick's in-place read: arenas as the pool lays them out
    (16-token bf16 pages), one query a slot, the block table walked."""
    from tpu_dist.ops.paged_attention import paged_decode_attention

    arena = ((num_pages + 1, 16, h, d), jnp.bfloat16)
    return (lambda q, k, v, bt, ln: paged_decode_attention(
        q, k, v, bt, ln, interpret=False)), \
        [((b, 1, h, d), jnp.bfloat16), arena, arena,
         ((b, max_pages), jnp.int32), ((b,), jnp.int32)]


def _grouped_decode(b, num_pages, max_pages, kv, group, d):
    """The grouped in-place read of a ROWS layer (16-token bf16 pages, a
    token's KV heads side by side), one query a slot: the expert cell's
    attention layer, 32 query heads over 2 KV heads of 128."""
    from tpu_dist.ops.paged_attention import paged_grouped_decode_attention

    arena = ((num_pages + 1, 16, kv * d), jnp.bfloat16)
    return (lambda q, k, v, bt, pos: paged_grouped_decode_attention(
        q, k, v, bt, pos, kv_heads=kv, scale=d ** -0.5, interpret=False)), \
        [((b, kv * group, d), jnp.bfloat16), arena, arena,
         ((b, max_pages), jnp.int32), ((b,), jnp.int32)]


def _selective_scan(l):
    """The hybrid LM's prefill scan at the published Mamba widths: one
    prompt, 5120 channels, 16 states, bf16 activations."""
    from tpu_dist.ops.selective_scan import selective_scan

    ch, n = 5120, 16
    f32 = jnp.float32
    return (lambda u, dt, a, b, c, d, s0, ln: selective_scan(
        u, dt, a, b, c, d, s0, ln, interpret=False)), \
        [((1, l, ch), jnp.bfloat16), ((1, l, ch), f32), ((ch, n), f32),
         ((1, l, n), f32), ((1, l, n), f32), ((ch,), f32),
         ((1, n, ch), f32), ((1,), jnp.int32)]


def _hit_experts(rows):
    """The expert cell's routed products over the hit list: 128 held
    experts of 1024 -> 2688 -> 1024 in bf16, a tick's 64 rows and the
    longest prefill bucket that takes the kernel."""
    from tpu_dist.ops.routed_experts import _hit_experts as call

    held, latent, width = 128, 1024, 2688
    return (lambda u, g, ids, n, w_in, w_out: call(
        u, g, ids, n, w_in, w_out, interpret=False)), \
        [((rows, latent), jnp.bfloat16), ((rows, held), jnp.float32),
         ((held,), jnp.int32), ((1,), jnp.int32),
         ((held, latent, width), jnp.bfloat16),
         ((held, width, latent), jnp.bfloat16)]


def _ssd_step_live(slots):
    """The expert cell's one-step Mamba-2 update over the live rows: 128
    heads of 64 channels, 128 states in 8 groups, bf16 activations, the
    float32 state donated as the tick program donates it."""
    from tpu_dist.ops import ssd

    h, p, n, g = 128, 64, 128, 8
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    state = ((slots, h, p, n), f32)
    tile = ssd.head_tile(jax.ShapeDtypeStruct(*state), g)
    assert tile == 32
    return (lambda x, dt, a, b, c, d, s, live, fresh: ssd.ssd_step_live(
        x, dt, a, b, c, d, s, ssd.live_rows(live), fresh, tile,
        interpret=False)), \
        [((slots, h, p), bf16), ((slots, h), f32), ((h,), f32),
         ((slots, g, n), bf16), ((slots, g, n), bf16), ((h,), f32), state,
         ((slots,), i32), ((slots,), i32)]


def _quant_matmul(m, k, n):
    from tpu_dist.ops.pallas_quant import fused_quant_matmul

    return (lambda x, w: fused_quant_matmul(x, w, False)), \
        [((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16)]


def _adamw(shape):
    from tpu_dist.ops.pallas_adamw import fused_adamw_leaf

    f32 = (shape, jnp.float32)
    return (lambda p, g, m, v, s: fused_adamw_leaf(p, g, m, v, s,
                                                   interpret=False)), \
        [f32, f32, f32, f32, ((1, 8), jnp.float32)]


def _sgd(shape):
    from tpu_dist.ops.pallas_sgd import fused_sgd_leaf

    f32 = (shape, jnp.float32)
    return (lambda p, g, m: fused_sgd_leaf(p, g, m, 0.1, 0.9, 1e-4,
                                           interpret=False)), [f32, f32, f32]


CASES = {
    "flash_train_b8_l2048_h8_d128": lambda: _flash_train(8, 2048, 8, 128),
    # the LM training cell's own call; L 16384 is the fused backward's
    # longest resident dQ (8 MiB of VMEM beside the tiles)
    "flash_train_b4_l2048_h16_d128": lambda: _flash_train(4, 2048, 16, 128),
    "flash_train_b1_l16384_h16_d64": lambda: _flash_train(1, 16384, 16, 64),
    "flash_prefill_l1024": lambda: _flash_prefill(1024),
    "flash_prefill_l256": lambda: _flash_prefill(256),
    "paged_int8_b8_l2048": lambda: _paged_int8(8, 2048),
    "paged_int8_b32_l4096": lambda: _paged_int8(32, 4096),
    "paged_decode_b16_p128_h16_d128":
        lambda: _paged_decode(16, 2048, 128, 16, 128),
    "grouped_decode_b64_p256_kv2_g16_d128":
        lambda: _grouped_decode(64, 8192, 256, 2, 16, 128),
    "selective_scan_l1024_c5120": lambda: _selective_scan(1024),
    "selective_scan_l256_c5120": lambda: _selective_scan(256),
    "hit_experts_r64_e128_1024x2688": lambda: _hit_experts(64),
    "hit_experts_r256_e128_1024x2688": lambda: _hit_experts(256),
    "ssd_step_live_b64_h128_p64_n128": lambda: _ssd_step_live(64),
    "quant_matmul_mlp_16384x1024x4096": lambda: _quant_matmul(16384, 1024, 4096),
    "quant_matmul_decode_8x1024x4096": lambda: _quant_matmul(8, 1024, 4096),
    "quant_matmul_head_16384x1024x32000":
        lambda: _quant_matmul(16384, 1024, 32000),
    "adamw_mlp_1024x4096": lambda: _adamw((1024, 4096)),
    "adamw_embedding_32000x1024": lambda: _adamw((32000, 1024)),
    "sgd_conv_3x3x512x512": lambda: _sgd((3, 3, 512, 512)),
    "sgd_bias_10": lambda: _sgd((10,)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e):
    fn, shapes = CASES[case]()
    chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{case}: no Mosaic kernel in the compiled program"
    # the program around the kernel fits one chip's 16 GB with room to spare
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < 16 * 2**30, f"{case}: {total / 2**30:.1f} GB"


def test_flash_under_a_four_chip_gspmd_step_runs_per_shard(v5e):
    """The fault PR 21 found on the way to four chips: GSPMD cannot
    partition a Mosaic kernel, so ``--attn flash`` under dp/tp/fsdp never
    compiled for more than one chip. ``flash_attention_fn(mesh=, spec=)``
    runs the kernel per shard; bare, the compiler still refuses."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.ops.flash_attention import flash_attention_fn
    from tpu_dist.parallel.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=v5e)
    spec = P("data", None, "model", None)   # batch rows x heads
    qkv = [jax.ShapeDtypeStruct((8, 2048, 8, 128), jnp.bfloat16,
                                sharding=NamedSharding(mesh, spec))] * 3

    def step(attn):
        loss = lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv)

    bound = flash_attention_fn(block_k=1024, interpret=False, mesh=mesh,
                               spec=spec)
    assert "tpu_custom_call" in step(bound).compile().as_text()
    with pytest.raises(NotImplementedError, match="shard_map"):
        step(flash_attention_fn(block_k=1024, interpret=False)).compile()


def _cellbench():
    """The cell benchmark's own AOT helpers (``_cell``, ``_lm``,
    ``_lm_train_step``), imported by path: tests/benchmarks is no package."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "test_cellbench_aot_compile.py")
    spec = importlib.util.spec_from_file_location("cellbench_aot", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serving_tick(aot, devices, monkeypatch):
    """The serving cell's decode tick, lowered for shapes on one chip. The
    tick picks its Pallas call's mode by the process's backend (the CPU's
    here): steered to the chip's branch while the program is traced."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.engine.serve import _tick_program
    from tpu_dist.parallel.mesh import make_mesh

    cell = aot._cell("cerebras-gpt-1.3b.serve-chat")
    s, srv = cell.config, cell.workload["serve"]
    mesh = make_mesh((1,), ("data",), devices=devices[:1])
    chip = NamedSharding(mesh, P())
    model = aot._lm(s, cell.workload["engine"], mesh)
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    params = shapes(jax.eval_shape(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32),
            train=False)["params"]), jax.random.PRNGKey(0)))
    layers = shapes(jax.eval_shape(lambda: PagedKVPool(
        s["num_layers"], srv["num_pages"], srv["page_size"], s["num_heads"],
        s["head_dim"], dtype=jnp.bfloat16).layers()))
    n = srv["max_slots"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        lowered = _tick_program(model, 0.0, 0, 0.0, None).lower(
            params, layers, i32(n, srv["max_len"] // srv["page_size"]),
            i32(n), i32(n), rng)
    return lowered.compile()


def test_timed_programs_carry_the_programs_scopes_for_v5e(v5e, monkeypatch):
    """The names the traced runs' readers look for survive the TPU
    compiler's fusion: the serving tick's 24 Mosaic custom-calls (the
    in-place paged read, one a layer) carry ``paged_read`` in their
    ``op_name``s, the LM step's ``flash_attention`` (forward and backward),
    and its instructions ``loss`` and ``optimizer``."""
    import re

    aot = _cellbench()
    op_names = lambda compiled: re.findall(r'op_name="([^"]*)"',
                                           compiled.as_text())
    tick = _serving_tick(aot, v5e, monkeypatch).as_text()
    mosaic = (r'custom-call\(.*custom_call_target="tpu_custom_call"'
              r'.*op_name="([^"]*)"')
    reads = re.findall(mosaic, tick)
    assert len(reads) == 24                     # one in-place read a layer
    assert all("/paged_read/" in n for n in reads)
    # and nothing of the gathered path's: no copy of the table's 2048 pages
    assert "[2048,16,16,128]" not in tick
    cell = aot._cell("cerebras-gpt-1.3b-depthcut.train")
    e = cell.workload["engine"]
    compiled = aot._lm_train_step(cell.config, e, v5e[:1], e["batch_size"],
                                  False)
    kernels = re.findall(mosaic, compiled.as_text())
    # one forward and one fused backward kernel a layer
    assert len(kernels) == 2 * cell.config["num_layers"]
    assert all("/flash_attention/" in n for n in kernels)
    assert sum("transpose(jvp(" in n for n in kernels) == len(kernels) // 2
    step = op_names(compiled)
    for scope in ("jvp(loss)/", "transpose(jvp(loss))/", "/optimizer/"):
        assert sum(scope in n for n in step) >= 3, scope


def test_the_expert_cells_tick_updates_its_live_rows_state_in_place(
        v5e, monkeypatch):
    """The expert cell's whole tick program (its own configuration and
    engine settings) compiled for one v5e: one ``ssd_step`` Mosaic call a
    Mamba-2 layer, each taking a state ARGUMENT of the program as its operand
    and aliasing it to the result the program hands back, and no other
    instruction that passes over a ``[slots, H, P, N]`` float32 array: no
    fusion, no copy, no cast (ISSUE 45: the parent's five
    ``multiply_reduce_fusion`` over ``f32[64,128,64,128]`` read and wrote
    2.7 GB a tick whatever the slots held)."""
    import re

    from benchmarks.families.nemotron_h_lm_server import model_fields
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.engine.serve import _tick_program
    from tpu_dist.models.nemotron_h import NemotronHLM
    from tpu_dist.ops.flash_attention import flash_attention_fn

    cell = _cellbench()._cell(
        "nemotron-3-super-120b-a12b-ep4.serve-assistant")
    s, srv = cell.config, cell.workload["serve"]
    chip = SingleDeviceSharding(v5e[0])
    model = NemotronHLM(**model_fields(s), dtype=jnp.bfloat16,
                        attn_fn=flash_attention_fn(block_k=1024))
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    params = shapes(jax.eval_shape(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim > 1 else x, model.init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0)))
    n = srv["max_slots"]
    layers = shapes(jax.eval_shape(lambda: PagedKVPool(
        model.cache_layout(), srv["num_pages"], srv["page_size"],
        dtype=jnp.bfloat16, max_slots=n).layers()))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        text = _tick_program(model, 0.0, 0, 0.0, None).lower(
            params, layers, i32(n, srv["max_len"] // srv["page_size"]),
            i32(n), i32(n), rng).compile().as_text()
    mixers = s["hybrid_override_pattern"].count("M")
    state = (f"f32[{n},{s['mamba_num_heads']},{s['mamba_head_dim']},"
             f"{s['ssm_state_size']}]")
    entry = text[text.index("\nENTRY "):]
    passes = [line.strip() for line in text.splitlines()
              if re.search(r" = \(?[^=]*" + re.escape(state), line)]
    calls = [line for line in passes if "custom-call(" in line]
    assert len(calls) == mixers and all(
        line.startswith("%ssd_step") and "/ssm_step/" in line
        for line in calls)
    params_in = set()
    for line in calls:
        # the state operand is the program's own argument, aliased to the
        # call's state result
        index = int(re.search(r"\{1\}: \((\d+), \{\}\)", line).group(1))
        operand = re.findall(r"%[\w.\-]+", line[line.index("custom-call("):]
                             )[index]
        defined = re.search(
            re.escape(operand) + r" = " + re.escape(state)
            + r"\S* parameter\((\d+)\)", entry)
        assert defined, operand
        params_in.add(int(defined.group(1)))
    assert len(params_in) == mixers
    # beside the calls: the arguments themselves, the results picked out of
    # the calls' tuples and the program's own result tuple, which move
    # nothing
    others = [line for line in passes if line not in calls]
    assert len(others) == 2 * mixers + 1 and all(
        re.search(r" (parameter|get-tuple-element|tuple)\(", line)
        for line in others), others
    # and the program hands those results back under the arguments' buffers
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert all(f"({i}, {{}}, may-alias)" in aliased for i in params_in)
