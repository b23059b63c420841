"""The ``phi-4-mini-flash-reasoning`` configuration and its cell: the files
load and every name resolves; the configuration's keys are the catalog's;
the layer pattern and the parameter count are the published model's, by
shapes alone; the family (``phi4flash_lm_server``) serves a toy
configuration end to end on the CPU with ``correct`` true, and false with
one cross layer's read offset by a page; the new per-layer readers read a
traced toy run; and the cell's tick and prefill programs compile for a
described v5e at the published widths inside one chip's memory (nothing
runs; no chip time).
"""

import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toyroot  # noqa: E402

REPO = toyroot.REPO
sys.path.insert(0, REPO)
CONFIG = "phi-4-mini-flash-reasoning"
CELL = CONFIG + ".serve-reason"
GIB = 2.0 ** 30
HBM_GIB = 15.0

TOY = dict(
    family="phi4flash_lm", hidden_size=64, num_hidden_layers=12,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    intermediate_size=128, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mb_per_layer=2, sliding_window=8, layer_norm_eps=1e-5,
    vocab_size=512, max_position_embeddings=64,
    # 0.02 * sqrt(2560 / 64): the matrices move a logit as the published
    # width's do, so a wrong read moves the served tokens
    init_std=0.125)
NEW_METRICS = ("shared_kv_read_share.serve", "window_read_share.serve",
               "gmu_share.serve", "prefill_cross_rows.serve",
               "shared_kv_roofline.serve", "window_read_roofline.serve")
SHARED_METRICS = ("tick_host_ms.serve", "prefill_stall_ms.serve",
                  "token_gap_p99_ms.serve", "tick_ahead_share.serve",
                  "paged_read_share.serve", "mamba_share.serve",
                  "ssm_step_share.serve", "state_slots.serve",
                  "scan_share.serve", "scan_roofline.serve")
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def _config():
    return json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                       CONFIG + ".json")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``toyroot``'s checkout plus a toy configuration of this family, a toy
    long-answer mix and their cell: new files and appended entries again."""
    root = toyroot.make(tmp_path_factory.mktemp("bench"))
    b = os.path.join(root, "benchmarks")
    toyroot._dump(f"{b}/configs/toy-phi4flash.json",
                  dict(TOY, source_keys=_config()["source_keys"]))
    toyroot._dump(f"{b}/traffic/toy-reason.json", dict(
        kind="open_loop", rate_per_s=4.0, preroll_s=0.5,
        prompt=dict(median=12, sigma=0.5, min=4, max=30),
        answer=dict(median=16, sigma=0.4, min=8, max=30)))
    # float32 at toy size: a served token is the reference's best but for
    # near-ties (logits agree to ~1e-4); a wrong read is off by 1e-2..1
    toyroot._dump(f"{b}/workloads/toy-phi4flash.serve.json", dict(
        family="phi4flash_lm_server", trace_seconds=2,
        engine=dict(precision="fp32", attn="full", attn_block=64),
        serve=dict(max_slots=4, page_size=8, num_pages=64, max_len=64),
        control=dict(serve=[dict(quant="int8_wo")]),
        check=dict(sample_requests=8, limits=dict(
            served_token_gap_max=2e-3, served_token_gap_mean=1e-4))))
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["configs"].append(dict(
        name="toy-phi4flash", source="toy", reduced=[], why="toy",
        file="benchmarks/configs/toy-phi4flash.json"))
    spec["workloads"].append(dict(
        name="toy-phi4flash.serve", config="toy-phi4flash",
        traffic="toy-reason", chips=1, why="toy"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-phi4flash.serve")
    toyroot._dump(path, spec)
    return root


def test_cell_files_load_and_every_name_resolves():
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(REPO, CELL)
    assert cell.chips == 1 and cell.family == "phi4flash_lm_server"
    assert {m["name"] for m in cell.end_to_end} == {"gap_p95_ms", "setup_s"}
    read = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= read
    assert {"tick_ms", "device_idle.serve", "compile_s",
            "window_compiles"} <= read
    assert "attn_read_share.serve" not in read
    for m in cell.per_layer:
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", m["name"] + ".py")), m["name"]
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:          # new entries list this cell alone
            assert m["workloads"] == [CELL] and m["moves"] == "gap_p95_ms"
    assert spec["workloads"][-1]["name"] == CELL      # appended, not inserted
    assert spec["configs"][-1]["name"] == CONFIG
    assert cell.workload["serve"] == dict(max_slots=64, page_size=16,
                                          num_pages=8192, max_len=4096)
    mix = cell.traffic
    assert mix["kind"] == "open_loop" and "burst" not in mix
    assert (mix["prompt"]["median"], mix["prompt"]["sigma"],
            mix["prompt"]["min"], mix["prompt"]["max"]) == (256, 0.8, 32, 1536)
    assert (mix["answer"]["median"], mix["answer"]["sigma"],
            mix["answer"]["min"], mix["answer"]["max"]) == (512, 0.6, 128, 2048)
    assert mix["preroll_s"] == 10 and mix["drain_limit_s"] == 120


def test_configuration_states_every_published_key_unchanged():
    """Every key of the catalog row's ``config`` under its own name, nothing
    reduced, every assumed size under ``assumed`` with its origin."""
    cfg = _config()
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == []
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/"
                               "config.json")
    assumed = {"head_dim": 64, "mamba_d_state": 16, "mamba_d_conv": 4,
               "mamba_expand": 2, "mamba_dt_rank": 160,
               "mamba_conv_bias": True, "mamba_proj_bias": False}
    for key, value in assumed.items():
        assert cfg[key] == value and key not in PUBLISHED
        assert "convention" in cfg["assumed"][key]
    for key in ("attention_bias", "positions", "layer_order",
                "differential_attention", "window_edge", "weights"):
        assert key in cfg["assumed"]
    assert "7.7 GB" in cfg["deployment"]


def test_every_seed_draws_the_same_lengths_and_gaps_in_another_order():
    from benchmarks.harness import cell as cells
    from benchmarks.harness import traffic

    mix = cells.load_cell(REPO, CELL).traffic
    a = traffic.open_loop_schedule(mix, 2**31 + 7, 45.0, 200064, 4096)
    b = traffic.open_loop_schedule(mix, 11, 45.0, 200064, 4096)
    for field in ("prompt_len", "answer_len"):
        x, y = getattr(a, field), getattr(b, field)
        assert not np.array_equal(x, y)
        for part in (a.in_window, ~a.in_window):
            assert np.array_equal(np.sort(x[part]), np.sort(y[part]))
    assert not np.allclose(a.due, b.due)
    assert np.allclose(np.sort(np.diff(a.due[a.in_window])),
                       np.sort(np.diff(b.due[b.in_window])), atol=1e-9) \
        or len(a.due) == len(b.due)
    assert (a.prompt_len + a.answer_len <= 4096).all()
    assert a.answer_len.max() == 2048 and a.answer_len.min() >= 128


def test_layer_pattern_and_parameter_count_are_the_published_models():
    """9 / 8 / 1 / 7 / 7 of the five kinds and 3.85 B parameters, by shapes
    alone (``jax.eval_shape``: nothing is allocated)."""
    from benchmarks.families.phi4flash_lm_server import model_fields
    from benchmarks.reference import phi4flash as ref
    from tpu_dist.models.phi4flash import Phi4FlashLM

    cfg = _config()
    model = Phi4FlashLM(**model_fields(cfg), dtype=jnp.bfloat16)
    kinds = model.layer_types
    assert [sum(t == k for t in kinds)
            for k in ("mamba", "window", "full", "cross", "gmu")] \
        == [9, 8, 1, 7, 7]
    assert ref.layer_kinds(cfg) == kinds
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9, 2) == 3.85, n
    assert n == sum(int(np.prod(s)) for s in ref.weight_shapes(cfg).values())
    # what a sequence costs: 5 KB a token in ONE layer, 8 rings of 528 rows
    layout = model.cache_layout()
    assert [k for k, *_ in layout].count("pages") == 1
    assert layout[17] == ("pages", 10, 128, 4, "rows")
    assert layout[1] == ("window", 10, 128, 4, 512)
    assert layout[19] == ("shared", 17) and layout[18] == ("slot_state", {})


def test_toy_cell_serves_and_agrees_with_the_reference(root):
    res = toyroot.run_toy(root, "toy-phi4flash.serve", seed=2**31 + 9,
                          seconds=3.0)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in res["metrics"].values())


def test_a_cross_layers_read_offset_by_a_page_is_not_correct(
        root, monkeypatch):
    """The timed path broken underneath: ONE cross layer (the last) reads
    the shared layer through a block table rolled by a page."""
    import tpu_dist.models.phi4flash as m
    from tpu_dist.engine import serve

    real = m.DiffAttention.__call__

    def last_cross_off_by_a_page(self, h, paged, paged_prefill, shared):
        if self.kind == "cross" and self.layer == 11 and paged is not None:
            paged = {**paged, "block_tables": jnp.roll(
                paged["block_tables"], 1, axis=1)}
        return real(self, h, paged, paged_prefill, shared)

    monkeypatch.setattr(m.DiffAttention, "__call__", last_cross_off_by_a_page)
    programs = (serve._prefill_program, serve._tick_program)
    for p in programs:
        p.cache_clear()                      # traced sound a test ago
    try:
        res = toyroot.run_toy(root, "toy-phi4flash.serve", seed=10,
                              seconds=2.0)
    finally:
        for p in programs:
            p.cache_clear()
    assert res["failed"] == 0 and res["correct"] is False


def test_traced_toy_run_reads_the_new_per_layer_metrics(root):
    from benchmarks.trace import reduce as tr

    real = tr.reduce_file

    def fake(path, offsets_s=None):
        # the CPU has no device plane: made-up device operations on the
        # recorded spans' clock, named as the chip names the two call sites
        # of the grouped read's custom call and the scan kernel's
        _, spans = tr.read_xplane(path)
        lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
        q = (hi - lo) / 8.0
        call = ('%{name}.3 = f32[4,4,128]{{2,1,0}} custom-call(%a, %b), '
                'custom_call_target="tpu_custom_call"')
        ops = [[("%fusion.1 = f32[4,512]{1,0} fusion(%p)", lo, lo + q),
                (call.format(name="shared_kv_read"), lo + 2 * q, lo + 3 * q),
                (call.format(name="window_read"), lo + 4 * q, lo + 5 * q),
                (call.format(name="selective_scan"), lo + 6 * q, lo + 7 * q)]]
        return tr.summarize(ops, spans, offsets_s)

    tr.reduce_file = fake
    try:
        res = toyroot.run_toy(root, "toy-phi4flash.serve", seed=12,
                              trace=True)
    finally:
        tr.reduce_file = real
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["prefill_cross_rows.serve"]["value"] == 1.0
    assert 0 < m["state_slots.serve"]["value"] <= 4
    # bytes of the toy reads over a made-up quarter second: tiny shares,
    # but read, finite and under 100
    for name in ("shared_kv_roofline.serve", "window_read_roofline.serve",
                 "scan_roofline.serve"):
        assert 0 < m[name]["value"] < 100, name
    assert 0 < m["scan_share.serve"]["value"] < 100
    assert m["tick_ms"]["value"] > 0
    assert "attn_read_share.serve" not in m


def test_new_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives them: no ``cross_rows``, ``live_tokens``
    or ``window_tokens`` attribute, no scope: each returns None and does not
    raise."""
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(REPO, CELL)
    for name in NEW_METRICS:
        mod = cells.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_"))
        assert mod.read({"cell": cell}) is None
        assert mod.read({"cell": cell, "engine_steps": [], "trace": None,
                         "hlo_text": None}) is None


def test_read_kernel_cost_is_what_the_shapes_say():
    from benchmarks.kernels import paged_grouped_read as k

    cost = k.read(700 * 50, 20, 64)           # 50 slots at 700 live tokens
    assert cost["bytes"] == 700 * 50 * 5120
    floor = k.least_seconds(cost, {"hbm_bytes_per_s": 819e9})
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(cost["bytes"] / 819e9)
    assert k.SHARED_CALL.search(
        '%shared_kv_read.7 = f32[64,40,128]{2,1,0} custom-call(%a)')
    assert not k.SHARED_CALL.search(
        '%window_read.7 = f32[64,40,128]{2,1,0} custom-call(%a)')
    assert k.WINDOW_CALL.search(
        '%window_read.7 = f32[64,40,128]{2,1,0} custom-call(%a)')


# ---------------------------------------------------- compiled for a v5e

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


@pytest.fixture(scope="module")
def published(v5e):
    """The cell's model, its parameters' and its pool's shapes on one
    described chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families.phi4flash_lm_server import model_fields
    from benchmarks.harness import cell as cells
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.models.phi4flash import Phi4FlashLM
    from tpu_dist.ops.flash_attention import flash_attention_fn
    from tpu_dist.parallel.mesh import make_mesh

    cell = cells.load_cell(REPO, CELL)
    srv = cell.workload["serve"]
    chip = NamedSharding(make_mesh((1,), ("data",), devices=v5e[:1]), P())
    model = Phi4FlashLM(**model_fields(cell.config), dtype=jnp.bfloat16,
                        attn_fn=flash_attention_fn(
                            block_k=cell.workload["engine"]["attn_block"],
                            interpret=False))
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    params = shapes(jax.eval_shape(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0)))
    layers = shapes(jax.eval_shape(lambda: PagedKVPool(
        model.cache_layout(), srv["num_pages"], srv["page_size"],
        dtype=jnp.bfloat16, max_slots=srv["max_slots"]).layers()))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    return dict(model=model, params=params, layers=layers, i32=i32, rng=rng,
                slots=srv["max_slots"],
                pages=srv["max_len"] // srv["page_size"])


def _total_gib(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GIB


def test_tick_of_the_whole_model_fits_one_chip(published, monkeypatch):
    from tpu_dist.engine.serve import _tick_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p, n = published, published["slots"]
    compiled = _tick_program(p["model"], 0.0, 0, 0.0, None).lower(
        p["params"], p["layers"], p["i32"](n, p["pages"]), p["i32"](n),
        p["i32"](n), p["rng"]).compile()
    text = compiled.as_text()
    held = compiled.memory_analysis().argument_size_in_bytes / GIB
    # 7.7 GB of weights + 0.67 GB of pages + 1.38 GB of rings + 0.2 of state
    assert 9.0 < held < 9.6, held
    assert _total_gib(compiled) <= HBM_GIB
    for scope in ("ssm_step", "mamba_mixer", "paged_read", "shared_kv_read",
                  "window_read", "diff_attn", "gmu"):
        assert scope in text, scope
    # the in-place grouped read: 8 rings, the full layer and 7 cross layers
    assert text.count("tpu_custom_call") == 16
    assert len(set(__import__("re").findall(
        r"%(shared_kv_read|window_read)[\w.]* = ", text))) == 2


def test_prefill_of_the_whole_model_fits_one_chip(published, monkeypatch):
    """The 2048 bucket (it holds the 1536-token prompts): the self-decoder's
    scans and the full layer's flash attention over the bucket, the
    cross-decoder's reads over ONE row."""
    from tpu_dist.engine.serve import _prefill_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p, bucket = published, 2048
    program = _prefill_program(p["model"], 0.0, 0, 0.0, None)
    compiled = program.lower(
        p["params"], p["layers"], p["i32"](1, p["pages"]), p["i32"](),
        p["i32"](), p["i32"](1, bucket), p["rng"], p["i32"]()).compile()
    text = compiled.as_text()
    assert program.head_rows[bucket] == 1
    assert text.count("%selective_scan.") >= 9       # one a Mamba layer
    # 9 scans, 1 flash attention (the full layer), 7 cross reads
    assert text.count("tpu_custom_call") == 9 + 1 + 7
    assert "bf16[1,2048,200064]" not in text and "f32[1,2048,200064]" not in text
    assert _total_gib(compiled) <= HBM_GIB, _total_gib(compiled)
