"""The ``jamba2-3b`` configuration and its cell: the files load and every
name resolves; the family (``hybrid_lm_server``) serves a toy configuration
end to end on the CPU with ``correct`` true, and false with the slot-state
write broken underneath; the new per-layer readers read a traced toy run;
and the cell's tick and prefill programs compile for a described v5e at the
published widths inside one chip's memory (nothing runs; no chip time).
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
CELL = "jamba2-3b.serve-chat-burst"
GIB = 2.0 ** 30
HBM_GIB = 15.0

TOY_JAMBA = dict(
    family="hybrid_lm", hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, attn_layer_period=4, attn_layer_offset=2,
    rms_norm_eps=1e-6, vocab_size=512,
    # 0.02 * sqrt(2560 / 64): the matrices move a logit as the published
    # width's do, so a wrong state moves the served tokens
    init_std=0.125)
NEW_METRICS = ("mamba_share.serve", "ssm_step_share.serve",
               "scan_roofline.serve", "state_slots.serve",
               "scan_share.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``toyroot``'s checkout plus a toy hybrid configuration, a toy burst
    mix and their cell: new files and appended entries again."""
    root = toyroot.make(tmp_path_factory.mktemp("bench"))
    b = os.path.join(root, "benchmarks")
    real = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                       "jamba2-3b.json")))
    toyroot._dump(f"{b}/configs/toy-jamba.json",
                  dict(TOY_JAMBA, source_keys=real["source_keys"]))
    toyroot._dump(f"{b}/traffic/toy-chat-burst.json", dict(
        kind="open_loop", rate_per_s=8.0, preroll_s=0.5,
        burst=dict(share=0.5, period_s=1.0, phase_s=0.5, width_s=0.1),
        prompt=dict(median=16, sigma=0.5, min=4, max=40),
        answer=dict(median=12, sigma=0.4, min=6, max=24)))
    # float32 at toy size: a served token is the reference's best but for
    # near-ties (logits agree to ~1e-4); a broken state is off by 1e-2..1
    toyroot._dump(f"{b}/workloads/toy-jamba.serve.json", dict(
        family="hybrid_lm_server", trace_seconds=2,
        engine=dict(precision="fp32", attn="full", attn_block=64),
        serve=dict(max_slots=4, page_size=8, num_pages=64, max_len=64),
        control=dict(serve=[dict(quant="int8_wo")]),
        check=dict(sample_requests=12, limits=dict(
            served_token_gap_max=2e-3, served_token_gap_mean=1e-4))))
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["configs"].append(dict(
        name="toy-jamba", source="toy", reduced=[], why="toy",
        file="benchmarks/configs/toy-jamba.json"))
    spec["workloads"].append(dict(
        name="toy-jamba.serve", config="toy-jamba", traffic="toy-chat-burst",
        chips=1, why="toy"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-jamba.serve")
    toyroot._dump(path, spec)
    return root


def test_cell_files_load_and_every_name_resolves():
    from benchmarks.harness import cell as cells

    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench = os.path.join(REPO, "benchmarks")
    for w in spec["workloads"]:
        cell = cells.load_cell(REPO, w["name"])
        assert os.path.exists(os.path.join(
            bench, "families", cell.family + ".py"))
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(
                bench, "layer_metrics", m["name"] + ".py")), m["name"]
    cell = cells.load_cell(REPO, CELL)
    assert cell.chips == 1 and cell.family == "hybrid_lm_server"
    assert {m["name"] for m in cell.end_to_end} == {"gap_p95_ms", "setup_s"}
    read = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= read
    assert {"tick_ms", "device_idle.serve", "compile_s", "window_compiles",
            "tick_host_ms.serve", "prefill_stall_ms.serve",
            "token_gap_p99_ms.serve", "paged_read_share.serve"} <= read
    # the dead shape-matched metric stays with the cell it was made for
    assert "attn_read_share.serve" not in read
    dead = next(m for m in spec["per_layer"]
                if m["name"] == "attn_read_share.serve")
    assert dead["workloads"] == ["cerebras-gpt-1.3b.serve-chat"]
    assert cell.workload["serve"] == dict(max_slots=32, page_size=16,
                                          num_pages=4096, max_len=2048)


def test_configuration_states_every_published_key_unchanged():
    """Every key of the catalog row's ``config`` under its own name, and
    nothing reduced."""
    cfg = json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                      "jamba2-3b.json")))
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["head_dim"] == 128
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == []
    assert entry["source"] == ("https://huggingface.co/ai21labs/"
                               "AI21-Jamba2-3B/blob/main/config.json")
    for key in ("layer_order", "attention_ratio", "weights",
                "max_position_embeddings"):
        assert key in cfg["assumed"]


def test_burst_schedule_is_the_issues_pattern():
    from benchmarks.harness import burst
    from benchmarks.harness import cell as cells

    mix = dict(cells.load_cell(REPO, CELL).traffic, rate_per_s=6.0)
    a = burst.burst_schedule(mix, 2**31 + 7, 45.0, 65536, 2048)
    b = burst.burst_schedule(mix, 11, 45.0, 65536, 2048)
    for s in (a, b):
        due = np.sort(s.due[s.in_window])
        assert len(due) == 135 + 9 * 15          # R/2 * 45 + 9 bursts of 2.5 R
        for k in range(9):                       # 15 in each half second
            lo = 2.5 + 5.0 * k
            assert ((due >= lo) & (due < lo + 0.5)).sum() >= 15
        assert (s.prompt_len >= 32).all() and (s.prompt_len <= 1536).all()
        assert (s.prompt_len + s.answer_len <= 2048).all()
        assert (~s.in_window).sum() == 15 + 15   # the pre-roll: same pattern
    # every seed the same work in another order: the bursts' times are the
    # mix's, the base's gaps and both length sets are one multiset that
    # the seed permutes (traffic.py's way)
    for field in ("prompt_len", "answer_len"):
        x, y = getattr(a, field), getattr(b, field)
        assert not np.array_equal(x, y), field
        for part in (a.in_window, ~a.in_window):
            assert np.array_equal(np.sort(x[part]), np.sort(y[part])), field
    # a part's arrivals: its base, then its bursts (15 + 15, 135 + 135)
    assert np.array_equal(a.due[165:], b.due[165:])         # the bursts
    gaps = lambda s: np.sort(np.append(np.diff(s.due[30:165]),
                                       2.0 * s.due[30]))
    assert np.allclose(gaps(a), gaps(b))                    # one multiset
    assert not np.allclose(a.due[30:165], b.due[30:165])    # another order
    assert not np.array_equal(a.prompts[0], b.prompts[0])
    # which lengths fall inside a burst is the seed's doing
    inside = lambda s: np.sort(s.answer_len[(s.due >= 2.5) & (s.due < 3.0)])
    assert not np.array_equal(inside(a), inside(b))
    # and one seed is one schedule
    c = burst.burst_schedule(mix, 11, 45.0, 65536, 2048)
    for field in ("due", "prompt_len", "answer_len"):
        assert np.array_equal(getattr(b, field), getattr(c, field))
    assert all(np.array_equal(x, y) for x, y in zip(b.prompts, c.prompts))


def test_toy_cell_serves_and_agrees_with_the_reference(root):
    res = toyroot.run_toy(root, "toy-jamba.serve", seed=2**31 + 9,
                          seconds=3.0)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 12 + 3 * 4        # 4 /s base + 3 bursts of 4
    assert set(res["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in res["metrics"].values())


def test_state_taken_at_the_buckets_end_is_not_correct(root, monkeypatch):
    """The timed path broken underneath: the Mamba mixers are told the
    bucket's length where they should be told the prompt's."""
    import tpu_dist.models.hybrid as hybrid
    from tpu_dist.engine import serve

    real = hybrid.MambaMixer.__call__

    def at_bucket_end(self, h, paged):
        if paged is not None and h.shape[1] > 1:
            paged = {**paged,
                     "live": jnp.full_like(paged["live"], h.shape[1])}
        return real(self, h, paged)

    monkeypatch.setattr(hybrid.MambaMixer, "__call__", at_bucket_end)
    serve._prefill_program.cache_clear()     # traced sound a test ago
    try:
        res = toyroot.run_toy(root, "toy-jamba.serve", seed=10, seconds=2.0)
    finally:
        serve._prefill_program.cache_clear()
    assert res["failed"] == 0 and res["correct"] is False


def test_traced_toy_run_reads_the_new_per_layer_metrics(root):
    from benchmarks.trace import reduce as tr

    real = tr.reduce_file

    def fake(path, offsets_s=None):
        # the CPU has no device plane: made-up device operations on the
        # recorded spans' clock, one of them named as the chip names the
        # scan kernel's custom call
        _, spans = tr.read_xplane(path)
        lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
        span = hi - lo
        ops = [[("%fusion.1 = f32[4,512]{1,0} fusion(%p)", lo,
                 lo + 0.25 * span),
                ("%selective_scan.3 = (f32[1,16,128]{2,1,0}, f32[1,16,128]"
                 "{2,1,0}) custom-call(%a, %b), custom_call_target="
                 '"tpu_custom_call"', lo + 0.5 * span, lo + 0.75 * span)]]
        return tr.summarize(ops, spans, offsets_s)

    tr.reduce_file = fake
    try:
        res = toyroot.run_toy(root, "toy-jamba.serve", seed=12, trace=True)
    finally:
        tr.reduce_file = real
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["state_slots.serve"]["value"] <= 4
    # bytes of the toy prefills over a made-up half second: a tiny share,
    # but read, finite and under 100
    assert 0 < m["scan_roofline.serve"]["value"] < 100
    # the made-up kernel call is one of the two device operations
    assert 0 < m["scan_share.serve"]["value"] < 100
    assert m["tick_ms"]["value"] > 0 and m["prefill_stall_ms.serve"]["value"] > 0
    assert "attn_read_share.serve" not in m


def test_new_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives them: no ``state_slots`` attribute, no
    ``state_layers``, no scope: each returns None and does not raise."""
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(REPO, CELL)
    for name in NEW_METRICS:
        mod = cells.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_"))
        assert mod.read({"cell": cell}) is None
        assert mod.read({"cell": cell, "engine_steps": [], "trace": None,
                         "hlo_text": None}) is None


def test_steady_sweep_reads_the_burst_cell_without_its_bursts(
        root, monkeypatch, capsys):
    """``benchmarks/sweep_steady.py``, the command that found the cell's
    knee, at toy size: ``sweep.py``'s rule over the mix less its ``burst``
    group, so Poisson arrivals (``rate * seconds`` requests a rate)."""
    from benchmarks import sweep_steady
    from benchmarks.harness import device

    monkeypatch.setattr(sweep_steady, "ROOT", root)
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(sys, "argv", [
        "sweep_steady.py", "--workload", "toy-jamba.serve", "--rates", "4,6",
        "--seconds", "2", "--seed", "5"])
    assert sweep_steady.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [r["due"] for r in out["table"]] == [8, 12]
    assert all(r["rejected"] == 0 for r in out["table"])


def test_scan_bench_runs_every_form_and_finds_one_state(monkeypatch, capsys):
    """``benchmarks/kernels/selective_scan_bench.py`` at toy size: the
    committed blocks, two handed to the kernel, the ``lax.scan`` form and
    the one-step form, every state the committed blocks'."""
    from benchmarks.kernels import selective_scan_bench as bench

    monkeypatch.setattr(bench, "BLOCKS", [(16, 128), (32, 256), (256, 512)])
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "bench", "--channels", "256", "--lengths", "64", "--layers", "2",
        "--slots", "2"])
    assert bench.main() == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    scans = [r for r in rows if "blocks" in r]
    assert [r["blocks"] for r in scans] == [[64, 256], [16, 128], [32, 256],
                                            "plain"]
    assert all(r["state_differs_by"] < 1e-5 for r in scans)
    assert rows[-1]["ssm_step_us_a_layer"] > 0


def test_scan_kernel_cost_is_what_the_shapes_say():
    from benchmarks.kernels import selective_scan as ss

    cost = ss.scan(1, 256, 5120, 16, 2)
    stream = 256 * 5120
    assert cost["bytes"] == (8 * stream + 8 * 256 * 16 + 4 * 5120 * 16
                             + 4 * 5120 + 8 * 16 * 5120)
    assert cost["exponentials"] == 16 * stream
    floor = ss.least_seconds(cost, {"hbm_bytes_per_s": 819e9})
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(cost["bytes"] / 819e9)


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

    from benchmarks.families.hybrid_lm_server import model_fields
    from benchmarks.harness import cell as cells
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.models.hybrid import HybridLM
    from tpu_dist.ops.flash_attention import flash_attention_fn
    from tpu_dist.parallel.mesh import make_mesh

    cell = cells.load_cell(REPO, CELL)
    srv = cell.workload["serve"]
    chip = NamedSharding(make_mesh((1,), ("data",), devices=v5e[:1]), P())
    model = HybridLM(**model_fields(cell.config), dtype=jnp.bfloat16,
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
    weights = compiled.memory_analysis().argument_size_in_bytes / GIB
    # 6.06 GB of weights + 0.30 GB of slot state + 0.07 GB of pages
    assert 5.8 < weights < 6.2, weights
    assert _total_gib(compiled) <= HBM_GIB
    assert "ssm_step" in text and "mamba_mixer" in text
    assert "paged_read" in text
    assert "tpu_custom_call" not in text     # 1 bf16 KV head: gathered read


@pytest.mark.parametrize("bucket", [256, 2048])   # 2048 holds 1536 prompts
def test_prefill_of_the_whole_model_fits_one_chip(published, bucket,
                                                  monkeypatch):
    from tpu_dist.engine.serve import _prefill_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = published
    compiled = _prefill_program(p["model"], 0.0, 0, 0.0, None).lower(
        p["params"], p["layers"], p["i32"](1, p["pages"]), p["i32"](),
        p["i32"](), p["i32"](1, bucket), p["rng"], p["i32"]()).compile()
    text = compiled.as_text()
    assert text.count("%selective_scan.") >= 26      # one a Mamba layer
    assert text.count("tpu_custom_call") == 28       # + 2 flash attentions
    assert _total_gib(compiled) <= HBM_GIB, _total_gib(compiled)
