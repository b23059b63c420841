"""The ``nemotron-3-super-120b-a12b-ep4`` configuration and its cell: the
files load and every name resolves; the configuration's keys are the
catalog's but the four reduced, whose published values it states; the
pattern is the published model's first period; by shapes alone the uncut
model counts 120.67 B parameters and the cut 4.648 B; the family
(``nemotron_h_lm_server``) serves a toy configuration end to end on the CPU
with ``correct`` true, and false with the held block shifted by one expert;
the new per-layer readers read a traced toy run; and the cell's tick and
2048-token prefill compile for a described v5e at the published widths
inside one chip's memory (nothing runs; no chip time).
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
CONFIG = "nemotron-3-super-120b-a12b-ep4"
CELL = CONFIG + ".serve-assistant"
GIB = 2.0 ** 30
HBM_GIB = 15.0

#: the catalog row's ``config`` (``architectures.jsonl``, name
#: NVIDIA-Nemotron-3-Super-120B-A12B-BF16), every key
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
           "n_routed_experts": 128, "vocab_size": 32768}

# two periods with all three kinds, 16 experts of which 4 are held, top 3,
# 2 groups, chunks of 8
TOY = dict(
    family="nemotron_h_lm", hidden_size=64, num_hidden_layers=12,
    hybrid_override_pattern="MEME*EMEME*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
    n_routed_experts=4, router_width=16, expert_share=dict(of=4, index=0),
    num_experts_per_tok=3, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=5,
    norm_eps=1e-5, vocab_size=512, max_position_embeddings=64,
    # 0.02 * sqrt(4096 / 64) would be 0.16; 0.125 moves a logit as the
    # published width's matrices do, so a wrong expert moves served tokens
    init_std=0.125)
NEW_METRICS = ("expert_share.serve", "router_share.serve",
               "expert_rows.serve", "expert_roofline.serve",
               "routed_prefill_roofline.serve")
SHARED_METRICS = ("tick_host_ms.serve", "token_gap_p99_ms.serve",
                  "tick_ahead_share.serve", "paged_read_share.serve",
                  "mamba_share.serve", "ssm_step_share.serve",
                  "state_slots.serve", "prefill_own_ms.serve",
                  "prefill_us_per_token.serve", "gap_prefill_share.serve",
                  "gap_gc_share.serve", "prefill_device_share.serve",
                  "prefill_ahead_share.serve")


def _config():
    return json.load(open(os.path.join(REPO, "benchmarks", "configs",
                                       CONFIG + ".json")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``toyroot``'s checkout plus a toy configuration of this family, a toy
    assistant mix and their cell: new files and appended entries again."""
    root = toyroot.make(tmp_path_factory.mktemp("bench"))
    b = os.path.join(root, "benchmarks")
    toyroot._dump(f"{b}/configs/toy-nemotron-h.json",
                  dict(TOY, source_keys=_config()["source_keys"]))
    toyroot._dump(f"{b}/traffic/toy-assistant.json", dict(
        kind="open_loop", rate_per_s=4.0, preroll_s=0.5,
        prompt=dict(median=12, sigma=0.5, min=4, max=30),
        answer=dict(median=16, sigma=0.4, min=8, max=30)))
    # float32 at toy size: a served token is the reference's best but for
    # near-ties (logits agree to ~1e-5); an expert off by one moves logits
    # by 1e-2..1
    toyroot._dump(f"{b}/workloads/toy-nemotron-h.serve.json", dict(
        family="nemotron_h_lm_server", trace_seconds=3,
        engine=dict(precision="fp32", attn="full", attn_block=64),
        serve=dict(max_slots=4, page_size=8, num_pages=64, max_len=64),
        control=dict(serve=[dict(quant="int8_wo")]),
        check=dict(sample_requests=8, limits=dict(
            served_token_gap_max=2e-3, served_token_gap_mean=1e-4))))
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["configs"].append(dict(
        name="toy-nemotron-h", source="toy", reduced=[], why="toy",
        file="benchmarks/configs/toy-nemotron-h.json"))
    spec["workloads"].append(dict(
        name="toy-nemotron-h.serve", config="toy-nemotron-h",
        traffic="toy-assistant", chips=1, why="toy"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-nemotron-h.serve")
    toyroot._dump(path, spec)
    return root


def test_cell_files_load_and_every_name_resolves():
    from benchmarks.harness import cell as cells

    cell = cells.load_cell(REPO, CELL)
    assert cell.chips == 1 and cell.family == "nemotron_h_lm_server"
    assert {m["name"] for m in cell.end_to_end} == {"gap_p95_ms", "setup_s"}
    read = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= read
    assert {"tick_ms", "device_idle.serve", "compile_s",
            "window_compiles"} <= read
    for absent in ("attn_read_share.serve", "prefill_stall_ms.serve",
                   "scan_share.serve", "scan_roofline.serve"):
        assert absent not in read
    for m in cell.per_layer:
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", m["name"] + ".py")), m["name"]
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:          # new entries list this cell alone
            assert m["workloads"] == [CELL] and m["moves"] == "gap_p95_ms"
    # appended after what was there, not inserted (a later PR appends after)
    for key, mine, before in (
            ("workloads", CELL, "phi-4-mini-flash-reasoning.serve-reason"),
            ("configs", CONFIG, "phi-4-mini-flash-reasoning"),
            ("per_layer", NEW_METRICS[0], "prefill_ahead_share.serve")):
        names = [x["name"] for x in spec[key]]
        assert names.index(mine) == names.index(before) + 1, key
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert tuple(names[at:at + len(NEW_METRICS)]) == NEW_METRICS
    assert cell.workload["serve"] == dict(max_slots=64, page_size=16,
                                          num_pages=8192, max_len=4096)
    assert cell.workload["control"]["serve"] == [{"quant": "int8_wo"}]
    assert cell.workload["check"]["sample_requests"] == 16
    mix = cell.traffic
    assert mix["kind"] == "open_loop" and "burst" not in mix
    assert (mix["prompt"]["median"], mix["prompt"]["sigma"],
            mix["prompt"]["min"], mix["prompt"]["max"]) == (256, 0.8, 32, 1536)
    assert (mix["answer"]["median"], mix["answer"]["sigma"],
            mix["answer"]["min"], mix["answer"]["max"]) == (256, 0.6, 32, 1024)
    assert mix["preroll_s"] == 8 and mix["drain_limit_s"] == 120
    assert "ladder" in mix["rate_note"] or "knee" in mix["rate_note"]


def test_configuration_states_the_catalogs_keys_and_the_four_it_reduces():
    """Every key of the catalog row's ``config`` under its own name but the
    four reduced, whose published values sit under ``published``; every
    assumed item under ``assumed`` with its origin; the deployment."""
    cfg = _config()
    kept = {k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert {k: cfg[k] for k in kept} == kept
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert cfg["reduced"] == list(REDUCED)
    assert cfg["hybrid_override_pattern"] == \
        PUBLISHED["hybrid_override_pattern"][:11]
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [5, 5, 1]
    assert [PUBLISHED["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [40, 40, 8]
    assert cfg["router_width"] == 512
    assert cfg["expert_share"] == {"of": 4, "index": 0}
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    for key in ("positions", "router_input", "router_scores", "gated_norm",
                "dt", "multi_token_prediction", "weights", "precision",
                "switches"):
        assert key in cfg["assumed"], key
    for key in ("positions", "router_input", "router_scores", "gated_norm",
                "dt", "multi_token_prediction"):
        assert "convention" in cfg["assumed"][key], key
    assert "no weight of it is made" in cfg["assumed"][
        "multi_token_prediction"]
    for words in ("4 chips share each layer", "9.30 GB", "120.67 B",
                  "32 chips"):
        assert words in cfg["deployment"], words


def test_every_seed_draws_the_same_lengths_and_gaps_in_another_order():
    from benchmarks.harness import cell as cells
    from benchmarks.harness import traffic

    mix = cells.load_cell(REPO, CELL).traffic
    a = traffic.open_loop_schedule(mix, 2**31 + 7, 45.0, 32768, 4096)
    b = traffic.open_loop_schedule(mix, 11, 45.0, 32768, 4096)
    for field in ("prompt_len", "answer_len"):
        x, y = getattr(a, field), getattr(b, field)
        assert not np.array_equal(x, y)
        for part in (a.in_window, ~a.in_window):
            assert np.array_equal(np.sort(x[part]), np.sort(y[part]))
    assert not np.allclose(a.due, b.due)
    assert (a.prompt_len + a.answer_len <= 4096).all()
    assert a.answer_len.max() <= 1024 and a.answer_len.min() >= 32
    # ids come from the slice of the vocabulary that is here
    assert max(int(p.max()) for p in a.prompts) < 32768
    assert 250 <= int(a.in_window.sum()) <= 700       # some 400 a window


def _count(model) -> int:
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))
        ["params"], jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes))


def test_pattern_and_parameter_counts_are_the_published_models():
    """5 / 5 / 1 of the three kinds in the cut; by shapes alone
    (``jax.eval_shape``: nothing is allocated) the uncut model counts
    120.67 B parameters and the cut 4.648 B."""
    from benchmarks.families.nemotron_h_lm_server import model_fields
    from benchmarks.reference import nemotron_h as ref
    from tpu_dist.models.nemotron_h import NemotronHLM

    cfg = _config()
    model = NemotronHLM(**model_fields(cfg), dtype=jnp.bfloat16)
    kinds = model.layer_types
    assert [kinds.count(k) for k in ("mamba2", "experts", "attention")] \
        == [5, 5, 1]
    assert ref.layer_kinds(cfg) == kinds
    assert model.held == (0, 128) and model.routed_layers() == (5, 128)
    n = _count(model)
    assert round(n / 1e9, 3) == 4.648, n
    assert n == sum(int(np.prod(s)) for s in ref.weight_shapes(cfg).values())
    uncut = NemotronHLM(**{
        **model_fields(cfg), "expert_share": (1, 0),
        "pattern": cfg["published"]["hybrid_override_pattern"],
        "vocab_size": cfg["published"]["vocab_size"]}, dtype=jnp.bfloat16)
    assert round(_count(uncut) / 1e9, 2) == 120.67, _count(uncut)
    # what a sequence costs: 4.19 MB of float32 state and three rows of
    # 10240 a Mamba-2 layer, nothing an expert layer, 1 KB a token in ONE
    layout = model.cache_layout()
    assert [k for k, *_ in layout].count("pages") == 1
    assert layout[7] == ("pages", 2, 128, 16)
    assert layout[1] == ("slot_state", {})
    kind, state = layout[0]
    assert kind == "slot_state" and state["ssm"] == ((128, 64, 128),
                                                     jnp.float32)
    assert state["conv"] == ((3, 10240), jnp.bfloat16)


def test_toy_cell_serves_and_agrees_with_the_reference(root, capsys):
    res = toyroot.run_toy(root, "toy-nemotron-h.serve", seed=2**31 + 9,
                          seconds=3.0)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in res["metrics"].values())
    # the share of (row, expert layer) pairs routed otherwise is printed
    # with the check: information, no limit
    assert "routing: the chosen set differs" in capsys.readouterr().out


def test_the_held_block_shifted_by_one_expert_is_not_correct(
        root, monkeypatch):
    """The timed path broken underneath: the program takes its held
    experts to be the router's 1-4 where its weights are those of 0-3."""
    import tpu_dist.parallel.ep as ep
    from tpu_dist.engine import serve

    real = ep.expert_share

    def shifted(num_experts, of, index):
        lo, n = real(num_experts, of, index)
        return lo + 1, n

    monkeypatch.setattr(ep, "expert_share", shifted)
    programs = (serve._prefill_program, serve._tick_program)
    for p in programs:
        p.cache_clear()                      # traced sound a test ago
    try:
        res = toyroot.run_toy(root, "toy-nemotron-h.serve", seed=10,
                              seconds=2.0)
    finally:
        for p in programs:
            p.cache_clear()
    assert res["failed"] == 0 and res["correct"] is False


@pytest.mark.parametrize("fault", ["shifted_block", "dropped_last"])
def test_a_planted_fault_reads_over_the_limits(root, monkeypatch, fault):
    """``precision_diag_nemotron_h.py --fault``: the cell's own comparison
    over short windows of a program broken underneath, as it is read on the
    chip for the limits' upper readings. In float32 at toy size both limits
    see both faults."""
    import tpu_dist.ops.routed_experts as rx
    import tpu_dist.parallel.ep as ep
    from benchmarks import precision_diag_nemotron_h as diag
    from benchmarks.harness import cell as cells
    from tpu_dist.engine import serve

    monkeypatch.setattr(ep, "expert_share", ep.expert_share)   # put back
    monkeypatch.setattr(rx, "route", rx.route)
    programs = (serve._prefill_program, serve._tick_program)
    for p in programs:
        p.cache_clear()
    cell = cells.load_cell(root, "toy-nemotron-h.serve")
    try:
        got = diag.read_planted(cell, fault, [31], jax.devices()[:1], 2.0)
    finally:
        for p in programs:
            p.cache_clear()
    limits = cell.workload["check"]["limits"]
    read = got[31]["sound"]
    assert read["served_token_logit_gap_max"] > limits["served_token_gap_max"]
    assert (read["served_token_logit_gap_mean"]
            > limits["served_token_gap_mean"])


class _MadeUpClock:
    """The traced toy window's clock: every reading lies ``dt`` after the
    last and a sleep passes at once, so what the window holds (which
    arrivals, prefills and ticks lie wholly inside it) follows from the
    schedule and the engine's own order of clock readings, not from how
    fast a loaded machine gets through an interpreted kernel. It starts at
    ``time.monotonic``'s reading and lags it from then on (a reading costs
    the engine far more than ``dt`` of real time), so the window that the
    family opens a pre-roll after the REAL clock lies ahead of it, and the
    loop sleeps up to it."""

    def __init__(self, dt: float):
        import time

        self.t, self.dt = time.monotonic(), dt

    def now(self) -> float:
        self.t += self.dt
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += max(seconds, 0.0)


def test_traced_toy_run_reads_the_new_per_layer_metrics(root, monkeypatch):
    import functools

    from benchmarks.harness import window
    from benchmarks.trace import program_spans as ps
    from benchmarks.trace import reduce as tr
    from benchmarks.trace import scopes
    from tpu_dist.engine import serve
    from tpu_dist.ops import routed_experts as rx

    # the toy's prompts fill buckets of 8 to 32 rows and its ticks have 4: a
    # row limit of 4 sends every prefill through the sorted form (the
    # grouped kernel, interpreted), as the cell's 1024 and 2048 buckets go
    monkeypatch.setattr(rx, "DENSE_ROWS", 4)
    # engine and open loop on one made-up clock (5 ms a reading: a tick a
    # few tens of ms, so that answers overlap in the slots)
    clock = _MadeUpClock(5e-3)
    t_start = clock.t
    monkeypatch.setattr(serve, "ServeEngine", functools.partial(
        serve.ServeEngine, now_fn=clock.now))
    monkeypatch.setattr(window, "drive_open_loop", functools.partial(
        window.drive_open_loop, now=clock.now, sleep=clock.sleep))
    programs = (serve._prefill_program, serve._tick_program)
    for p in programs:
        p.cache_clear()
    real, real_seconds = tr.reduce_file, scopes.scope_seconds

    def fake(path, offsets_s=None):
        # the CPU has no device plane: made-up device operations on the
        # recorded spans' clock, over the whole ``bench:window`` span (the
        # harness's offsets are the made-up clock's, not the profiler's)
        offsets_s = None
        _, spans = tr.read_xplane(path)
        lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
        q = (hi - lo) / 8.0
        ops = [[("%fusion.1 = f32[4,512]{1,0} fusion(%p)", lo, lo + q),
                ("%fusion.2 = f32[4,32]{1,0} fusion(%p)", lo + 2 * q,
                 lo + 4 * q),
                ('%gmm.3 = f32[128,32]{1,0} custom-call(%a, %b), '
                 'custom_call_target="tpu_custom_call"', lo + 5 * q,
                 lo + 6 * q)]]
        return tr.summarize(ops, spans, offsets_s)

    def fake_seconds(op_seconds, hlo_text, scope):
        # the CPU's program names the scopes but the made-up operations
        # are none of its instructions: a quarter of their time a scope
        # the program does name
        named = scopes.in_scope(scope).search(hlo_text) is not None
        return 0.25 * sum(op_seconds.values()) if named else 0.0

    tr.reduce_file, scopes.scope_seconds = fake, fake_seconds
    try:
        res = toyroot.run_toy(root, "toy-nemotron-h.serve", seed=12,
                              trace=True)
    finally:
        tr.reduce_file, scopes.scope_seconds = real, real_seconds
        for p in programs:
            p.cache_clear()
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    # this run's spans (earlier tests' lie before the clock's first
    # reading): every prefill says it ran the sorted form, two grouped
    # products in each of 6 layers, and every tick the dense one
    mine = [sp for sp in ps.ring_spans() if sp.start >= t_start]
    by_name = lambda name: [sp.attrs["grouped_calls"] for sp in mine
                            if sp.name == name]
    assert len(by_name("serve.prefill")) >= 10
    assert set(by_name("serve.prefill")) == {2 * 6}
    assert len(by_name("serve.tick")) > 20
    assert set(by_name("serve.tick")) == {0}
    assert 1 < m["state_slots.serve"]["value"] <= 4
    for name in NEW_METRICS:
        assert name in m, name
    # dropless: an expert sees active rows x top_k / router width a tick
    assert m["expert_rows.serve"]["value"] == pytest.approx(
        m["state_slots.serve"]["value"] * 3 / 16, rel=0.5)
    assert 0 < m["expert_roofline.serve"]["value"] < 100
    assert 0 < m["routed_prefill_roofline.serve"]["value"] < 100
    for name in ("expert_share.serve", "router_share.serve",
                 "mamba_share.serve", "ssm_step_share.serve"):
        assert m[name]["value"] == pytest.approx(25.0), name
    assert m["tick_ms"]["value"] > 0
    for absent in ("attn_read_share.serve", "scan_roofline.serve",
                   "scan_share.serve"):
        assert absent not in m


def test_new_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives them: no ``expert_rows`` or
    ``expert_layers`` attribute, no scope: each returns None and does not
    raise."""
    from benchmarks.harness import cell as cells
    from tpu_dist.obs import trace

    cell = cells.load_cell(REPO, CELL)
    now = [0.0]
    with trace.ring().span("serve.tick", now=lambda: now[0], rids=[1]):
        now[0] = 1.0

    class Step:
        start, end = -1.0, 2.0

    for name in NEW_METRICS:
        mod = cells.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_"))
        assert mod.read({"cell": cell}) is None
        assert mod.read({"cell": cell, "engine_steps": [], "trace": None,
                         "hlo_text": None}) is None
        assert mod.read({"cell": cell, "engine_steps": [Step()],
                         "trace": None, "hlo_text": None}) is None


def test_routed_experts_cost_is_what_the_counts_say():
    from benchmarks.kernels import routed_experts as k

    # one tick: 120 of 5 x 128 experts hit, 64 rows through 5 layers
    # a 2048-row prefill: 5 x 128 experts hit by 56,000 landed assignments
    cost = k.prefill(640, 56_000, 1024, 2688)
    assert cost["flops"] == 56_000 * 4 * 1024 * 2688
    assert cost["bytes"] == 640 * 11_010_048 + 56_000 * (1024 * 6
                                                         + 2 * 2688 * 2)
    floor = k.least_seconds(cost, {"hbm_bytes_per_s": 819e9,
                                   "bf16_flops": 197e12})
    assert floor["bound"] == "memory"          # 87 rows an expert: the read
    assert k.GMM_CALL.search('%gmm.7 = f32[45056,1024]{1,0} custom-call(%a)')
    assert not k.GMM_CALL.search('%fusion.7 = f32[4,4]{1,0} fusion(%gmm.7)')
    cost = k.tick(120, 64 * 5, 1024, 2688)
    per_expert = 2 * 1024 * 2688 * 2
    assert per_expert == 11_010_048                       # 11.01 MB
    assert cost["bytes"] == 120 * per_expert + 320 * 1024 * 6
    floor = k.least_seconds(cost, {"hbm_bytes_per_s": 819e9,
                                   "bf16_flops": 197e12})
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

    from benchmarks.families.nemotron_h_lm_server import model_fields
    from benchmarks.harness import cell as cells
    from tpu_dist.engine.kv_cache import PagedKVPool
    from tpu_dist.models.nemotron_h import NemotronHLM
    from tpu_dist.ops.flash_attention import flash_attention_fn
    from tpu_dist.parallel.mesh import make_mesh

    cell = cells.load_cell(REPO, CELL)
    srv = cell.workload["serve"]
    chip = NamedSharding(make_mesh((1,), ("data",), devices=v5e[:1]), P())
    model = NemotronHLM(**model_fields(cell.config), dtype=jnp.bfloat16,
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


def test_tick_of_the_cut_fits_one_chip(published, monkeypatch):
    from tpu_dist.engine.serve import _tick_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p, n = published, published["slots"]
    compiled = _tick_program(p["model"], 0.0, 0, 0.0, None).lower(
        p["params"], p["layers"], p["i32"](n, p["pages"]), p["i32"](n),
        p["i32"](n), p["rng"]).compile()
    text = compiled.as_text()
    held = compiled.memory_analysis().argument_size_in_bytes / GIB
    # 9.30 GB of weights + 1.34 GB of Mamba-2 state + 0.13 GB of pages
    assert 9.9 < held < 10.2, held
    assert _total_gib(compiled) <= HBM_GIB
    for scope in ("ssm_step", "mamba_mixer", "gated_norm", "paged_read",
                  "moe", "moe_router", "latent_proj", "routed_experts",
                  "shared_expert"):
        assert scope in text, scope
    assert "ssd_scan" not in text
    # the masked dense form: no grouped product in a tick, and the tokens
    # come back with the int32 counters (rows, experts hit, grouped calls)
    assert "%gmm" not in text
    assert "s32[3]" in text


def test_prefill_of_the_cut_fits_one_chip(published, monkeypatch):
    """The 2048 bucket (it holds the 1536-token prompts): the chunked
    Mamba-2 form, the attention layer's flash kernel over the bucket, the
    expert layers' grouped products over the sorted assignments, the head
    over ONE row."""
    from tpu_dist.engine.serve import _prefill_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p, bucket = published, 2048
    program = _prefill_program(p["model"], 0.0, 0, 0.0, None)
    compiled = program.lower(
        p["params"], p["layers"], p["i32"](1, p["pages"]), p["i32"](),
        p["i32"](), p["i32"](1, bucket), p["rng"], p["i32"]()).compile()
    text = compiled.as_text()
    assert program.head_rows[bucket] == 1
    assert "ssd_scan" in text and "ssm_step" not in text
    # the grouped products: two calls of the gmm kernel an expert layer
    assert len(set(__import__("re").findall(r"%(gmm[\w.]*) = ", text))) == 10
    assert "f32[1,2048,32768]" not in text and "bf16[1,2048,32768]" not in text
    assert _total_gib(compiled) <= HBM_GIB, _total_gib(compiled)
