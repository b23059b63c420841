"""MFU accounting: model FLOPs vs device peak.

Shared by the trainers' step records (obs.RunObs), the tuner and the cell
benchmark so every throughput number can carry a
model-FLOPs-utilization figure. Peaks are public bf16 spec-sheet
numbers per chip, keyed by ``device_kind``; a TPU that is not in the table
is an error (:func:`lookup_peak`), never a default.
"""

from __future__ import annotations

# bf16 peak TFLOP/s per chip by device kind (public spec sheets)
PEAK_TFLOPS = (
    ("v6", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def lookup_peak(device_kind: str, table, what: str) -> float | None:
    """The published peak for a ``device_kind`` string from one of the
    substring-keyed tables (PEAK_TFLOPS here, obs.attr.PEAK_GBPS). A
    non-TPU kind (``cpu``, a tuner's ``unknown``) has no published peak:
    None, and the caller flags whatever nominal value it substitutes. A
    TPU kind the table does not list raises — a utilization against a
    made-up peak must not be printable on the chip."""
    kind = (device_kind or "").lower()
    for key, peak in table:
        if key in kind:
            return peak
    if kind.startswith("tpu"):
        raise ValueError(
            f"no published {what} for TPU device kind {device_kind!r}: "
            "add it to the peaks table, with its source")
    return None


def peak_tflops_for(device) -> float | None:
    return lookup_peak(getattr(device, "device_kind", ""), PEAK_TFLOPS,
                       "bf16 peak TFLOP/s")


def lm_flops_per_token(params, num_layers: int, seq_len: int,
                       d_model: int) -> float:
    """Analytical model FLOPs per trained token for a dense causal LM:
    6 * N_non-embedding + 6 * layers * L * d (fwd+bwd, causal-halved
    attention). THE shared accounting for LMTrainer and the benchmark — XLA's
    cost model counts scan bodies once and cannot cost Pallas custom calls,
    so it understates flash-attention runs."""
    import jax
    import numpy as np

    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    n_embed = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        key = jax.tree_util.keystr(path)
        if "tok_emb" in key or "pos_emb" in key:
            n_embed += int(np.prod(leaf.shape))
    return 6.0 * (n_params - n_embed) + 6.0 * num_layers * seq_len * d_model


def moe_lm_flops_per_token(params, num_layers: int, seq_len: int,
                           d_model: int, num_experts: int,
                           router_top_k: int, total_tokens: int,
                           group_size: int = 512,
                           capacity_factor: float = 1.25) -> float:
    """Analytical model FLOPs per trained token for the MoE LM (the
    XLA-cost-model fallback understates scan bodies and cannot see
    how many experts a token activates). Terms, all fwd+bwd (x6 per
    multiply-add pair, the same convention as lm_flops_per_token):

    * dense part: 6 x non-embedding, non-expert params (attention, norms,
      gate, head) + 6 x layers x L x d causal attention;
    * expert MLPs: a token activates top_k of E experts, so
      6 x top_k x (expert params / E);
    * dispatch/combine einsums: (G,S,E,C)x(G,S,D) contractions cost
      E x C x D multiply-adds per token per layer, twice (dispatch and
      combine) — the price of all-static GShard routing, which the XLA
      model DOES count but only per-scan-trip.
    The capacity C comes from the same moe_group_geometry the layer uses.
    """
    import jax
    import numpy as np

    from tpu_dist.models.moe import moe_group_geometry

    n_params = n_embed = n_expert = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        key = jax.tree_util.keystr(path)
        size = int(np.prod(leaf.shape))
        n_params += size
        if "tok_emb" in key or "pos_emb" in key:
            n_embed += size
        elif "w_in" in key or "w_out" in key:
            n_expert += size
    dense = 6.0 * (n_params - n_embed - n_expert) \
        + 6.0 * num_layers * seq_len * d_model
    experts = 6.0 * router_top_k * n_expert / num_experts
    _, cap = moe_group_geometry(total_tokens, seq_len, num_experts,
                                router_top_k, group_size, capacity_factor)
    routing = 2 * 6.0 * num_experts * cap * d_model * num_layers
    return dense + experts + routing


# (the XLA-cost-model probe is utils.telemetry.program_stats — one AOT
# lower for flops/hbm/HLO together)
