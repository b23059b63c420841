"""Int8 quantized matmuls (AQT-style): the rung above bf16 on the
precision ladder.

The reference's precision stack tops out at apex AMP O1/O2
(4.apex_distributed2.py), which this repo maps to the bf16 policy
(ops.precision). TPU MXUs additionally execute int8 x int8 -> int32 dots at
up to 2x the bf16 rate, and quantized training in the AQT mold captures that
without losing convergence:

* **weights**: per-channel symmetric int8 — one scale per output channel
  (amax over the contracting dims / 127), so a single outlier row cannot
  crush the resolution of every other channel;
* **activations**: dynamic per-row symmetric int8, computed inside the
  jitted step from the live tensor (no calibration pass, no state);
* **accumulation**: ``preferred_element_type=jnp.int32`` — the MXU's native
  int8 path — with the dequant folded into one fp multiply on the way out
  (``scale_lhs x scale_rhs`` broadcast into the output tile);
* **backward**: straight-through estimator — gradients flow as if the dot
  were the fp dot of the unquantized operands, the standard QAT recipe
  (quantization noise is treated as identity-gradient noise).

Two modes ride one knob (``quant`` in configs.TrainConfig/LMConfig):

* ``int8``    — quantize BOTH operands (the 2x-MXU training mode);
* ``int8_wo`` — weight-only: weights fake-quantize (train) or live in HBM
  as int8 with fp32 scales (decode — :func:`wo_quantize_params`), while
  activations stay in the compute dtype. This is the memory-bound-decode
  mode: the per-tick weight traffic halves vs bf16 and the matmul itself
  stays fp.

Scales are tiny (one fp32 per output channel) and replicated, so GSPMD
partitioning of the surrounding program is unchanged — under dp x tp the
quantize/amax ops partition like any other elementwise/reduce op.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

QUANT_MODES = ("none", "int8", "int8_wo")

_INT8_MAX = 127.0
_EPS = 1e-8  # floor for all-zero channels: keeps scale finite, q = 0

# ---- fused-kernel dispatch (ops.pallas_quant) ------------------------------
# Tri-state: None = auto (fused on TPU, reference math elsewhere — the
# interpret-mode kernel is correct but slow, so CPU tests keep the cheap
# XLA path unless they opt in); True/False force it. The env knob
# TPU_DIST_FUSED_QUANT=1/0 seeds the state so bench/CLI runs can flip it
# without code. Trace-time static: set it BEFORE building step functions.
_FUSED_QUANT: Optional[bool] = (
    None if os.environ.get("TPU_DIST_FUSED_QUANT", "") == ""
    else os.environ["TPU_DIST_FUSED_QUANT"] not in ("0", "false", ""))


def set_fused_quant(enabled: Optional[bool]) -> None:
    """Force the fused Pallas int8 kernel on/off (None restores auto).
    Trace-time static — call before step functions are built."""
    global _FUSED_QUANT
    _FUSED_QUANT = enabled


def fused_quant_active() -> bool:
    """Whether ``quant_matmul(mode='int8')`` routes through the fused
    Pallas kernel right now (the engines stamp this into step records as
    the ``fused`` flag so ledger readers can attribute MFU deltas)."""
    if _FUSED_QUANT is not None:
        return _FUSED_QUANT
    return jax.default_backend() == "tpu"


def validate_quant(mode: str) -> str:
    if mode not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {mode!r} "
                         f"({'|'.join(QUANT_MODES)})")
    return mode


def quantize_int8(x: jax.Array, reduce_dims) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of ``x`` with one scale per slice along
    the non-reduced dims (``reduce_dims`` = the contracting dims: amax over
    them, keepdims). Returns (q int8, scale fp32); ``q * scale`` dequantizes.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=tuple(reduce_dims), keepdims=True)
    scale = jnp.maximum(amax, _EPS) / _INT8_MAX
    q = jnp.clip(jnp.round(xf / scale), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _contracted_dims(spec: str, operand: str) -> tuple:
    """Dims of ``operand`` (one side of an 'ab,bc->ac' einsum) that do not
    survive to the output — the contracting dims the scale reduces over."""
    out = spec.split("->")[1]
    return tuple(i for i, ch in enumerate(operand) if ch not in out)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def quant_einsum(spec: str, lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """``jnp.einsum(spec, lhs, rhs)`` with both operands int8-quantized and
    int32 accumulation; backward is the straight-through estimator (the vjp
    of the FP einsum on the unquantized operands).

    ``spec`` must be a two-operand explicit einsum (``'...->...'``). Scales
    reduce over each operand's contracted dims, so the dequant is exact:
    the same einsum applied to the (1-sized over contracted dims) scale
    tensors yields the per-output-element ``scale_lhs * scale_rhs`` product.
    """
    return _quant_einsum_fwd_impl(spec, lhs, rhs)


def _quant_einsum_fwd_impl(spec, lhs, rhs):
    ins, _ = spec.split("->")
    l_sub, r_sub = ins.split(",")
    ql, sl = quantize_int8(lhs, _contracted_dims(spec, l_sub))
    qr, sr = quantize_int8(rhs, _contracted_dims(spec, r_sub))
    out_i32 = jnp.einsum(spec, ql, qr, preferred_element_type=jnp.int32)
    out_scale = jnp.einsum(spec, sl, sr)  # contracted dims are size 1: product
    return (out_i32.astype(jnp.float32) * out_scale).astype(lhs.dtype)


def _quant_einsum_fwd(spec, lhs, rhs):
    return _quant_einsum_fwd_impl(spec, lhs, rhs), (lhs, rhs)


def _quant_einsum_bwd(spec, res, g):
    lhs, rhs = res
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b), lhs, rhs)
    return vjp(g)


quant_einsum.defvjp(_quant_einsum_fwd, _quant_einsum_bwd)


def wo_fake_quant(w: jax.Array, reduce_dims=(0,)) -> jax.Array:
    """Weight-only fake quantization with an STE: forward sees the int8
    round-trip of ``w`` (per-channel scales over ``reduce_dims``), backward
    sees identity — plain autodiff delivers the STE, no custom_vjp needed."""
    q, scale = quantize_int8(w, reduce_dims)
    wq = dequantize(q, scale, w.dtype)
    return w + jax.lax.stop_gradient(wq - w)


def _dense_spec(ndim: int) -> str:
    """'abd,dZ->abZ'-style spec for an (..., D) x (D, F) dense matmul."""
    batch = "abcegh"[:ndim - 1]  # skip d/f/Z, enough for any sane rank
    return f"{batch}d,dZ->{batch}Z"


def quant_matmul(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """THE mode dispatch for a (..., D) x (D, F) matmul — the single home
    of what each quant mode means, shared by QuantDense and the pipeline
    head (parallel.pp._head_logits) so the two can never diverge: dynamic-
    activation int8 einsum for 'int8', fake-quantized weights for
    'int8_wo', an exact fp matmul for 'none'. Both operands must already
    be in the compute dtype."""
    if mode == "int8":
        if fused_quant_active():
            # one Pallas kernel: quantize + int8 MXU dot + dequant, no
            # int8/int32 HBM intermediates (ops.pallas_quant); identical
            # scales/rounding to the reference einsum, STE backward
            from tpu_dist.ops.pallas_quant import fused_quant_matmul
            return fused_quant_matmul(x, w)
        # both operands quantized, int32 accumulation, STE backward
        return quant_einsum(_dense_spec(x.ndim), x, w)
    if mode == "int8_wo":
        return jnp.dot(x, wo_fake_quant(w))
    validate_quant(mode)  # 'none' (exact fp) is all that remains
    return jnp.dot(x, w)


class QuantDense(nn.Module):
    """Drop-in quantized ``nn.Dense``: same param names ("kernel"/"bias"),
    same init, same (in, out) kernel layout — checkpoints and the Megatron
    TP sharding rules (parallel.tp) apply unchanged.

    ``mode='int8'`` quantizes activations (dynamic per-row) AND weights
    (per-output-channel) into an int32-accumulated dot with an STE backward;
    ``mode='int8_wo'`` fake-quantizes only the weights and keeps the matmul
    in the compute dtype.

    Weight-only DECODE: when the param dict carries a pre-quantized kernel
    (int8 ``kernel`` + fp32 ``kernel_scale`` — :func:`wo_quantize_params`),
    the kernel stays int8 in HBM and is dequantized on the fly, halving the
    per-tick weight traffic that bounds autoregressive decode. The branch is
    static (variable presence), so train and decode programs never mix.
    """

    features: int
    mode: str = "int8"
    use_bias: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        validate_quant(self.mode)
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        x = x.astype(self.dtype)
        if self.has_variable("params", "kernel_scale"):
            if self.mode == "int8":
                # refuse rather than silently degrade: a wo-quantized tree
                # has lost the fp weights, so the dynamic-activation int8
                # program the caller asked for cannot be built from it
                raise ValueError(
                    "params carry a pre-quantized int8 kernel "
                    "(kernel_scale leaf, wo_quantize_params) but "
                    "mode='int8' was requested; pre-quantized trees only "
                    "support the weight-only path — pass quant='int8_wo', "
                    "or keep the fp params for dynamic int8.")
            # pre-quantized weight-only path (decode): int8-resident kernel
            scale = self.get_variable("params", "kernel_scale")
            w = dequantize(kernel, scale, self.dtype)
            y = jnp.dot(x, w)
        else:
            y = quant_matmul(x, kernel.astype(self.dtype), self.mode)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,))
            y = y + bias.astype(self.dtype)
        return y


def make_dense(features: int, *, use_bias: bool = True,
               dtype=jnp.float32, name: Optional[str] = None,
               quant: str = "none", tp_impl: str = "gspmd",
               tp_kind: Optional[str] = None, tp_fused: int = 1) -> nn.Module:
    """THE dense-layer factory of the transformer families: ``nn.Dense``
    when quantization is off, :class:`QuantDense` (identical param tree)
    otherwise — so the quant knob never forks model param structure.

    ``tp_impl`` other than 'gspmd' with a ``tp_kind`` ('column'|'row')
    routes through the ring collective matmul
    (:class:`tpu_dist.parallel.overlap.RingDense` — still the identical
    param tree, quant riding the same ring); layers with no parallel role
    (tp_kind=None, e.g. a replicated lm_head under ring) stay on the
    plain/quant path whatever the impl."""
    if tp_impl != "gspmd" and tp_kind is not None:
        # local import: parallel.overlap imports quant_matmul from here
        from tpu_dist.parallel.overlap import RingDense
        return RingDense(features, kind=tp_kind, flavor=tp_impl,
                         use_bias=use_bias, dtype=dtype, n_fused=tp_fused,
                         quant=validate_quant(quant), name=name)
    if validate_quant(quant) == "none":
        return nn.Dense(features, use_bias=use_bias, dtype=dtype, name=name)
    return QuantDense(features, mode=quant, use_bias=use_bias, dtype=dtype,
                      name=name)


# ---- MoE expert matmuls ----------------------------------------------------
# The expert contractions carry a batch dim (the expert index e) next to the
# contracting dim, so they route through quant_einsum directly with
# per-expert-per-channel weight scales; the router gate and the one-hot
# dispatch/combine einsums stay in fp (they are selection, not compute).

def moe_expert_matmul(spec: str, acts: jax.Array, w: jax.Array,
                      quant: str = "none") -> jax.Array:
    """One expert contraction ('gecd,edf->gecf' or 'gecf,efd->gecd') under
    the active quant mode: fp einsum (none), weight fake-quant (int8_wo),
    or fully quantized with STE (int8)."""
    if validate_quant(quant) == "none":
        return jnp.einsum(spec, acts, w)
    if quant == "int8_wo":
        r_sub = spec.split("->")[0].split(",")[1]
        return jnp.einsum(spec, acts,
                          wo_fake_quant(w, _contracted_dims(spec, r_sub)))
    return quant_einsum(spec, acts, w)


# ---- weight-only decode: pre-quantized param trees -------------------------

_MOE_EXPERT_LEAVES = ("w_in", "w_out")


#: experts of a 3D tensor quantized at a time: eagerly, the float32 copy and
#: the rounding's temporaries of a whole 0.7 GB expert tensor stand beside
#: each other (4 GB on top of the tree being quantized); a scale belongs to
#: one expert and output channel, so blocks of experts give the same values
_EXPERT_BLOCK = 16


def _quantize_experts(v):
    parts = [quantize_int8(v[i:i + _EXPERT_BLOCK], (1,))
             for i in range(0, v.shape[0], _EXPERT_BLOCK)]
    return (jnp.concatenate([q for q, _ in parts]),
            jnp.concatenate([s for _, s in parts]))


def _quantize_tree(tree):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            # the router gate stays fp32: its argmax picks the expert, and
            # int8 logits would reroute tokens rather than perturb them
            out[k] = v if k == "gate" else _quantize_tree(v)
        elif k == "kernel" and getattr(v, "ndim", 0) == 2:
            q, s = quantize_int8(v, (0,))
            out[k], out[k + "_scale"] = q, s
        elif k in _MOE_EXPERT_LEAVES and getattr(v, "ndim", 0) == 3:
            q, s = _quantize_experts(v)  # (E, in, out): amax over in
            out[k], out[k + "_scale"] = q, s
        else:
            out[k] = v  # embeddings, norms, biases, cls/pos tokens
    return out


def wo_quantize_params(params):
    """Pre-quantize a transformer-family param tree for weight-only int8
    decode: every 2D dense ``kernel`` (and 3D MoE expert tensor) becomes an
    int8 leaf with a sibling ``<name>_scale`` fp32 leaf; everything else
    (embeddings, norms, biases, the MoE router gate) is untouched. The
    quantized tree feeds ``model.apply`` of a ``quant='int8_wo'`` model —
    QuantDense/MoEMLP detect the scale leaves and read the int8 weights
    directly (engine.generate wires this up for decode)."""
    return _quantize_tree(params)


def params_are_wo_quantized(params) -> bool:
    """True if ``params`` already carries wo-quantized scale leaves."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return any(str(getattr(k, "key", "")).endswith("_scale")
               for path, _ in flat for k in path)
