"""Pallas fused int8 matmul kernel: quantize -> MXU int8 dot -> dequant in
ONE kernel (the kernel-side half of ROADMAP item 4).

The reference math (ops.quant.quant_einsum) builds the int8 path out of
separate XLA ops: quantize lhs, quantize rhs, int32 einsum, scale multiply.
XLA fuses the elementwise pieces it can, but the int8 operand tensors and
the int32 accumulator are real HBM intermediates at matmul boundaries —
an int8 matmul that still pays ~fp8-sized quantize/dequantize round trips
around every dot. This kernel moves the whole ladder into VMEM:

* **activation quantization** — dynamic per-row symmetric int8 (amax over
  the contracting dim, computed on the (bm, K) VMEM tile);
* **weight quantization** — per-output-channel symmetric int8 (amax over
  K on the (K, bn) tile; K is whole per grid cell, so the block-local
  amax IS the exact global per-channel scale);
* **MXU accumulation** — int8 x int8 -> int32 ``dot_general``;
* **dequant** — one fp32 multiply by ``scale_x * scale_w`` broadcast into
  the output tile, cast to the input dtype on the way out.

Nothing int8 or int32 ever touches HBM; the only HBM traffic is the fp
inputs in and the fp output out. The re-quantize per (row-block, col-block)
pair is deliberate recompute — the FlashAttention trade of VMEM math for
HBM bytes.

Backward is the straight-through estimator, exactly like
``quant_einsum``: the custom_vjp's bwd is the vjp of the FP matmul on the
unquantized operands, so swapping the kernel in changes no training
semantics. ``interpret=True`` (auto-selected off-TPU) keeps the kernel
CPU-testable like ops.pallas_adamw; parity against the reference math is
pinned in tests/test_pallas_quant.py.

Entry point: :func:`fused_quant_matmul` — wired behind
``ops.quant.quant_matmul(mode='int8')`` when the fused path is active
(``ops.quant.set_fused_quant`` / ``TPU_DIST_FUSED_QUANT``), so QuantDense,
RingDense and the pipeline head all ride it with zero new plumbing.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_dist.runtime import pallas_interpret

_INT8_MAX = 127.0
_EPS = 1e-8          # all-zero rows/channels: scale floor keeps q = 0
BLOCK_M = 128        # default output tile rows per grid cell
BLOCK_N = 128        # default output tile cols per grid cell

# ---- searchable block sizes (plan IR, round 15) ---------------------------
# The 128x128 tiles above were hard-coded through round 14; the plan
# auto-tuner searches (bm, bn, bk) now. bm/bn pick the output tile; bk
# chunks the int8 MXU dot over the contracting dim INSIDE the kernel —
# the int32 accumulation is exact, and the per-row/per-channel amaxes are
# still taken over the WHOLE (bm, K)/(K, bn) VMEM blocks, so any bk
# produces bit-identical results to bk=0 (whole-K, the default): the knob
# trades MXU issue shape, never numerics. Trace-time static: set before
# building step functions (plan.compile.activate_plan does).
_BLOCKS: Tuple[int, int, int] = (BLOCK_M, BLOCK_N, 0)


def set_quant_blocks(bm: Optional[int] = None, bn: Optional[int] = None,
                     bk: Optional[int] = None) -> None:
    """Set the fused-kernel tile sizes ((None, None, None) restores the
    128x128 whole-K defaults). Legality (bm: multiple of 8; bn: multiple
    of 128; bk: 0 = whole contracting dim, else a multiple of 128) is THE
    shared rule in plan.ir.validate_quant_block — the IR and this setter
    cannot drift."""
    from tpu_dist.plan.ir import validate_quant_block

    global _BLOCKS
    bm = BLOCK_M if bm is None else int(bm)
    bn = BLOCK_N if bn is None else int(bn)
    bk = 0 if bk is None else int(bk)
    validate_quant_block(bm, bn, bk)
    _BLOCKS = (bm, bn, bk)


def quant_blocks() -> Tuple[int, int, int]:
    """The (bm, bn, bk) tile sizes the next trace will use."""
    return _BLOCKS


def _seed_blocks_from_env() -> None:
    # the env seed goes through the SAME validated setter, so a malformed
    # TPU_DIST_QUANT_BLOCKS fails loudly at import, not as a Mosaic
    # tiling abort at first trace
    spec = os.environ.get("TPU_DIST_QUANT_BLOCKS", "")
    if not spec:
        return
    parts = spec.split(",")
    if len(parts) != 3:
        raise ValueError(f"TPU_DIST_QUANT_BLOCKS={spec!r}: expected "
                         "'bm,bn,bk' (bk 0 = whole contracting dim)")
    set_quant_blocks(*(int(v) for v in parts))


_seed_blocks_from_env()


def _fused_quant_kernel(x_ref, w_ref, o_ref, *, bk: int):
    """One (bm, bn) output tile: quantize the (bm, K) activation block and
    the (K, bn) weight block in VMEM, int8 dot with int32 accumulation,
    dequant into the output dtype. K is whole per grid cell, so both
    amaxes are exact; ``bk`` > 0 chunks only the MXU dot over K (int32
    adds are exact — identical output, different issue shape)."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                     _EPS) / _INT8_MAX                      # (bm, 1)
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                     _EPS) / _INT8_MAX                      # (1, bn)
    qx = jnp.clip(jnp.round(x / sx), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    qw = jnp.clip(jnp.round(w / sw), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    k = qx.shape[1]
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    if bk and bk < k:
        acc = dot(qx[:, :bk], qw[:bk, :])
        for lo in range(bk, k, bk):
            hi = min(lo + bk, k)
            acc = acc + dot(qx[:, lo:hi], qw[lo:hi, :])
    else:
        acc = dot(qx, qw)
    o_ref[...] = (acc.astype(jnp.float32) * (sx * sw)).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = -size % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fused_quant_matmul_2d(x2, w, interpret: bool):
    """(M, K) x (K, N) with padding to the block grid; padded rows/cols
    quantize against the EPS floor to exact zeros and are sliced away."""
    m, k = x2.shape
    n = w.shape[1]
    blk_m, blk_n, blk_k = _BLOCKS
    # block rows rounded UP to the fp32 sublane multiple (8): a ragged
    # (12, K) block compiles under interpret but violates Mosaic's (8,128)
    # tiling on the TPU — exactly the backend where the fused path is
    # auto-enabled; the padding below absorbs the excess rows. bn rounds
    # up to the LANE multiple (128) for the same reason: with a tuned
    # blk_n > 128, min(blk_n, n) could land on a ragged lane tile (e.g.
    # n=200 under blk_n=256) that interpret accepts and Mosaic aborts on
    bm = min(blk_m, -(-max(m, 1) // 8) * 8)
    bn = min(blk_n, -(-max(n, 128) // 128) * 128)
    xp = _pad_to(x2, 0, bm)
    wp = _pad_to(w, 1, bn)
    grid = (xp.shape[0] // bm, wp.shape[1] // bn)
    out = pl.pallas_call(
        functools.partial(_fused_quant_kernel, bk=blk_k),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]), x2.dtype),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_quant_matmul(x, w, interpret=None):
    """``quant_matmul(x, w, 'int8')`` as one fused Pallas kernel.

    ``x`` (..., K) in the compute dtype, ``w`` (K, N); returns (..., N) in
    ``x.dtype``. Forward is the fused quantize/int8-dot/dequant kernel
    (numerically the reference ``quant_einsum`` dense path: same per-row /
    per-channel scales, same round/clip, int32 accumulation); backward is
    the straight-through estimator — the vjp of the FP matmul on the
    unquantized operands. ``interpret=None`` auto-selects interpreter mode
    off-TPU (the pallas_adamw convention)."""
    return _fused_fwd_impl(x, w, pallas_interpret(interpret))


def _fused_fwd_impl(x, w, interpret: bool):
    lead = x.shape[:-1]
    out2 = _fused_quant_matmul_2d(x.reshape(-1, x.shape[-1]), w, interpret)
    return out2.reshape(*lead, w.shape[1])


def _fused_fwd(x, w, interpret):
    return _fused_fwd_impl(x, w, pallas_interpret(interpret)), (x, w)


def _fused_bwd(interpret, res, g):
    x, w = res
    # STE: gradients of the FP matmul (ops.quant custom_vjp contract)
    _, vjp = jax.vjp(lambda a, b: jnp.dot(a, b), x, w)
    return vjp(g)


fused_quant_matmul.defvjp(_fused_fwd, _fused_bwd)
