"""Block-table (paged) KV attention primitives — the serving read/write path.

vLLM's PagedAttention [SOSP '23] observation, TPU-shaped: a contiguous
per-sequence KV cache sized to the worst-case total fragments HBM the moment
requests of mixed length share a batch — every slot pays max_len whether it
decodes 4 tokens or 4000. Instead the KV lives in ONE preallocated arena of
fixed-size pages per layer (``[num_pages, page_size, heads, head_dim]``) and
each sequence owns an ordered *block table* of page indices; allocation is a
free-list pop, eviction a push, and utilization follows actual lengths.

This module is the ops half (pure array programs — the pool/allocator lives
in ``engine.kv_cache``, the scheduler in ``engine.serve``):

* :func:`paged_write` — scatter new K/V rows into the arena through a block
  table at per-row positions (prefill writes a whole prompt, the decode tick
  one token per sequence). Masked rows route to the arena's *trash page*
  (index ``num_pages``, the reason arenas carry one extra page): the scatter
  stays branch-free and fully static under jit.
* :func:`gather_pages` — the gathered read: block table -> contiguous
  ``(B, max_pages * page_size, ...)`` copy of each sequence's cache, which
  :func:`masked_attention` then scores with a per-row causal horizon.
* :func:`paged_decode_attention` — the in-place read of the decode tick: a
  Pallas kernel that walks the block table itself and fetches only the
  pages below each row's length out of the arena as it lies in HBM (no
  gathered copy, no float32 copy, no work past a row's length).
* :func:`paged_attend` — the attention entry ``models.transformer.
  attend_maybe_cached`` delegates to: prefill attends within the prompt via
  the model's own ``attn_fn`` (+ page writes); the decode tick writes one
  row and attends over its pages with PER-ROW positions — the
  continuous-batching difference from the flax cache, whose scalar
  ``cache_index`` forces every batch row to the same position. Which read
  it takes is :func:`decode_read`'s rule, made from the inputs alone: one
  query a row over unsharded bf16/fp32 arenas of lane-wide heads reads in
  place, everything else gathers. A layer laid out in ROWS (3-D arenas: a
  grouped-head layer such as the expert model's) goes through the same
  entry by its rank: the tick reads it with the grouped in-place kernel,
  a wider window gathers its rows. The same non-prefill path generalizes
  to Lq > 1 (gathered) as the speculative-decoding
  VERIFY read: row ``b`` carries ``Lq`` queries at positions
  ``pos[b]..pos[b]+Lq-1`` (the last real token plus the draft proposals),
  writes all their K/V rows through the block table, and attends each
  local query at its own causal horizon — one dispatch validates a whole
  draft window.
* :func:`cow_fork_pages` — the copy-on-write fork behind cross-request
  prefix sharing (``engine.kv_cache``): gather the shared source pages,
  scatter them onto freshly-granted destinations, so the writer diverges
  on its own copy and the other holders keep reading the original bits.
* int8 arenas: pages hold int8 values + one fp32 scale per (page-slot, head)
  row — the ``ops.flash_attention.quantize_kv`` layout, quantized by
  ``ops.quant.quantize_int8`` itself so the rounding convention can never
  drift. The exact read path dequantizes the gathered tiles;
  :func:`int8kv_paged_flash_attention_fn` is the Pallas variant that
  consumes the gathered int8 layout directly (dequant per VMEM tile, K/V
  never fp in HBM) with a per-row LENGTH mask instead of the training
  kernels' causal offsets — the decode-tick geometry where every batch row
  sits at a different position.

Exactness contract: both reads score in float32 (bf16 products
accumulated in float32), keep the softmax and its statistics in float32,
and give masked positions *exactly zero* weight. The gathered read mirrors
``full_attention`` op-for-op (same einsum contractions, softmax weights
rounded to the value dtype before the weighted sum), so greedy decode
through it is bit-identical to the contiguous-cache path
(tests/test_serve.py pins the tokens). The in-place read is an online
softmax over chunks with float32 weights: at least as precise, not
bit-equal in its logits; what is pinned for it is EQUAL GREEDY TOKENS
against ``engine.generate`` (tests/test_paged_attention.py) and one bf16
step against the gathered read.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_dist._compat import shard_map
from tpu_dist.ops.flash_attention import _STAT_LANES, NEG_INF, _blocks, _fold
from tpu_dist.parallel.mesh import SP_AXIS
from tpu_dist.runtime import pallas_interpret


def pages_for(length: int, page_size: int) -> int:
    """Pages a sequence of ``length`` tokens occupies (host-side helper)."""
    return -(-int(length) // int(page_size))


@jax.tree_util.register_pytree_node_class
class PagedLayer:
    """One layer's KV page arenas as a jit-traversable pack.

    ``k``/``v`` are ``(num_pages + 1, page_size, heads, head_dim)`` — the
    +1 is the trash page masked writes land on. int8 arenas additionally
    carry ``k_scale``/``v_scale`` ``(num_pages + 1, page_size, heads)``
    fp32. ``quant`` ("none" | "int8") and ``read`` ("exact" | "flash")
    ride in the pytree *aux data*: they are static, participate in jit
    cache keys, and can never be confused for traced values.

    Two more layouts, both read by :func:`grouped_read`. ROWS: ``k``/``v``
    are ``(pages, page_size, kv_heads * head_dim)``, a token's KV heads side
    by side in one lane-wide row (what a grouped read on the MXU wants, and
    what a head count that is no whole sublane tile needs); behind the
    scheduler's block tables with the trash page, it is also what
    :func:`paged_attend` takes in place of the 4-D layout. A RING
    (``ring`` > 0, static like the other two): rows again, ``ring`` pages a
    SLOT and no trash page; slot ``b`` owns pages ``b * ring .. (b + 1) *
    ring - 1`` and position ``t`` lives at row ``t % (ring * page_size)``
    of them, so a window layer holds ``window + page_size`` tokens a slot
    however long its sequence grows.
    """

    def __init__(self, k, v, k_scale=None, v_scale=None, *,
                 quant: str = "none", read: str = "exact", ring: int = 0):
        self.k, self.v = k, v
        self.k_scale, self.v_scale = k_scale, v_scale
        self.quant, self.read, self.ring = quant, read, ring

    @property
    def num_pages(self) -> int:
        return self.k.shape[0] - 1               # minus the trash page

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    def replace(self, **kw) -> "PagedLayer":
        fields = dict(k=self.k, v=self.v, k_scale=self.k_scale,
                      v_scale=self.v_scale, quant=self.quant,
                      read=self.read, ring=self.ring)
        fields.update(kw)
        return PagedLayer(**fields)

    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale),
                (self.quant, self.read, self.ring))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, ks, vs = children
        return cls(k, v, ks, vs, quant=aux[0], read=aux[1], ring=aux[2])


# ---------------------------------------------------------------------------
# arena scatter / gather
# ---------------------------------------------------------------------------

def flat_slot_index(block_table, positions, page_size: int):
    """(B, L) global arena slot indices for per-row token positions.

    ``page_size`` is static (an arena shape constant); positions beyond the
    table's reach are the CALLER's bug — the scheduler sizes tables to
    ``ceil(max_len / page_size)`` so every legal position has a page.
    """
    page = jnp.take_along_axis(block_table,
                               positions // page_size, axis=1)
    return page * page_size + positions % page_size


def paged_write(arena, block_table, positions, values, valid,
                trash_page: int):
    """Scatter ``values`` (B, L, ...) into ``arena`` (N+1, page_size, ...)
    at per-row ``positions`` (B, L); rows where ``valid`` (B, L) is False
    land on the trash page (slot 0) instead — a branch-free masked write.

    Distinct live sequences own disjoint pages (the allocator's contract),
    so live scatter indices never collide; trash collisions are harmless by
    definition.
    """
    n1, page_size = arena.shape[0], arena.shape[1]
    flat = flat_slot_index(block_table, positions, page_size)
    flat = jnp.where(valid, flat, trash_page * page_size)
    flat_arena = arena.reshape((n1 * page_size,) + arena.shape[2:])
    flat_arena = flat_arena.at[flat.reshape(-1)].set(
        values.reshape((-1,) + values.shape[2:]).astype(arena.dtype))
    return flat_arena.reshape(arena.shape)


def gather_pages(arena, block_table):
    """Block table (B, max_pages) -> (B, max_pages * page_size, ...) —
    each sequence's cache as one contiguous view (gather, no copy under
    XLA fusion when consumed immediately)."""
    g = arena[block_table]                       # (B, P, page_size, ...)
    b, p, s = g.shape[:3]
    return g.reshape((b, p * s) + g.shape[3:])


# ---------------------------------------------------------------------------
# sp-sharded arenas (engine.kv_cache sharded pool)
# ---------------------------------------------------------------------------
#
# When the pool shards its arenas over the serving sequence-parallel axis
# (``parallel.mesh.SP_AXIS``), dim 0 is laid out as ``n`` per-device blocks
# of ``rows_local = pages_per_device + 1`` rows — each device carries its
# own pages PLUS its own local trash row (the block's last row), so the
# branch-free masked-write discipline survives sharding without any
# cross-device scatter. Block tables then hold FLAT arena row indices
# (``engine.kv_cache.PagedKVPool.flat_block_table``); ownership of row
# ``r`` is ``r // rows_local``. The two collectives below are the ONLY
# sharded-arena primitives: every read/write composes out of them, and for
# a 1-device mesh both degenerate to the unsharded gather/scatter exactly.

def _sp_local_bt(block_table, rows_local: int, me):
    """Global flat rows -> this device's local rows; foreign rows route to
    the LOCAL trash (rows_local - 1), which their owner will serve."""
    owner = block_table // rows_local
    local = jnp.where(owner == me, block_table % rows_local, rows_local - 1)
    return owner, local


def sp_gather_pages(arena, block_table, mesh):
    """:func:`gather_pages` over an sp-sharded arena: each device gathers
    the pages it owns (foreign entries masked to exact zeros) and one
    ``psum`` over the sp axis assembles the full per-sequence view on
    every device. Bit-exact: every page has exactly one owner, so each
    output row is one contribution plus zeros."""

    def gather(local_arena, bt):
        rows_local = local_arena.shape[0]
        me = jax.lax.axis_index(SP_AXIS)
        owner, local_bt = _sp_local_bt(bt, rows_local, me)
        g = gather_pages(local_arena, local_bt)      # (B, P*ps, ...)
        own = jnp.repeat(owner == me, local_arena.shape[1], axis=1)
        own = own.reshape(own.shape + (1,) * (g.ndim - 2))
        g = jnp.where(own, g, jnp.zeros((), g.dtype))
        return jax.lax.psum(g, SP_AXIS)

    return shard_map(gather, mesh=mesh, in_specs=(P(SP_AXIS), P()),
                     out_specs=P())(arena, block_table)


def sp_paged_write(arena, block_table, positions, values, valid, mesh):
    """:func:`paged_write` over an sp-sharded arena: every device sees the
    (replicated) values and scatters exactly the rows whose page it owns;
    everything else — foreign rows and masked rows alike — lands on the
    device's LOCAL trash row. No communication at all: ownership is a
    pure function of the flat row index."""

    def write(local_arena, bt, pos, vals, ok):
        rows_local = local_arena.shape[0]
        me = jax.lax.axis_index(SP_AXIS)
        _, local_bt = _sp_local_bt(bt, rows_local, me)
        return paged_write(local_arena, local_bt, pos, vals, ok,
                           rows_local - 1)

    return shard_map(write, mesh=mesh,
                     in_specs=(P(SP_AXIS), P(), P(), P(), P()),
                     out_specs=P(SP_AXIS))(
        arena, block_table, positions, values, valid)


def _fork_arena(arena, src_pages, dst_pages):
    """Whole-page gather-then-scatter: arena[dst] <- arena[src]."""
    return arena.at[dst_pages].set(arena[src_pages])


@functools.partial(jax.jit, donate_argnums=(0,))
def cow_fork_pages(layers, src_pages, dst_pages):
    """Copy-on-write fork: duplicate ``src_pages`` onto ``dst_pages`` in
    every layer's arenas (K, V and, for int8 arenas, their scales).

    The prefix-sharing allocator (``engine.kv_cache``) hands a new request
    the SAME physical pages another sequence's identical prompt prefix
    already occupies; the first write that would diverge (the frontier
    page's first generated token) must land on a private copy instead.
    This is that fork as one jitted gather-then-scatter over all layers —
    whole pages are copied (stale rows beyond the shared prefix ride
    along harmlessly: the per-row causal mask hides them until the new
    owner overwrites them in position order), and the arenas are DONATED
    like every other page program so a fork never duplicates an arena.

    ``src_pages``/``dst_pages`` are (n,) i32; forks are rare host-decided
    events (at most one frontier page per admitted request), so n is tiny
    and jit re-specialization per n is immaterial.
    """
    out = []
    for layer in layers:
        fields = {"k": _fork_arena(layer.k, src_pages, dst_pages),
                  "v": _fork_arena(layer.v, src_pages, dst_pages)}
        if layer.k_scale is not None:
            fields["k_scale"] = _fork_arena(layer.k_scale, src_pages,
                                            dst_pages)
            fields["v_scale"] = _fork_arena(layer.v_scale, src_pages,
                                            dst_pages)
        out.append(layer.replace(**fields))
    return tuple(out)


# ---------------------------------------------------------------------------
# exact read path (per-row positions)
# ---------------------------------------------------------------------------

def masked_attention(q, k, v, q_positions):
    """``full_attention`` with a PER-ROW causal horizon: row ``b`` of ``q``
    (B, Lq, H, D) sits at global position ``q_positions[b]`` (+ the local
    offset for Lq > 1) and may attend to keys ``kpos <= qpos``. Mirrors
    ``models.transformer.full_attention`` op-for-op (fp32 scores/softmax,
    identical contractions) so the scalar-offset case is bit-identical —
    the serving tick's degenerate-to-generate contract rides on this."""
    d = q.shape[-1]
    qpos = q_positions[:, None] + jnp.arange(q.shape[1])[None, :]  # (B, Lq)
    kpos = jnp.arange(k.shape[1])                                  # (Lk,)
    mask = kpos[None, None, :] <= qpos[:, :, None]                 # (B,Lq,Lk)
    if q.shape[2] != k.shape[2]:
        # grouped heads: query heads j*g .. (j+1)*g - 1 read KV head j, as
        # one contraction over the group; K and V are never repeated.
        # A branch of its own, not the one below at g = 1: the five-index
        # contraction lowers to another dot and differs from the equal-
        # heads one in the last float32 bit (3.6e-7 on the CPU, PR 26),
        # and the contract above pins that one to ``generate``'s bits
        b, lq, h, _ = q.shape
        kv = k.shape[2]
        qg = q.reshape(b, lq, kv, h // kv, d)
        scores = jnp.einsum(
            "bqjgd,bkjd->bjgqk", qg, k,
            preferred_element_type=jnp.float32) / jnp.sqrt(d).astype(
                jnp.float32)
        scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bjgqk,bkjd->bqjgd", weights.astype(v.dtype),
                          v).reshape(b, lq, h, d)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k,
        preferred_element_type=jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# int8-KV paged flash kernel (per-row length mask)
# ---------------------------------------------------------------------------
#
# The training-side kernels (ops.flash_attention) mask causally from static
# q/kv offsets — every batch row shares one geometry. A continuous-batching
# decode tick breaks that: each row is ONE query at its OWN position over
# its OWN gathered pages. This variant replaces the causal bounds with a
# per-row live-length input read from SMEM-adjacent stat lanes (same
# (B*H, L, _STAT_LANES) layout as the int8 scales), masking kpos >= length.

def _paged_int8kv_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, len_ref,
                         o_ref, acc_ref, m_ref, l_ref, *,
                         bq, bk, nk, scale):
    import jax.experimental.pallas as pl

    ik = pl.program_id(1)
    k_start = ik * bk

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant on the VMEM tile — the only fp copy of this KV block
    kf = k_ref[0].astype(jnp.float32) * ks_ref[0][:, :1]         # (bk, D)
    vf = v_ref[0].astype(jnp.float32) * vs_ref[0][:, :1]
    s = jax.lax.dot_general(
        q_ref[0].astype(jnp.float32), kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale               # (bq, bk)
    live = len_ref[0][:1, :1]                                     # (1, 1)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(kpos < live.astype(jnp.int32), s, NEG_INF)
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, :1]))
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                    + jax.lax.dot_general(
                        p, vf, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l_cur = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l_cur).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def int8kv_paged_flash_attention_fn(block_k: int = 512,
                                    interpret: bool | None = None):
    """Returns ``attn(q, kq, ks, vq, vs, lengths)`` over GATHERED int8 KV
    pages: ``q`` (B, 1, H, D) one query per row, ``kq``/``vq``
    (B, L, H, D) int8 with per-(b, l, h) fp32 scales (the
    ``quantize_kv``/arena layout), ``lengths`` (B,) live tokens per row —
    keys at ``kpos >= length`` are masked, which IS the causal mask when
    ``length = position + 1``. Dequant happens per VMEM tile inside the
    kernel; the fp K/V never exist in HBM. Forward-only (decode).
    ``interpret=None`` auto-selects interpreter mode off-TPU."""

    def attn(q, kq, ks, vq, vs, lengths):
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        use_interpret = pallas_interpret(interpret)
        b, lq, h, d = q.shape
        if lq != 1:
            raise ValueError(f"paged decode kernel is one query per row "
                             f"(got Lq={lq})")
        lk = kq.shape[1]
        _, bk = _blocks(lq, lk, lq, block_k)
        qf = _fold(q)                                  # (B*H, 1, D)
        kf, vf = _fold(kq), _fold(vq)                  # (B*H, L, D) int8
        scale = 1.0 / math.sqrt(d)

        def fold_scale(s):
            s2 = jnp.swapaxes(s, 1, 2).reshape(b * h, lk)
            return jnp.broadcast_to(s2[..., None], (b * h, lk, _STAT_LANES))
        ksf, vsf = fold_scale(ks), fold_scale(vs)
        # per-(b, h) live length in the stat-lane layout: (B*H, 1, LANES)
        lens = jnp.broadcast_to(
            jnp.repeat(lengths.astype(jnp.float32), h)[:, None, None],
            (b * h, 1, _STAT_LANES))
        grid = (b * h, lk // bk)

        out = pl.pallas_call(
            functools.partial(_paged_int8kv_kernel, bq=lq, bk=bk,
                              nk=lk // bk, scale=scale),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, _STAT_LANES),
                             lambda bh, ik: (bh, ik, 0)),
                pl.BlockSpec((1, bk, _STAT_LANES),
                             lambda bh, ik: (bh, ik, 0)),
                pl.BlockSpec((1, 1, _STAT_LANES),
                             lambda bh, ik: (bh, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, lq, d), lambda bh, ik: (bh, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
            scratch_shapes=[
                pltpu.VMEM((lq, d), jnp.float32),           # acc
                pltpu.VMEM((lq, _STAT_LANES), jnp.float32),  # running max
                pltpu.VMEM((lq, _STAT_LANES), jnp.float32),  # running sum
            ],
            interpret=use_interpret,
        )(qf, kf, vf, ksf, vsf, lens)
        return jnp.swapaxes(out.reshape(b, h, lq, d), 1, 2)

    return attn


# ---------------------------------------------------------------------------
# in-place decode read (block table -> live pages, no gathered copy)
# ---------------------------------------------------------------------------
#
# The gathered read above costs what the TABLE holds: every block-table
# entry of every slot is copied out of the arena, cast, scored and summed
# whatever the slots' lengths are. The decode tick needs one query a row
# over the rows below its length, so this kernel walks the block table
# itself: grid over the slots, block table and lengths prefetched to SMEM,
# the arenas left in HBM in the layout they have, and whole pages (one
# contiguous (page_size, H, D) block each) fetched by async copies into a
# two-slot VMEM buffer, ``_DECODE_CHUNK_PAGES`` a chunk, the next chunk's
# copies (the next SLOT's first chunk after a slot's last) in flight while
# the current one is scored. Only chunks below a row's length are walked and
# only pages below it fetched; an inactive slot (length 1, all-trash table)
# costs one page.
#
# Math per chunk, on the VPU with H on the sublanes throughout (one query
# a row and no grouped heads leave the MXU nothing to win; the v5e has no
# bf16 VPU, so the tile is cast to float32 in VMEM): s = sum_d k * q,
# mask kpos < length, online max/sum, acc = acc * alpha + sum_t p * v.

_DECODE_CHUNK_PAGES = 8      # pages a chunk (a constant of the kernel)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sem, parity_ref, *,
                         page_size, chunk_pages, scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    nb = pl.num_programs(0)
    tokens = chunk_pages * page_size             # rows a chunk
    h, d = q_ref.shape[1], q_ref.shape[2]

    def copies(row, chunk, slot, op):
        """Start or wait for chunk ``chunk`` of slot ``row``: its pages
        below the row's length, K and V, into buffer ``slot``. Pages past
        the length are not fetched."""
        first = chunk * chunk_pages
        live = jnp.minimum(pl.cdiv(len_ref[row], page_size) - first,
                           chunk_pages)

        def page_copy(j, _):
            page = bt_ref[row, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for i, (arena, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    arena.at[page], buf.at[slot, dst], sem.at[i, slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return 0

        jax.lax.fori_loop(0, live, page_copy, 0)

    @pl.when(b == 0)
    def _first():
        parity_ref[0] = 0
        copies(0, 0, 0, "start")

    length = len_ref[b]
    n_chunks = pl.cdiv(length, tokens)           # >= 1: lengths are >= 1
    parity = parity_ref[0]                       # buffer of this row's chunk 0
    q = q_ref[0].astype(jnp.float32) * scale     # (H, D)

    def chunk_step(c, carry):
        slot = (parity + c) % 2
        last = c == n_chunks - 1

        @pl.when(jnp.logical_not(last))
        def _():
            copies(b, c + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(last, b + 1 < nb))
        def _():
            copies(b + 1, 0, 1 - slot, "start")

        copies(b, c, slot, "wait")

        def attend(carry, first, rows):
            """``rows`` (static) rows of the buffer from ``first`` on."""
            m, l, acc = carry
            at = pl.ds(first, rows)
            k = kbuf[slot, at].astype(jnp.float32)                # (T, H, D)
            v = vbuf[slot, at].astype(jnp.float32)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)      # (T, H, 1)
            kpos = c * tokens + first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, h, 1), 0)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))            # (H, 1)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[None])         # masked rows: exactly 0
            l_new = l * alpha + jnp.sum(p, axis=0)
            acc_new = acc * alpha + jnp.sum(p * v, axis=0)        # (H, D)
            return m_new, l_new, acc_new

        # a chunk wholly below the length is one block of arithmetic (the
        # least VPU time a row, which a full table needs to stay behind its
        # DMA); a row's last chunk, and an inactive slot's only one, walks
        # its fetched pages one by one and touches nothing past them
        live = length - c * tokens
        return jax.lax.cond(
            live >= tokens,
            lambda: attend(carry, 0, tokens),
            lambda: jax.lax.fori_loop(
                0, pl.cdiv(live, page_size),
                lambda j, carry: attend(
                    carry, pl.multiple_of(j * page_size, page_size),
                    page_size), carry))

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_step, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    parity_ref[0] = (parity + n_chunks) % 2


def paged_decode_attention(q, k_arena, v_arena, block_tables, lengths, *,
                           interpret: bool | None = None):
    """One query a row over its LIVE pages, read in place: ``q``
    (B, 1, H, D), arenas (num_pages + 1, page_size, H, D) as the pool lays
    them out (bf16 or fp32, never copied or cast in HBM), ``block_tables``
    (B, max_pages) i32 whose entries past a row's pages hold the trash row,
    ``lengths`` (B,) i32 >= 1 (``position + 1``: keys at ``kpos >= length``
    are masked, which IS the causal mask of a decode tick). Float32 scores,
    softmax statistics and accumulator; forward-only.
    ``interpret=None`` auto-selects interpreter mode off-TPU."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, h, d = q.shape
    if lq != 1:
        raise ValueError(f"paged decode kernel is one query per row "
                         f"(got Lq={lq})")
    page_size = k_arena.shape[1]
    chunk_pages = min(_DECODE_CHUNK_PAGES, block_tables.shape[1])
    buf = (2, chunk_pages * page_size, h, d)
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, chunk_pages=chunk_pages,
        scale=1.0 / math.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, bt, ln: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda i, bt, ln: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(buf, k_arena.dtype),
                pltpu.VMEM(buf, v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=pallas_interpret(interpret),
    )(block_tables.astype(jnp.int32),
      # a length the table cannot hold (a draft window overrunning max_len)
      # reads what the gathered mask would let it read: every table entry
      jnp.clip(lengths.astype(jnp.int32), 1,
               block_tables.shape[1] * page_size),
      q[:, 0], k_arena, v_arena)
    return out[:, None]


def decode_read(layer: PagedLayer, lq: int, sp_mesh=None,
                group: int = 1, head_dim: int = None) -> str:
    """Which way a non-prefill read of ``layer`` goes, from what the inputs
    show and nothing else: ``"pages"`` (:func:`paged_decode_attention`) for
    one query a row over unsharded bf16 or fp32 arenas whose head_dim fills
    the lanes, ``"gathered"`` for everything else — the Lq > 1 verify and
    chunk windows, int8 pages, sp-sharded arenas, heads narrower than a
    lane row (every toy model of tests/test_serve.py), and grouped heads
    (``group`` query heads a KV head: the kernel reads one head for one).

    A layer laid out in ROWS (3-D arenas, :class:`PagedLayer`; rings too)
    is read one query a row by :func:`grouped_read`: ``"pages"``
    (:func:`paged_grouped_decode_attention`, any ``group``) where a head of
    ``head_dim`` fills whole lane rows and a page whole sublane tiles,
    else ``"gathered"``. A wider window (Lq > 1: a prefill chunk, a verify
    window) over a block-table rows layer is ``"gathered"`` too:
    :func:`paged_attend` gathers the slot's rows and views them by head.
    Refused by name: Lq > 1 over a ring (a chunk's rows would need the ring
    as it stood at each of them), int8 rows, an sp mesh."""
    if layer.k.ndim == 3:
        if (layer.quant != "none" or sp_mesh is not None
                or (lq != 1 and layer.ring)):
            raise NotImplementedError(
                "a KV layer laid out in rows (a window ring, a layer that "
                "other layers read, a grouped-head layer read in place) is "
                "read from unsharded bf16 or fp32 pages, and a ring one "
                "query a row: no int8 pages, no sp mesh, no verify or chunk "
                "window (Lq > 1) over a ring")
        if lq != 1:
            return "gathered"
        _, page, f = layer.k.shape
        d = head_dim or f
        tile = 32 // layer.k.dtype.itemsize      # rows a sublane tile
        return ("pages" if d % 128 == 0 and f % d == 0 and page % tile == 0
                else "gathered")
    if (lq != 1 or layer.quant != "none" or sp_mesh is not None
            or group != 1):
        return "gathered"
    # what Mosaic's tiling of the arena's (H, D) minor dims takes: D whole
    # lane rows, and a page slice's H rows whole sublane tiles ((8, 128)
    # rows for 4-byte arenas of any H; 2-byte arenas tile H by 8, or by
    # 4 or 2 when H is that small)
    _, _, h, d = layer.k.shape
    whole_tiles = (layer.k.dtype.itemsize == 4 or h % 8 == 0
                   or h in (2, 4))
    return "pages" if d % 128 == 0 and whole_tiles else "gathered"


# ---------------------------------------------------------------------------
# grouped in-place decode read (rows layout: block-table pages and rings)
# ---------------------------------------------------------------------------
#
# The kernel above reads one KV head for one query head on the VPU. A layer
# whose query heads share KV heads (``group`` of them a KV head) has ``group``
# times the arithmetic a byte, which the VPU cannot hide behind the DMA, and
# its KV head count need not tile the sublanes (10 bf16 heads do not). So its
# pages are ROWS: a token's ``kv_heads * D`` values side by side, whole lane
# rows whatever the head count, and the read runs on the MXU with every head
# in ONE product a chunk: the queries are laid out block-diagonally, row
# ``h`` of ``q_bd`` [H, kv_heads * D] holding query head ``h`` in the lanes of
# its KV head ``h // group`` and zeros elsewhere, so ``q_bd K^T`` [H, T] is
# every head's scores over a chunk's T tokens, and ``P V`` [H, kv_heads * D]
# holds head ``h``'s output in the lanes of its KV head (the other lanes are
# other heads' values under this head's weights: computed, not read). The
# walk is the kernel above's: block table and lengths prefetched, whole pages
# fetched by async copies into a two-slot buffer, a chunk ahead.
#
# A ring (``window`` set) is the same walk over a slot's own pages: position
# ``t`` sits at row ``t % ring_rows``, so with the query at position ``pos``
# row ``r`` holds the key of age ``(pos - r) mod ring_rows`` (the latest
# position at that row), live iff it was written (``r <= pos``) and lies
# inside the window (age < ``window``). Attention over a set of keys does not
# care in which order the rows hold them.

_GROUPED_CHUNK_PAGES = 16    # pages a chunk (a constant of the kernel)


def _live_rows(rows, pos, length, window, ring_rows):
    """Which KV rows a query at ``pos`` reads: below ``length`` and, in a
    ring, of an age inside the window. Shapes broadcast."""
    live = rows < length
    if window is not None:
        live &= jax.lax.rem(pos - rows + ring_rows, ring_rows) < window
    return live


def _grouped_decode_kernel(bt_ref, len_ref, pos_ref, q_ref, k_hbm, v_hbm,
                           o_ref, kbuf, vbuf, sem, parity_ref, *, page_size,
                           chunk_pages, kv_heads, window, ring_rows,
                           precision):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    nb = pl.num_programs(0)
    tokens = chunk_pages * page_size             # rows a chunk
    h, d = q_ref.shape[1], q_ref.shape[2]
    group = h // kv_heads

    def copies(row, chunk, slot, op):
        first = chunk * chunk_pages
        live = jnp.minimum(pl.cdiv(len_ref[row], page_size) - first,
                           chunk_pages)

        def page_copy(j, _):
            page = bt_ref[row, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for i, (arena, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    arena.at[page], buf.at[slot, dst], sem.at[i, slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return 0

        jax.lax.fori_loop(0, live, page_copy, 0)

    @pl.when(b == 0)
    def _first():
        # rows of a chunk past a slot's last page are never fetched; their
        # scores are masked, and the zeros here keep 0 * (whatever the
        # buffer held) out of the weighted sum
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        parity_ref[0] = 0
        copies(0, 0, 0, "start")

    length = len_ref[b]
    pos = pos_ref[b]
    n_chunks = pl.cdiv(length, tokens)           # >= 1: lengths are >= 1
    parity = parity_ref[0]
    # the block-diagonal queries: head h in the lanes of KV head h // group
    q = q_ref[0]                                                   # (H, D)
    q_bd = jnp.concatenate([q] * kv_heads, axis=1)                 # (H, F)
    own = (jax.lax.broadcasted_iota(jnp.int32, q_bd.shape, 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, q_bd.shape, 0) // group)
    q_bd = jnp.where(own, q_bd, jnp.zeros_like(q_bd))

    def chunk_step(c, carry):
        m, l, acc = carry
        slot = (parity + c) % 2
        last = c == n_chunks - 1

        @pl.when(jnp.logical_not(last))
        def _():
            copies(b, c + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(last, b + 1 < nb))
        def _():
            copies(b + 1, 0, 1 - slot, "start")

        copies(b, c, slot, "wait")
        s = jax.lax.dot_general(
            q_bd, kbuf[slot], (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)                    # (H, T)
        rows = c * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, tokens), 1)
        s = jnp.where(_live_rows(rows, pos, length, window, ring_rows),
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))  # (H, 1)
        alpha = jnp.exp(m - m_new)
        # a chunk with no live row at all (a ring's last page can be one)
        # leaves m where it was: its weights are zeros, not exp(0)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, kv_heads * d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_step, (m0, l0, acc0))
    # head h's own lanes of its row
    head = jax.lax.broadcasted_iota(jnp.int32, (h, d), 0) // group
    out = jnp.zeros((h, d), jnp.float32)
    for j in range(kv_heads):
        out = jnp.where(head == j, acc[:, j * d:(j + 1) * d], out)
    o_ref[0] = (out / l).astype(o_ref.dtype)
    parity_ref[0] = (parity + n_chunks) % 2


def paged_grouped_decode_attention(q, k_arena, v_arena, block_tables,
                                   positions, *, kv_heads: int, scale: float,
                                   window: int = None,
                                   interpret: bool | None = None):
    """One query a row over its LIVE rows of a ROWS-layout arena, read in
    place: ``q`` (B, H, D) with ``H = kv_heads * group`` (query heads ``j *
    group .. (j + 1) * group - 1`` read KV head ``j``), arenas (pages,
    page_size, kv_heads * D), ``block_tables`` (B, P) i32, ``positions``
    (B,) i32 the queries' own positions (keys at ``t <= position`` are
    read). ``window``: the table is a RING of ``P * page_size`` rows
    (position ``t`` at row ``t % rows``) and only keys at ``t > position -
    window`` are read. ``scale`` multiplies the scores (a model's own: the
    rows may hold heads padded wider than the softmax's). Float32 scores,
    statistics and accumulator; returns float32 (B, H, D); forward-only.
    ``interpret=None`` auto-selects interpreter mode off-TPU."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    page_size, f = k_arena.shape[1:]
    table_rows = block_tables.shape[1] * page_size
    chunk_pages = min(_GROUPED_CHUNK_PAGES, block_tables.shape[1])
    buf = (2, chunk_pages * page_size, f)
    full = k_arena.dtype == jnp.float32
    kernel = functools.partial(
        _grouped_decode_kernel, page_size=page_size, chunk_pages=chunk_pages,
        kv_heads=kv_heads, window=window, ring_rows=table_rows,
        precision=jax.lax.Precision.HIGHEST if full else None)
    positions = positions.astype(jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, bt, ln, ps: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda i, bt, ln, ps: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(buf, k_arena.dtype),
                pltpu.VMEM(buf, v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        interpret=pallas_interpret(interpret),
    )(block_tables.astype(jnp.int32),
      # rows to walk: a ring is walked whole once it has wrapped
      jnp.clip(positions + 1, 1, table_rows), positions,
      (q.astype(jnp.float32) * scale).astype(k_arena.dtype),
      k_arena, v_arena)
    return out


def grouped_gathered_attention(q, k_arena, v_arena, block_tables, positions,
                               *, kv_heads: int, scale: float,
                               window: int = None):
    """:func:`paged_grouped_decode_attention`'s result by a gathered copy of
    every table entry and one masked softmax: the read of a rows layout whose
    shapes the kernel's tiling does not take (toy widths), and what the
    kernel is tested against."""
    b, h, d = q.shape
    rows = block_tables.shape[1] * k_arena.shape[1]
    gk = gather_pages(k_arena, block_tables).reshape(b, rows, kv_heads, d)
    gv = gather_pages(v_arena, block_tables).reshape(b, rows, kv_heads, d)
    qg = (q.astype(jnp.float32) * scale).astype(k_arena.dtype).reshape(
        b, kv_heads, h // kv_heads, d)
    s = jnp.einsum("bjgd,bkjd->bjgk", qg, gk,
                   preferred_element_type=jnp.float32)
    pos = positions.astype(jnp.int32)[:, None]
    live = _live_rows(jnp.arange(rows, dtype=jnp.int32)[None, :], pos,
                      jnp.minimum(pos + 1, rows), window, rows)
    w = jax.nn.softmax(jnp.where(live[:, None, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bjgk,bkjd->bjgd", w.astype(gv.dtype), gv,
                      preferred_element_type=jnp.float32).reshape(b, h, d)


def ring_block_tables(slots, ring: int):
    """(B,) slot indices -> (B, ring) the pages of each slot's ring."""
    return (slots.astype(jnp.int32)[:, None] * ring
            + jnp.arange(ring, dtype=jnp.int32)[None, :])


def grouped_write(layer: PagedLayer, k, v, block_tables, positions, valid):
    """Scatter ``k``/``v`` (B, L, kv_heads * D) into a rows-layout layer at
    ``positions`` (B, L) through ``block_tables`` (a ring's own, from
    :func:`ring_block_tables`, or the scheduler's); rows where ``valid`` is
    False are written nowhere. A ring wraps: position ``t`` lands at row
    ``t % (ring * page_size)`` of its slot."""
    n, page = layer.k.shape[:2]
    if layer.ring:
        positions = positions % (layer.ring * page)
    flat = jnp.where(valid, flat_slot_index(block_tables, positions, page),
                     n * page).reshape(-1)              # out of range: dropped

    def put(arena, x):
        rows = arena.reshape((n * page,) + arena.shape[2:])
        return rows.at[flat].set(
            x.reshape((-1,) + x.shape[2:]).astype(arena.dtype),
            mode="drop").reshape(arena.shape)

    return layer.replace(k=put(layer.k, k), v=put(layer.v, v))


def grouped_read(q, layer: PagedLayer, block_tables, positions, *,
                 kv_heads: int, scale: float, window: int = None):
    """One query a row (``q`` (B, H, D)) over a rows-layout layer, in place
    where :func:`decode_read` says the shapes allow it."""
    how = decode_read(layer, 1, None, q.shape[1] // kv_heads, q.shape[2])
    read = (paged_grouped_decode_attention if how == "pages"
            else grouped_gathered_attention)
    return read(q, layer.k, layer.v, block_tables, positions,
                kv_heads=kv_heads, scale=scale, window=window)


# ---------------------------------------------------------------------------
# the attend_maybe_cached delegate
# ---------------------------------------------------------------------------

def _quantize_rows(x):
    """(B, L, H, D) -> int8 values + per-(b, l, h) fp32 scales — the
    ``quantize_kv`` arena convention, via ``ops.quant.quantize_int8``."""
    from tpu_dist.ops.quant import quantize_int8

    q, scale = quantize_int8(x, (-1,))
    return q, scale[..., 0].astype(jnp.float32)


def _attend_prompt(q, k, v, attn_fn):
    """Causal self-attention over the prompt itself — exactly the training
    contraction, so flash/blockwise plug-ins keep working (grouped heads:
    the pages hold the KV heads, the contraction gets them broadcast to
    the query heads)."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    return attn_fn(q, k, v)


def _rows_attend(q, k, v, layer, bt, positions, write_pos, valid, sp_mesh,
                 *, prefill: bool, attn_fn):
    """:func:`paged_attend` over a ROWS layer behind the scheduler's block
    tables: ``q`` (B, Lq, H, D), ``k``/``v`` (B, Lq, kv_heads, D)."""
    b, lq, kv, d = k.shape
    decode_read(layer, lq, sp_mesh)      # a ring, int8 rows, an sp mesh
    new_layer = grouped_write(layer, k.reshape(b, lq, kv * d),
                              v.reshape(b, lq, kv * d), bt, write_pos, valid)
    if prefill:
        return _attend_prompt(q, k, v, attn_fn), new_layer
    with jax.named_scope("paged_read"):
        if lq == 1:
            out = grouped_read(q[:, 0], new_layer, bt, positions,
                               kv_heads=kv, scale=1.0 / math.sqrt(d))
            return out[:, None].astype(q.dtype), new_layer
        # a chunk or verify window: the slot's rows gathered and viewed by
        # head, each local query at its own causal horizon
        gk, gv = (gather_pages(x, bt).reshape(b, -1, kv, d)
                  for x in (new_layer.k, new_layer.v))
        return masked_attention(q, gk, gv, positions), new_layer


def paged_attend(q, k, v, paged: dict, *, prefill: bool, attn_fn, dtype):
    """One layer's paged-cache attention step; the delegate
    ``models.transformer.attend_maybe_cached`` calls when a ``paged`` pack
    is threaded through the model.

    ``paged`` carries the layer's arenas plus the shared context:
    ``{"layer": PagedLayer, "block_tables": (B, max_pages) i32,
    "positions": (B,) i32, "lengths": (B,) i32}`` plus an optional
    ``"valid"`` (B, Lq) bool write mask and an optional ``"sp_mesh"``
    (a static ``jax.sharding.Mesh`` carrying :data:`~tpu_dist.parallel.
    mesh.SP_AXIS`): when set, the arenas are sp-sharded, the block tables
    hold FLAT arena rows, and reads/writes route through
    :func:`sp_gather_pages` / :func:`sp_paged_write`. Prefill (``prefill=True``): the
    queries attend within the prompt through the model's own ``attn_fn``
    (plain causal self-attention — nothing to read back), and the leading
    ``lengths[b]`` K/V rows are written to the pages — unless ``valid``
    narrows them further (prefix caching skips the rows whose pages are
    SHARED with an identical earlier prompt: rewriting them would race
    the frontier fork and the bits are already there). The tick
    (``prefill=False``) writes Lq rows at ``positions[b]..positions[b]+
    Lq-1`` and attends each local query at its own per-row position —
    Lq == 1 is the classic decode tick, Lq > 1 the speculative-decoding
    verify window (``valid`` masks rows past a sequence's token cap to
    the trash page: a draft can overrun the end of a short request, and
    an unmasked overrun would clamp into a LIVE page).

    A layer laid out in ROWS (3-D arenas: the pool builds them where a
    model's ``cache_layout()`` entry ends in ``"rows"``; the expert model's
    attention layer is one) goes the same way by the rank of the arena it
    is handed and nothing else: written through :func:`grouped_write`
    (masked rows land nowhere), a prefill attends within the prompt as
    above, the tick (Lq == 1) reads through :func:`grouped_read` at
    ``positions`` (in place where :func:`decode_read` says the shapes tile,
    the gathered twin where not), and a wider window (Lq > 1: a prefill
    chunk) gathers the slot's rows, views them ``(B, T, kv_heads,
    head_dim)`` and runs :func:`masked_attention` as the 4-D layout does.
    Int8 rows, an sp mesh and a ring are :func:`decode_read`'s refusals.

    Returns ``(out, new_layer)`` — the functionally-updated arenas thread
    back out through the model call.
    """
    layer = paged["layer"]
    bt = paged["block_tables"]
    positions = paged["positions"]
    lengths = paged["lengths"]
    sp_mesh = paged.get("sp_mesh")               # None = unsharded arenas
    trash = layer.num_pages                      # the extra page's index

    b, lq = q.shape[0], q.shape[1]
    # unified write geometry: rows land at positions[b]..positions[b]+Lq-1.
    # Monolithic prefill passes positions == 0 (identical indices to the
    # old arange-only form); CHUNKED prefill and the sp prefill shard pass
    # the chunk/shard's global start here, which is what lets one scatter
    # serve whole-prompt, chunk-at-a-time, and per-device-shard writes.
    write_pos = (positions[:, None].astype(jnp.int32)
                 + jnp.arange(lq, dtype=jnp.int32)[None, :])      # (B, Lq)
    if prefill:
        valid = write_pos < lengths[:, None]
    else:
        valid = jnp.ones((b, lq), dtype=bool)
    if paged.get("valid") is not None:
        valid = valid & paged["valid"]

    if layer.k.ndim == 3:
        return _rows_attend(q, k, v, layer, bt, positions, write_pos, valid,
                            sp_mesh, prefill=prefill, attn_fn=attn_fn)

    if sp_mesh is None:
        def write(arena, vals):
            return paged_write(arena, bt, write_pos, vals, valid, trash)

        def read(arena):
            return gather_pages(arena, bt)
    else:
        # sp-sharded arenas: block tables hold FLAT rows, ownership is
        # row // rows_local, and the collectives above do the routing
        def write(arena, vals):
            return sp_paged_write(arena, bt, write_pos, vals, valid,
                                  sp_mesh)

        def read(arena):
            return sp_gather_pages(arena, bt, sp_mesh)

    if layer.quant == "int8":
        kq, ks = _quantize_rows(k)
        vq, vs = _quantize_rows(v)
        new_layer = layer.replace(
            k=write(layer.k, kq), v=write(layer.v, vq),
            k_scale=write(layer.k_scale, ks),
            v_scale=write(layer.v_scale, vs))
    else:
        new_layer = layer.replace(
            k=write(layer.k, k), v=write(layer.v, v))

    group = q.shape[2] // k.shape[2]
    if prefill:
        return _attend_prompt(q, k, v, attn_fn), new_layer

    # the paged read: the in-place kernel, or the gather of every slot's
    # pages and everything that consumes the gathered rows (cast or dequant,
    # scores, weighted sum), named so that a trace finds it whatever shapes
    # or kernel it has
    with jax.named_scope("paged_read"):
        if decode_read(layer, lq, sp_mesh, group) == "pages":
            # positions + 1, not ``lengths``: the tick's own causal horizon
            # (the same the gathered mask below is built from)
            out = paged_decode_attention(q, new_layer.k, new_layer.v, bt,
                                         positions + 1)
            return out, new_layer

        if (layer.quant == "int8" and layer.read == "flash" and lq == 1
                and group == 1):
            # the Pallas kernel is one-query-per-row (the decode tick); the
            # Lq > 1 verify window reads through the exact dequant path
            # below — same math, and verify dispatches are 1-in-k ticks by
            # design. Under an sp-sharded pool the gathered view is
            # replicated by the psum, so the kernel composes UNCHANGED —
            # sharding lives entirely in the gather.
            out = int8kv_paged_flash_attention_fn()(
                q, read(new_layer.k), read(new_layer.k_scale),
                read(new_layer.v), read(new_layer.v_scale),
                positions + 1)
            return out.astype(q.dtype), new_layer

        gk = read(new_layer.k)
        gv = read(new_layer.v)
        if layer.quant == "int8":
            gk = (gk.astype(jnp.float32)
                  * read(new_layer.k_scale)[..., None]).astype(dtype)
            gv = (gv.astype(jnp.float32)
                  * read(new_layer.v_scale)[..., None]).astype(dtype)
        return masked_attention(q, gk, gv, positions), new_layer
