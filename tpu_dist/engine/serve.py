"""Continuous-batching decode engine over the paged KV cache.

``engine.generate`` answers "decode this batch"; a server has to answer
"decode this *stream*": requests arrive at their own times with their own
prompt/output lengths, and the offline pattern — admit a fixed batch, run
it to the longest sequence's completion, repeat — leaves most slots idle
most of the time. This module implements Orca-style iteration-level
scheduling [OSDI '22]: admission decisions happen at every decode tick, a
finished sequence's slot and pages are reclaimed and refilled at the next
iteration, and the headline metric becomes throughput-under-load (completed
requests/s at a latency SLO), not offline tok/s.

Composition (each piece usable alone):

* :class:`tpu_dist.engine.kv_cache.PagedKVPool` backs every sequence with
  block-table pages (bf16/fp32, or int8+scales via the PR 9 ``quantize_kv``
  convention) — mixed-length sequences share HBM without fragmentation —
  and, for the layers whose ``model.cache_layout()`` says ``slot_state``
  (a state-space layer's recurrent state and convolution tail), with
  per-slot arrays beside the pages; a ``window`` layer keeps a ring a
  slot, a ``shared`` layer nothing (the class docstring has the four
  kinds and their rules);
* two jitted programs serve all traffic: ``prefill`` (one admit's prompt,
  padded to a length bucket, writing its pages and sampling the first
  token) and ``decode_tick`` (the packed slot set, one token per active
  sequence, per-slot positions — inactive slots ride along masked to the
  pool's trash page, so the program never re-specializes on occupancy);
* the plain decode tick runs **one tick ahead of the host**: its small
  inputs (block tables, each slot's last token, its position) stay on the
  device, and an iteration dispatches tick n + 1 before it reads tick n's
  tokens, so the device computes while the host emits, evicts and admits
  (``ServeEngine._tick_plain`` has the rules: who ends by budget is known
  before the dispatch, an ``eos_id`` end is seen one tick late and its
  extra token dropped);
* a step's monolithic admissions run as a **pipeline of depth one**:
  admission k + 1 is planned and its prefill program called before
  admission k's first token is read (``ServeEngine._admit``), so a burst's
  prefills follow each other on the device while the host plans, uploads
  and reads; the device's order of programs, the random key's path and
  each request's stamps are what a one-at-a-time loop gives, and nothing
  is unread when ``_admit`` returns;
* **speculative decoding** (``spec_k > 0``): a small draft model over the
  shared base proposes k greedy tokens per slot and ONE jitted program per
  tick both drafts and verifies — the draft scan rides its own page arenas
  (same block tables, so pages stay interchangeable) and the base
  verification is a single (k+1)-wide multi-position read over the main
  arenas; accept/reject resolves as an in-program per-row gather, so the
  tick stays one dispatch and emits up to k tokens per slot. Greedy
  emission is token-for-token identical to non-speculative greedy decode
  for ANY draft (the verifier's argmax corrects the first divergence), so
  acceptance rate only moves THROUGHPUT, never output;
* **copy-on-write prefix caching** (``prefix_cache``): admission asks the
  pool for pages an identical earlier prompt prefix already filled
  (refcounted sharing + token-hash prefix index, ``engine.kv_cache``),
  prefill skips the resident rows, and the one shareable page a request
  can ever write — the frontier page holding its prompt tail — is forked
  onto a page reserved at admission (``ops.paged_attention.
  cow_fork_pages``) right before its first divergent write. Hot system
  prompts cost ~0 fresh pages per request; shared decode is bit-identical
  to unshared because shared rows are the original writer's bits, re-read
  not re-written;
* admission control is SLO-aware: a hard queue-depth cap rejects at
  submit time, and an EMA of queue wait (the
  ``GoodputMonitor`` hysteresis pattern) sheds new work while the backlog
  breaches the floor — emitting the standard ``slo`` ledger event, which
  auto-triggers the flight recorder through the existing sink fan-out;
* every request lands in the ledger (``admit``/``request`` events), pool
  pressure in periodic ``kv_cache`` events, and the metrics sink exports
  ``tpu_dist_serve_queue_depth`` / ``tpu_dist_serve_active_seqs`` /
  ``tpu_dist_kv_pages_free`` gauges — scrape-able on day one.

Sampling and weight quantization are SHARED with ``engine.generate``
(:func:`~tpu_dist.engine.generate._sample`,
:func:`~tpu_dist.engine.generate._quantize_for_decode`): the one-shot
contiguous-cache call is the single-request degenerate case of this path,
and greedy tokens are bit-identical across the two (tests/test_serve.py).

The scheduler itself is host-side and clock-agnostic: ``now_fn`` defaults
to ``time.monotonic``, and tests/trace replay pass a virtual clock for
fully deterministic runs (the ROADMAP's million-user-on-CPU direction).
Multi-host/mesh serving is future work — params stay wherever the caller
put them (single-process serving is the shape this PR pins down).
"""

from __future__ import annotations

import signal
import threading
import time
from collections import deque
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Callable, Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist._compat import shard_map
from tpu_dist.engine.generate import (_quantize_for_decode, _refuse_wo_tree,
                                      _sample, prepare_draft)
from tpu_dist.engine.kv_cache import PagedKVPool
from tpu_dist.obs import trace
from tpu_dist.obs.reqtrace import RequestTracer
from tpu_dist.ops.paged_attention import cow_fork_pages, decode_read
from tpu_dist.parallel.mesh import SP_AXIS
from tpu_dist.parallel.ring_attention import ring_attention_fn
from tpu_dist.plan.compile import check_audit_sentry, register_audit_program

# weight of the newest queue wait in the EMA that SLO shedding reads
_SLO_ALPHA = 0.5


@dataclass
class DecodeRequest:
    """One generation request: continue ``prompt`` by ``max_new_tokens``
    (or until ``ServeConfig.eos_id``). ``rid`` is the caller's correlation
    id — it rides every ledger event this request produces — and
    ``tenant`` (optional) names the traffic class, so multi-tenant
    deployments get per-tenant SLO accounting from the same ``request``
    events (tools/fleet_report.py renders the percentiles)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    tenant: Optional[str] = None


@dataclass
class Completion:
    """A finished request with its serving timeline (engine-clock
    timestamps: real seconds under the default clock, virtual units under
    an injected one)."""

    rid: int
    tokens: np.ndarray           # (prompt + generated,) int32
    prompt_len: int
    n_generated: int
    admit_ts: float              # entered the queue (submit time)
    start_ts: float              # left the queue (prefill start)
    first_token_ts: float
    finish_ts: float
    # one engine-clock time per generated token (the clock read after the
    # device_get that brought it): token_ts[0] is first_token_ts,
    # token_ts[-1] is finish_ts
    token_ts: np.ndarray
    # of first_token_ts..finish_ts: the seconds this request stood behind
    # OTHER requests' admissions (their prefills' own time, engine clock)
    # and inside Python's garbage collections (real seconds always)
    behind_prefill_s: float = 0.0
    behind_gc_s: float = 0.0

    @property
    def queue_wait_s(self) -> float:
        return self.start_ts - self.admit_ts

    @property
    def ttft_s(self) -> float:
        return self.first_token_ts - self.admit_ts


@dataclass
class ServeConfig:
    """Scheduler + paged-cache knobs (README "Serving" has the tour)."""

    max_slots: int = 4           # concurrent sequences (the packed batch)
    page_size: int = 16          # tokens per KV page
    num_pages: int = 256         # pool capacity (per layer, +1 trash page)
    max_len: Optional[int] = None   # per-sequence cap (default model.max_len)
    quant: str = "none"          # weight quant (int8_wo pre-quantizes once)
    kv_quant: str = "none"       # page dtype: none (model dtype) | int8
    attn_read: str = "exact"     # exact | flash (int8-KV Pallas kernel)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: Optional[int] = None
    queue_depth_max: int = 64    # hard admission cap
    slo_queue_wait_s: float = 0.0      # EMA floor; 0 disables shedding
    slo_min_samples: int = 2
    kv_event_every: int = 0      # ticks between kv_cache events (0 = final)
    spec_k: int = 0              # draft tokens per tick (0 = plain decode)
    prefix_cache: bool = False   # CoW prefix sharing across requests
    # chunked prefill (long-context tail stability): prompts longer than
    # this run as fixed-size chunks, at most ONE chunk interleaved per
    # scheduler iteration with the decode tick — a 16k admit costs many
    # bounded steps instead of one full-prompt stall. 0 = monolithic.
    prefill_chunk: int = 0
    # sequence-parallel prefill (needs ServeEngine(mesh=...)): prompts at
    # or past this threshold prefill under ring attention over the 'sp'
    # axis, each device scattering its shard's K/V into its LOCAL pages —
    # no full-sequence K/V on any one device. 0 = never.
    sp_prefill_threshold: int = 0
    # request tracing: decode spans coalesce this many ticks per slot into
    # one window span (per-token spans would dwarf the ledger; windows
    # keep the waterfall readable AND tile first-token->finish exactly)
    trace_window_ticks: int = 8


@dataclass
class _Slot:
    req: DecodeRequest
    pages: List[int]
    block_table: np.ndarray      # (max_pages_per_seq,) int32
    buf: np.ndarray              # (prompt + max_new,) int32
    token_ts: np.ndarray         # (max_new,) float64: when each token came
    trace_id: object             # the id this request's spans share
    prompt_len: int
    admit_ts: float
    start_ts: float
    position: int = 0            # next KV write position
    generated: int = 0
    first_token_ts: float = 0.0
    finish_ts: float = 0.0
    done: bool = False
    # copy-on-write: (bt_slot, src_page, dst_page) of a SHARED frontier
    # page this sequence will write into — forked right before its first
    # decode write (engine._resolve_cow), None once private
    cow_pending: Optional[Tuple[int, int, int]] = None
    # chunked prefill state: the next prompt offset to prefill (-1 once
    # the prompt is fully resident and the first token sampled — only
    # then does the slot join the decode tick's active set)
    chunk_next: int = -1
    shared_len: int = 0          # prefix-cache-resident prompt rows
    n_fresh: int = 0             # admission page accounting (span fields)
    n_shared: int = 0
    # request tracing: the open decode-window span (obs.reqtrace) — opens
    # at the first token, closes every trace_window_ticks ticks and at
    # finish, so the windows tile first-token->finish contiguously
    win_start_ts: float = 0.0
    win_ticks: int = 0
    win_tokens: int = 0
    win_drafted: int = 0
    # the engine's prefill_own_s and obs.trace.gc_seconds() as they stood
    # at the first token (this request's own prefill counted already), and
    # how far each had moved when finish_ts was stamped
    own_mark: float = 0.0
    gc_mark: float = 0.0
    behind_prefill_s: float = 0.0
    behind_gc_s: float = 0.0


@dataclass
class _Flight:
    """A plain decode tick the device has been given and the host has not
    read yet."""

    nxt: jax.Array                       # (slots,) its sampled tokens
    slots: List[Tuple[int, _Slot]]       # who decodes in it
    ahead: int   # 1: dispatched while the tick before it was still unread
    # a model with routed expert layers: (3,) i32, unread beside the tokens
    counts: Optional[jax.Array] = None


@dataclass
class _Admission:
    """A request on its way from the queue into a slot: what
    :meth:`ServeEngine._planned` decided for it and, for a monolithic
    prefill, what :meth:`ServeEngine._issue_prefill` leaves for the
    landing in :meth:`ServeEngine._prefill`."""

    slot_idx: int
    req: DecodeRequest
    prompt: np.ndarray
    enq_ts: float
    start_ts: float              # when it left the queue
    kind: str                    # "prefill" | "chunked" | "sp"
    bucket: Optional[int]        # the prefill's (or sp) bucket; chunked: None
    pages: List[int]             # shared first, then the fresh ones
    n_shared: int
    shared_len: int              # prompt rows resident on shared pages
    # a shared frontier page to fork before the first decode write:
    # (block-table slot, the shared page, its reserved destination)
    cow: Optional[Tuple[int, int, int]]
    bt_pages: List[int]          # what the block table reads through
    block_table: np.ndarray
    # a monolithic prefill once its program is called
    tok: Optional[jax.Array] = None   # the first token, unread
    issued: float = 0.0          # the clock just before the program's call
    ahead: int = 0   # 1: called while the prefill before it was unread
    cross_rows: Optional[int] = None
    counts: Optional[jax.Array] = None   # as _Flight.counts

    @property
    def n_fresh(self) -> int:
        return len(self.pages) - self.n_shared


def _slot_state_layers(layout) -> int:
    """How many layers of a model's ``cache_layout()`` keep slot state (an
    entry with no array, a layer that keeps nothing, is not one)."""
    return sum(kind == "slot_state" and bool(spec[0])
               for kind, *spec in layout)


def _kinds(layout, kind: str) -> int:
    return sum(k == kind for k, *_ in layout)


def _routed_layers(model) -> Tuple[int, int]:
    """``(expert layers, experts held in each)`` by the model's own answer
    (``routed_layers()``); (0, 0) for a model without routed experts."""
    return getattr(model, "routed_layers", lambda: (0, 0))()


def _apply_counted(model, params, *args, **kwargs):
    """``model.apply`` for a serving program: ``(outputs, counts)``. Where
    the model has routed expert layers, ``counts`` is what they sowed into
    ``expert_counts``, summed over the layers, as one (3,) int32 array:
    ``rows`` (assignments of live rows that landed on held experts),
    ``hit`` (held experts with at least one) and ``grouped`` (calls of the
    grouped product: the form the program was traced in, 0 where every
    layer took the masked dense one). For every other model the call is
    the plain one and ``counts`` is None: its program returns nothing
    more than it did."""
    if not _routed_layers(model)[0]:
        return model.apply({"params": params}, *args, **kwargs), None
    out, sown = model.apply({"params": params}, *args,
                            mutable=["expert_counts"], **kwargs)
    flat = jax.tree_util.tree_flatten_with_path(sown["expert_counts"])[0]
    total = lambda name: sum(
        v for path, v in flat
        if any(getattr(k, "key", None) == name for k in path))
    return out, jnp.stack([total("rows"), total("hit"),
                           total("grouped")]).astype(jnp.int32)


def _default_buckets(max_len: int) -> Tuple[int, ...]:
    """Powers of two up to max_len (plus max_len itself): each bucket is
    one compiled prefill geometry, so a handful covers every prompt."""
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


# The compiled serving programs are memoized per (model, sampling)
# signature — jit itself re-specializes per shape (prefill buckets, slot
# count), so one entry serves every geometry of one deployment. Same
# rationale as engine.generate's program caches.

@lru_cache(maxsize=32)
def _prefill_program(model, temperature, top_k, top_p, sp_mesh=None):
    # the arenas are DONATED: the caller (the pool) adopts the returned
    # ones and never touches the old buffers again, and without aliasing
    # every call would copy every layer's whole page arena — per admitted
    # prompt, in the feature that exists to keep KV HBM tight
    @partial(jax.jit, donate_argnums=(1,))
    def prefill(params, layers, block_table, length, shared_len, prompt,
                rng, slot=0):
        # block_table (1, max_pages) i32, length () i32, prompt (1, bucket):
        # causal self-attention over the padded prompt (positions >= length
        # influence nothing earlier), pages written for the live prefix,
        # first token sampled from the last LIVE row's logits. Rows below
        # ``shared_len`` sit on pages SHARED with an earlier identical
        # prefix (prefix caching): already resident, so the write mask
        # skips them — rewriting could drift bits across prefill buckets
        # and would race the other holders' reads. shared_len is traced
        # (0 when nothing is shared), so sharing never re-specializes.
        valid = (jnp.arange(prompt.shape[1], dtype=jnp.int32)[None, :]
                 >= jnp.asarray(shared_len, jnp.int32))
        # Slot state (a layer whose cache_layout is "slot_state") is taken
        # at the prompt's true length, ``live``, not at the bucket's end,
        # and written to row ``slot`` of its arrays.
        lengths = jnp.asarray(length, jnp.int32)[None]
        paged = {"layers": layers, "block_tables": block_table,
                 "positions": jnp.zeros((1,), jnp.int32),
                 "lengths": lengths, "valid": valid, "sp_mesh": sp_mesh,
                 "live": lengths, "slots": jnp.asarray(slot, jnp.int32)[None]}
        (logits, new_layers), counts = _apply_counted(
            model, params, prompt, train=False,
            paged=paged, paged_prefill=True)
        # a model whose last layers cache nothing runs them on the prompt's
        # last live row alone and returns that row's logits, [1, 1, V];
        # what the traced program returned is kept for the span, a bucket
        head_rows[prompt.shape[1]] = logits.shape[1]
        last = logits[:, 0] if logits.shape[1] == 1 else jnp.take_along_axis(
            logits, jnp.reshape(length - 1, (1, 1, 1)).astype(jnp.int32),
            axis=1)[:, 0]
        nxt, rng = _sample(last, temperature, rng, top_k, top_p)
        out = (nxt[0].astype(jnp.int32), new_layers, rng)
        # a model with routed experts: its counters beside the token
        return out if counts is None else (*out, counts)

    head_rows = prefill.head_rows = {}      # bucket -> rows of logits
    return prefill


@lru_cache(maxsize=32)
def _tick_program(model, temperature, top_k, top_p, sp_mesh=None):
    # arenas donated for the same reason as _prefill_program: the tick
    # writes one row per slot and the un-aliased alternative is a full
    # arena copy per generated token
    @partial(jax.jit, donate_argnums=(1,))
    def tick(params, layers, block_tables, tokens, positions, rng):
        # one token per slot at its OWN position; inactive slots carry
        # all-trash block tables and position 0, so their writes land on
        # the trash page and their (ignored) logits cost one lane of the
        # same program — occupancy changes never retrace. The read follows
        # the block table in place where the shapes allow it
        # (ops.paged_attention.decode_read): its cost then follows the
        # slots' lengths, an inactive slot's is one page
        # Slot state: row b of a slot-state array IS slot b, updated in
        # place; a slot the tick carries but does not decode keeps its row
        # (``live`` 0). A decoding slot's position is its prompt's length
        # or more, so position 0 names exactly the slots that sit out.
        # The three small inputs are the engine's device-resident decode
        # state (ServeEngine._tick_plain): ``block_tables`` is handed back
        # in as it is tick after tick, the sampled tokens returned here ARE
        # the next tick's ``tokens``, and ``positions`` comes back through
        # _advance_positions; the host uploads none of them a tick. The
        # random key rides through as before: one split a tick.
        paged = {"layers": layers, "block_tables": block_tables,
                 "positions": positions, "lengths": positions + 1,
                 "sp_mesh": sp_mesh,
                 "live": (positions > 0).astype(jnp.int32)}
        (logits, new_layers), counts = _apply_counted(
            model, params, tokens[:, None], train=False,
            pos_offset=positions, paged=paged)
        nxt, rng = _sample(logits[:, 0], temperature, rng, top_k, top_p)
        out = (nxt.astype(jnp.int32), new_layers, rng)
        return out if counts is None else (*out, counts)

    return tick


# The decode state that lives on the device between ticks (block tables,
# each slot's last token, its position) is moved by two small programs;
# jit specializes each on the engine's (slots, max_pages) once.

@jax.jit
def _advance_positions(positions):
    # dispatched right behind every plain tick: a decoding slot's position
    # moves on by one, a slot that sits out (position 0) stays there
    return jnp.where(positions > 0, positions + 1, 0)


@jax.jit
def _patch_slot_row(block_tables, tokens, positions, patch):
    # one slot's row of the decode state, when it changes: a slot joins the
    # tick (its block-table row, its last token, its position) or leaves it
    # (the trash row, position 0). ``patch`` is one int32 vector, one
    # upload: [slot, token, position, *block-table row]
    slot = patch[0]
    return (block_tables.at[slot].set(patch[3:]),
            tokens.at[slot].set(patch[1]),
            positions.at[slot].set(patch[2]))


@lru_cache(maxsize=32)
def _chunk_prefill_program(model, chunk, sp_mesh=None):
    # One prefill CHUNK: rows [start, start+chunk) of a prompt, written
    # and attended through the SAME per-row-position machinery the decode
    # tick uses (ops.paged_attention, prefill=False) — the chunk's queries
    # read the gathered pages, which at that point hold exactly the
    # earlier chunks' rows plus this chunk's own (causally masked), so
    # chunked greedy is token-for-token the monolithic prefill
    # (tests/test_serve.py pins it; int8 KV pages are the one exception —
    # earlier chunks re-read quantized rows monolithic never quantizes).
    # Returns the last LIVE row's logits (meaningful on the final chunk
    # only) + updated arenas; sampling stays host-sequenced in
    # _sample_first_program so the rng stream advances exactly once per
    # admit, same as monolithic.
    @partial(jax.jit, donate_argnums=(1,))
    def chunk_step(params, layers, block_table, start, length, shared_len,
                   tokens, slot=0):
        pos = jnp.asarray(start, jnp.int32)[None]               # (1,)
        rows = pos[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
        valid = ((rows < jnp.asarray(length, jnp.int32))
                 & (rows >= jnp.asarray(shared_len, jnp.int32)))
        # slot state rides from chunk to chunk in the slot's own row: a
        # chunk starts from it (from zeros at start 0) and leaves the state
        # of its last live row
        paged = {"layers": layers, "block_tables": block_table,
                 "positions": pos, "lengths": pos + chunk,
                 "valid": valid, "sp_mesh": sp_mesh,
                 "live": jnp.clip(jnp.asarray(length, jnp.int32) - pos, 0,
                                  chunk),
                 "slots": jnp.asarray(slot, jnp.int32)[None]}
        logits, new_layers = model.apply(
            {"params": params}, tokens, train=False,
            pos_offset=pos, paged=paged)
        last = jnp.take_along_axis(
            logits,
            jnp.reshape(jnp.clip(length - 1 - start, 0, chunk - 1),
                        (1, 1, 1)).astype(jnp.int32), axis=1)[:, 0]
        return last, new_layers

    return chunk_step


@lru_cache(maxsize=32)
def _sample_first_program(temperature, top_k, top_p):
    # the final chunk's first-token sample: the same _sample call (and the
    # same single rng consumption) _prefill_program fuses in-program
    @jax.jit
    def sample_first(last, rng):
        nxt, rng = _sample(last, temperature, rng, top_k, top_p)
        return nxt[0].astype(jnp.int32), rng

    return sample_first


@lru_cache(maxsize=32)
def _sp_prefill_program(model, mesh, temperature, top_k, top_p):
    # Sequence-parallel prefill: the padded prompt splits into n
    # contiguous shards over the 'sp' axis inside shard_map; each device
    # runs the model on ITS shard with ring attention as the attention
    # contraction (parallel.ring_attention — K/V rotate, exact causal
    # attention, O(bucket/n) sequence memory per device) and scatters its
    # shard's K/V rows straight into the pages it physically owns (the
    # sp-sharded pool's striped prompt allocation guarantees ownership).
    # The full-sequence K/V never materializes on any one device — the
    # whole point. The last live row's logits live on one shard; a
    # masked psum replicates them for the (replicated) first-token sample.
    n = mesh.shape[SP_AXIS]
    sp_model = model.clone(attn_fn=ring_attention_fn(SP_AXIS))

    @partial(jax.jit, donate_argnums=(1,))
    def prefill(params, layers, block_table, length, shared_len, prompt,
                rng):
        lsh = prompt.shape[1] // n         # bucket % (n * page_size) == 0

        def shard_fn(params, layers, bt, length, shared_len, prompt):
            rows_local = layers[0].k.shape[0]
            me = jax.lax.axis_index(SP_AXIS)
            pos = jnp.asarray(me * lsh, jnp.int32)[None]        # (1,)
            rows = pos[:, None] + jnp.arange(lsh, dtype=jnp.int32)[None]
            valid = rows >= jnp.asarray(shared_len, jnp.int32)
            # FLAT global rows -> my local rows; foreign pages route to my
            # LOCAL trash row (their owner writes the real bits)
            local_bt = jnp.where(bt // rows_local == me,
                                 bt % rows_local, rows_local - 1)
            paged = {"layers": layers, "block_tables": local_bt,
                     "positions": pos,
                     "lengths": jnp.asarray(length, jnp.int32)[None],
                     "valid": valid}
            logits, new_layers = sp_model.apply(
                {"params": params}, prompt, train=False, pos_offset=pos,
                paged=paged, paged_prefill=True)
            idx = jnp.clip(length - 1 - pos[0], 0, lsh - 1)
            last = jnp.take_along_axis(
                logits, jnp.reshape(idx, (1, 1, 1)).astype(jnp.int32),
                axis=1)[:, 0]
            owns_last = (length - 1 >= pos[0]) & (length - 1 < pos[0] + lsh)
            last = jax.lax.psum(
                jnp.where(owns_last, last, jnp.zeros_like(last)), SP_AXIS)
            return last, new_layers

        last, new_layers = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(SP_AXIS), P(), P(), P(), P(None, SP_AXIS)),
            out_specs=(P(), P(SP_AXIS)))(
            params, layers, block_table,
            jnp.asarray(length, jnp.int32),
            jnp.asarray(shared_len, jnp.int32), prompt)
        nxt, rng = _sample(last, temperature, rng, top_k, top_p)
        return nxt[0].astype(jnp.int32), new_layers, rng

    return prefill


@lru_cache(maxsize=32)
def _draft_prefill_program(draft_model):
    # the draft's prompt pass: writes the DRAFT arenas' prompt rows through
    # the same block table the base prefill used (the pools share page
    # indices) and discards the logits — the first emitted token is the
    # base's, sampled by _prefill_program
    @partial(jax.jit, donate_argnums=(1,))
    def prefill(params, layers, block_table, length, shared_len, prompt):
        valid = (jnp.arange(prompt.shape[1], dtype=jnp.int32)[None, :]
                 >= jnp.asarray(shared_len, jnp.int32))
        paged = {"layers": layers, "block_tables": block_table,
                 "positions": jnp.zeros((1,), jnp.int32),
                 "lengths": jnp.asarray(length, jnp.int32)[None],
                 "valid": valid}
        _, new_layers = draft_model.apply(
            {"params": params}, prompt, train=False,
            paged=paged, paged_prefill=True)
        return new_layers

    return prefill


@lru_cache(maxsize=32)
def _spec_tick_program(model, draft_model, k):
    # The speculative tick: k greedy draft steps (a lax.scan over the draft
    # arenas, one token per step) + ONE (k+1)-wide base verification over
    # the main arenas + the accept/reject gather — all inside a single
    # jitted dispatch, so speculation never adds host round-trips.
    #
    # Greedy emission rule (the bit-parity invariant): with drafts d_1..d_k
    # and base argmaxes g_0..g_k at offsets 0..k, let ``a`` be the length
    # of the longest prefix with d_i == g_{i-1}. Emit d_1..d_a plus the
    # correction g_a when a < k (a+1 tokens — the correction IS what
    # non-speculative greedy would have emitted next), and exactly d_1..d_k
    # when a == k (k tokens, NO bonus token: g_k's source row is the k-th
    # draft's KV, which the DRAFT arenas don't hold yet — emitting it would
    # break the "draft rows cover 0..position-1" invariant the next tick's
    # scan relies on). Either way every emitted token equals the base
    # model's greedy continuation, for ANY draft — acceptance moves
    # throughput, never output.
    #
    # Stale-row discipline: both pools' arenas accumulate speculative rows
    # past the accepted frontier. They are invisible (per-row causal
    # horizon) and the next tick overwrites them in position order before
    # any read, so rejection needs NO rollback work — the block table and
    # position simply don't advance past the accepted count.
    @partial(jax.jit, donate_argnums=(2, 3))
    def tick(params, draft_params, layers, draft_layers, block_tables,
             tokens, positions, caps):
        b = tokens.shape[0]

        def draft_step(carry, _):
            dlayers, tok, pos = carry
            # a draft can overrun a short request's allocated rows; the
            # cap mask routes those writes to the trash page (an unmasked
            # overrun would CLAMP into the sequence's last live page)
            paged = {"layers": dlayers, "block_tables": block_tables,
                     "positions": pos, "lengths": pos + 1,
                     "valid": (pos < caps)[:, None]}
            logits, new_dlayers = draft_model.apply(
                {"params": draft_params}, tok[:, None], train=False,
                pos_offset=pos, paged=paged)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (new_dlayers, nxt, pos + 1), nxt

        (draft_layers, _, _), drafts = jax.lax.scan(
            draft_step, (draft_layers, tokens, positions), None, length=k)
        drafts = jnp.swapaxes(drafts, 0, 1)              # (B, k)

        # one multi-position verify: row b carries queries for [t0, d1..dk]
        # at positions pos..pos+k, writing all their K/V rows and reading
        # each at its own causal horizon (ops.paged_attention)
        ver = jnp.concatenate([tokens[:, None], drafts], axis=1)  # (B, k+1)
        write_pos = positions[:, None] + jnp.arange(k + 1,
                                                    dtype=jnp.int32)[None, :]
        paged = {"layers": layers, "block_tables": block_tables,
                 "positions": positions, "lengths": positions + k + 1,
                 "valid": write_pos < caps[:, None]}
        logits, new_layers = model.apply(
            {"params": params}, ver, train=False,
            pos_offset=positions, paged=paged)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)

        # longest accepted prefix, resolved per row with no host trip
        matches = (drafts == greedy[:, :k]).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)       # (B,)
        emit_n = jnp.minimum(a + 1, k)
        corr = jnp.take_along_axis(greedy, a[:, None], axis=1)  # (B, 1)
        idx = jnp.arange(k, dtype=jnp.int32)[None, :]
        emitted = jnp.where(idx < a[:, None], drafts, corr)     # (B, k)
        return emitted, emit_n, new_layers, draft_layers

    return tick


class ServeEngine:
    """The continuous-batching scheduler (module docstring has the tour).

    Drive it either with :meth:`run` (submit everything, drain — the test
    and bit-identity shape) or manually: ``submit()`` as requests arrive,
    ``step()`` once per scheduler iteration (evict -> admit+prefill ->
    decode tick), each returning the requests that finished.

    Which models: the dense ``TransformerLM``, the hybrid state-space /
    attention ``HybridLM``, the decoder-hybrid-decoder ``Phi4FlashLM`` and
    the Mamba-2 / latent-expert / attention ``NemotronHLM``, whose routed
    layers take rows, drop nothing and compute the part their held experts
    give (one rank of an expert-parallel group, without the exchange).
    The engine asks a model one question, once: ``model.cache_layout()``,
    one entry a layer of FOUR kinds, and builds its pool from the answer:
    ``("pages", kv_heads, head_dim, query heads a KV head[, "rows"])``, K
    and V rows behind the block tables (with ``"rows"`` a token's KV heads
    lie side by side in 3-D arenas, which ``ops.paged_attention.
    paged_attend`` takes by their rank: the tick reads them in place with
    the grouped kernel where a head fills the lanes, a prefill chunk's
    window (Lq > 1) gathers the slot's rows and views them by head, and
    ``kv_quant="int8"`` is refused, the pool building no int8 rows; the
    expert model's attention layer is one, the phi-4 model's full layer
    another, read from its own model file); ``("slot_state", {name: (shape
    a slot, dtype)})``; ``("window", kv_heads, head_dim, group, window)``, a
    ring a slot of ``window + page_size`` rows whose bytes do not depend on
    ``max_len``, written by prefill (the prompt's last ring's worth of
    rows) and by every tick at ``position % rows``; and ``("shared",
    layer)``, a layer that keeps nothing and is handed layer ``layer``'s
    pages as the same program (prefill or tick) wrote them. A model may run
    its last layers on a prompt's last live row only and return a prefill's
    logits as ``[1, 1, V]`` (``serve.prefill``'s ``cross_rows``). Slot state is
    addressed by slot, not by block table: prefill writes a slot's rows at
    the prompt's true length (the programs hand the model ``live`` and
    ``slots`` beside the block tables), every tick updates every decoding
    slot's row in place and holds the others, a call that feeds position 0
    starts from zeros (no reset at admission; eviction frees pages only),
    and chunked prefill carries the state from chunk to chunk in the
    slot's own row. Refused by name for a model with slot state or rings:
    ``prefix_cache`` (shared pages bring neither recurrent state nor a
    ring's rows), ``spec_k > 0`` (a rejected draft would need state and
    ring rolled back) and ``mesh=`` (the sp-sharded pool shards by page);
    for a model with rings or a shared layer also ``prefill_chunk`` (their
    reads take one query a row). A model with routed expert layers says so
    (``routed_layers()``: how many, and the experts each holds here); its
    tick and prefill programs then return, beside the tokens and in the
    same ``device_get``, two int32 counters summed over those layers
    (``expert_rows``, ``experts_hit``: span attributes, ``stats()``, the
    ``kv_cache`` event) and the form the program was traced in
    (``grouped_calls``: the grouped products it runs, 0 in the form over
    the hit list), and no other model's programs return anything more. Refused: a model with no ``cache_layout()`` at all, which is
    ``MoETransformerLM`` (its ``MoEBlock`` takes no ``paged`` and its
    capacity-factor dispatch drops rows by group).
    """

    def __init__(self, model, params, config: Optional[ServeConfig] = None,
                 *, draft_model=None, draft_params=None, ledger=None,
                 tracer: Optional[RequestTracer] = None,
                 now_fn: Callable[[], float] = time.monotonic,
                 rng: Optional[jax.Array] = None, mesh=None):
        config = config if config is not None else ServeConfig()
        if not hasattr(model, "cache_layout"):
            raise NotImplementedError(
                f"{type(model).__name__} has no cache_layout(): the engine "
                "asks a model what each layer keeps for a sequence and "
                "hands its blocks `paged=`. MoETransformerLM answers "
                "neither: its MoEBlock attends through the flax decode "
                "cache, and its capacity-factor dispatch drops rows by "
                "group, so a served token would depend on who shares its "
                "tick (ROADMAP D3). An expert model whose routed layer "
                "takes rows and drops nothing is served "
                "(models.nemotron_h)")
        cfg = config
        if cfg.quant != "none":
            model, params = _quantize_for_decode(model, params, cfg.quant)
        else:
            _refuse_wo_tree(getattr(model, "quant", "none"), params)
        self.model = model
        self.params = params
        self.cfg = cfg
        self.max_len = min(cfg.max_len or model.max_len, model.max_len)
        # sp-sharded serving (mesh= with the 'sp' axis): the pool's arenas
        # shard over the axis, so effective KV capacity scales with the
        # mesh and contexts larger than ONE device's page budget serve
        self.sp_mesh = mesh
        self.sp_n = 1
        if mesh is not None:
            if SP_AXIS not in mesh.shape:
                raise ValueError(
                    f"ServeEngine mesh needs the {SP_AXIS!r} axis (got "
                    f"axes {tuple(mesh.axis_names)})")
            self.sp_n = mesh.shape[SP_AXIS]
            if cfg.spec_k > 0:
                raise NotImplementedError(
                    "speculative decoding over an sp-sharded pool is the "
                    "named residue: the draft scan's per-step sharded "
                    "writes need their own collective story")
        # the one question to the model: what does each layer keep for a
        # sequence? Pages (K and V rows behind block tables) or slot state
        # (arrays a slot, written by prefill and updated by every tick)
        layout = model.cache_layout()
        self.state_layers = _slot_state_layers(layout)
        self.window_layers = _kinds(layout, "window")
        # layers that read ONE layer's pages in a tick: it and its readers
        readers = _kinds(layout, "shared")
        self.shared_readers = readers + bool(readers)
        self.window = max((spec[3] for kind, *spec in layout
                           if kind == "window"), default=0)
        # routed expert layers, and the experts each holds here (0, 0 for
        # a model without them): what the tick's and the prefill's two
        # counters are summed over
        self.expert_layers, self.experts_held = _routed_layers(model)
        self.expert_rows = 0         # assignments that landed on held experts
        self._experts_hit_sum = 0    # held experts hit, summed over the ticks
        self._refuse_over_slot_state(cfg, mesh, layout)
        self.pool = self._pool_for(layout, model.dtype, cfg.attn_read, mesh)
        self.max_pages_per_seq = self.pool.pages_needed(self.max_len)
        # speculative decoding: a draft proposes cfg.spec_k tokens per tick
        # over its OWN arenas (a second pool, same page geometry + indices,
        # so it rides the SAME block tables and the base pool's allocator
        # is the single source of truth for page ownership)
        self.draft_model = self.draft_params = self.draft_pool = None
        if cfg.spec_k > 0:
            if cfg.temperature > 0.0:
                raise ValueError(
                    "speculative decoding serves greedy verification only "
                    "(spec_k > 0 needs temperature == 0): sampled "
                    "acceptance is a different estimator with different "
                    "output distribution guarantees")
            if draft_model is None:
                # self-speculation: the base drafts for itself (useful as a
                # default and as the acceptance upper bound — the draft
                # arenas still diverge numerically from the multi-position
                # verify, so acceptance is high, not trivially 1.0)
                self.draft_model, self.draft_params = self.model, self.params
            else:
                self.draft_model, self.draft_params = prepare_draft(
                    self.model, draft_model, draft_params, cfg.quant)
            # draft reads stay on the exact path: the flash kernel is a
            # bandwidth optimization for the big base arenas; the draft's
            # are small by construction
            draft_layout = self.draft_model.cache_layout()
            self._refuse_over_slot_state(cfg, None, draft_layout)
            self.draft_pool = self._pool_for(
                draft_layout, self.draft_model.dtype, "exact", None)
        elif draft_model is not None:
            raise ValueError("draft_model given but cfg.spec_k == 0: set "
                             "spec_k to the draft window size")
        self.buckets = _default_buckets(self.max_len)
        # sp prefill needs buckets whose shards hold WHOLE pages: the
        # striped prompt allocation places block-table slot t on device
        # (t*page_size)//shard_len, which is only well-defined when
        # shard_len % page_size == 0
        self.sp_buckets: Tuple[int, ...] = ()
        if cfg.sp_prefill_threshold > 0:
            if mesh is None:
                raise ValueError("sp_prefill_threshold > 0 needs "
                                 "ServeEngine(mesh=...) with the "
                                 f"{SP_AXIS!r} axis")
            step = self.sp_n * cfg.page_size
            if self.max_len % step:
                raise ValueError(
                    f"sp prefill needs max_len ({self.max_len}) divisible "
                    f"by sp devices x page_size ({self.sp_n} x "
                    f"{cfg.page_size}) so every prompt has an sp bucket")
            self.sp_buckets = tuple(b for b in self.buckets if b % step == 0)
        self.slots: List[Optional[_Slot]] = [None] * cfg.max_slots
        self.queue: Deque[Tuple[DecodeRequest, float]] = deque()
        self._now = now_fn
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.ledger = ledger
        # request tracing (obs.reqtrace): a ledger implies spans — callers
        # with a fleet identity (sim.worker) inject their own tracer so
        # trace ids stitch across hosts; standalone serving defaults to a
        # local single-job namespace
        self.tracer = tracer
        if self.tracer is None and ledger is not None:
            self.tracer = RequestTracer(ledger, job_id="serve", attempt=0)
        # the pool's prefix/CoW work happens inside admission — bind the
        # trace context so hits and forks surface as detail spans
        self.pool.bind_trace(self.tracer, self._now)
        # program spans (obs.trace): always on, on this engine's clock
        self._span = partial(trace.ring().span, now=now_fn)
        self._dispatched = set()     # programs called once (so compiled)
        # counters / SLO state
        self.ticks = 0
        self.completed = 0
        self.rejected = 0
        self.prefills = 0
        self.sp_prefills = 0
        # chunked-prefill accounting: chunk dispatches interleaved with
        # the decode stream (the kv_cache event's chunk-occupancy trend)
        self.chunk_ticks = 0
        # speculative accounting: emitted tokens vs active-slot tick
        # opportunities — accepted_per_tick = spec_emitted/spec_slot_ticks
        # (identically 1.0 for plain decode; > 1.0 is speculation's win)
        self.spec_emitted = 0
        self.spec_slot_ticks = 0
        # prefix-cache accounting: prompt pages needed vs served shared
        self.prompt_pages = 0
        self.shared_prompt_pages = 0
        self._occupancy_sum = 0.0
        # how the tick's program reads its pages ("pages": the in-place
        # kernel; "gathered": the gathered copy): the rule
        # ops.paged_attention applies when the program is traced, asked
        # once here for the one program this engine dispatches (the
        # speculative tick is named by its verify window, Lq = k + 1)
        paged = [spec for kind, *spec in layout
                 if kind in ("pages", "window")]
        self.tick_read = decode_read(
            self.pool.page_layers()[0], 1 + cfg.spec_k, self.sp_mesh,
            paged[0][2], paged[0][1]) if paged else "none"
        self.state_writes = 0        # prefills that wrote a slot's state
        # what admissions cost the decoding slots: the sum over all
        # prefills of a prefill's own time (_note_prefill), beside the
        # process's seconds in garbage collections since this engine began
        self.prefill_own_s = 0.0
        self._gc_start = trace.gc_seconds()
        # the plain tick's decode state, resident on the device: (flat block
        # tables, each slot's last token, its position), every row on the
        # trash page at position 0 to begin with; which slot each of its
        # rows decodes for (None: that trash row); and the ticks dispatched
        # but not read yet. Beside an sp-sharded pool the state is
        # replicated over the mesh from the start, as the tick returns it:
        # one placement, so one compiled tick
        put = (jnp.asarray if mesh is None else partial(
            jax.device_put, device=NamedSharding(mesh, P())))
        self._dev = (
            put(self.pool.flat_block_table(np.full(
                (cfg.max_slots, self.max_pages_per_seq), self.pool.num_pages,
                np.int32))),
            put(np.zeros((cfg.max_slots,), np.int32)),
            put(np.zeros((cfg.max_slots,), np.int32)))
        self._dev_rows: List[Optional[_Slot]] = [None] * cfg.max_slots
        self._flights: Deque[_Flight] = deque()
        self.ticks_ahead = 0         # ticks dispatched one ahead of a read
        self.prefills_ahead = 0      # prefills called ahead of a read
        self.overrun_tokens = 0      # computed for a slot already ended
        self._live_pages_sum = 0
        self._wait_ema: Optional[float] = None
        self._wait_samples = 0
        self._in_breach = False
        self.shedding = False
        self._last_kv_tick = 0
        # graceful drain (round 13): a preemption SIGTERM finishes the
        # in-flight sequences and sheds the rest instead of dying mid-tick
        self._t_start = self._now()
        self.draining = False
        self._drained = False
        self._preempt_event = threading.Event()
        self._prev_sigterm = None

    def _pool_for(self, layout, dtype, read, mesh) -> PagedKVPool:
        cfg = self.cfg
        return PagedKVPool(
            layout, cfg.num_pages, cfg.page_size, dtype=dtype,
            kv_quant=cfg.kv_quant, read=read, mesh=mesh,
            max_slots=cfg.max_slots)

    @staticmethod
    def _refuse_over_slot_state(cfg: ServeConfig, mesh, layout) -> None:
        """What has no meaning yet for a model that keeps something a slot
        (recurrent state, a window ring) or whose layers read another
        layer's pages, each refused by name rather than served wrong."""
        rings, readers = _kinds(layout, "window"), _kinds(layout, "shared")
        # what a slot keeps beside the pages: its name, what a prefix hit
        # would not bring of it, what a rejected draft would have to restore
        kept = [words for words, n in (
            (("slot state", "the recurrent state at the end of the shared "
              "prefix (a Mamba-1 or Mamba-2 layer's, with its "
              "convolution's tail)", "the recurrent state (Mamba-1's a "
              "channel and state, Mamba-2's a head)"),
             _slot_state_layers(layout)),
            (("window rings", "the window rings' rows",
              "the rings' overwritten rows"), rings)) if n]
        if kept:
            held, lacks, restores = (list(col) for col in zip(*kept))
            held = " and ".join(held)
        if cfg.prefix_cache and kept:
            raise NotImplementedError(
                f"prefix_cache over {held}: a hit on shared pages brings "
                "the K and V of the layers that keep pages"
                + (" (the layer that others read among them)"
                   if readers else "") + " but not " + " nor ".join(lacks))
        if cfg.spec_k > 0 and kept:
            raise NotImplementedError(
                f"speculative decoding (spec_k > 0) over {held}: a "
                "rejected draft would need " + " and ".join(restores)
                + " rolled back to the accepted token")
        if mesh is not None and kept:
            raise NotImplementedError(
                f"an sp-sharded pool (mesh=) over {held}: the arenas "
                "shard by page, and what a slot keeps has no page")
        if cfg.prefill_chunk > 0 and (rings or readers):
            raise NotImplementedError(
                "chunked prefill (prefill_chunk > 0) over window rings or "
                "a layer whose pages other layers read: their read takes "
                "one query a row, and a chunk's rows would need the ring "
                "as it stood at each of them")

    # -- admission --------------------------------------------------------
    def submit(self, req: DecodeRequest) -> bool:
        """Queue one request; False = rejected by admission control (the
        caller's signal to back off / retry elsewhere)."""
        now = self._now()
        prompt_len = int(np.asarray(req.prompt).size)
        total = prompt_len + req.max_new_tokens
        if prompt_len < 1 or req.max_new_tokens < 1 or total > self.max_len:
            # degenerate geometry (empty prompt, nothing to generate, or
            # beyond max_len) can never be served — reject at the door
            # rather than crash a slot after pages were granted
            self._emit_admit(req, now, False, "too_long")
            return False
        if self.pool.pages_needed(total) > self.pool.num_pages:
            self._emit_admit(req, now, False, "exceeds_pool")
            return False
        if self.draining:
            # a draining server takes nothing new: the caller's signal to
            # retry elsewhere, same contract as SLO shedding
            self._emit_admit(req, now, False, "shed")
            return False
        if self.shedding:
            self._emit_admit(req, now, False, "slo_shedding")
            return False
        if len(self.queue) >= self.cfg.queue_depth_max:
            self._emit_admit(req, now, False, "queue_full")
            return False
        self.queue.append((req, now))
        self._emit_admit(req, now, True, None)
        return True

    def _emit_admit(self, req, now, accepted, reason, enq_ts=None):
        if not accepted:
            self.rejected += 1
        if self.ledger is None:
            return
        self.ledger.emit("admit", rid=req.rid, accepted=accepted,
                         queue_depth=len(self.queue),
                         pages_free=self.pool.pages_free,
                         reason=reason, tenant=req.tenant,
                         ts_engine=round(now, 6))
        if accepted or self.tracer is None:
            return
        # every rejection is a 'shed' span: zero-duration at the door
        # (submit-time admission control), enq->now for a queued request
        # shed by drain — the trace-side record that lets a re-admission
        # on ANOTHER host stitch into the same trace_id
        tr = self.tracer
        tid, sid, par = tr.ids(req.rid, "shed")
        tr.ledger.emit("span", trace_id=tid, span_id=sid, parent_id=par,
                       name="shed", rid=req.rid,
                       start=round(now if enq_ts is None else enq_ts, 6),
                       end=round(now, 6), reason=reason,
                       tenant=req.tenant, **tr.attrs())

    def _observe_wait(self, wait: float) -> None:
        a = _SLO_ALPHA
        self._wait_ema = (wait if self._wait_ema is None
                          else a * wait + (1 - a) * self._wait_ema)
        self._wait_samples += 1
        floor = self.cfg.slo_queue_wait_s
        if floor <= 0 or self._wait_samples < self.cfg.slo_min_samples:
            return
        if self._wait_ema > floor and not self._in_breach:
            self._in_breach = True
            self.shedding = True
            if self.ledger is not None:
                # the standard progress-SLO event: the flight recorder and
                # the slo-breach counter hang off the normal sink fan-out
                self.ledger.emit("slo", step=self.ticks, kind="queue_wait",
                                 value=round(self._wait_ema, 6), floor=floor,
                                 unit="s")
        elif self._wait_ema <= floor and self._in_breach:
            self._in_breach = False   # re-arm; resume admitting
            self.shedding = False

    def _decay_wait_if_idle(self) -> None:
        """While shedding with an EMPTY queue, the only wait evidence left
        is stale — a fresh request would start from a drained backlog. The
        EMA only updates on admissions, so without this decay a transient
        overload would shed forever once the queue drained (no admissions
        -> no observations -> no re-arm). One alpha-decay toward zero per
        scheduler iteration restores the hysteresis loop's downswing."""
        if not self.shedding or self.queue or self._wait_ema is None:
            return
        self._wait_ema *= (1 - _SLO_ALPHA)
        if self._wait_ema <= self.cfg.slo_queue_wait_s:
            self._in_breach = False
            self.shedding = False

    def _trace_id(self, rid):
        """The identifier a request's spans share."""
        return self.tracer.trace_id(rid) if self.tracer is not None else rid

    def _first_call(self, program) -> bool:
        """True the first time ``program`` (a name, with its bucket where
        it has one) is dispatched: that call compiles, and the watchdog
        reads a long ``*.dispatch`` span with this set as a compilation."""
        if program in self._dispatched:
            return False
        self._dispatched.add(program)
        return True

    # -- the scheduler iteration -----------------------------------------
    def step(self) -> List[Completion]:
        """One iteration: evict finished sequences (freeing their slots
        and pages), admit + prefill from the queue into the free slots,
        then one pass of the decode tick over the packed active set.
        Returns the completions evicted this iteration.

        The plain tick runs ONE TICK AHEAD of the host (:meth:`_tick_plain`):
        a pass dispatches the next tick and only then reads the tokens of
        the tick dispatched a pass earlier, so the device computes tick
        n + 1 while the host emits, evicts and admits on tick n's tokens
        (after an idle spell a pass dispatches two ticks and reads the
        first). Every pass still hands each decoding request one token, but
        a finished sequence is evicted, and its slot refilled, one tick
        later than a synchronous loop would, and a prefill queues behind
        the tick in flight (``prefill.behind`` times that wait apart from
        the prefill's own). Timestamps are taken when the host HOLDS a
        token's value. ``run()``, ``drain()`` and any loop that steps while
        a slot is occupied leave no tick unread:
        a tick is in flight only while some slot is still occupied."""
        with self._span("serve.step", tick=self.ticks,
                        n_active=sum(s is not None for s in self.slots),
                        queue_depth=len(self.queue)):
            with self._span("serve.evict") as sp:
                completions = self._evict()
                sp.attrs["n"] = len(completions)
            with self._span("serve.admit") as sp:
                sp.attrs["n"] = self._admit()
            self._chunk_tick()
            self._tick()
            self._decay_wait_if_idle()
            every = self.cfg.kv_event_every
            # keyed on DECODE ticks, deduplicated: idle iterations don't
            # advance the counter and must neither spam one event per loop
            # nor re-emit the same tick's snapshot
            if (every > 0 and self.ticks % every == 0
                    and self.ticks != self._last_kv_tick):
                self._last_kv_tick = self.ticks
                self._emit_kv_cache()
        return completions

    def run(self, requests=(), max_ticks: int = 100_000) -> List[Completion]:
        """Submit everything, then step until drained (tests, batch jobs).
        Rejected submissions are simply absent from the completions. A
        preemption SIGTERM (:meth:`install_sigterm_drain`) switches to
        :meth:`drain` at the next tick boundary instead of dying mid-tick."""
        for req in requests:
            self.submit(req)
        out: List[Completion] = []
        while self.queue or any(s is not None for s in self.slots):
            if self._preempt_event.is_set() and not self.draining:
                # drain() already emitted the final kv_cache + run_end;
                # falling through to the normal-completion epilogue would
                # double-emit the final pressure snapshot
                out.extend(self.drain(reason="sigterm"))
                return out
            out.extend(self.step())
            if self.ticks > max_ticks:
                raise RuntimeError(
                    f"serve drain exceeded {max_ticks} ticks "
                    f"({len(self.queue)} queued, "
                    f"{sum(s is not None for s in self.slots)} active)")
        self._emit_kv_cache()
        return out

    # -- graceful shutdown (round 13) -------------------------------------
    def install_sigterm_drain(self):
        """Route the scheduler's preemption SIGTERM into a graceful drain:
        the handler only sets a flag (signal-safe — no jax, no locks), and
        :meth:`run` drains at its next tick boundary. Main thread only;
        returns an uninstall callable."""
        prev = signal.signal(signal.SIGTERM,
                             lambda signum, frame: self._preempt_event.set())
        self._prev_sigterm = prev

        def uninstall():
            signal.signal(signal.SIGTERM, prev)
            self._prev_sigterm = None

        return uninstall

    def drain(self, reason: str = "sigterm", max_ticks: int = 100_000,
              emit_run_end: bool = True) -> List[Completion]:
        """Graceful shutdown: finish every IN-FLIGHT sequence (they hold
        pages and partial generations — killing them wastes the work),
        reject the whole queue with a ``shed`` admission record (the
        caller's signal to retry elsewhere), free all pages via the normal
        eviction path, and emit ``run_end`` so the ledger shows a drained
        server, not a mid-tick corpse. Idempotent; returns the completions
        of the in-flight sequences. ``emit_run_end=False`` leaves the
        final ``run_end`` to a caller that owns run lifecycle already
        (the fleet-sim worker's RunObs stamps its own status/lineage —
        two run_end records in one attempt would corrupt classification)."""
        if self._drained:
            return []
        self.draining = True
        shed = list(self.queue)
        self.queue.clear()
        now = self._now()
        for req, enq_ts in shed:
            # the shed span covers the request's whole queued life — the
            # wait it paid before this host gave up on it
            self._emit_admit(req, now, False, "shed", enq_ts=enq_ts)
        out: List[Completion] = []
        t0_ticks = self.ticks
        while any(s is not None for s in self.slots):
            out.extend(self.step())
            if self.ticks - t0_ticks > max_ticks:
                raise RuntimeError(
                    f"graceful drain exceeded {max_ticks} ticks with "
                    f"{sum(s is not None for s in self.slots)} still active")
        self._drained = True
        self._emit_kv_cache()  # final pressure snapshot: all pages free
        if self.ledger is not None:
            self.ledger.emit(
                "scale", action="drain", processes=1, epoch=None,
                reason=reason, shed=len(shed), finished=len(out))
            if emit_run_end:
                self.ledger.emit(
                    "run_end", steps=self.ticks,
                    seconds=round(self._now() - self._t_start, 6),
                    status="preempted", reason=reason,
                    completed=self.completed, rejected=self.rejected,
                    shed=len(shed))
        return out

    # -- internals --------------------------------------------------------
    def _evict(self) -> List[Completion]:
        out = []
        for i, slot in enumerate(self.slots):
            if slot is None or not slot.done:
                continue
            self.pool.free(slot.pages)
            self.slots[i] = None
            n = slot.prompt_len + slot.generated
            comp = Completion(
                rid=slot.req.rid, tokens=slot.buf[:n].copy(),
                prompt_len=slot.prompt_len, n_generated=slot.generated,
                admit_ts=slot.admit_ts, start_ts=slot.start_ts,
                first_token_ts=slot.first_token_ts,
                finish_ts=slot.finish_ts,
                token_ts=slot.token_ts[:slot.generated].copy(),
                behind_prefill_s=slot.behind_prefill_s,
                behind_gc_s=slot.behind_gc_s)
            self.completed += 1
            out.append(comp)
            if self.ledger is not None:
                self.ledger.emit(
                    "request", rid=comp.rid, tokens=comp.n_generated,
                    queue_wait_s=round(comp.queue_wait_s, 6),
                    admit_ts=round(comp.admit_ts, 6),
                    first_token_ts=round(comp.first_token_ts, 6),
                    finish_ts=round(comp.finish_ts, 6),
                    prompt_len=comp.prompt_len,
                    tenant=slot.req.tenant,
                    ttft_s=round(comp.ttft_s, 6),
                    behind_prefill_s=round(comp.behind_prefill_s, 6),
                    behind_gc_s=round(comp.behind_gc_s, 6))
            if self.tracer is not None:
                # the root span: this (job, attempt)'s whole view of the
                # request, admit->finish. Emitted at eviction, after every
                # child — readers key the tree on ids, not emit order
                tr = self.tracer
                tid, sid, par = tr.root_ids(comp.rid)
                tr.ledger.emit("span", trace_id=tid, span_id=sid,
                               parent_id=par, name="request", rid=comp.rid,
                               start=round(comp.admit_ts, 6),
                               end=round(comp.finish_ts, 6),
                               ttft_s=round(comp.ttft_s, 6),
                               queue_wait_s=round(comp.queue_wait_s, 6),
                               tokens=comp.n_generated,
                               prompt_len=comp.prompt_len,
                               behind_prefill_s=round(
                                   comp.behind_prefill_s, 6),
                               behind_gc_s=round(comp.behind_gc_s, 6),
                               tenant=slot.req.tenant, **tr.attrs())
        return out

    def _admit(self) -> int:
        """Fill free slots from the queue; returns how many it admitted.

        The monolithic prefills of one call run as a pipeline of depth
        one: admission k + 1 is planned and its program called inside
        admission k's ``prefill.dispatch`` (:meth:`_prefill`), before k's
        first token is read, so the device goes from one prefill to the
        next while the host plans, uploads and reads. The device's order
        of programs (tick in flight, prefill 1, prefill 2, ..., next
        tick), the random key's path through them, each request's stamps
        and the order of planning (k's ``register_prefix`` before k + 1's
        ``share_prefix``) are a one-at-a-time loop's; a call with one
        admission makes that loop's runtime calls in its order. At most
        one program is out beyond the one being read, and none when this
        returns. A chunked or sequence-parallel admission keeps its
        synchronous form and is never issued ahead."""
        admitted = 0
        plans = self._planned()
        adm = next(plans, None)
        while adm is not None:
            admitted += 1
            if adm.kind == "prefill":
                adm = self._prefill(adm, plans)
                continue
            if adm.kind == "sp":
                self._prefill_sp(adm)
            else:
                self._begin_chunked(adm)
            adm = next(plans, None)
        return admitted

    def _planned(self):
        """The admissions this step can make, free slot by free slot in
        queue order, each planned only when it is asked for (so after the
        one before it was issued and had registered its prefix): prefix
        match, pages, out of the queue, the queue span. Pool pressure
        ends it and leaves the request queued."""
        for i in range(len(self.slots)):
            if not self.queue:
                return
            if self.slots[i] is not None:
                continue
            req, enq_ts = self.queue[0]
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            p = prompt.size
            total = p + req.max_new_tokens
            total_slots = self.pool.pages_needed(total)
            use_sp = (self.cfg.sp_prefill_threshold > 0
                      and p >= self.cfg.sp_prefill_threshold)
            use_chunk = (not use_sp and self.cfg.prefill_chunk > 0
                         and p > self.cfg.prefill_chunk)
            sp_bucket = (next(b for b in self.sp_buckets if b >= p)
                         if use_sp else None)
            match = (self.pool.share_prefix(prompt, rid=req.rid)
                     if self.cfg.prefix_cache else None)
            # fresh pages: everything past the FULL-page hits. A frontier
            # (partial-page) hit replaces one fresh prompt page but
            # reserves one fresh page as its copy-on-write destination —
            # reserving at admission means the later fork can never fail,
            # so the net fresh cost is total_slots - full either way.
            n_fresh = total_slots - (match.full if match is not None else 0)
            if use_sp:
                # striped prompt pages: slot t's rows are scattered by the
                # device whose prompt shard covers them, so the page must
                # physically live there. Shared slots sit wherever their
                # writer put them (reads are location-free); decode-tail
                # pages (and the CoW reserve) are unconstrained.
                shard = sp_bucket // self.sp_n
                shared_slots = len(match.pages) if match is not None else 0
                stripe = [(t * self.cfg.page_size) // shard
                          for t in range(shared_slots,
                                         self.pool.pages_needed(p))]
                fresh = self.pool.alloc_for_slots(stripe)
                if fresh is not None:
                    rest = self.pool.alloc(n_fresh - len(stripe))
                    if rest is None:
                        self.pool.free(fresh)
                        fresh = None
                    else:
                        fresh = fresh + rest
            else:
                fresh = self.pool.alloc(n_fresh)
            if fresh is None:
                if match is not None:
                    self.pool.unshare(match)
                return  # pool pressure: leave it queued, decode on
            self.queue.popleft()
            now = self._now()
            self._observe_wait(now - enq_ts)
            if self.tracer is not None:
                # the queue span closes the moment the request leaves the
                # backlog — with prefill starting the same instant, queue +
                # prefill tile admit->first-token exactly (the attribution
                # sum-check's first half)
                tr = self.tracer
                tid, sid, par = tr.ids(req.rid, "queue")
                tr.ledger.emit("span", trace_id=tid, span_id=sid,
                               parent_id=par, name="queue", rid=req.rid,
                               start=round(enq_ts, 6), end=round(now, 6),
                               queue_depth=len(self.queue),
                               tenant=req.tenant, **tr.attrs())
            if use_sp:
                kind, bucket = "sp", sp_bucket
            elif use_chunk:
                kind, bucket = "chunked", None
            else:
                kind, bucket = "prefill", next(
                    b for b in self.buckets if b >= p)
            shared = list(match.pages) if match is not None else []
            cow = None
            if match is not None and match.partial:
                # the block table reads through the SHARED frontier page at
                # slot match.full; the last fresh page is its reserved CoW
                # destination, forked right before this sequence's first
                # decode write (_resolve_cow)
                cow = (match.full, shared[-1], fresh[-1])
                bt_pages = shared + fresh[:-1]
            else:
                bt_pages = shared + fresh
            bt = np.full((self.max_pages_per_seq,), self.pool.num_pages,
                         np.int32)                   # unassigned -> trash
            bt[:len(bt_pages)] = bt_pages
            yield _Admission(
                i, req, prompt, enq_ts, now, kind, bucket, shared + fresh,
                len(shared), match.cov if match is not None else 0, cow,
                bt_pages, bt)

    def _new_slot(self, req, prompt, pages, bt, enq_ts, start_ts,
                  **fields) -> _Slot:
        p = prompt.size
        slot = _Slot(req=req, pages=pages, block_table=bt,
                     buf=np.zeros((p + req.max_new_tokens,), np.int32),
                     token_ts=np.zeros((req.max_new_tokens,), np.float64),
                     trace_id=self._trace_id(req.rid), prompt_len=p,
                     admit_ts=enq_ts, start_ts=start_ts, position=p, **fields)
        slot.buf[:p] = prompt
        return slot

    def _issue_prefill(self, adm: _Admission, send) -> None:
        """The first half of a monolithic admission: everything up to and
        including the program's call (and the draft's, the prefix index,
        the counters), inside the open ``prefill.dispatch`` span ``send``.
        The first token stays on the device in ``adm.tok``."""
        prompt, bt, shared_len = adm.prompt, adm.block_table, adm.shared_len
        p, bucket = prompt.size, adm.bucket
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt
        if self._first_call(("prefill", bucket)):
            send.attrs["first_call"] = True
        program = _prefill_program(self.model, self.cfg.temperature,
                                   self.cfg.top_k, self.cfg.top_p,
                                   self.sp_mesh)
        # recompile sentry (analysis.proglint PL005): prefill
        # specializes per bucket BY DESIGN, so its allowed trace-cache
        # size is the bucket-ladder length, not 1 (no-op when the
        # audit is off)
        register_audit_program("serve_prefill", program,
                               allowed=len(self.buckets))
        args = (self.params, self.pool.layers(),
                jnp.asarray(self.pool.flat_block_table(bt[None])),
                jnp.int32(p), jnp.int32(shared_len),
                jnp.asarray(padded), self._rng, jnp.int32(adm.slot_idx))
        adm.issued = self._now()
        adm.tok, new_layers, self._rng, *counts = program(*args)
        adm.counts = counts[0] if counts else None
        self.pool.adopt(new_layers)
        # rows of the prompt the model's last layers and head ran
        # on, as the traced program had them (the bucket, or 1)
        adm.cross_rows = program.head_rows.get(bucket)
        self.state_writes += bool(self.state_layers)
        if self.draft_pool is not None:
            # the draft's prompt rows, through the same block table
            # (the pools share page indices); shared rows were written
            # by the earlier prefix owner's draft prefill, so the mask
            # matches
            dprog = _draft_prefill_program(self.draft_model)
            self.draft_pool.adopt(dprog(
                self.draft_params, self.draft_pool.layers(),
                jnp.asarray(bt[None]), jnp.int32(p),
                jnp.int32(shared_len), jnp.asarray(padded)))
        if self.cfg.prefix_cache:
            # index this prompt's freshly-written pages for future
            # sharers (shared slots are already indexed by their
            # original writer)
            self.pool.register_prefix(prompt, adm.bt_pages,
                                      skip_slots=adm.n_shared)
            self.prompt_pages += self.pool.pages_needed(p)
            self.shared_prompt_pages += adm.n_shared
        self.prefills += 1

    def _prefill(self, adm: _Admission, plans) -> Optional[_Admission]:
        """Land ``adm``'s monolithic prefill and return the admission
        planned after it (None: no more this step).

        ``prefill.dispatch`` calls ``adm``'s program where the admission
        before it has not already, then asks ``plans`` for the next
        admission and, if that one is monolithic too, calls ITS program:
        it is then **ahead** (``serve.prefill`` attribute ``ahead`` 1,
        ``prefills_ahead``), queued on the device behind this one while
        the host reads this one's token. ``prefill.behind`` blocks on the
        tick in flight for a step's first admission and finds it landed
        for the later ones. For a prefill issued ahead ``issued`` is its
        own span's start, the first clock read after its predecessor
        landed: the moment the device can have begun it as far as the host
        knows. So the own times of one step's admissions ((end -
        ``issued``) - ``behind_s``) lie end to end without overlap, each
        within its span; a step's first one holds what was left of the
        tick in flight where that landed while the host issued the second
        (it reads high there, never low)."""
        req, prompt = adm.req, adm.prompt
        p = prompt.size
        with self._span("serve.prefill", rid=req.rid,
                        trace_id=self._trace_id(req.rid), prompt_len=p,
                        bucket=adm.bucket, shared_len=adm.shared_len,
                        state_layers=self.state_layers,
                        window_layers=self.window_layers,
                        shared_readers=self.shared_readers,
                        ahead=adm.ahead) as span:
            with self._span("prefill.dispatch", first_call=False) as send:
                if adm.tok is None:
                    self._issue_prefill(adm, send)
                following = next(plans, None)
                if following is not None and following.kind == "prefill":
                    self._issue_prefill(following, send)
                    following.ahead = 1
                    self.prefills_ahead += 1
            span.attrs["issued"] = span.start if adm.ahead else adm.issued
            span.attrs["cross_rows"] = adm.cross_rows
            self._wait_behind(span)
            with self._span("prefill.wait"):
                # the scheduler IS the drain boundary: the first token
                # decides done/eos and the TTFT stamp before the next iteration
                # distlint: disable=DL002 -- iteration-level scheduling syncs once per admit by design
                # (the counters of a model with routed experts ride the same
                # transfer; every other model's read is the token's alone)
                got = jax.device_get(adm.tok if adm.counts is None
                                     else (adm.tok, adm.counts))
                tok, counts = (got, None) if adm.counts is None else got
                tok = int(tok)
            if counts is not None:
                span.attrs.update(self._count_experts(counts))
            now = self._now()
            slot = self._new_slot(req, prompt, adm.pages, adm.block_table,
                                  adm.enq_ts, adm.start_ts, generated=1,
                                  first_token_ts=now, cow_pending=adm.cow,
                                  win_start_ts=now,
                                  gc_mark=trace.gc_seconds())
            slot.buf[p] = tok
            slot.token_ts[0] = now
            if (slot.generated >= req.max_new_tokens
                    or tok == self.cfg.eos_id):
                slot.done = True
                slot.finish_ts = now
            self.slots[adm.slot_idx] = slot
            if self.tracer is not None:
                # prefill span: queue-exit -> first token, carrying the knobs
                # that explain a slow one (bucket padding, fresh vs shared
                # pages, a pending CoW fork)
                tr = self.tracer
                tid, sid, par = tr.ids(req.rid, "prefill")
                tr.ledger.emit("span", trace_id=tid, span_id=sid,
                               parent_id=par, name="prefill", rid=req.rid,
                               start=round(adm.start_ts, 6),
                               end=round(now, 6),
                               bucket=adm.bucket, prompt_len=p,
                               pages_fresh=adm.n_fresh,
                               pages_shared=adm.n_shared,
                               shared_len=adm.shared_len,
                               cow=adm.cow is not None,
                               tenant=req.tenant, **tr.attrs())
        self._note_prefill(span, slot)
        return following

    def _wait_behind(self, span) -> None:
        """``prefill.behind``, between a prefill's dispatch and its wait:
        block until the newest tick in flight has landed and do nothing
        else. The prefill's program is queued behind that tick already
        (:meth:`step`), and the host was going to block for both in
        ``prefill.wait``: the device's order is the same, and
        ``prefill.wait`` now holds the admission alone. With no tick in
        flight the span closes at once, and still exists. Its seconds are
        ``span``'s (the ``serve.prefill``'s) ``behind_s``."""
        with self._span("prefill.behind") as behind:
            if self._flights:
                # distlint: disable=DL002 -- the host blocks for this tick in prefill.wait anyway; here it is timed apart
                jax.block_until_ready(self._flights[-1].nxt)
        span.attrs["behind_s"] = behind.seconds

    def _note_prefill(self, span, slot: _Slot) -> None:
        """Count a closed ``serve.prefill`` span's own time, what this
        admission cost every decoding slot: (end - ``issued``) -
        ``behind_s``. Where a tick was in flight the device began the
        prefill as ``prefill.behind`` ended; where it had landed already,
        at ``issued`` or later: the host's lines between count in, so the
        reading can be high against the device's time, never low. The
        request's own prefill is in ``slot.own_mark`` and so out of its
        ``behind_prefill_s``."""
        self.prefill_own_s += (span.start + span.seconds
                               - span.attrs["issued"]
                               - span.attrs["behind_s"])
        slot.own_mark = self.prefill_own_s

    # -- chunked prefill ---------------------------------------------------
    def _begin_chunked(self, adm: _Admission):
        """Admit a long prompt WITHOUT running its prefill: the slot parks
        with ``chunk_next >= 0`` (outside the decode tick's active set) and
        :meth:`_chunk_tick` feeds it one fixed-size chunk per scheduler
        iteration — a 16k admit costs many bounded steps interleaved with
        the decode stream instead of one full-prompt stall. First token,
        prefix registration, and the prefill span all land on the FINAL
        chunk (the pages only hold the whole prompt then)."""
        p, chunk = adm.prompt.size, self.cfg.prefill_chunk
        self.slots[adm.slot_idx] = self._new_slot(
            adm.req, adm.prompt, adm.pages, adm.block_table, adm.enq_ts,
            adm.start_ts, generated=0, cow_pending=adm.cow,
            # start at the chunk holding the first NON-shared row (a
            # fully-shared prompt still runs its last chunk: writes are
            # masked, but the final chunk's logits are where the first
            # token comes from)
            chunk_next=min(adm.shared_len, p - 1) // chunk * chunk,
            shared_len=adm.shared_len, n_fresh=adm.n_fresh,
            n_shared=adm.n_shared)

    def _chunk_tick(self) -> None:
        """At most ONE prefill chunk per scheduler iteration — the knob
        that bounds how much prefill compute any decode tick waits behind.
        Lowest slot index first: admission order, no starvation."""
        for i, s in enumerate(self.slots):
            if s is not None and not s.done and s.chunk_next >= 0:
                self._run_chunk(i, s)
                return

    def _run_chunk(self, slot_idx: int, s: _Slot) -> None:
        cfg = self.cfg
        chunk = cfg.prefill_chunk
        p = s.prompt_len
        with self._span("serve.prefill", rid=s.req.rid, trace_id=s.trace_id,
                        prompt_len=p, bucket=chunk, shared_len=s.shared_len,
                        chunk_start=s.chunk_next,
                        state_layers=self.state_layers, ahead=0) as span:
            with self._span("prefill.dispatch",
                            first_call=self._first_call("chunk_prefill")):
                issued = self._now()
                tok = self._dispatch_chunk(slot_idx, s)
            if tok is None:
                # not the last chunk: nothing to wait for yet, and its
                # device time lands in the next tick.wait, not here
                return
            span.attrs["issued"] = issued
            self._wait_behind(span)
            with self._span("prefill.wait"):
                # distlint: disable=DL002 -- iteration-level scheduling syncs once per admit by design
                tok = int(jax.device_get(tok))
            now = self._now()
            s.buf[p] = tok
            s.generated = 1
            s.first_token_ts = s.token_ts[0] = now
            s.win_start_ts = now
            s.gc_mark = trace.gc_seconds()
            if s.generated >= s.req.max_new_tokens or tok == cfg.eos_id:
                s.done = True
                s.finish_ts = now
            if self.tracer is not None:
                tr = self.tracer
                tid, sid, par = tr.ids(s.req.rid, "prefill")
                first = min(s.shared_len, p - 1) // chunk * chunk
                tr.ledger.emit("span", trace_id=tid, span_id=sid,
                               parent_id=par, name="prefill", rid=s.req.rid,
                               start=round(s.start_ts, 6), end=round(now, 6),
                               mode="chunked", chunk=chunk,
                               chunks=-(-(p - first) // chunk),
                               prompt_len=p, pages_fresh=s.n_fresh,
                               pages_shared=s.n_shared,
                               shared_len=s.shared_len,
                               cow=s.cow_pending is not None,
                               tenant=s.req.tenant, **tr.attrs())
        self._note_prefill(span, s)

    def _dispatch_chunk(self, slot_idx: int, s: _Slot):
        """Dispatch the slot's next prefill chunk; on the final chunk also
        the first token's sampling, whose device value is returned (None
        before that)."""
        cfg = self.cfg
        chunk = cfg.prefill_chunk
        p = s.prompt_len
        start = s.chunk_next
        tokens = np.zeros((1, chunk), np.int32)
        seg = s.buf[start:min(start + chunk, p)]
        tokens[0, :seg.size] = seg
        program = _chunk_prefill_program(self.model, chunk, self.sp_mesh)
        # one chunk geometry per deployment: any retrace is a bug
        register_audit_program("serve_chunk_prefill", program)
        last, new_layers = program(
            self.params, self.pool.layers(),
            jnp.asarray(self.pool.flat_block_table(s.block_table[None])),
            jnp.int32(start), jnp.int32(p), jnp.int32(s.shared_len),
            jnp.asarray(tokens), jnp.int32(slot_idx))
        self.pool.adopt(new_layers)
        self.chunk_ticks += 1
        if start + chunk < p:
            s.chunk_next = start + chunk
            return None
        # final chunk: the prompt is fully resident — sample the first
        # token (ONE rng consumption per admit, same as monolithic),
        # index the pages for future sharers, open the decode life
        s.chunk_next = -1
        sampler = _sample_first_program(cfg.temperature, cfg.top_k,
                                        cfg.top_p)
        register_audit_program("serve_chunk_sample", sampler)
        tok, self._rng = sampler(last, self._rng)
        if self.draft_pool is not None:
            # the draft arenas are tiny: its prompt pass stays monolithic
            # (and on the LOGICAL block table — the draft pool is never
            # sharded), keeping the chunked path draft-compatible
            bucket = next(b for b in self.buckets if b >= p)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :p] = s.buf[:p]
            dprog = _draft_prefill_program(self.draft_model)
            self.draft_pool.adopt(dprog(
                self.draft_params, self.draft_pool.layers(),
                jnp.asarray(s.block_table[None]), jnp.int32(p),
                jnp.int32(s.shared_len), jnp.asarray(padded)))
        if cfg.prefix_cache:
            # register only NOW: until the final chunk the pages hold a
            # partial prompt and a hit against them would read garbage
            bt_pages = [int(x) for x in s.block_table
                        if int(x) < self.pool.num_pages]
            self.pool.register_prefix(s.buf[:p], bt_pages,
                                      skip_slots=s.n_shared)
            self.prompt_pages += self.pool.pages_needed(p)
            self.shared_prompt_pages += s.n_shared
        self.prefills += 1
        self.state_writes += bool(self.state_layers)
        return tok

    # -- sequence-parallel prefill -----------------------------------------
    def _prefill_sp(self, adm: _Admission):
        """Monolithic-shaped admission, sequence-parallel execution: the
        prompt pads to an sp bucket and every device prefills ITS shard
        under ring attention, scattering K/V into the pages the striped
        allocation placed on it (_sp_prefill_program has the mechanics)."""
        req, prompt, bucket = adm.req, adm.prompt, adm.bucket
        bt, shared_len = adm.block_table, adm.shared_len
        p = prompt.size
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt
        with self._span("serve.prefill", rid=req.rid,
                        trace_id=self._trace_id(req.rid), prompt_len=p,
                        bucket=bucket, shared_len=shared_len,
                        ahead=0) as span:
            with self._span("prefill.dispatch", first_call=self._first_call(
                    ("sp_prefill", bucket))):
                program = _sp_prefill_program(
                    self.model, self.sp_mesh, self.cfg.temperature,
                    self.cfg.top_k, self.cfg.top_p)
                # specializes per sp bucket, same contract as serve_prefill
                register_audit_program("serve_sp_prefill", program,
                                       allowed=max(len(self.sp_buckets), 1))
                args = (self.params, self.pool.layers(),
                        jnp.asarray(self.pool.flat_block_table(bt[None])),
                        jnp.int32(p), jnp.int32(shared_len),
                        jnp.asarray(padded), self._rng)
                span.attrs["issued"] = self._now()
                tok, new_layers, self._rng = program(*args)
                self.pool.adopt(new_layers)
                if self.cfg.prefix_cache:
                    self.pool.register_prefix(prompt, adm.bt_pages,
                                              skip_slots=adm.n_shared)
                    self.prompt_pages += self.pool.pages_needed(p)
                    self.shared_prompt_pages += adm.n_shared
                self.prefills += 1
                self.sp_prefills += 1
            self._wait_behind(span)
            with self._span("prefill.wait"):
                # distlint: disable=DL002 -- iteration-level scheduling syncs once per admit by design
                tok = int(jax.device_get(tok))
            now = self._now()
            slot = self._new_slot(req, prompt, adm.pages, bt, adm.enq_ts,
                                  adm.start_ts, generated=1,
                                  first_token_ts=now, cow_pending=adm.cow,
                                  shared_len=shared_len,
                                  n_fresh=adm.n_fresh, n_shared=adm.n_shared,
                                  win_start_ts=now,
                                  gc_mark=trace.gc_seconds())
            slot.buf[p] = tok
            slot.token_ts[0] = now
            if (slot.generated >= req.max_new_tokens
                    or tok == self.cfg.eos_id):
                slot.done = True
                slot.finish_ts = now
            self.slots[adm.slot_idx] = slot
            if self.tracer is not None:
                tr = self.tracer
                tid, sid, par = tr.ids(req.rid, "prefill")
                tr.ledger.emit("span", trace_id=tid, span_id=sid,
                               parent_id=par, name="prefill", rid=req.rid,
                               start=round(adm.start_ts, 6),
                               end=round(now, 6),
                               mode="sp", sp_devices=self.sp_n,
                               bucket=bucket, prompt_len=p,
                               pages_fresh=adm.n_fresh,
                               pages_shared=adm.n_shared,
                               shared_len=shared_len,
                               cow=adm.cow is not None,
                               tenant=req.tenant, **tr.attrs())
        self._note_prefill(span, slot)

    def _resolve_cow(self, active) -> None:
        """Fork every pending shared frontier page before this tick's
        writes: each forking sequence gets the page's bits duplicated onto
        its admission-reserved destination (both pools when speculating —
        the arenas mirror page indices) and swaps its block-table entry;
        the other holders keep reading the original page untouched."""
        for _i, s in active:
            if s.cow_pending is None:
                continue
            bt_slot, src, dst = s.cow_pending
            # copies arenas, drops our src ref
            self.pool.fork_page(src, dst, rid=s.req.rid)
            if self.draft_pool is not None:
                src_a = jnp.asarray([src], jnp.int32)
                dst_a = jnp.asarray([dst], jnp.int32)
                self.draft_pool.adopt(cow_fork_pages(
                    self.draft_pool.layers(), src_a, dst_a))
            s.block_table[bt_slot] = dst
            s.pages.remove(src)
            s.cow_pending = None

    def _tick(self) -> None:
        if self.cfg.spec_k > 0:
            self._tick_spec_pass()
        else:
            self._tick_plain()

    def _tick_attrs(self, slots, ahead: int) -> dict:
        """What a ``serve.tick`` span says of the tick whose tokens
        ``slots`` receive at its end."""
        # pages holding a row that tick attends to: what a read that
        # follows the block table touches, of the table's slots x max_pages
        attrs = {"rids": [s.req.rid for _, s in slots],
                 "read": self.tick_read,
                 "live_pages": sum(self.pool.pages_needed(s.position + 1)
                                   for _, s in slots),
                 # the rows themselves, and those of them a window layer's
                 # read sees (0 without one)
                 "live_tokens": sum(s.position + 1 for _, s in slots),
                 "window_tokens": sum(min(s.position + 1, self.window)
                                      for _, s in slots),
                 "state_slots": len(slots) if self.state_layers else 0,
                 "ahead": ahead}
        if self.tracer is not None:
            attrs["trace_ids"] = [s.trace_id for _, s in slots]
        return attrs

    def _count_experts(self, counts, tick: bool = False) -> dict:
        """A program's expert counters as its span's attributes, and into
        the engine's totals (``experts_hit`` of the ticks alone: a
        prefill's rows hit nearly every expert whatever the load).
        ``grouped_calls`` is the program's own word on the routed layers'
        form: the grouped products it ran, 0 in the form over the hit
        list."""
        rows, hit, grouped = (int(c) for c in counts)
        self.expert_rows += rows
        if tick:
            self._experts_hit_sum += hit
        return {"expert_rows": rows, "experts_hit": hit,
                "grouped_calls": grouped,
                "expert_layers": self.expert_layers,
                "experts_held": self.experts_held}

    def _count_tick(self, attrs: dict) -> None:
        self.ticks += 1
        self._occupancy_sum += len(attrs["rids"]) / max(len(self.slots), 1)
        self._live_pages_sum += attrs["live_pages"]

    def _tick_spec_pass(self) -> None:
        # a slot mid-chunked-prefill (chunk_next >= 0) has no token to
        # decode yet — it keeps its pages but sits out the tick
        active = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None and not s.done and s.chunk_next < 0]
        if not active:
            return
        attrs = self._tick_attrs(active, ahead=0)    # read as dispatched
        with self._span("serve.tick", **attrs):
            self._tick_spec(active)
        self._count_tick(attrs)

    def _tick_inputs(self, active):
        """The speculative tick's host-side inputs, built and uploaded
        every tick (the plain tick keeps its own on the device): each
        slot's block table, last token, position and write-mask cap."""
        n = len(self.slots)
        tokens = np.zeros((n,), np.int32)
        positions = np.zeros((n,), np.int32)
        caps = np.zeros((n,), np.int32)
        bts = np.full((n, self.max_pages_per_seq), self.pool.num_pages,
                      np.int32)
        for i, s in active:
            tokens[i] = s.buf[s.prompt_len + s.generated - 1]
            positions[i] = s.position
            # the write-mask cap: rows past the allocation routed to trash
            # (a draft window can overrun a nearly-done request)
            caps[i] = s.prompt_len + s.req.max_new_tokens
            bts[i] = s.block_table
        return [jnp.asarray(self.pool.flat_block_table(bts)),
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(caps)]

    def _emit_token(self, s: _Slot, tok: int, now: float) -> None:
        s.buf[s.prompt_len + s.generated] = tok
        s.token_ts[s.generated] = now
        s.generated += 1
        s.position += 1
        if s.generated >= s.req.max_new_tokens or tok == self.cfg.eos_id:
            s.done = True
            s.finish_ts = now
            s.behind_prefill_s = self.prefill_own_s - s.own_mark
            s.behind_gc_s = trace.gc_seconds() - s.gc_mark

    def _decoding_next(self) -> List[Tuple[int, _Slot]]:
        """Who decodes in the next tick to dispatch. A slot mid-chunked-
        prefill (chunk_next >= 0) has no token to decode yet: it keeps its
        pages but sits out. A slot whose budget the tokens already in
        flight fill is known to end before they are read, and sits out
        too."""
        flying = {}
        for flight in self._flights:
            for _, s in flight.slots:
                flying[id(s)] = flying.get(id(s), 0) + 1
        return [(i, s) for i, s in enumerate(self.slots)
                if s is not None and not s.done and s.chunk_next < 0
                and s.generated + flying.get(id(s), 0)
                < s.req.max_new_tokens]

    def _tick_plain(self) -> None:
        """One pass of the plain decode tick, one tick ahead of the host:
        dispatch tick n + 1, THEN read tick n's tokens and emit them.

        Tick n + 1 needs nothing of the host that tick n's tokens decide,
        because its inputs stay on the device (``self._dev``: the flat
        block tables, each slot's last token, its position; the tick's
        sampled tokens are the next tick's input, ``_advance_positions``
        moves the positions, and :meth:`_sync_rows` patches a row only when
        a slot joins or leaves the tick: a block-table row is fixed at
        admission, pages being reserved for prompt + ``max_new_tokens``),
        and because who is done is known beforehand: a slot whose budget
        the token in flight fills (``generated + 1 >= max_new_tokens``) is
        taken out of tick n + 1 before the dispatch. After an idle spell
        nothing is in flight, so that pass dispatches two ticks and reads
        the first. The one end the host cannot foresee is ``eos_id``: that
        slot is seen done when tick n is read, tick n + 1 has computed one
        more token for it, and that token is dropped here (never emitted,
        counted or stamped: ``overrun_tokens``); its write fell inside the
        slot's own reservation, and slot and pages are freed once, by the
        next ``_evict``. Greedy tokens are those of a synchronous loop
        always. Sampled tokens (``temperature > 0``) are too wherever the
        engine dispatches the same sequence of prefills and ticks as a
        synchronous loop would, since the random key is carried through the
        programs in dispatch order; a request admitted into a slot that
        another just left joins one tick later, and a dropped tick has
        advanced the key once, so later samples are then another draw of
        the same distribution.

        A pass is one ``serve.tick`` span: ``rids`` are the requests that
        receive a token at its end, ``ahead`` is 1 when those tokens' tick
        was dispatched while the tick before it was still unread (every
        tick but the first after an idle spell). ``token_ts`` and
        ``finish_ts`` are read after the ``device_get`` returned."""
        flights = self._flights
        active = self._decoding_next()
        if not flights and not active:
            return
        with self._span("serve.tick") as tick:
            with self._span("tick.build"):
                if active:
                    self._resolve_cow(active)
                    self._sync_rows(active)
            with self._span("tick.dispatch", first_call=bool(active)
                            and self._first_call("tick")):
                if active:
                    self._dispatch_tick(active)
                    if len(flights) == 1:
                        # nothing was in flight: the tick after this one
                        # goes out too, so that the read below has a tick
                        # behind it
                        active = self._decoding_next()
                        if active:
                            self._sync_rows(active)
                            self._dispatch_tick(active)
            flight = flights.popleft()
            # a slot that ended on eos_id while this tick was already
            # dispatched has a token in it: dropped
            landing = [(i, s) for i, s in flight.slots if not s.done]
            tick.attrs.update(self._tick_attrs(landing, ahead=flight.ahead))
            with self._span("tick.wait"):
                # this tick was dispatched a pass ago (or, after an idle
                # spell, a moment ago with the next one behind it): the
                # host blocks only for what is left of a tick the device
                # has been working on while the host emitted, evicted and
                # admitted. Scheduling no longer waits on this sync: who
                # ends by budget was known before the dispatch, and an
                # eos_id is acted on one tick late
                # distlint: disable=DL002 -- reads the tick dispatched a pass earlier; the next tick is already queued behind it
                got = jax.device_get(flight.nxt if flight.counts is None
                                     else (flight.nxt, flight.counts))
                nxt, counts = (got, None) if flight.counts is None else got
                nxt = np.asarray(nxt)
            if counts is not None:
                tick.attrs.update(self._count_experts(counts, tick=True))
            with self._span("tick.emit"):
                now = self._now()
                for i, s in landing:
                    self._emit_token(s, int(nxt[i]), now)
                    self._note_decode(s, now, tokens=1)
        self._count_tick(tick.attrs)
        self.ticks_ahead += flight.ahead
        self.overrun_tokens += len(flight.slots) - len(landing)

    def _sync_rows(self, active) -> None:
        """Patch the device's decode state where it differs from who
        decodes next: a row whose slot left the tick (ended, evicted) goes
        back to the trash page at position 0, a slot that joins (admitted,
        or its chunked prefill's last chunk done) gets its block-table row,
        last token and position. Nothing changes on most ticks."""
        want: List[Optional[_Slot]] = [None] * len(self.slots)
        for i, s in active:
            want[i] = s
        for i, s in enumerate(want):
            if self._dev_rows[i] is s:
                continue
            patch = np.empty((3 + self.max_pages_per_seq,), np.int32)
            if s is None:
                patch[:3] = i, 0, 0
                patch[3:] = self.pool.flat_block_table(self.pool.num_pages)
            else:
                patch[:3] = (i, s.buf[s.prompt_len + s.generated - 1],
                             s.position)
                patch[3:] = self.pool.flat_block_table(s.block_table)
            self._dev = _patch_slot_row(*self._dev, jnp.asarray(patch))
            self._dev_rows[i] = s

    def _dispatch_tick(self, active) -> None:
        program = _tick_program(self.model, self.cfg.temperature,
                                self.cfg.top_k, self.cfg.top_p,
                                self.sp_mesh)
        # tick shapes are occupancy-invariant (inactive slots ride the
        # trash page), so ANY cache growth is a retrace hazard: allowed=1
        register_audit_program("serve_tick", program)
        block_tables, tokens, positions = self._dev
        nxt, new_layers, self._rng, *counts = program(
            self.params, self.pool.layers(), block_tables, tokens,
            positions, self._rng)
        self.pool.adopt(new_layers)
        self._dev = (block_tables, nxt, _advance_positions(positions))
        self._flights.append(
            _Flight(nxt, active, ahead=int(bool(self._flights)),
                    counts=counts[0] if counts else None))

    def _tick_spec(self, active) -> None:
        """One speculative iteration: k draft proposals + one base verify
        per active slot, all in one dispatch (_spec_tick_program), then
        host-side emission with per-slot budget/eos truncation — the same
        sync point the plain tick already pays, now worth up to k tokens."""
        k = self.cfg.spec_k
        with self._span("tick.build"):
            self._resolve_cow(active)
            inputs = self._tick_inputs(active)
        with self._span("tick.dispatch",
                        first_call=self._first_call("spec_tick")):
            program = _spec_tick_program(self.model, self.draft_model, k)
            # same occupancy-invariance as the plain tick: allowed=1
            register_audit_program("serve_spec_tick", program)
            emitted, emit_n, new_layers, new_dlayers = program(
                self.params, self.draft_params, self.pool.layers(),
                self.draft_pool.layers(), *inputs)
            self.pool.adopt(new_layers)
            self.draft_pool.adopt(new_dlayers)
        with self._span("tick.wait"):
            # distlint: disable=DL002 -- the per-tick sync is the scheduler's eviction/refill decision point
            emitted, emit_n = map(np.asarray,
                                  jax.device_get((emitted, emit_n)))
        with self._span("tick.emit"):
            now = self._now()
            for i, s in active:
                took = 0
                for j in range(int(emit_n[i])):
                    self._emit_token(s, int(emitted[i, j]), now)
                    self.spec_emitted += 1
                    took += 1
                    if s.done:
                        break
                self.spec_slot_ticks += 1
                self._note_decode(s, now, tokens=took, drafted=k)

    def _note_decode(self, s: _Slot, now: float, tokens: int,
                     drafted: int = 0) -> None:
        """Advance the slot's open decode window; close it into a span
        every ``trace_window_ticks`` ticks and at finish. Consecutive
        windows share their boundary timestamp, so a request's decode
        spans tile first-token->finish with zero residue — the property
        the attribution sum-check (tools/request_report.py) leans on."""
        if self.tracer is None:
            return
        s.win_ticks += 1
        s.win_tokens += tokens
        s.win_drafted += drafted
        if not s.done and s.win_ticks < max(self.cfg.trace_window_ticks, 1):
            return
        tr = self.tracer
        tid, sid, par = tr.ids(s.req.rid, "decode")
        tr.ledger.emit("span", trace_id=tid, span_id=sid, parent_id=par,
                       name="decode", rid=s.req.rid,
                       start=round(s.win_start_ts, 6), end=round(now, 6),
                       ticks=s.win_ticks, tokens=s.win_tokens,
                       spec_drafted=s.win_drafted, **tr.attrs())
        s.win_start_ts = now
        s.win_ticks = s.win_tokens = s.win_drafted = 0

    def _emit_kv_cache(self) -> None:
        # serving's drain boundary: the periodic pressure snapshot — the
        # recompile sentry's host-only counter read rides it (PL005)
        check_audit_sentry()
        if self.ledger is None:
            return
        st = self.pool.stats()
        self.ledger.emit("kv_cache", pages_free=st["pages_free"],
                         pages_used=st["pages_used"],
                         active_seqs=sum(s is not None for s in self.slots),
                         pages_total=st["pages_total"],
                         high_water_used=st["high_water_used"],
                         shared_pages=st["shared_pages"],
                         cow_copies=st["cow_copies"],
                         prefix_hits=st["prefix_hits"],
                         spec_emitted=self.spec_emitted,
                         spec_slot_ticks=self.spec_slot_ticks,
                         sharded_devices=st["sharded_devices"],
                         chunks_pending=self.chunks_pending,
                         chunk_ticks=self.chunk_ticks,
                         state_bytes=st["state_bytes"],
                         state_bytes_per_slot=self.state_bytes_per_slot,
                         expert_rows=self.expert_rows,
                         experts_hit_mean=self.experts_hit_mean,
                         window_bytes=st["window_bytes"],
                         kv_bytes_per_token=st["kv_bytes_per_token"],
                         state_writes=self.state_writes,
                         ticks_ahead=self.ticks_ahead,
                         prefills_ahead=self.prefills_ahead,
                         overrun_tokens=self.overrun_tokens,
                         prefill_own_s=round(self.prefill_own_s, 6),
                         gc_pause_s=round(self.gc_pause_s, 6),
                         slots=len(self.slots), tick=self.ticks)

    # -- introspection ----------------------------------------------------
    @property
    def gc_pause_s(self) -> float:
        """Seconds the process spent in Python's garbage collections since
        this engine was built (``obs.trace.gc_seconds``; real seconds)."""
        return trace.gc_seconds() - self._gc_start

    @property
    def state_bytes_per_slot(self) -> int:
        """Slot state one sequence holds beside its pages (a state-space
        layer's recurrent state and convolution tail, all layers)."""
        return self.pool.state_bytes // max(len(self.slots), 1)

    @property
    def experts_hit_mean(self) -> Optional[float]:
        """Held experts with at least one row, a routed layer and decode
        tick (of ``experts_held``): the weights a tick's routed products
        NEED of those they read. None for a model without routed
        experts."""
        if not self.expert_layers or not self.ticks:
            return None
        return round(self._experts_hit_sum
                     / (self.ticks * self.expert_layers), 6)

    @property
    def occupancy(self) -> float:
        """Mean active-slot share across decode ticks — the utilization
        number that separates continuous from static batching."""
        return self._occupancy_sum / self.ticks if self.ticks else 0.0

    @property
    def accepted_per_tick(self) -> Optional[float]:
        """Mean tokens emitted per active-slot tick — identically 1.0 for
        plain decode (not tracked there: None), > 1.0 is speculation's
        whole win. The serving-side analog of offline tok/s."""
        if not self.spec_slot_ticks:
            return None
        return self.spec_emitted / self.spec_slot_ticks

    @property
    def chunks_pending(self) -> int:
        """Prefill chunks still owed to parked slots — the chunk-queue
        depth the ledger's kv_cache events trend (a growing number means
        admission outruns the one-chunk-per-iteration budget)."""
        c = self.cfg.prefill_chunk
        if c <= 0:
            return 0
        return sum(-(-(s.prompt_len - s.chunk_next) // c)
                   for s in self.slots
                   if s is not None and s.chunk_next >= 0)

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        """Share of prompt pages served from the prefix cache instead of
        freshly written (None until a prefix-cached prompt is admitted)."""
        if not self.prompt_pages:
            return None
        return self.shared_prompt_pages / self.prompt_pages

    def stats(self) -> dict:
        apt = self.accepted_per_tick
        phr = self.prefix_hit_rate
        return {"ticks": self.ticks, "completed": self.completed,
                "read": self.tick_read,
                "ticks_by_read": {self.tick_read: self.ticks},
                # mean share of the block table's entries (slots x
                # max_pages) that held a live page, over the ticks
                "live_pages": (round(self._live_pages_sum / (
                    self.ticks * len(self.slots) * self.max_pages_per_seq), 6)
                    if self.ticks else None),
                "rejected": self.rejected, "prefills": self.prefills,
                "state_writes": self.state_writes,
                "state_bytes_per_slot": self.state_bytes_per_slot,
                # a model with routed expert layers: assignments of live
                # rows that landed on held experts (ticks and prefills),
                # and the held experts hit a layer and tick
                "expert_rows": self.expert_rows,
                "experts_hit_mean": self.experts_hit_mean,
                # plain ticks dispatched while the tick before them was
                # still unread, and tokens such a tick computed for a slot
                # that had ended on eos_id (dropped, never emitted)
                "ticks_ahead": self.ticks_ahead,
                "overrun_tokens": self.overrun_tokens,
                # monolithic prefills whose program was called while the
                # prefill before them, of the same step, was still unread
                "prefills_ahead": self.prefills_ahead,
                # what the admissions cost every decoding slot (the sum of
                # the prefills' own time), and this process's seconds in
                # garbage collections since the engine was built
                "prefill_own_s": round(self.prefill_own_s, 6),
                "gc_pause_s": round(self.gc_pause_s, 6),
                "sp_prefills": self.sp_prefills,
                "chunk_ticks": self.chunk_ticks,
                "chunks_pending": self.chunks_pending,
                "occupancy": round(self.occupancy, 6),
                "spec_k": self.cfg.spec_k,
                "spec_emitted": self.spec_emitted,
                "spec_slot_ticks": self.spec_slot_ticks,
                "accepted_per_tick": (None if apt is None
                                      else round(apt, 6)),
                "prompt_pages": self.prompt_pages,
                "shared_prompt_pages": self.shared_prompt_pages,
                "prefix_hit_rate": (None if phr is None
                                    else round(phr, 6)),
                "pages_per_request": (
                    round(self.pool.alloc_total / self.completed, 6)
                    if self.completed else None),
                "queue_depth": len(self.queue),
                "active_seqs": sum(s is not None for s in self.slots),
                "wait_ema_s": self._wait_ema,
                "shedding": self.shedding,
                "draining": self.draining,
                **self.pool.stats()}
