"""Paged KV cache pool: one arena per layer, a free-list, block tables.

The contiguous cache ``engine.generate`` allocates is sized ``(B, prompt +
steps)`` per call — fine for one batch, fatal for a server: N concurrent
sequences of mixed length would each reserve ``max_len`` rows of HBM whether
they use 4 or 4000, and finished sequences leave holes no later request
fits. The paged pool (vLLM's PagedAttention memory model, SOSP '23) fixes
both: K/V rows live in ONE preallocated ``[num_pages, page_size, heads,
head_dim]`` arena per layer, each sequence owns an ordered block table of
page indices, and allocation/eviction are O(pages) free-list ops — HBM
utilization follows *actual* lengths, and there is no fragmentation to
compact because every page is interchangeable.

Round 16 makes pages SHARED, not just interchangeable: every page carries a
refcount, and a prefix index keyed by the token-hash of whole pages maps a
new request's prompt prefix onto the physical pages an identical earlier
prefix already filled (system prompts and few-shot headers — the dominant
bytes in real multi-tenant traffic). A prefix hit costs ~0 fresh pages; a
page whose refcount drops to zero but that is still indexed parks in a
CACHED set (content preserved, reclaimed FIFO only under pool pressure), so
hits survive across non-overlapping requests and effective HBM capacity
multiplies with traffic similarity. Divergence is copy-on-write: the one
page a new request can ever write while shared — the frontier page holding
the tail of its prompt — is forked (``ops.paged_attention.cow_fork_pages``)
onto a destination page reserved at admission, at the moment of the first
divergent write.

Division of labor: the device-side scatter/gather/attention programs live
in ``ops.paged_attention`` (this module only *holds* arrays and page
bookkeeping); the request scheduler that drives both lives in
``engine.serve``. Arenas ride ``ops.paged_attention.PagedLayer`` packs —
int8 mode stores pages as int8 with per-(slot, head) fp32 scales (the
``quantize_kv`` layout, PR 9), halving the HBM the decode tick is
bandwidth-bound by; ``read='flash'`` additionally routes the tick's reads
through the int8-KV Pallas kernel.

A second kind of sequence state lives beside the pages (PR 26): a layer
whose entry in the model's ``cache_layout()`` is ``("slot_state", {name:
(shape a slot, dtype)})`` keeps no K and V rows but arrays
``[max_slots, ...]`` made once (a Mamba layer's float32 recurrent state
and its convolution tail), addressed by the scheduler's SLOT index:
written by prefill at the prompt's true length, updated in place by every
tick, started from zeros by the call that feeds position 0, never shared
and never allocated or freed (eviction frees pages only). The pool holds
one entry a model layer, a ``PagedLayer`` or such a dict, in the model's
order; ``layers()`` / ``adopt()`` carry both through the programs' one
donated argument. Prefix sharing, copy-on-write forks and the sp-sharded
layout are about pages and have no meaning for slot state: the serving
engine refuses them for such a model, and a sharded pool refuses a
slot-state layer here.

Two more kinds (PR 35), for a model whose attention layers do not each keep
a whole sequence. ``("window", kv_heads, head_dim, group, window)``: a RING
a slot, ``pages_for(window) + 1`` pages of rows (``ops.paged_attention.
PagedLayer``'s ring layout) addressed by slot like slot state, so its bytes
are ``max_slots x (window + page_size)`` rows whatever ``max_len`` is;
position ``t`` wraps to row ``t % rows``, nothing is allocated or freed.
``("shared", layer)``: NOTHING; the layer reads layer ``layer``'s pages as
the same program wrote them a few layers earlier, and its entry here is
``None``. A ``("pages", kv_heads, head_dim, group, "rows")`` layer keeps
block-table pages like any other but in the rows layout (a token's KV heads
side by side), which the grouped in-place read wants. With these a pool may
hold full-length pages for ONE layer of many: ``kv_bytes_per_token`` in
``stats()`` is what a token costs in pages, ``window_bytes`` what the rings
hold.

The allocator is HOST-side state (plain Python ints): page grants happen
at admission time on the scheduler thread, never inside a jitted program —
the device programs only ever see block tables as arrays.
"""

from __future__ import annotations

import hashlib
import heapq
from collections import Counter, OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dist.ops.paged_attention import PagedLayer, pages_for
from tpu_dist.parallel.mesh import SP_AXIS


def _prefix_key(tokens) -> str:
    """Content address of a token prefix: sha1 over the raw int32 bytes.
    Deterministic across runs/processes (unlike ``hash()``), collision-
    negligible, and O(len) — the whole-page token-hash the prefix index
    is keyed by."""
    return hashlib.sha1(
        np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()
    ).hexdigest()


class PrefixMatch:
    """One admission's prefix-index result (``PagedKVPool.share_prefix``).

    ``pages`` are the shared physical pages, refcounts already bumped, in
    block-table order; ``full`` of them are whole-page hits (positions
    ``0..full*page_size`` never rewritten, never forked), and when
    ``partial`` is set the LAST entry is a frontier page matched through
    ``cov - full*page_size`` leading rows only — the one page the new
    request will write into, so it must fork on first write. ``cov`` is
    the total number of prompt positions whose K/V rows are already
    resident."""

    __slots__ = ("pages", "full", "partial", "cov")

    def __init__(self, pages: List[int], full: int, partial: bool,
                 cov: int):
        self.pages = pages
        self.full = full
        self.partial = partial
        self.cov = cov


class PagedKVPool:
    """Preallocated paged KV arenas + the refcounting free-list allocator.

    ``num_pages`` is the real capacity; arenas carry one extra *trash* page
    (index ``num_pages``) that masked writes are routed to, so the jitted
    scatter needs no branches. ``alloc`` returns page indices or ``None``
    when the pool cannot satisfy the request — admission control's signal
    to queue (never a partial grant). ``high_water_used`` tracks the peak
    concurrent page usage for the ``kv_cache`` ledger event.

    Allocation states per page: FREE (refcount 0, on the min-heap, grants
    come lowest-index-first for run-to-run determinism), LIVE (refcount
    >= 1 — shared when >= 2), or CACHED (refcount 0 but still in the
    prefix index: content preserved for future hits, reclaimed FIFO when
    the heap runs dry). ``pages_free`` counts FREE + CACHED — both are
    allocatable, so admission watermarks see true headroom.

    A contiguous allocator serving the same ``max_len``-capable slots would
    need ``slots * pages_for(max_len, page_size)`` pages up front; the pool
    needs only the sum of live sequences' ACTUAL pages — the fragmentation
    pin in tests/test_serve.py runs mixed-length traffic through a pool the
    contiguous layout provably cannot fit.
    """

    def __init__(self, layers, num_pages: int, page_size: int,
                 num_heads: int = None, head_dim: int = None,
                 dtype=jnp.float32, kv_quant: str = "none",
                 read: str = "exact", mesh=None, max_slots: int = 0):
        # ``layers``: a model's ``cache_layout()``, which says what EACH
        # layer keeps (pages of so many KV heads, or ``max_slots`` rows of
        # state), or a COUNT of layers that all keep pages of ``num_heads``
        # heads of ``head_dim``, as the pool was made before PR 26
        layout = layers
        if isinstance(layers, int):
            layout = (("pages", num_heads, head_dim, 1),) * layers
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be 'none' or 'int8', "
                             f"got {kv_quant!r}")
        if read not in ("exact", "flash"):
            raise ValueError(f"read must be 'exact' or 'flash', "
                             f"got {read!r}")
        if read == "flash" and kv_quant != "int8":
            raise ValueError("read='flash' is the int8-KV kernel path; "
                             "pass kv_quant='int8' (the fp exact path "
                             "needs no kernel)")
        # sp sharding (long-context serving): the arenas' page dimension is
        # laid out as `n` per-DEVICE blocks of `pages/n + 1` rows — every
        # device carries its own pages plus its own LOCAL trash row, so the
        # branch-free masked scatter survives sharding with zero cross-
        # device traffic. Logical page ids stay 0..num_pages-1 host-side;
        # device programs see FLAT rows via flat_block_table(). A 1-device
        # (or absent) mesh degenerates to the classic num_pages+1 layout
        # and an identity translation.
        self.sp_mesh = mesh
        n = 1
        if mesh is not None:
            if SP_AXIS not in mesh.shape:
                raise ValueError(
                    f"sharded pool needs a mesh with the {SP_AXIS!r} axis "
                    f"(got axes {tuple(mesh.axis_names)})")
            n = mesh.shape[SP_AXIS]
            if num_pages % n:
                raise ValueError(
                    f"num_pages {num_pages} must divide by the {SP_AXIS!r} "
                    f"axis size {n} (whole pages per device)")
        self.sharded_devices = n
        self.num_layers = len(layout)
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_quant = kv_quant
        self.read = read
        self.pages_per_device = num_pages // n
        self._rows_local = self.pages_per_device + 1   # + local trash row
        rows = n * self._rows_local

        def zeros(shp, dt):
            z = jnp.zeros(shp, dt)
            if mesh is not None:
                z = jax.device_put(z, NamedSharding(mesh, P(SP_AXIS)))
            return z

        # one entry a model layer, in the model's order: a PagedLayer of
        # page arenas, or (slot state) a dict of [max_slots, ...] arrays
        # made once and addressed by SLOT: written by prefill, updated in
        # place by every tick, never shared between sequences
        self._layers: List = []
        self.state_bytes = 0
        self.window_bytes = 0            # the rings, all slots
        self.kv_bytes_per_token = 0      # K and V of one token, all pages
        for kind, *spec in layout:
            if kind == "shared":
                # reads layer spec[0]'s pages; keeps nothing
                self._layers.append(None)
                continue
            if kind == "slot_state":
                if mesh is not None:
                    raise NotImplementedError(
                        "slot state in an sp-sharded pool: the arenas "
                        "shard by page, and per-slot recurrent state has "
                        "no page to shard by")
                state = {name: jnp.zeros((max_slots, *shp), dt)
                         for name, (shp, dt) in spec[0].items()}
                self.state_bytes += sum(x.nbytes for x in state.values())
                self._layers.append(state)
                continue
            heads, hdim = spec[0], spec[1]
            # K and V of one token in this layer (int8: + a float32 scale a
            # head)
            token_bytes = 2 * heads * (
                hdim + 4 if kv_quant == "int8"
                else hdim * jnp.dtype(dtype).itemsize)
            if kind == "window" or "rows" in spec[3:]:
                if mesh is not None or kv_quant != "none":
                    raise NotImplementedError(
                        "a window ring or a rows-layout KV layer in an "
                        "sp-sharded or int8 pool: its read "
                        "(ops.paged_attention.grouped_read) takes unsharded "
                        "bf16 or fp32 rows")
                # a ring: one page more than the window holds, a slot
                ring = (pages_for(spec[3], page_size) + 1
                        if kind == "window" else 0)
                shape = (max_slots * ring if ring else rows, page_size,
                         heads * hdim)
                self._layers.append(PagedLayer(
                    zeros(shape, dtype), zeros(shape, dtype), read=read,
                    ring=ring))
                if ring:
                    self.window_bytes += (max_slots * ring * page_size
                                          * token_bytes)
                else:
                    self.kv_bytes_per_token += token_bytes
                continue
            shape = (rows, page_size, heads, hdim)
            sshape = (rows, page_size, heads)
            self.kv_bytes_per_token += token_bytes
            if kv_quant == "int8":
                self._layers.append(PagedLayer(
                    zeros(shape, jnp.int8), zeros(shape, jnp.int8),
                    zeros(sshape, jnp.float32), zeros(sshape, jnp.float32),
                    quant="int8", read=read))
            else:
                self._layers.append(PagedLayer(
                    zeros(shape, dtype), zeros(shape, dtype),
                    quant="none", read=read))
        # per-device min-heaps of free page indices: O(log n) per
        # free/grant (round-18 discipline), grants lowest GLOBAL index
        # first across the heaps — for an unsharded pool this is ONE heap
        # and exactly the round-11 grant order (determinism pin in
        # test_serve). The per-device split exists for the sp prefill's
        # striped prompt allocation (alloc_for_slots), where each device
        # scatters its own shard's K/V into pages it physically holds.
        self._free_by_dev: List[List[int]] = [
            list(range(d * self.pages_per_device,
                       (d + 1) * self.pages_per_device))
            for d in range(n)]
        for h in self._free_by_dev:
            heapq.heapify(h)
        self._ref: List[int] = [0] * num_pages
        # rc==0 pages still carrying indexed prefix content, FIFO by
        # release order (deterministic reclaim under pressure)
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        # prefix index: full-prefix sha1 -> page holding its last whole
        # page of K/V rows, plus parent-hash -> [(page_tokens, page)] for
        # frontier (partial-page) matches; _reg maps page -> its keys so
        # reclaim can unregister in O(children)
        self._full_index: Dict[str, int] = {}
        self._children: Dict[str, List[Tuple[Tuple[int, ...], int]]] = {}
        self._reg: Dict[int, Tuple[Optional[str], str,
                                   Tuple[int, ...]]] = {}
        self.high_water_used = 0
        # cumulative counters (the kv_cache ledger event + bench headline)
        self.prefix_hits = 0        # pages served from the index
        self.prefix_lookups = 0     # share_prefix calls
        self.cow_copies = 0         # frontier forks performed
        self.alloc_total = 0        # fresh pages granted (pages/request)
        # request tracing (obs.reqtrace): bound by the serving engine so
        # prefix hits and CoW forks surface as per-request detail spans;
        # a standalone pool stays silent
        self._tracer = None
        self._now = None

    def bind_trace(self, tracer, now_fn) -> None:
        """Attach the engine's trace context: ``tracer`` derives span ids
        (None disables), ``now_fn`` is the ENGINE clock — span timestamps
        must live on the same axis as the scheduler's queue/prefill
        spans, not this module's idea of time."""
        self._tracer = tracer
        self._now = now_fn

    # -- allocator --------------------------------------------------------
    @property
    def pages_free(self) -> int:
        """Allocatable pages: truly free + cached (reclaimable) ones."""
        return (sum(len(h) for h in self._free_by_dev)
                + len(self._cached))

    @property
    def pages_used(self) -> int:
        return self.num_pages - self.pages_free

    @property
    def shared_pages(self) -> int:
        """Pages currently referenced by 2+ sequences."""
        return sum(1 for r in self._ref if r >= 2)

    def pages_needed(self, total_tokens: int) -> int:
        return pages_for(total_tokens, self.page_size)

    def page_device(self, page: int) -> int:
        """The device block a logical page physically lives in (always 0
        for an unsharded pool)."""
        return page // self.pages_per_device

    def _pop_free(self) -> Optional[int]:
        """Pop the lowest GLOBAL free index across the per-device heaps
        (O(devices) peek — devices is single digits)."""
        best = None
        for h in self._free_by_dev:
            if h and (best is None or h[0] < best[0]):
                best = h
        return heapq.heappop(best) if best is not None else None

    def alloc(self, n: int) -> Optional[List[int]]:
        """Grant ``n`` fresh pages at refcount 1 (all-or-nothing; None
        when short). Free pages go first, lowest index first; cached
        prefix pages are reclaimed FIFO (and unregistered) only when the
        free heaps run dry — pool pressure evicts the cache, never the
        other way around."""
        if n > self.pages_free:
            return None
        grant: List[int] = []
        while len(grant) < n:
            page = self._pop_free()
            if page is None:
                page, _ = self._cached.popitem(last=False)
                self._unregister(page)
            grant.append(page)
        for p in grant:
            self._ref[p] = 1
        self.alloc_total += n
        self.high_water_used = max(self.high_water_used, self.pages_used)
        return grant

    def alloc_for_slots(self, devs: Sequence[int]) -> Optional[List[int]]:
        """Grant one page per requested DEVICE, in slot order (all-or-
        nothing; None when any device is short). The sp prefill's striped
        prompt allocation: block-table slot ``t`` of a sequence prefilled
        over ``n`` sequence shards must live on the device whose shard
        writes its rows (``(t * page_size) // shard_len``) — reads never
        care (the gather psum is location-free), so only the prompt slots
        an sp prefill will scatter into come through here. Per-device
        grants are lowest-index-first; cached pages on the right device
        reclaim FIFO, same policy as :meth:`alloc`."""
        need = Counter(devs)
        for d, c in need.items():
            avail = len(self._free_by_dev[d]) + sum(
                1 for p in self._cached if self.page_device(p) == d)
            if avail < c:
                return None
        grant: List[int] = []
        for d in devs:
            if self._free_by_dev[d]:
                p = heapq.heappop(self._free_by_dev[d])
            else:
                p = next(q for q in self._cached
                         if self.page_device(q) == d)
                del self._cached[p]
                self._unregister(p)
            self._ref[p] = 1
            grant.append(p)
        self.alloc_total += len(grant)
        self.high_water_used = max(self.high_water_used, self.pages_used)
        return grant

    def flat_block_table(self, bt: np.ndarray) -> np.ndarray:
        """Logical page ids -> FLAT arena rows (the device programs' view):
        page ``p`` sits at ``p + p // pages_per_device`` (its device block
        offset by one trash row per preceding device), and the unassigned
        sentinel (``num_pages``) maps to the LAST arena row — a trash row,
        so masked writes and padded gathers keep landing on garbage that
        no live sequence owns. Identity for an unsharded pool."""
        bt = np.asarray(bt)
        return np.where(
            bt >= self.num_pages,
            self.sharded_devices * self._rows_local - 1,
            bt + bt // self.pages_per_device).astype(np.int32)

    def free(self, pages: List[int]) -> None:
        """Drop one reference per listed page. A page parks in the cached
        set when it still carries indexed prefix content, else returns to
        the free heap. Double-frees raise — a leaked or double-counted
        page corrupts another sequence's cache silently otherwise."""
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"double-free of page {p} (refcount 0)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                if p in self._reg:
                    self._cached[p] = None
                else:
                    heapq.heappush(self._free_by_dev[self.page_device(p)],
                                   p)

    def contiguous_pages_needed(self, slots: int, max_total: int) -> int:
        """What a contiguous per-slot allocator would preallocate for the
        same capacity — the fragmentation comparison baseline."""
        return slots * self.pages_needed(max_total)

    # -- prefix index -----------------------------------------------------
    def share_prefix(self, prompt: np.ndarray,
                     rid: Optional[int] = None) -> PrefixMatch:
        """Map the longest resident prefix of ``prompt`` onto shared
        pages: whole-page hits first (index walk by cumulative prefix
        hash), then one frontier page whose leading rows match the
        remaining tail. Bumps refcounts (un-parking cached pages) and
        returns a :class:`PrefixMatch`; ``unshare`` undoes it when the
        admission cannot complete. ``rid`` attributes a hit to a request
        trace (a ``prefix_hit`` detail span) when tracing is bound."""
        t0 = self._now() if self._now is not None else 0.0
        self.prefix_lookups += 1
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        ps = self.page_size
        pages: List[int] = []
        full = 0
        parent = _prefix_key(prompt[:0])
        while (full + 1) * ps <= prompt.size:
            key = _prefix_key(prompt[:(full + 1) * ps])
            page = self._full_index.get(key)
            if page is None:
                break
            self._retain(page)
            pages.append(page)
            full += 1
            parent = key
        cov = full * ps
        partial = False
        tail = tuple(int(t) for t in prompt[cov:])
        if tail:
            for content, page in self._children.get(parent, ()):
                if len(content) >= len(tail) \
                        and content[:len(tail)] == tail:
                    self._retain(page)
                    pages.append(page)
                    partial = True
                    cov += len(tail)
                    break
        self.prefix_hits += len(pages)
        if pages:
            self.high_water_used = max(self.high_water_used,
                                       self.pages_used)
        if pages and self._tracer is not None and rid is not None:
            # a HIT is trace-worthy (it explains a cheap prefill); misses
            # are the default and would only pad the ledger
            tr = self._tracer
            tid, sid, par = tr.ids(rid, "prefix_hit")
            tr.ledger.emit("span", trace_id=tid, span_id=sid,
                           parent_id=par, name="prefix_hit", rid=rid,
                           start=round(t0, 6), end=round(self._now(), 6),
                           pages=len(pages), full=full, partial=partial,
                           cov=cov, **tr.attrs())
        return PrefixMatch(pages, full, partial, cov)

    def unshare(self, match: PrefixMatch) -> None:
        """Roll back ``share_prefix`` (admission failed downstream)."""
        self.free(match.pages)
        self.prefix_hits -= len(match.pages)

    def _retain(self, page: int) -> None:
        if self._ref[page] == 0:
            self._cached.pop(page, None)
        self._ref[page] += 1

    def register_prefix(self, prompt: np.ndarray, pages: List[int],
                        skip_slots: int = 0) -> None:
        """Index a freshly-prefilled prompt's pages for future sharing:
        whole prompt pages under their cumulative prefix hash, every page
        (including the final partial one) as a child of its parent hash
        with its prompt-resident token content — the frontier-match side.
        ``skip_slots`` leading block-table slots came from ``share_prefix``
        and are already indexed (registering them again would double-map
        one hash to two pages)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        ps = self.page_size
        n_slots = pages_for(prompt.size, ps)
        for i in range(skip_slots, n_slots):
            page = pages[i]
            content = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            parent = _prefix_key(prompt[:i * ps])
            is_full = len(content) == ps
            full_key = _prefix_key(prompt[:(i + 1) * ps]) if is_full \
                else None
            if full_key is not None and full_key in self._full_index:
                continue         # identical prefix already indexed
            siblings = self._children.setdefault(parent, [])
            if any(c == content for c, _ in siblings):
                continue
            if page in self._reg:
                continue         # one page, one identity
            siblings.append((content, page))
            if full_key is not None:
                self._full_index[full_key] = page
            self._reg[page] = (full_key, parent, content)

    def _unregister(self, page: int) -> None:
        full_key, parent, content = self._reg.pop(page)
        if full_key is not None:
            self._full_index.pop(full_key, None)
        kids = self._children.get(parent)
        if kids:
            kids[:] = [(c, p) for c, p in kids if p != page]
            if not kids:
                del self._children[parent]

    def fork_page(self, src: int, dst: int,
                  rid: Optional[int] = None) -> None:
        """Copy-on-write fork: duplicate ``src``'s rows onto the already-
        granted ``dst`` in every layer's arenas and drop one reference
        from ``src`` (the forking sequence's). The caller swaps its block
        table entry; other holders keep reading ``src``. ``rid``
        attributes the fork cost to a request trace (a ``cow_fork``
        detail span) when tracing is bound."""
        from tpu_dist.ops.paged_attention import cow_fork_pages

        t0 = self._now() if self._now is not None else 0.0
        # arenas index by FLAT rows (sharded pools interleave trash rows);
        # identity when unsharded
        flat = self.flat_block_table(np.asarray([src, dst], np.int32))
        src_a = jnp.asarray(flat[:1])
        dst_a = jnp.asarray(flat[1:])
        self._layers = list(cow_fork_pages(tuple(self._layers),
                                           src_a, dst_a))
        self.free([src])
        self.cow_copies += 1
        if self._tracer is not None and rid is not None:
            tr = self._tracer
            tid, sid, par = tr.ids(rid, "cow_fork")
            tr.ledger.emit("span", trace_id=tid, span_id=sid,
                           parent_id=par, name="cow_fork", rid=rid,
                           start=round(t0, 6), end=round(self._now(), 6),
                           src=src, dst=dst, **tr.attrs())

    # -- arena plumbing ---------------------------------------------------
    def layers(self) -> tuple:
        """What each layer holds (a ``PagedLayer`` pack or a slot-state
        dict), as one jit argument: the programs donate it and ``adopt``
        takes it back, so neither kind is copied a token."""
        return tuple(self._layers)

    def page_layers(self) -> tuple:
        """The layers that hold pages (block-table pages or rings)."""
        return tuple(l for l in self._layers if isinstance(l, PagedLayer))

    def adopt(self, new_layers) -> None:
        """Store the functionally-updated arenas a jitted program returned
        (the scheduler calls this after every prefill/tick)."""
        self._layers = list(new_layers)

    def stats(self) -> dict:
        return {"pages_free": self.pages_free,
                "pages_used": self.pages_used,
                "pages_total": self.num_pages,
                "pages_cached": len(self._cached),
                "page_size": self.page_size,
                "sharded_devices": self.sharded_devices,
                "pages_per_device": self.pages_per_device,
                "high_water_used": self.high_water_used,
                "shared_pages": self.shared_pages,
                "prefix_hits": self.prefix_hits,
                "prefix_lookups": self.prefix_lookups,
                "cow_copies": self.cow_copies,
                "alloc_total": self.alloc_total,
                "kv_quant": self.kv_quant,
                "state_bytes": self.state_bytes,
                "window_bytes": self.window_bytes,
                "kv_bytes_per_token": self.kv_bytes_per_token}
