"""Program spans: one bounded ring, on the engine's clock and the profiler's.

A span has a name, a start, an end, the span that caused it (``parent``:
the span open on the same thread when it began) and attributes; the spans
of one request carry its identifier. Every span the program opens

* is appended, when it closes, to ONE process-wide bounded ring
  (:func:`ring`: a ``collections.deque`` of :data:`RING_SIZE` entries; a
  traced 8 s serving window makes about 2,000). It is always on: no config
  field, no environment variable, no flag. A reader takes
  ``ring().snapshot()``, an operator ``ring().dump(path)`` (JSONL), the
  watchdog and the flight recorder ``open_stack()`` and ``tail()``;
* enters a ``jax.profiler.TraceAnnotation("tpu_dist:<name>", sid=<id>)``,
  which is free while no profiler session runs and, while one runs (a
  benchmark's, the flight recorder's, a ``profile_dir``'s), puts the same
  span on the profiler's clock beside the device's operations. ``sid`` is
  the ring entry's, so a reader joins the two clocks span by span.

Timestamps are ``time.monotonic`` in the trainers and the engine's own
``now_fn`` in ``ServeEngine`` (virtual under a test's virtual clock).
:func:`gc_seconds` times Python's garbage collections and puts the full
ones into the same ring as ``host.gc`` spans.

:class:`StepTracer` is the trainers' view: the same spans, plus the
per-step sums of seconds (``pop()``) that the ledger's ``step`` record
carries as ``data_s``/``dispatch_s``/``device_s``. :func:`step_annotation`
wraps ``StepTraceAnnotation`` so XLA's per-step grouping matches the
ledger's step numbering, and :func:`profile_session` starts a
``profile_dir`` trace and stops it on every exit path.
"""

from __future__ import annotations

import gc
import itertools
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional

RING_SIZE = 65_536
ANNOTATION_PREFIX = "tpu_dist:"

_TraceAnnotation = None


def _annotation(name: str, sid: int):
    # imported on first use: the supervisor imports tpu_dist.obs jax-free
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(ANNOTATION_PREFIX + name, sid=sid)


class Span(NamedTuple):
    sid: int
    name: str
    start: float
    end: float
    parent: Optional[int]        # sid of the span that caused this one
    attrs: dict


class _OpenSpan:
    """One span while it is open; ``attrs`` may be added to until it
    closes (what a phase found out, e.g. how many it evicted)."""

    __slots__ = ("ring", "sid", "name", "now", "attrs", "start", "parent",
                 "seconds", "_ann", "_stack")

    def __init__(self, ring: "SpanRing", name: str, now, attrs: dict):
        self.ring, self.name, self.now, self.attrs = ring, name, now, attrs
        self.sid = next(ring._ids)
        self.seconds = 0.0

    def __enter__(self) -> "_OpenSpan":
        stacks, ident = self.ring._stacks, threading.get_ident()
        stack = self._stack = stacks.get(ident)
        if stack is None:
            stack = self._stack = stacks[ident] = []
        self.parent = stack[-1].sid if stack else None
        stack.append(self)
        self.start = self.now()
        self._ann = _annotation(self.name, self.sid)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        end = self.now()
        self.seconds = end - self.start
        self._stack.pop()
        self.ring._spans.append(Span(self.sid, self.name, self.start, end,
                                     self.parent, self.attrs))


def span_record(span: Span) -> dict:
    """A span as one flat JSON object (the dump's line)."""
    return {"sid": span.sid, "name": span.name, "start": span.start,
            "end": span.end, "parent": span.parent, **span.attrs}


class SpanRing:
    """The bounded ring of closed spans plus each thread's open stack."""

    def __init__(self, size: int = RING_SIZE):
        self._spans: deque = deque(maxlen=size)
        self._ids = itertools.count(1)
        self._stacks: Dict[int, List[_OpenSpan]] = {}

    def span(self, name: str, now: Callable[[], float] = time.monotonic,
             **attrs) -> _OpenSpan:
        """``with ring().span("serve.evict", now=self._now) as sp: ...``"""
        return _OpenSpan(self, name, now, attrs)

    def snapshot(self) -> List[Span]:
        """The closed spans, oldest first (a child closes, and so comes,
        before its parent)."""
        return list(self._spans)

    def tail(self, n: int = 32) -> List[Span]:
        """The last ``n`` closed spans, oldest first."""
        return list(itertools.islice(reversed(self._spans), n))[::-1]

    def open_stack(self, thread_ident: Optional[int] = None) -> List[Span]:
        """The spans open right now on one thread (default: the caller's),
        outermost first; ``end`` is the start again, nothing has ended."""
        ident = threading.get_ident() if thread_ident is None else thread_ident
        return [Span(s.sid, s.name, s.start, s.start, s.parent, s.attrs)
                for s in list(self._stacks.get(ident, ()))]

    def open_stacks(self) -> Dict[int, List[Span]]:
        """thread ident -> its open spans, for the threads that have any."""
        stacks = {ident: self.open_stack(ident)
                  for ident in list(self._stacks)}
        return {ident: spans for ident, spans in stacks.items() if spans}

    def dump(self, path: str) -> int:
        """Write the ring as JSONL, one span a line; returns the count."""
        spans = self.snapshot()
        with open(path, "w") as f:
            for span in spans:
                f.write(json.dumps(span_record(span), default=str) + "\n")
        return len(spans)


def format_spans(spans: Iterable[Span]) -> str:
    """One line a span, for the watchdog's dump."""
    return "\n".join(
        f"  {s.name} [{s.start:.6f} .. {s.end:.6f}] sid={s.sid} "
        f"parent={s.parent} {s.attrs or ''}".rstrip() for s in spans)


_RING = SpanRing()


def ring() -> SpanRing:
    """THE process-wide ring."""
    return _RING


_compiles: Optional[List[int]] = None


def backend_compiles() -> int:
    """Backend compilations of this process since the first call (which
    registers the ``jax.monitoring`` listener; a persistent-cache read
    counts as one, as in ``chip_smoke.py:CompileMeter``). A phase reads it
    before and after: ``Trainer()``'s ``build_compiles``. One listener a
    process, like the ring: ``jax.monitoring`` has no public way to take a
    single listener off again, so a counter an object would leak one each."""
    global _compiles
    if _compiles is None:
        import jax.monitoring

        counter = _compiles = [0]

        def count(event: str, secs: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                counter[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
    return _compiles[0]


_gc_watch: Optional[list] = None


def gc_seconds() -> float:
    """Seconds this process has spent inside Python's garbage collections
    since the first call (which installs ONE ``gc.callbacks`` entry, like
    :func:`backend_compiles`' listener; the engines' constructors make that
    call). Every collection is timed with two ``time.monotonic`` reads. A
    collection of the OLDEST generation (the full ones, tens to hundreds of
    milliseconds on an engine's heap) is also a ring span ``host.gc``
    (``generation``, ``collected``), opened and closed on the thread it
    interrupts, so its ``parent`` is the span it fell into
    (``tick.emit``, ``serve.admit``, ``train.dispatch``); the younger
    generations run many times a second and leave the counter only. The
    span is stamped on ``time.monotonic`` whatever clock an engine was
    given: beside a test's virtual clock its times mean nothing, its
    seconds here still do."""
    global _gc_watch
    if _gc_watch is None:
        watch = _gc_watch = [0.0, None, None]   # seconds, start, open span
        oldest = len(gc.get_stats()) - 1

        def on_gc(phase: str, info: dict) -> None:
            if phase == "start":
                if info["generation"] >= oldest:
                    watch[2] = _RING.span(
                        "host.gc", generation=info["generation"])
                    watch[2].__enter__()
                watch[1] = time.monotonic()
                return
            if watch[1] is None:      # installed inside this collection
                return
            watch[0] += time.monotonic() - watch[1]
            sp, watch[2] = watch[2], None
            if sp is not None:
                sp.attrs["collected"] = info["collected"]
                sp.__exit__(None, None, None)

        gc.callbacks.append(on_gc)
    return _gc_watch[0]


class StepTracer:
    """The trainers' spans: each goes to the ring as ``<prefix><name>`` and
    also accumulates its seconds under its path (``data`` ->
    ``data/decode``; a parent's total includes its children's) until
    :meth:`pop` collects {path: seconds} at a step boundary."""

    def __init__(self, prefix: str = ""):
        self.prefix = prefix
        self._acc: Dict[str, float] = {}
        self._stack: List[str] = []

    @contextmanager
    def span(self, name: str, **attrs):
        path = "/".join(self._stack + [name])
        self._stack.append(name)
        sp = _RING.span(self.prefix + name, **attrs)
        try:
            with sp:
                yield sp
        finally:
            self._stack.pop()
            self._acc[path] = self._acc.get(path, 0.0) + sp.seconds

    def timed_iter(self, name: str, iterable):
        """Yield the iterable's items with every wait for the next one
        under a ``name`` span (the loops' wait for their input)."""
        it = iter(iterable)
        while True:
            with self.span(name):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def pop(self) -> Dict[str, float]:
        """Collect the accumulated {path: seconds} and reset for the next
        step."""
        out, self._acc = self._acc, {}
        return out


@contextmanager
def step_annotation(step_num: int):
    """``jax.profiler.StepTraceAnnotation`` so XLA's per-step trace
    grouping carries the ledger's step number (free with no session)."""
    import jax.profiler
    with jax.profiler.StepTraceAnnotation("step", step_num=step_num):
        yield


@contextmanager
def profile_session(profile_dir: str, enabled: bool = True):
    """Start a ``jax.profiler`` trace into ``profile_dir`` and STOP IT ON
    EVERY EXIT PATH (normal, OOM, interrupt). The engines' only device
    tracing entry point since the round-6 obs refactor (both previously
    carried their own start/stop_trace try/finally)."""
    if not (profile_dir and enabled):
        yield False
        return
    import jax.profiler
    jax.profiler.start_trace(profile_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
