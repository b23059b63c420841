"""What an admission costs the requests that are decoding, from the
program's own spans (PR 38): the helper the ``prefill_own_ms.serve``,
``prefill_us_per_token.serve``, ``gap_prefill_share.serve``,
``gap_gc_share.serve`` and ``prefill_device_share.serve`` readers share.

**A prefill's own time** is ``(serve.prefill's end - issued) - behind_s``:
``issued`` is the engine clock read just before the prefill's program was
called, ``behind_s`` the length of the ``prefill.behind`` child, in which
the host waited for the decode tick that was in flight and for nothing
else. Where that tick was still running the device began the prefill as
``prefill.behind`` ended; where it had landed, at ``issued`` or later. The
host's few lines in between count in, so the reading can be high against
the device's time of the program and never low. A ``serve.prefill`` span
without ``behind_s`` (a program older than PR 38; a chunked prompt's
earlier chunks, which nothing waits for) has no own time.

**Decoding time** is the sum over the window's requests of the time from
the first to the last token they received in it (``program_spans.
token_times``: the ends of the ``serve.prefill`` and ``serve.tick`` spans
that list them), so a moment counts once for every request that was
waiting for its next token then. ``decoding_overlap`` weighs an interval
the same way.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchmarks.trace import program_spans as ps


def own_s(span) -> float:
    """A waited-for ``serve.prefill`` span's own seconds."""
    return (span.end - span.attrs["issued"]) - span.attrs["behind_s"]


def waited_prefills(spans: Optional[Sequence]) -> Optional[list]:
    """The ``serve.prefill`` spans that carry ``behind_s`` and ``issued``;
    None where there is none (no ring, no admission in the window, or a
    program without the attributes)."""
    found = [sp for sp in spans or () if sp.name == "serve.prefill"
             and "behind_s" in sp.attrs and "issued" in sp.attrs]
    return found or None


def own_intervals(prefills: Sequence) -> List[Tuple[float, float, object]]:
    """(start, end, rid) of each prefill's own time, ending at its span's
    end: when every decoding request stood still for it."""
    return [(sp.end - own_s(sp), sp.end, sp.attrs["rid"]) for sp in prefills]


def decoding_spells(spans: Sequence) -> dict:
    """rid -> (first, last) token time inside the window, for the requests
    that received two tokens or more there."""
    return {rid: (times[0], times[-1])
            for rid, times in ps.token_times(spans).items()
            if len(times) > 1}


def decoding_overlap(spells: dict, start: float, end: float,
                     but=None) -> float:
    """Seconds of [start, end] summed over the requests decoding then
    (``but``: a request to leave out, an admission's own)."""
    return sum(max(0.0, min(end, last) - max(start, first))
               for rid, (first, last) in spells.items() if rid != but)


def share_of_decoding(spans: Sequence, intervals) -> Optional[float]:
    """Percent of the window's decoding time that lies inside
    ``intervals`` ((start, end, rid to leave out or None) each); None
    where nothing decoded."""
    spells = decoding_spells(spans)
    total = sum(last - first for first, last in spells.values())
    if total <= 0:
        return None
    inside = sum(decoding_overlap(spells, a, b, rid)
                 for a, b, rid in intervals)
    return 100.0 * inside / total
