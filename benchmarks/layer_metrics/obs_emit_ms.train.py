"""What the engine's observability costs an optimizer step: the seconds of
the ``train.emit`` spans (``_drain`` from the ``device_get``'s return to its
own: the ``step`` records and their fan-out to the CSV, metrics,
flight-recorder and goodput sinks, health, heartbeat) whose ``step`` is one
of the window's records', over the window's optimizer steps."""

from benchmarks.trace import program_spans as ps


def read(obs):
    recs, spans = obs.get("step_records"), ps.ring_spans()
    if not recs or not spans:
        return None
    steps = {r["step"] for r in recs}
    emit = [sp.end - sp.start for sp in spans
            if sp.name == "train.emit" and sp.attrs.get("step") in steps]
    if not emit:
        return None
    return 1e3 * sum(emit) / sum(r["steps_in_dispatch"] for r in recs)
