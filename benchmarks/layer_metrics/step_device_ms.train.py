"""Device busy milliseconds per optimizer step: the union of the device's
operation intervals in the traced window over the steps whose ledger
records fall in it."""


def read(obs):
    trace, recs = obs.get("trace"), obs.get("step_records")
    if trace is None or not recs:
        return None
    steps = sum(r["steps_in_dispatch"] for r in recs)
    return 1e3 * trace.busy_s / steps
