"""Share of the traced serving window in which no operation ran on the
device: 1 - union of device-op intervals / window."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or "engine_steps" not in obs:
        return None
    return 100.0 * trace.idle_share
