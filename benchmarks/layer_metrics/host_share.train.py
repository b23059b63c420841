"""Share of the timed steps' wall time the host spent feeding and
dispatching: sum(data_s + dispatch_s) / sum(data_s + dispatch_s + device_s)
over the engine's own ``step`` ledger records of the timed epochs
(``Trainer._drain`` / ``LMTrainer._drain``)."""


def read(obs):
    recs = obs.get("step_records")
    if not recs:
        return None
    host = sum(r["data_s"] + r["dispatch_s"] for r in recs)
    wall = host + sum(r["device_s"] for r in recs)
    return 100.0 * host / wall if wall > 0 else None
