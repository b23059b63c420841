"""Share of a serving window's device time in the paged read: the
operations that gather every slot's pages out of a layer's arena
(``gather_pages``) and those that consume the gathered rows (the cast, the
scores, the weighted sum). They are found by shape, from the cell's own
``ServeConfig``: results or operands of [slots * pages_per_seq, page_size,
heads, head_dim] and scores of [slots, max_len, heads]. (The program gives
these operations no stable name yet: PERF.md, Open question 9.)"""

import re


def read(obs):
    trace = obs.get("trace")
    if trace is None or "engine_steps" not in obs:
        return None
    cell = obs["cell"]
    s, srv = cell.config, cell.workload["serve"]
    pages = srv["max_slots"] * (srv["max_len"] // srv["page_size"])
    gathered = f"[{pages},{srv['page_size']},{s['num_heads']},{s['head_dim']}]"
    scores = f"[{srv['max_slots']},{srv['max_len']},{s['num_heads']}]"
    hit = re.compile(re.escape(gathered) + "|" + re.escape(scores))
    read_s = sum(v for k, v in trace.op_seconds.items() if hit.search(k))
    total = sum(trace.op_seconds.values())
    return 100.0 * read_s / total if read_s > 0 else None
