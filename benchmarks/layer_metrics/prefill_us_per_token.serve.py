"""Microseconds of a prefill's own time a prompt token, from inside the
program: the sum over the window's ``serve.prefill`` spans of
``(end - issued) - behind_s`` (``benchmarks/trace/admissions.py``) over the
sum of their ``prompt_len``: live tokens, not the bucket, because padding
is cost and not work. The number a change to long prompts moves, which a
median over admissions never shows. None for a program whose spans lack
``behind_s``."""

from benchmarks.trace import admissions
from benchmarks.trace import program_spans as ps


def read(obs):
    prefills = admissions.waited_prefills(ps.serving_spans(obs))
    if not prefills:
        return None
    tokens = sum(sp.attrs["prompt_len"] for sp in prefills)
    own = sum(admissions.own_s(sp) for sp in prefills)
    return 1e6 * own / tokens if tokens else None
