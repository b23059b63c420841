"""Share (%) of the window's admissions whose prefill ran ahead of the
host, from inside the program: of the window's waited-for ``serve.prefill``
spans, those whose ``ahead`` attribute is 1 (the prefill's program was
called while an earlier prefill of the same step was still unread, so the
device went from one prompt to the next without the host in between). A
span without the attribute counts as 0, so a program that reads every
first token before it plans the next admission reads 0.0, not nothing."""

from benchmarks.trace import admissions
from benchmarks.trace import program_spans as ps


def read(obs):
    prefills = admissions.waited_prefills(ps.serving_spans(obs))
    if not prefills:
        return None
    return 100.0 * sum(sp.attrs.get("ahead", 0) == 1
                       for sp in prefills) / len(prefills)
