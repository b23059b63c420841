"""99th percentile of the gaps between successive tokens of one request,
over every request and token of the window, with the token times taken
inside the program (the ends of the ``serve.prefill`` and ``serve.tick``
spans that produced them). ``gap_p95_ms`` is a per-request mean and
averages a prefill stall away; this sees each one."""

from benchmarks.harness.stats import percentile
from benchmarks.trace import program_spans as ps


def read(obs):
    spans = ps.serving_spans(obs)
    if not spans:
        return None
    gaps = [1e3 * (b - a) for times in ps.token_times(spans).values()
            for a, b in zip(times, times[1:])]
    if not gaps:
        return None
    print(f"token gaps: {len(gaps)} in the window, p50 "
          f"{percentile(gaps, 50):.3f} ms, p99 {percentile(gaps, 99):.3f} ms, "
          f"longest {max(gaps):.3f} ms", flush=True)
    return percentile(gaps, 99)
