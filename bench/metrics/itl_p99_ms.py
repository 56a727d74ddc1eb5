"""itl_p99_ms (ms, lower is better; host clock): 99th percentile of every gap
between consecutive output tokens of a request, whose later token lands in
the window."""
from bench.harness import stats


def read(run):
    w0, w1 = run.log.window
    gaps = stats.inter_token_gaps(run.log.token_times.values(), w0, w1)
    p = stats.percentile(gaps, 99)
    return None if p is None else p * 1e3
