"""ttft_p90_ms (ms, lower is better; host clock): 90th percentile of the time
to first token over every request due in the window, measured from its due
time; a request with no first token by the close counts with its wait so
far."""
from bench.harness import stats


def read(run):
    first = {rid: times[0] for rid, times in run.log.token_times.items() if times}
    ttft = stats.censored_ttft(run.log.due, first, run.log.window[1])
    p = stats.percentile(ttft, 90)
    return None if p is None else p * 1e3
