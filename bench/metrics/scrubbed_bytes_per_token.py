"""scrubbed_bytes_per_token (bytes/token; pool and reactive repair,
serving/pool.py and serving/repair.py; moves itl_p99_ms): KV bytes the
reactive scrub rewrote over the window per output token, from
``Engine.metrics()["scrubbed_bytes"]``.  Nothing to read at BER 0."""
from bench.harness import readers


def read(run):
    c = run.log.counters
    n = readers.window_token_count(run)
    if run.cell.ber <= 0 or "open" not in c or n == 0:
        return None
    return (c["close"]["scrubbed_bytes"] - c["open"]["scrubbed_bytes"]) / n
