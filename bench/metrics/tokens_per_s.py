"""tokens_per_s (tokens/s, higher is better; host clock): output tokens
emitted in the window over the window's seconds."""
from bench.harness import readers


def read(run):
    w0, w1 = run.log.window
    return readers.window_token_count(run) / (w1 - w0)
