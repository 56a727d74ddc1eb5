"""prefill_calls_per_step (1/step; model step, the engine's prefill
program; moves ttft_p90_ms): prompt-chunk dispatches (``engine.prefill_chunk``
spans) per traced ``engine.step`` that holds at least one.  Each call
re-reads the weights, so fewer, wider calls per step cost less."""
from bench.harness import spans


def read(run):
    t = run.trace
    if t is None:
        return None
    held = [n for n in spans.children_per_step(t, "engine.prefill_chunk") if n]
    return sum(held) / len(held) if held else None
