"""decode_batch_mean (1; scheduler, serving/scheduler.py; moves tokens_per_s):
requests emitting a decode token per engine step that decoded, over the
window, counted from the tokens each ``Engine.step()`` returned."""
from bench.harness import readers


def read(run):
    sizes = [len(ctx) for _, _, ctx in readers.window_steps(run) if ctx]
    return sum(sizes) / len(sizes) if sizes else None
