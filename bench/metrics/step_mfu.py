"""step_mfu (%; whole step; moves itl_p99_ms): model operations of the tokens
the traced steps processed (decode tokens with their readout, prompt chunks
with the first token's readout; ``harness/counts``) over the traced steps'
wall time times the chip's peak bf16 FLOP/s."""
from bench.harness import readers


def read(run):
    if run.trace is None:
        return None
    steps = readers.traced_steps(run)
    wall = sum(t1 - t0 for t0, t1, _ in steps)
    f = readers.traced_step_flops(run)
    if wall <= 0 or f == 0:
        return None
    return 100.0 * f / (wall * run.peaks["bf16_flops_per_s"])
