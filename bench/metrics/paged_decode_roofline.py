"""paged_decode_roofline (%; kernels, kernels/paged_attention.py; moves
itl_p99_ms): the bytes the traced decode steps' attention needs (the live
contexts' K/V plus q and out, ``harness/counts.decode_kernel``, every layer)
over the decode kernels' device time, against the chip's HBM bandwidth.
Decode attention is bandwidth-bound, so bytes set its roofline."""
import re

from bench.harness import counts, readers

KERNEL = re.compile(r"paged_attention")


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(KERNEL.search)
    s = run.cell.shapes
    need = sum(counts.decode_kernel(s, ctx)[1]
               for _, _, ctx in readers.traced_steps(run) if ctx) * s.n_layers
    if t <= 0 or need == 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
