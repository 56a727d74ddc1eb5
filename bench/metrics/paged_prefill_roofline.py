"""paged_prefill_roofline (%; kernels, kernels/paged_attention.py; moves
ttft_p90_ms): the least time the traced prompt chunks' attention could take
(the larger of its operations over peak bf16 FLOP/s and its bytes over HBM
bandwidth, ``harness/counts.prefill_kernel``, every layer) over the prefill
kernel's device time."""
import re

from bench.harness import counts, readers

KERNEL = re.compile(r"paged_prefill")


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(KERNEL.search)
    s, p = run.cell.shapes, run.peaks
    least = 0.0
    for q0, n, _ in readers.traced_prefill_chunks(run):
        f, b = counts.prefill_kernel(s, q0, n)
        least += max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
    if t <= 0 or least == 0:
        return None
    return 100.0 * least * s.n_layers / t
