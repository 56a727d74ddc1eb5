"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests the system finished,
drawn from the seed and always holding the one with the most served tokens,
is replayed through the reference: one teacher-forced pass over each prompt
with its served tokens.  At every served position the gap is how far the
served token's reference logit lies below the reference's best.  Greedy
decoding serves the argmax, so an exact system reads 0, and rounding reads
small gaps at near-ties only.  The number compared is the widest gap over
the sample.

Where the configuration runs in approximate memory, a flip that its
detector lets through (a legal float, such as an exponent bit that
multiplies a K lane of magnitude 2 or more by 2**8 or 2**16) changes a
request's answers as the configuration allows.  The loop logs, at each
injection pass, the requests whose pages took such a flip (``loop.inject``)
and the sample is drawn from the others: they took only flips the detector
repairs, or none, so the scrub's write-back is compared with the rest.

The control (``control=True``) reads the same positions of the same
requests with the tokens that the reference computed in float8
(``precision="fp8"``) puts first, and the verdict judges those.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

S_BUCKET = 1024
# tainted requests the control mode also compares, for the record only
N_TAINTED = 2


def sample(finished: Dict[int, Dict[str, Any]], seed: int, min_tokens: int,
           min_requests: int, max_requests: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, then
    others in an order drawn from ``seed`` until both ``min_tokens``
    served tokens and ``min_requests`` requests, or ``max_requests``."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r]["generated"]), -r))
    rest = [r for r in rids if r != longest]
    rest = [rest[i] for i in np.random.default_rng([seed, 1]).permutation(len(rest))]
    out, n = [longest], len(finished[longest]["generated"])
    for r in rest:
        if (n >= min_tokens and len(out) >= min_requests) or len(out) >= max_requests:
            break
        out.append(r)
        n += len(finished[r]["generated"])
    return out


def s_pad_for(n_tokens: int, max_seq: int) -> int:
    cap = -(-max_seq // 512) * 512
    return min(-(-n_tokens // S_BUCKET) * S_BUCKET, cap)


def gaps(reference, cfg: Dict[str, Any], weights: Any,
         requests: Sequence[Dict[str, Any]], *, max_seq: int, r_pad: int,
         control: bool = False) -> Dict[str, Any]:
    """Per request of ``requests`` (each ``{"prompt", "generated"}``, and
    what else it carries): the served tokens, the widest gap of the served
    tokens, the positions where they are not the reference's argmax, and
    with ``control`` the widest gap of the float8 control's first choices."""
    out, n_tokens = [], 0
    for req in requests:
        prompt, gen = list(req["prompt"]), list(req["generated"])
        tokens = prompt + gen[:-1]
        rows = np.arange(len(prompt) - 1, len(tokens))
        s_pad = s_pad_for(len(tokens), max_seq)
        ref = reference.logits_rows(cfg, weights, tokens, rows, s_pad=s_pad,
                                    r_pad=r_pad)
        best = ref.max(axis=1)
        g = best - ref[np.arange(len(gen)), np.asarray(gen)]
        entry = {k: v for k, v in req.items() if k not in ("prompt", "generated")}
        entry.update(n=len(gen), widest=float(g.max()),
                     mismatch=int(np.sum(ref.argmax(axis=1) != np.asarray(gen))))
        if control:
            low = reference.logits_rows(cfg, weights, tokens, rows, s_pad=s_pad,
                                        r_pad=r_pad, precision="fp8")
            cg = best - ref[np.arange(len(gen)), low.argmax(axis=1)]
            entry["control_widest"] = float(cg.max())
        out.append(entry)
        n_tokens += len(gen)
    return {"tokens": n_tokens, "requests": out}


def verdict(per_request: Sequence[Dict[str, Any]], limit: float,
            key: str) -> Tuple[float, bool]:
    """The widest gap under ``key`` over the compared requests, and whether
    it keeps to ``limit`` (nothing compared is not correct)."""
    gap = max((r[key] for r in per_request), default=0.0)
    return gap, bool(per_request) and gap <= limit
