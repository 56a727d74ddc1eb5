"""`ServingConfig` — the knob surface of the continuous-batching engine.

One frozen dataclass owns the pool geometry (pages × page size), the batch
shape (decode slots × block-table width — both static so every decode step
hits one compiled executable), the repair granularity, the background-sweep
cadence, and the simulation BER.  README §Serving engine documents each
field; the invariants below keep the scheduler deadlock-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

_REPAIR_MODES = ("page", "whole", "off")
_PAGED_DECODE = ("auto", "off")
_PAGED_PREFILL = ("auto", "off")
_SWAP_POLICIES = ("swap", "recompute")

# split-K auto heuristic: engage flash decoding once the block-table walk
# is at least this many pages wide (below it the serial walk wins — the
# merge stage costs more than it saves)
_SPLIT_K_MIN_PAGES = 8
# ... into at most this many splits: the split-K walk reads only live
# pages, in blocks of up to 256 keys, so on one TensorCore more splits
# add grid cells and merge work and no parallelism (a TPU v5e sweep at
# the chat cell's batch: 1 to 5 splits within a few percent, 10 and up
# slower; PERF.md §6)
_SPLIT_K_AUTO_MAX = 4


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Pool / scheduler / repair configuration for the serving engine.

    Pool geometry:
      page_size              tokens per KV page (the repair + accounting unit)
      n_pages                pool capacity (one extra null page is allocated
                             internally for block-table padding)

    Batch shape (static — one compiled decode step for the whole run):
      max_batch              concurrent decode slots
      max_pages_per_request  block-table width; caps a request's context at
                             ``max_seq = page_size * max_pages_per_request``

    Repair:
      repair                 "page"  — scrub only the faulted pages among
                                       those the step touched (the paper's
                                       reactive design at page granularity)
                             "whole" — scrub the entire pool whenever any
                                       touched page faulted (the pre-engine
                                       scrub_cache baseline)
                             "off"   — no repair (zero-BER / oracle runs)
      sweep_interval         background low-rate sweep cadence in engine
                             steps (0 disables); catches flips in cold pages
                             no step touches.  This is the demoted role of
                             the old whole-cache ``ScrubSchedule``.
      sweep_pages            pages repaired per background sweep tick
      paged_decode           "auto" — decode straight off the pool through
                                      the fused paged-attention kernel when
                                      the model + pool rules allow it (zero
                                      full-view copies; README §Serving
                                      engine)
                             "off"  — always use the gathered-view decode
                                      (the PR-2 baseline; bench comparison
                                      arm)
      paged_prefill          "auto" — admission prefills straight off the
                                      pool through the chunked-q paged
                                      kernel whenever the fused decode plan
                                      engages (zero full-view copies at
                                      admission too)
                             "off"  — gathered-view prefill (comparison arm)
      prefill_chunk          vllm-style chunked prefill: at most this many
                             prompt tokens per request per engine step, so
                             long admissions interleave with decode instead
                             of stalling it (0 = whole remaining prompt in
                             one chunk).  Only the fused paged prefill
                             chunks; the gathered fallback always prefills
                             whole.
      split_k                split-K flash decoding (``SNIPPETS.md`` 3):
                             0 — auto: split the page walk once the block
                                 table is >= 8 pages wide, into the largest
                                 divisor of ``max_pages_per_request`` that
                                 is <= 4
                             1 — always serial (comparison arm)
                             N — split into (the largest divisor of the
                                 block-table width <=) N grid cells
      drain_interval         desynchronized stats drain (README §Serving
                             engine — "Sharded decode & load testing"):
                             0 — legacy lockstep: every fused lane reads its
                                 per-page fatal counts back to the host and
                                 scrubs within the same engine step
                             N — the fused kernels' counter vectors stay
                                 resident on device and accumulate across
                                 steps; every N steps ONE readback drains
                                 them and the reactive scrub covers the
                                 union of flagged pages.  Token streams are
                                 unchanged (the fused kernels repair on
                                 read with a value-independent fill, so
                                 deferring the HBM scrub never changes what
                                 attention consumes); ``N == 1`` replays
                                 the legacy scrub trajectory exactly while
                                 still batching each step's readbacks into
                                 one.  Requires the fused paged path;
                                 ignored on the gathered fallback.

    Prefix cache (README §Serving engine):
      prefix_cache           share KV pages between requests with a common
                             token prefix: admit matches the longest cached
                             prefix, prefills only the suffix, and finished
                             prefixes stay resident (refcounted, copy-on-
                             write forks at page-interior divergence)
      max_cached_pages       cap on pages the cache may keep referenced
                             (0 = no cap beyond the pool itself); LRU
                             eviction reclaims cache-only pages when the
                             cap — or an allocation — demands it
      dwell_threshold        expected-fault gate for scrub-on-reuse: a hit
                             page is scrubbed before re-sharing only when
                             ``ApproxConfig.expected_faults(page_bytes,
                             dwell_steps, ber)`` reaches this value.  ≤ 0
                             means scrub on EVERY hit (the always-scrub
                             comparison arm in benchmarks/prefix_cache.py)

    Tiered KV (README §Serving engine — "Tiered KV"):
      host_pages             capacity of the host-memory exact tier in pages
                             (0 disables tiering entirely).  May exceed
                             ``n_pages`` — host DRAM is the cheap tier.
      swap_policy            "swap"      — preemption parks the victim's
                                           pages in the host tier (boundary
                                           scrub on the way out) and swap-in
                                           restores them on re-admission;
                                           recompute survives only as the
                                           host-store-full fallback
                             "recompute" — preemption always drops pages and
                                           re-prefills (the pre-tier
                                           behavior; comparison arm).  The
                                           prefix cache still demotes cold
                                           entries when ``host_pages > 0``.

    Simulation:
      ber                    bit-error rate of one approximate-memory window
                             (applied to the pool between engine steps;
                             0 disables injection)
      seed                   PRNG seed for injection + pool init

    Observation:
      record_logits          keep every generated token's readout logits
                             (f32, one row of ``vocab``) in
                             ``Engine.results[rid]["logits"]`` — for
                             parity checks between serving paths.  Costs
                             one extra (max_batch, vocab) readback per lane.
    """

    page_size: int = 16
    n_pages: int = 64
    max_batch: int = 8
    max_pages_per_request: int = 8

    repair: str = "page"
    sweep_interval: int = 0
    sweep_pages: int = 4
    paged_decode: str = "auto"
    paged_prefill: str = "auto"
    prefill_chunk: int = 0
    split_k: int = 0
    drain_interval: int = 0

    prefix_cache: bool = False
    max_cached_pages: int = 0
    dwell_threshold: float = 1.0

    host_pages: int = 0
    swap_policy: str = "swap"

    ber: float = 0.0
    seed: int = 0

    record_logits: bool = False

    # Online autopilot guard (README §Autopilot): an ``AutopilotConfig``
    # (runtime.config) arms the engine's per-window fault monitor — drifting
    # pool rule groups are tightened (stricter detector, then exact
    # demotion) against the profiled expectations.  ``None`` disables it.
    autopilot: Optional[Any] = None

    def __post_init__(self):
        if self.repair not in _REPAIR_MODES:
            raise ValueError(f"bad repair granularity {self.repair!r}")
        if self.paged_decode not in _PAGED_DECODE:
            raise ValueError(f"bad paged_decode mode {self.paged_decode!r}")
        if self.paged_prefill not in _PAGED_PREFILL:
            raise ValueError(f"bad paged_prefill mode {self.paged_prefill!r}")
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 ({self.prefill_chunk})")
        if self.split_k < 0:
            raise ValueError(f"split_k must be >= 0 ({self.split_k})")
        if self.drain_interval < 0:
            raise ValueError(
                f"drain_interval must be >= 0 ({self.drain_interval})"
            )
        if self.page_size < 1 or self.n_pages < 1:
            raise ValueError("page_size and n_pages must be >= 1")
        if self.max_pages_per_request > self.n_pages:
            # a lone request must always be able to make progress — otherwise
            # preemption has no victim and the scheduler deadlocks
            raise ValueError(
                "max_pages_per_request must not exceed n_pages "
                f"({self.max_pages_per_request} > {self.n_pages})"
            )
        if self.swap_policy not in _SWAP_POLICIES:
            raise ValueError(f"bad swap_policy {self.swap_policy!r}")
        if self.host_pages < 0:
            raise ValueError(f"host_pages must be >= 0 ({self.host_pages})")
        if self.max_cached_pages < 0 or self.max_cached_pages > self.n_pages:
            raise ValueError(
                "max_cached_pages must lie in [0, n_pages] "
                f"({self.max_cached_pages} vs {self.n_pages})"
            )

    @property
    def max_seq(self) -> int:
        """Per-request context cap implied by the block-table width."""
        return self.page_size * self.max_pages_per_request

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` cache positions."""
        return -(-n_tokens // self.page_size)

    def resolve_split_k(self) -> int:
        """Grid splits for the decode page walk, resolved against the
        block-table width M.  The kernel requires a divisor of M (each slot
        walked exactly once, or per-page counts would double-charge), so
        both the explicit setting and the auto heuristic round down to the
        largest divisor within their budget."""
        M = self.max_pages_per_request
        if self.split_k == 1:
            return 1
        if self.split_k > 1:
            want = min(self.split_k, M)
        elif M < _SPLIT_K_MIN_PAGES:
            return 1
        else:
            want = _SPLIT_K_AUTO_MAX
        return max(d for d in range(1, want + 1) if M % d == 0)
