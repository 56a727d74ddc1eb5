"""Production mesh construction (lazy — importing this module never touches
jax device state; the dry-run sets the host-device-count flag before any
jax import, see dryrun.py).

Topology model: TPU v5e pods of 256 chips in a 16×16 2D torus.  Single-pod
mesh (data=16, model=16); multi-pod adds a leading "pod" axis (pure DP
across pods — the slowest links carry only gradient all-reduces).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices=None
) -> Mesh:
    """A mesh whose axes are all ``Auto``: the repo places arrays with
    ``NamedSharding`` constraints and lets the partitioner propagate them
    (``jax.make_mesh`` itself now defaults to ``Explicit`` axes, under
    which sharded slicing, reshapes and scatters must name their output
    sharding)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = jax.device_count()
    data = data if data is not None else n // model
    return make_mesh((data, model), ("data", "model"))


# Hardware constants (TPU v5e) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (conservative: 1 link/hop)
