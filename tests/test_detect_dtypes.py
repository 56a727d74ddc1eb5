"""Per-dtype detection edge cases through ``fatal_masks`` — the satellite
coverage for every dtype a ``RepairRule`` can bind (float16/float64 join
float32/bfloat16): signaling vs quiet NaN patterns, subnormals, negative
zero, max-finite, and the range guard's exponent-field compare.

``fatal_masks`` is the ONE definition of "fatal" shared by the jnp repair
path, the rule detectors, and (via the constants operand) the Pallas
kernels, so these patterns pin the contract at the bit level per dtype.
float64 runs under a local ``enable_x64`` scope (the suite is x32).
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detect
from repro.core.repair import fatal_masks
from repro.core.rules import Detector

DTYPES = [jnp.float16, jnp.float32, jnp.bfloat16, jnp.float64]


def _scope(dtype):
    """float64 bit views need x64 enabled; everything else runs as-is."""
    if jnp.dtype(dtype) == jnp.float64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _cases(lay):
    """(bits, is_nan, is_inf) triples covering the per-dtype edge patterns."""
    quiet_bit = 1 << (lay.man_bits - 1)
    return [
        (0, False, False),                                   # +0
        (lay.sign_mask, False, False),                       # -0 (NOT fatal)
        (1, False, False),                                   # min subnormal
        (lay.man_mask, False, False),                        # max subnormal
        (lay.exp_mask - 1, False, False),                    # max finite
        (lay.exp_mask, False, True),                         # +inf
        (lay.exp_mask | lay.sign_mask, False, True),         # -inf
        (lay.exp_mask | 1, True, False),                     # signaling NaN
        (lay.exp_mask | quiet_bit, True, False),             # quiet NaN
        (lay.exp_mask | lay.man_mask, True, False),          # all-ones mantissa
        (lay.sign_mask | lay.exp_mask | quiet_bit, True, False),  # -qNaN
        (lay.sign_mask | lay.exp_mask | 1, True, False),     # -sNaN
    ]


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_fatal_masks_edge_patterns(dtype):
    with _scope(dtype):
        lay = detect.layout_of(dtype)
        cases = _cases(lay)
        bits = np.array([b for b, _, _ in cases], np.dtype(lay.int_dtype))
        x = jax.lax.bitcast_convert_type(jnp.asarray(bits), dtype)

        nan_m, inf_m = fatal_masks(x)                        # NaN + Inf
        assert nan_m.tolist() == [n for _, n, _ in cases]
        assert inf_m.tolist() == [i for _, _, i in cases]

        nan_m, inf_m = fatal_masks(x, include_inf=False)     # NaN-only
        assert nan_m.tolist() == [n for _, n, _ in cases]
        assert not any(inf_m.tolist())


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_signaling_vs_quiet_nan_both_fatal(dtype):
    """The paper's pattern is structural (exp all-ones + mantissa != 0):
    quiet (MSB of mantissa set) and signaling NaNs are the same flip class,
    and both must repair identically under a rule detector."""
    with _scope(dtype):
        lay = detect.layout_of(dtype)
        quiet = lay.exp_mask | (1 << (lay.man_bits - 1))
        signaling = lay.exp_mask | 1
        bits = np.array([quiet, signaling], np.dtype(lay.int_dtype))
        x = jax.lax.bitcast_convert_type(jnp.asarray(bits), dtype)
        nan_m, _ = Detector(inf=False).masks(x)
        assert nan_m.tolist() == [True, True]
        # IEEE agreement, via numpy's own view of the same bits
        np_dt = {16: np.uint16, 32: np.uint32, 64: np.uint64}[lay.width]
        if jnp.dtype(dtype) != jnp.bfloat16:     # numpy has no bf16
            host = bits.astype(np_dt).view(np.dtype(dtype).str)
            np.testing.assert_array_equal(np.isnan(host), [True, True])


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_subnormals_and_negzero_never_fatal(dtype):
    """Subnormals (exp field == 0) and ±0 must never trip any detector
    bucket — a repair that zeroed denormals would silently quantize."""
    with _scope(dtype):
        lay = detect.layout_of(dtype)
        bits = np.array(
            [0, lay.sign_mask, 1, lay.man_mask, lay.sign_mask | 1],
            np.dtype(lay.int_dtype),
        )
        x = jax.lax.bitcast_convert_type(jnp.asarray(bits), dtype)
        for det in (Detector(), Detector(inf=False),
                    Detector(max_magnitude=1e3)):
            nan_m, inf_m = det.masks(x)
            assert not any(nan_m.tolist()), det
            assert not any(inf_m.tolist()), det


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_range_guard_exponent_compare(dtype):
    """max_magnitude is an exponent-field compare: values at/above the
    threshold's binade are fatal (inf bucket), values below are not, NaN
    keeps its own bucket — per dtype layout."""
    with _scope(dtype):
        x = jnp.array([1.0, 900.0, 2048.0, jnp.inf, jnp.nan], dtype)
        nan_m, inf_m = fatal_masks(x, max_magnitude=1024.0)
        assert nan_m.tolist() == [False, False, False, False, True]
        # 900 sits in the binade below 1024 -> not fatal; 2048 and inf are
        assert inf_m.tolist() == [False, False, True, True, False]


def test_float16_vs_bfloat16_layouts_differ():
    """The same 16-bit pattern classifies differently under the two 16-bit
    layouts (5/10 vs 8/7 split) — per-dtype constants are load-bearing."""
    pattern = 0x7C01                       # f16: sNaN; bf16: a finite value
    bits = jnp.asarray(np.array([pattern], np.uint16))
    f16_nan = detect.is_nan_bits(bits, jnp.float16)
    bf16_nan = detect.is_nan_bits(bits, jnp.bfloat16)
    assert bool(f16_nan[0]) is True
    assert bool(bf16_nan[0]) is False


def test_custom_bitpattern_binds_per_dtype():
    """A bitpattern entry tagged with a dtype fires only there; an untagged
    entry fires for every dtype."""
    det = Detector(nan=False, inf=False,
                   bitpatterns=(("float16", 0x7FFF, 0x7C01),))
    f16 = jax.lax.bitcast_convert_type(
        jnp.asarray(np.array([0x7C01], np.uint16)), jnp.float16
    )
    f32 = jnp.array([1.0], jnp.float32)
    assert det.masks(f16)[0].tolist() == [True]
    assert det.masks(f32)[0].tolist() == [False]
