"""m/z quantization — the shared grid that makes backends bit-identical.

Both backends quantize m/z values and ppm-window bounds to int32 units of
1e-5 Da before matching.  Rationale (TPU-first design, SURVEY.md §7):

- TPU has no native f64 (emulated, slow); int32 compares are native.
- Quantizing *identically* on the host makes the numpy_ref and jax_tpu hit
  sets exactly equal — window-edge parity is by construction, not tolerance.
- 1e-5 Da = 0.01 ppm at m/z 1000; windows are ppm-scale, so the quantization
  error is far below instrument accuracy (the reference matches in f64
  [U, formula_imager_segm], a difference without scientific consequence).

int32 ceiling: 2**31 * 1e-5 = 21474 Da, far above any MS m/z range.
"""

from __future__ import annotations

import numpy as np

from ..analysis.numerics import numerics_surface

# Declared numerics contracts (ISSUE 15): the quantization grid IS the
# cross-backend bit-exactness mechanism — host f64 in, shared int32/f32
# grids out, identical for numpy_ref and jax_tpu by construction.  The
# extraction/metric parity tests are the committed proof.
NUMERICS = numerics_surface(__name__, {
    "quantize_mz":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_extraction_parity",
    "quantize_window":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_extraction_parity",
    "quantize_intensities":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_backend_parity_metrics_and_ranks",
    "intensity_scale":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_backend_parity_metrics_and_ranks",
    # resident-intensity compaction: bf16 rounds the quantized integer
    # grid to 8 significant bits — still integers, still summed exactly in
    # any order, so the drift vs the f32 residents is DATA-level (a coarser
    # grid, ~2**-9 relative), not reduction-order: orders of magnitude
    # above the same-data ulp ceilings, which is why this contract is wide.
    # What the test asserts hard is the RANKING: FDR ranks bit-identical on
    # the sentinel fixture.
    "compact_cube":
        "contract=ulp(4096); test=tests/test_cube_compaction.py::"
        "test_quantized_cube_rank_identity",
    "expand_cube_jnp":
        "contract=bit_exact; test=tests/test_cube_compaction.py::"
        "test_compact_expand_roundtrip",
})

MZ_SCALE = 1e5  # quantization steps per Da
MZ_MAX = (2**31 - 2) / MZ_SCALE
# padding sentinel for m/z cubes: larger than any real quantized m/z
MZ_PAD_Q = np.int32(2**31 - 1)


def quantize_mz(mz: np.ndarray) -> np.ndarray:
    """Host-side f64 -> int32 grid. Values beyond MZ_MAX (incl. +inf padding)
    saturate to the padding sentinel."""
    # one f64 buffer, three passes over it (a dataset's flat m/z array is
    # tens of MB: every fresh temporary of that size is mapped and faulted in)
    q = np.asarray(np.multiply(np.asarray(mz, dtype=np.float64), MZ_SCALE))
    np.rint(q, out=q)
    np.minimum(q, float(MZ_PAD_Q), out=q)
    return q.astype(np.int32)


def quantize_window(mzs: np.ndarray, ppm: float) -> tuple[np.ndarray, np.ndarray]:
    """ppm windows [mz*(1-ppm*1e-6), mz*(1+ppm*1e-6)) on the quantized grid.
    Computed in f64 on host, identically in both backends."""
    mzs = np.asarray(mzs, dtype=np.float64)
    lo = quantize_mz(mzs * (1.0 - ppm * 1e-6))
    hi = quantize_mz(mzs * (1.0 + ppm * 1e-6))
    return lo, hi


# -- intensity quantization: order-free exact accumulation --------------------
#
# Ion-image pixel values are sums of peak intensities.  Summation order on a
# TPU (scatter-add trees, MXU accumulation) is implementation-defined, so f32
# sums of arbitrary floats are NOT reproducible across backends or shard
# counts.  The fix is structural: snap intensities to an integer grid scaled
# so that every per-(pixel, window) sum stays below 2**24 — every partial sum
# is then an exactly-representable f32 integer and ANY summation order yields
# the same bits.  The scale is a power of two, so de-quantization (a
# division by 2**k) is also exact in f32 and all MSM metrics — which are
# scale-invariant (chaos thresholds relative to vmax; correlation and
# pattern match are cosines) — see identical values either way.

INT_SUM_BITS = 24  # f32 exact-integer range


# Steps of the occupancy walk before ``window_occupancy`` hands over to the
# binary search.  A step is one compare over the keys; the search and its
# ``hi - arange`` cost 70-100 of them (PERF.md section 6, PR 36, on the chip
# host for 4.37 M peaks: 2.6-3.5 ms a step, 0.23-0.35 s the search), so a
# dataset that has to search after all has lost at most a third of that to
# the walk.  Centroided sections stop at 2-6; profile-like or very dense
# spectra take the search.  Either road gives the same integer.
OCCUPANCY_WALK_CAP = 32


def window_occupancy(
    mzs_flat: np.ndarray,       # (P,) f64, m/z per peak, sorted within pixel
    pixel_of_peak: np.ndarray,  # (P,) pixel index per peak (non-decreasing)
    ppm: float,
    mz_q: np.ndarray | None = None,  # quantize_mz(mzs_flat), when the caller has it
) -> tuple[int, str]:
    """(hmax, route): the most peaks any 2.5 x ppm window of any pixel holds
    on the quantized m/z grid, exactly, and the road that found it.

    key = pixel * 2**32 + mz_q is globally ascending and a window never
    spans the 2**32 inter-pixel gap, so the peaks inside peak i's window
    [key[i], key[i] + width[i]] are i .. hi[i]-1 and
    ``hi[i] - i >= h`` iff ``key[i+h-1] <= key[i] + width[i]``.  ``walk``:
    one shifted compare per h until no i qualifies, a linear pass each.
    ``search``: past ``OCCUPANCY_WALK_CAP`` steps, ``hi`` by a binary search
    of the reaches into the keys, as every dataset was measured before PR 36."""
    n = int(mzs_flat.size)
    if n == 0:
        return 0, "walk"
    if mz_q is None:
        mz_q = quantize_mz(mzs_flat)
    key = pixel_of_peak.astype(np.int64)
    key <<= 32
    key += mz_q
    # generous window bound (2.5x ppm covers any window whose left edge is
    # at this peak, including the center-to-edge asymmetry)
    width = np.multiply(np.asarray(mzs_flat, np.float64), 2.5 * ppm * 1e-6)
    width *= MZ_SCALE
    np.ceil(width, out=width)
    reach = width.astype(np.int64)
    del width
    reach += key
    hmax = 1
    while hmax < n and (key[hmax:] <= reach[:n - hmax]).any():
        hmax += 1
        if hmax > OCCUPANCY_WALK_CAP:
            hi = np.searchsorted(key, reach, side="right")
            hi -= np.arange(n)
            return int(hi.max()), "search"
    return hmax, "walk"


def intensity_scale(
    mzs_flat: np.ndarray,      # (P,) f64, m/z per peak, sorted within pixel
    ints_flat: np.ndarray,     # (P,) intensities
    pixel_of_peak: np.ndarray,  # (P,) pixel index per peak (non-decreasing)
    ppm: float,
    mz_q: np.ndarray | None = None,  # quantize_mz(mzs_flat), when the caller has it
) -> tuple[float, int, str]:
    """(scale, hmax, route): the power-of-two scale 2**k such that
    hmax * max(rint(i*2**k)) < 2**24, where hmax bounds the peak count
    inside any ppm window of any pixel (``window_occupancy``, whose route
    is handed on for the span and the counter)."""
    hmax, route = window_occupancy(mzs_flat, pixel_of_peak, ppm, mz_q=mz_q)
    max_raw = float(np.max(ints_flat)) if ints_flat.size else 0.0
    if max_raw <= 0:
        return 1.0, hmax, route
    target = (2**INT_SUM_BITS - 1) / (max(hmax, 1) + 1) / max_raw
    return float(2.0 ** np.floor(np.log2(target))), hmax, route


def quantize_intensities(ints_flat: np.ndarray, scale: float) -> np.ndarray:
    """Snap to the integer grid; values stay integer-valued float32."""
    q = np.asarray(np.multiply(ints_flat, scale, dtype=np.float64))
    np.rint(q, out=q)
    return q.astype(np.float32)


# -- resident-intensity compaction ---------------------------------------------
#
# The flat sorted-peaks intensities are HBM-resident for the whole run; bf16
# halves them, and the expanded f32 view exists only as a per-batch transient
# inside the scoring jit (XLA fuses the cast into the histogram scatter's
# operand read).
#
# bf16 is a straight cast.  The intensities are already integer-valued f32
# (quantize_intensities); bf16 keeps 8 significant bits and rounds to
# NEAREST-EVEN, so every stored value is STILL an integer (e.g. 300 ->
# 75 * 2**2) and every per-(pixel, window) sum stays below 2**24 — the
# order-free exact-accumulation property survives, cross-backend identity
# survives, and the drift vs the f32 residents is a data-level regrid bounded
# by hmax * max_int * 2**-9 per pixel sum.  That drift is over the
# benchmark's `correct` limits on the chip (PERF.md section 2): bf16 is kept
# as the benchmark's failing control, not as a serving mode.

CUBE_DTYPES = ("f32", "bf16")


def compact_cube(in_s: np.ndarray, cube_dtype: str) -> np.ndarray:
    """Host-side compaction of the (N,) f32 resident intensities to
    ``cube_dtype``."""
    if cube_dtype not in CUBE_DTYPES:
        raise ValueError(f"cube_dtype must be one of {CUBE_DTYPES}, "
                         f"got {cube_dtype!r}")
    in_s = np.ascontiguousarray(in_s, dtype=np.float32)
    if cube_dtype == "f32":
        return in_s
    import ml_dtypes  # jax dependency; baked into the image
    return in_s.astype(ml_dtypes.bfloat16)


def expand_cube_jnp(codes):
    """In-graph f32 view of the resident intensities — the first op of
    every scoring jit.  A value-preserving cast under bf16; under f32 a
    python-level no-op, so the traced program has no trace of it."""
    import jax.numpy as jnp  # deferred: quantize.py is host-importable
    if codes.dtype == jnp.float32:
        return codes
    return codes.astype(jnp.float32)
