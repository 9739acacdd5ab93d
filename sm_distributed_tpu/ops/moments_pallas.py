"""Fused per-ion image moments: one HBM read for every metric reduction.

The MSM metric stage needs, per (ion, peak) image row of the (N, K, P)
block: the pixel sum (spectral pattern match + correlation means), the
centered norm and centered dot against the principal row (spatial
correlation), and per ion the principal row's max + positive count (chaos
thresholds / alive gating).  As separate XLA reductions those are ~2.5
passes over the block at the VPU reduce rate — pure HBM traffic.

This Pallas kernel streams each ion's (K, P) row block through VMEM once
(grid over ions, block (1, K, P)) and computes ALL of them in-kernel,
reading the tile twice from VMEM (free) for the exact two-pass centered
formulas — the one-pass raw-moment identity (sum(x^2) - P*mean^2) is NOT
used: with integer-grid pixel values up to 2**24 it cancels
catastrophically in f32.  Reduction ORDER differs from XLA's tree, so
spatial/spectral values can move within the documented 1e-6 cross-backend
contract (chaos integer counts are unaffected — thresholds come from the
exact max).

Reference semantics: ``img_measures.py::isotope_image_correlation /
isotope_pattern_match [U]`` (SURVEY.md §3.4) — the math matches
ops/metrics_np.py; this file only changes where the flops run.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.numerics import numerics_surface
from ..analysis.surface import compile_surface

# Declared numerics contracts (ISSUE 15, analysis/numerics.py): the
# Pallas kernels reduce in a different order than XLA's tree (ulp-grade
# drift, the documented cross-backend contract); the masked jnp fallback
# is bit-exact vs unpadded by construction.  `padded=images` seeds the
# masked-reduction rule's taint — every raw reduction below carries its
# own pad-invariance argument as a masked-ok annotation.
NUMERICS = numerics_surface(__name__, {
    "batch_moments_pallas":
        "contract=ulp(16); test=tests/test_moments.py::"
        "test_moments_interpret_matches_f64",
    "batch_moments_pallas_masked":
        "contract=ulp(16); test=tests/test_buckets.py::"
        "test_masked_moments_match_unpadded; padded=images",
    "batch_moments_jnp":
        "contract=bit_exact; test=tests/test_buckets.py::"
        "test_masked_moments_match_unpadded; padded=images",
    "batch_moments":
        "contract=ulp(16); test=tests/test_moments.py::"
        "test_moments_jnp_fallback_matches_f64; padded=images",
})

# Declared compile surface (ISSUE 12, analysis/surface.py).
COMPILE_SURFACE = compile_surface(__name__, {
    "batch_moments_pallas":
        "statics=interpret; buckets=one executable per padded (N, K, P) "
        "batch shape — N/K ride the formula_batch padding, P is the "
        "row-bucketed pixel lattice point (ops/buckets.row_bucket)",
    "batch_moments_pallas_masked":
        "statics=interpret; buckets=same (N, K, P) lattice as the unmasked "
        "kernel; the real-pixel count is a TRACED operand, so every "
        "dataset size in a pixel bucket shares one executable (ISSUE 13)",
})

# VMEM budget for ONE buffer of an ion's (K, P) f32 row block.  Mosaic
# pads K up to a sublane tile (1/2/4/8 rows, then multiples of 8) and
# Pallas double-buffers the block, so the kernel holds two of these plus
# small per-tile transients — under the explicit limit below (v5e's default
# scoped limit is 16 MiB of 128 MiB and already refuses K=4 at 524,288 px).
_MAX_BLOCK_BYTES = 8 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)
# in-kernel VMEM tile width (lanes) for the two passes
_TILE = 16384


def _sublane_rows(k: int) -> int:
    for rows in (1, 2, 4, 8):
        if k <= rows:
            return rows
    return -(-k // 8) * 8


def moments_fit(k: int, n_pix: int) -> bool:
    """True when one ion's (K, P) block fits the kernel's VMEM budget."""
    return (4 * _sublane_rows(k) * n_pix <= _MAX_BLOCK_BYTES
            and n_pix % 128 == 0)


def _moments_kernel(img_ref, out_ref, *, k: int, p: int):
    nt = p // _TILE if p % _TILE == 0 else 1
    tw = _TILE if p % _TILE == 0 else p

    def pass1(i, acc):
        sums, vmax, nn = acc
        t = img_ref[0, :, pl.dslice(i * tw, tw)]        # (K, tw) f32
        sums = sums + jnp.sum(t, axis=1, keepdims=True)
        r0 = t[0:1]
        vmax = jnp.maximum(vmax, jnp.max(r0, axis=1, keepdims=True))
        nn = nn + jnp.sum((r0 > 0.0).astype(jnp.float32), axis=1,
                          keepdims=True)
        return sums, vmax, nn

    sums0 = jnp.zeros((k, 1), jnp.float32)
    vmax0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
    nn0 = jnp.zeros((1, 1), jnp.float32)
    sums, vmax, nn = jax.lax.fori_loop(0, nt, pass1, (sums0, vmax0, nn0))
    mean = sums / np.float32(p)                          # (K, 1)

    def pass2(i, acc):
        normsq, dots = acc
        t = img_ref[0, :, pl.dslice(i * tw, tw)]
        c = t - mean                                     # (K, tw) centered
        c0 = c[0:1]                                      # principal row
        normsq = normsq + jnp.sum(c * c, axis=1, keepdims=True)
        dots = dots + jnp.sum(c0 * c, axis=1, keepdims=True)
        return normsq, dots

    z = jnp.zeros((k, 1), jnp.float32)
    normsq, dots = jax.lax.fori_loop(0, nt, pass2, (z, z))

    out = jnp.concatenate(
        [sums, normsq, dots,
         jnp.broadcast_to(vmax, (k, 1)), jnp.broadcast_to(nn, (k, 1))],
        axis=1)                                          # (K, 5)
    out_ref[0] = out


def _moments_kernel_masked(n_ref, img_ref, out_ref, *, k: int, p: int):
    """The masked sibling of ``_moments_kernel`` (ISSUE 13 lattice): the
    trailing ``p - n_real`` pixels are zero padding from the row bucket.
    Sums/max/positive-count are exactly invariant to zero pads; only the
    centering changes — the mean divides by the TRACED real count and the
    centered tile is masked back to zero past it, mirroring the masked
    XLA fallback (``batch_moments_jnp``) op for op."""
    nt = p // _TILE if p % _TILE == 0 else 1
    tw = _TILE if p % _TILE == 0 else p
    n_real = n_ref[0, 0]                                 # i32 scalar

    def pass1(i, acc):
        sums, vmax, nn = acc
        t = img_ref[0, :, pl.dslice(i * tw, tw)]        # (K, tw) f32
        sums = sums + jnp.sum(t, axis=1, keepdims=True)
        r0 = t[0:1]
        vmax = jnp.maximum(vmax, jnp.max(r0, axis=1, keepdims=True))
        nn = nn + jnp.sum((r0 > 0.0).astype(jnp.float32), axis=1,
                          keepdims=True)
        return sums, vmax, nn

    sums0 = jnp.zeros((k, 1), jnp.float32)
    vmax0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
    nn0 = jnp.zeros((1, 1), jnp.float32)
    sums, vmax, nn = jax.lax.fori_loop(0, nt, pass1, (sums0, vmax0, nn0))
    mean = sums / n_real.astype(jnp.float32)             # (K, 1)

    def pass2(i, acc):
        normsq, dots = acc
        t = img_ref[0, :, pl.dslice(i * tw, tw)]
        cols = jax.lax.broadcasted_iota(jnp.int32, (k, tw), 1) + i * tw
        c = jnp.where(cols < n_real, t - mean, 0.0)      # (K, tw) centered
        c0 = c[0:1]                                      # principal row
        normsq = normsq + jnp.sum(c * c, axis=1, keepdims=True)
        dots = dots + jnp.sum(c0 * c, axis=1, keepdims=True)
        return normsq, dots

    z = jnp.zeros((k, 1), jnp.float32)
    normsq, dots = jax.lax.fori_loop(0, nt, pass2, (z, z))

    out = jnp.concatenate(
        [sums, normsq, dots,
         jnp.broadcast_to(vmax, (k, 1)), jnp.broadcast_to(nn, (k, 1))],
        axis=1)                                          # (K, 5)
    out_ref[0] = out


@partial(jax.jit, static_argnames=("interpret",))
def batch_moments_pallas_masked(images: jnp.ndarray, n_real,
                                interpret: bool = False):
    """Masked-moments Pallas route: like ``batch_moments_pallas`` but the
    real-pixel count is a traced (1, 1) i32 SMEM operand, so every dataset
    size inside one pixel bucket shares this executable (ISSUE 13)."""
    n, k, p = images.shape
    n_arr = jnp.asarray(n_real, jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        partial(_moments_kernel_masked, k=k, p=p),
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, k, p), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, k, 5), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k, 5), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(n_arr, images)
    sums = out[:, :, 0]
    normsq = out[:, :, 1]
    dots = out[:, :, 2]
    vmax = out[:, 0, 3]
    nn = out[:, 0, 4]
    return sums, normsq, dots, vmax, nn


@partial(jax.jit, static_argnames=("interpret",))
def batch_moments_pallas(images: jnp.ndarray, interpret: bool = False):
    """(sums (N,K), normsq (N,K), dots (N,K), vmax (N,), n_notnull (N,))
    from an (N, K, P) image block, one streaming pass."""
    n, k, p = images.shape
    out = pl.pallas_call(
        partial(_moments_kernel, k=k, p=p),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, k, p), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, k, 5), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k, 5), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(images)
    sums = out[:, :, 0]
    normsq = out[:, :, 1]
    dots = out[:, :, 2]
    vmax = out[:, 0, 3]
    nn = out[:, 0, 4]
    return sums, normsq, dots, vmax, nn


def batch_moments_jnp(images: jnp.ndarray, n_real=None):
    """XLA fallback with identical semantics (non-TPU backends, or image
    rows past the VMEM budget).

    ``n_real`` (ISSUE 13 shape-bucket lattice): traced i32 scalar count of
    REAL pixels when the trailing pixels are lattice padding (whole zero
    rows appended by ``ops/buckets.row_bucket``).  Padded zeros are exact
    no-ops for sums/norms/dots/max/count, but the correlation's mean
    divides by the PIXEL COUNT — so the mean takes the real count and the
    centered block is masked back to zero on pad pixels.  With
    ``n_real == P`` (or None) the arithmetic is the unpadded sequence
    bit-for-bit: the mask keeps every value and the division sees the
    same operands."""
    # smlint: masked-ok[pad pixels are exact zeros and add exactly 0 to every f32 sum; only the MEAN divides by a count, and it takes n_real below]
    sums = images.sum(axis=-1)
    if n_real is None:
        mean = sums[..., None] / np.float32(images.shape[-1])
        cent = images - mean
    else:
        mean = sums[..., None] / n_real.astype(jnp.float32)
        real = (jnp.arange(images.shape[-1], dtype=jnp.int32)
                < n_real)[None, None, :]
        cent = jnp.where(real, images - mean, 0.0)
    # smlint: masked-ok[cent is masked back to exact zero past n_real, so pad slots contribute 0.0 to the squared norm]
    normsq = jnp.sum(cent * cent, axis=-1)
    # smlint: masked-ok[both einsum operands are zero-masked past n_real; pad products are exact zeros]
    dots = jnp.einsum("np,nkp->nk", cent[:, 0, :], cent)
    principal = images[:, 0, :]
    # smlint: masked-ok[zero pads never exceed a positive maximum; empty rows yield 0 either way]
    vmax = principal.max(axis=1)
    # smlint: masked-ok[zero pads are never > 0; the positive count is pad-invariant]
    nn = jnp.sum((principal > 0).astype(jnp.float32), axis=1)
    return sums, normsq, dots, vmax, nn


def batch_moments(images: jnp.ndarray, n_real=None):
    """Route to a Pallas kernel on TPU when the block shape fits.
    ``n_real`` (lattice-padded pixels, ISSUE 13) selects the masked
    kernel — the real-pixel count rides as a traced operand so the
    executable is shared across every dataset size in the bucket."""
    n, k, p = images.shape
    if jax.default_backend() == "tpu" and moments_fit(k, p):
        if n_real is None:
            return batch_moments_pallas(images)
        return batch_moments_pallas_masked(images, n_real)
    return batch_moments_jnp(images, n_real=n_real)
