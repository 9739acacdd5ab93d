"""Fused window-gather + MSM-moment Pallas kernel (ISSUE 18).

The flat scoring path (models/msm_jax.fused_score_fn_flat_banded) is a
chain of XLA dispatches over the same bytes: histogram scatter -> per-chunk
band slice -> membership matmul -> materialized (B*K, P) image block ->
moments kernel -> metric epilogues.  The image block round-trips HBM
between the matmul and the moments pass — at DESI shapes that is ~1 GB
written and ~1 GB re-read per 256-ion batch that the roofline ledger
(``fused_score_cost_model``) charges to pure memory traffic.

This kernel fuses the band matmul WITH the moment reductions so each image
tile lives only in VMEM: grid ``(C, 2, nt)`` — C m/z-sorted window chunks
(the ``ion_window_chunks`` plan) x the exact two-pass centered-moment
schedule x nt pixel tiles.  TPU grids run sequentially, so the per-chunk
``(1, Wc, 5)`` partials block stays resident across the pass/tile steps
and accumulates in place (flushed when the chunk index advances).  Only
the PRINCIPAL image rows (chaos needs the full spatial layout of peak 0)
are written back at full width — 1/K of the unfused image traffic.

Banding is data-dependent (each chunk reads grid rows
``[start_c, start_c + gc_width + 2)``), which Pallas expresses with
SCALAR PREFETCH: the histogram is reshaped to ``(cols_p/SC, SC, P)``
super-rows and the block index map fetches ``nsb`` super-rows starting at
``starts[c] // SC`` — the in-kernel rank shift ``starts[c] - SC *
(starts[c] // SC)`` re-aligns window ranks exactly like the unfused
path's clamped ``dynamic_slice`` shift.

Numerics: the membership matmul accumulates the same quantized-grid
integer sums (< 2**24, order-free) at ``Precision.HIGHEST``, so principal
images, pixel sums, maxima and positive counts — hence chaos and the
spectral pattern match — are BIT-EXACT versus the unfused path; the
centered norm/dot reductions tile in ``pt`` columns instead of XLA's tree,
so the spatial correlation moves within the declared ulp ceiling.  The
exact contracts are declared below and proven by tests/test_score_pallas.py.
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

NUMERICS = numerics_surface(__name__, {
    # principal rows + sums/vmax/nn are exact integer-grid sums (any
    # association order) at HIGHEST precision; normsq/dots re-associate
    # per pixel tile -> same ulp class as the moments kernel it replaces.
    "fused_window_moments":
        "contract=ulp(16); test=tests/test_score_pallas.py::"
        "test_fused_matches_unfused; padded=whp",
})

COMPILE_SURFACE = compile_surface(__name__, {
    "fused_window_moments":
        "statics=gc_width,k,interpret; buckets=one executable per "
        "(cols_p, P) scratch x (C, Wc) chunk-plan shape; every dimension "
        "rides the shape-bucket lattice (peak_bucket/row_bucket + the "
        "formula_batch ladder), and starts/n_real are traced scalar-"
        "prefetch operands, so dataset sizes inside a bucket share one "
        "executable",
})

# f32 sublane height: the histogram super-row granularity.  The scalar-
# prefetch block index map can only address whole blocks, so chunk bands
# are fetched as nsb super-rows of SC grid rows and the <SC-row residual
# start offset becomes an in-kernel rank shift.
SC = 8
# VMEM accounting for one grid step, in bytes.  Pallas double-buffers every
# pipelined operand, and Mosaic keeps the kernel body's big values in VMEM
# too: the membership matrix costs ~4 (Wc, rows) arrays (rank iota, the f32
# matrix and its bf16 splits for the HIGHEST-precision dot) and each pass
# ~3 (Wc, pt) image-sized values.  `step_vmem_bytes` prices all of them; it
# sat 10-20% above what Mosaic reported for every shape compiled during
# bring-up (PERF.md, PR 21 findings).  v5e's default scoped-VMEM limit is 16 MiB
# of the core's 128 MiB, so the call raises it explicitly.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_VMEM_BUDGET_BYTES = 40 * 1024 * 1024
# pixel-tile ladder (lanes): largest dividing tile wins
_PT_LADDER = (4096, 2048, 1024, 512, 256, 128)
_LANES = 128


def n_super_blocks(gc_width: int) -> int:
    """Super-rows per chunk band: cover gc_width + 2 rows from any
    within-super-row start offset, i.e. ceil((gc + 2 + SC - 1) / SC) —
    the shift (<= SC - 1) eats into the first super-row."""
    return (gc_width + 2 + 2 * (SC - 1)) // SC


def cols_padded(g: int, gc_width: int) -> int:
    """Histogram scratch rows for the fused path: the unfused scratch
    width rounded up to whole super-rows, plus nsb - 1 spare super-rows so
    ``starts // SC + nsb`` stays in bounds without clamping (starts <= g;
    see the inequality chain in fused_window_moments)."""
    base = max(g + 1, gc_width + 2)
    return -(-base // SC) * SC + (n_super_blocks(gc_width) - 1) * SC


def step_vmem_bytes(pt: int, wc: int, ipc: int, gc_width: int) -> int:
    """VMEM one (chunk, pass, tile) step holds at pixel tile ``pt``."""
    rows = n_super_blocks(gc_width) * SC
    rows_l = -(-rows // _LANES) * _LANES
    cells = (2 * rows * pt          # staged band tile, double-buffered
             + 2 * ipc * pt         # principal output block
             + 6 * wc * _LANES      # lo/hi columns + partials, lane-padded
             + 4 * wc * rows_l      # membership matrix and its temporaries
             + 3 * wc * pt)         # image tile, centered tile, a product
    return 4 * cells


def pick_tile(n_pix: int, wc: int, ipc: int, gc_width: int):
    """Largest pixel tile (multiple of 128 dividing n_pix) whose resident
    set fits the VMEM budget, or None when none fits, n_pix is off the
    128-lane lattice or the per-peak row groups are not whole sublane
    tiles (the caller then keeps the unfused path)."""
    if n_pix <= 0 or n_pix % _LANES != 0 or ipc % SC != 0:
        return None
    for pt in _PT_LADDER:
        if n_pix % pt == 0 and step_vmem_bytes(
                pt, wc, ipc, gc_width) <= _VMEM_BUDGET_BYTES:
            return pt
    return None


def fused_fit(wc: int, ipc: int, n_pix: int, gc_width: int) -> bool:
    """True when the fused kernel can run COMPILED for this plan shape."""
    return pick_tile(n_pix, wc, ipc, gc_width) is not None


def _fused_kernel(starts_ref, s3_ref, nr_ref, wh_ref, rlo_ref, rhi_ref,
                  out_ref, prin_ref, *, ipc: int, k: int, pt: int):
    """One (chunk, pass, tile) step.

    Pass 0 accumulates sums/vmax/nn; pass 1 re-derives the image tile
    (one extra VMEM matmul — memory-bound, the band tile is already
    staged) and accumulates the centered normsq/dots with the mean taken
    from the pass-0 sums.  The partials block's index map ignores
    (pass, tile), so it stays VMEM-resident per chunk — the standard
    Pallas accumulation pattern.  Principal rows are written on BOTH
    passes (bit-identical values) so every visited output block is fully
    defined.

    Window rows arrive PEAK-MAJOR (row ``j * ipc + i`` = peak j of the
    chunk's ion i; the wrapper permutes the tiny rank-bound arrays), so
    the principal rows and every per-peak group are contiguous
    sublane-aligned row slices — Mosaic has no cheap sublane-strided
    ``reshape(ipc, k, pt)[:, 0]``.  Every value stays 2-D, bounds arrive as
    (Wc, 1) columns, and the five moment columns are assembled with lane
    selects: a 5-lane ``stack`` or a lane->sublane relayout does not lower.
    """
    ps = pl.program_id(1)
    t = pl.program_id(2)
    wc = ipc * k
    c = pl.program_id(0)
    rows = wh_ref.shape[0]
    # re-align local window ranks to the fetched super-row origin: staged
    # row r holds global grid row s3*SC + r, i.e. local rank r - shift
    shift = starts_ref[c] - s3_ref[c] * SC

    band = wh_ref[...]                                    # (nsb*SC, pt)
    lo = rlo_ref[0] + shift                               # (Wc, 1)
    hi = rhi_ref[0] + shift
    gg = jax.lax.broadcasted_iota(jnp.int32, (wc, rows), 1)
    d = ((gg > lo) & (gg <= hi)).astype(jnp.float32)
    # integer-grid sums < 2**24: exact in f32 at HIGHEST in any order
    imgs = jnp.dot(d, band, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)    # (Wc, pt)
    prin_ref[0] = imgs[:ipc]
    lane = jax.lax.broadcasted_iota(jnp.int32, (wc, 5), 1)

    @pl.when((ps == 0) & (t == 0))
    def _init():
        out_ref[0] = jnp.zeros((wc, 5), jnp.float32)

    @pl.when(ps == 0)
    def _pass0():
        acc = out_ref[0]
        # pad pixel columns are exact zeros (pad peaks scatter 0.0), so
        # sums/vmax/nn need no n_real mask — same argument as the masked
        # jnp moments (images >= 0: window sums of nonnegative intensity)
        sums = jnp.sum(imgs, axis=1, keepdims=True)       # (Wc, 1)
        vmax = jnp.max(imgs, axis=1, keepdims=True)
        nn = jnp.sum((imgs > 0.0).astype(jnp.float32), axis=1,
                     keepdims=True)
        out_ref[0] = jnp.where(
            lane == 0, acc + sums,
            jnp.where(lane == 3, jnp.maximum(acc, vmax),
                      jnp.where(lane == 4, acc + nn, acc)))

    @pl.when(ps == 1)
    def _pass1():
        acc = out_ref[0]
        nre = nr_ref[0]
        mean = acc[:, 0:1] / nre.astype(jnp.float32)      # (Wc, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (wc, pt), 1) + t * pt
        cent = jnp.where(col < nre, imgs - mean, 0.0)
        c0 = cent[:ipc]                                   # principal rows
        dots = jnp.concatenate(
            [jnp.sum(cent[j * ipc:(j + 1) * ipc] * c0, axis=1,
                     keepdims=True) for j in range(k)], axis=0)
        normsq = jnp.sum(cent * cent, axis=1, keepdims=True)
        out_ref[0] = jnp.where(
            lane == 1, acc + normsq,
            jnp.where(lane == 2, acc + dots, acc))


@partial(jax.jit, static_argnames=("gc_width", "k", "interpret"))
def fused_window_moments(whp, starts, r_lo_loc, r_hi_loc, n_real, *,
                         gc_width: int, k: int, interpret: bool = False):
    """Fused band-matmul + moments over every chunk of the plan.

    Args:
      whp: (cols_p, P) f32 histogram scratch, ``cols_p ==
        cols_padded(g, gc_width)`` (whole super-rows; spare rows are
        zero-initialized and never referenced by a window).
      starts: (C,) i32 chunk grid offsets (``ion_window_chunks``).
      r_lo_loc / r_hi_loc: (C, Wc) i32 local window rank bounds.
      n_real: traced i32 scalar (or python int) — REAL pixel count for
        the lattice-padded grid; pads past it are masked out of the
        centered reductions exactly like the masked moments kernel.
      gc_width / k: static band width and isotope-peak count.
      interpret: run the Pallas interpreter (CPU tests only).

    Returns:
      partials: (C, Wc, 5) f32 — columns (sums, normsq, dots, vmax, nn)
        per window row, in the PLAN's chunk-sorted ion order.
      principal: (C, ipc, P) f32 principal (peak-0) images per ion.
    """
    cols_p, n_pix = whp.shape
    C, wc = r_lo_loc.shape
    if cols_p % SC != 0:
        raise ValueError(f"cols_p={cols_p} must be a multiple of SC={SC}")
    if wc % k != 0:
        raise ValueError(f"Wc={wc} not divisible by k={k}")
    ipc = wc // k
    pt = pick_tile(n_pix, wc, ipc, gc_width)
    if pt is None:
        if not interpret:
            raise ValueError(
                f"fused kernel unfit for n_pix={n_pix}, wc={wc}, "
                f"gc_width={gc_width} (use fused_fit before dispatch)")
        pt = n_pix  # interpreter has no lane-tiling constraint
    nsb = n_super_blocks(gc_width)
    nt = n_pix // pt

    starts = starts.astype(jnp.int32)
    # no-op while starts <= g (cols_padded guarantees room); same clamp
    # role as the unfused path's start_eff = min(start, cols - (gc + 2))
    s3 = jnp.minimum(starts // SC, np.int32(cols_p // SC - nsb))
    nr = jnp.reshape(jnp.asarray(n_real, jnp.int32), (1,))

    def peak_major(r):
        # (C, ipc*k) ion-major -> (C, k*ipc, 1) peak-major columns
        return r.reshape(C, ipc, k).transpose(0, 2, 1).reshape(C, wc, 1)

    # the band start is data-dependent (scalar-prefetched), so the
    # histogram operand uses ELEMENT-offset indexing (all dims or none):
    # row offset s3*SC is sublane-aligned, column offset t*pt lane-aligned
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # starts, s3, n_real
        grid=(C, 2, nt),
        in_specs=[
            pl.BlockSpec((pl.Element(nsb * SC), pl.Element(pt)),
                         lambda c, ps, t, starts, s3, nr:
                         (s3[c] * SC, t * pt)),
            pl.BlockSpec((1, wc, 1), lambda c, ps, t, *_: (c, 0, 0)),
            pl.BlockSpec((1, wc, 1), lambda c, ps, t, *_: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, wc, 5), lambda c, ps, t, *_: (c, 0, 0)),
            pl.BlockSpec((1, ipc, pt), lambda c, ps, t, *_: (c, 0, t)),
        ],
    )
    partials, principal = pl.pallas_call(
        partial(_fused_kernel, ipc=ipc, k=k, pt=pt),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((C, wc, 5), jnp.float32),
            jax.ShapeDtypeStruct((C, ipc, n_pix), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # the partials block accumulates across (pass, tile) and
            # chunks share nothing, but one TensorCore runs them in order
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(starts, s3, nr, whp, peak_major(r_lo_loc), peak_major(r_hi_loc))
    # back to the plan's ion-major window order
    partials = partials.reshape(C, k, ipc, 5).transpose(0, 2, 1, 3).reshape(
        C, wc, 5)
    return partials, principal
