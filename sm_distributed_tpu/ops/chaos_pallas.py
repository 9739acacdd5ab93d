"""measure_of_chaos connected components — Pallas TPU kernel.

The round-1 implementation (ops/metrics_jax.py) runs the min-label flood as
``lax.associative_scan`` sweeps over the WHOLE formula batch inside one
``lax.while_loop``: every sweep round-trips (batch, nrows, ncols) labels
through HBM and the loop iterates until the *worst* image in the batch
converges.  A profile of the 512-ion bench batch put ~113 ms of the ~190 ms
batch in these whiles (VERDICT r1 "what's weak" #1).

This kernel keeps the same exact algorithm — min-label flooding by
segmented min-scans, fixpoint detection, count = #pixels whose final label
equals their own index, bit-equal to ``scipy.ndimage.label`` — but runs it
entirely in VMEM with convergence tracked per PROGRAM (a handful of images),
not per batch:

- Layout: images side by side along the lane axis — block (R, IB*C) where
  IB*C is a multiple of 128.  Label floods never cross image boundaries
  because the row-scan "open" flags are seeded with a boundary guard
  (``col % C != 0`` forward, ``!= C-1`` backward).
- All ``nlevels`` thresholds are processed inside the kernel (fori over
  levels); per level a ``lax.while_loop`` sweeps to the exact fixpoint of
  the IB images only — empty decoy images exit after one sweep instead of
  riding the batch worst case.
- Segmented min-scan = Hillis–Steele distance doubling with an int32
  "open" flag (TPU cannot rotate i1 vectors): after step d, ``open[i]``
  means "window (i-d, i] is fully masked and crosses no image boundary".
- HBM traffic: each image is read ONCE (f32) and one count row is written —
  everything else (labels, flags, masks) lives in registers/VMEM.
- A program whose block has no two 4-adjacent pixels above 0 (a decoy's
  few noise pixels: most ion images of a search) floods nothing: every
  mask pixel is its own component at every level, so a level's count is
  its pixels above threshold.  The kernel tests that once a program and
  writes which path it took beside the counts
  (tests/test_chaos_pallas.py::test_cell_blocks_both_paths holds both paths
  to scipy on the blocks the benchmark's cells run).

Reference semantics: ``pyImagingMSpec.measure_of_chaos`` per-level component
counts [U] (SURVEY.md #11); oracle: ops/metrics_np.py::measure_of_chaos.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.numerics import numerics_surface
from ..analysis.surface import compile_surface

# Declared numerics contracts (ISSUE 15): all chaos routes are EXACT —
# integer component counts off exact thresholds — so the dispatch can
# never change results; pad pixels are below every positive threshold
# and join no component, so the kernels are pad-invariant without
# masking (the batch_metrics docstring carries the argument).
NUMERICS = numerics_surface(__name__, {
    "chaos_count_sums":
        "contract=bit_exact; test=tests/test_chaos_pallas.py::"
        "test_cell_blocks_both_paths",
    "chaos_count_sums_strips":
        "contract=bit_exact; test=tests/test_chaos_pallas.py::"
        "test_strip_kernel_matches_scipy",
})

# Declared compile surface (ISSUE 12, analysis/surface.py): both kernels'
# statics are per-dataset image geometry plus fixed tuning constants, so
# each dataset config compiles exactly one executable per kernel.
COMPILE_SURFACE = compile_surface(__name__, {
    "chaos_count_sums":
        "statics=nrows,ncols,nlevels,lane_width,interpret,work_span; "
        "buckets=one executable per dataset — geometry is per-dataset "
        "static, lane_width/work_span/nlevels are config constants",
    "chaos_count_sums_strips":
        "statics=nrows,ncols,nlevels,interpret,work_span,strip_rows; "
        "buckets=one executable per dataset — strip_rows derives from the "
        "fixed strip geometry of (nrows, ncols)",
})

_BIG = np.int32(2**30)


def _level_fracs(nlevels: int) -> np.ndarray:
    """The oracle's threshold fractions ``f32(i) / f32(nlevels)``, divided
    on the HOST.  The kernels look them up instead of dividing a loop index
    at run time: a run-time ``x / 30.0`` is a reciprocal multiply on the TPU
    (and after XLA's strength reduction on CPU), one ulp off numpy's true
    division for many of the levels — enough to flip a pixel that sits
    on a threshold, which integer-grid images do all the time (seen on the
    v5e: 3,091,698 vs scipy's 3,091,699 components at 1024x1024)."""
    return np.arange(nlevels, dtype=np.float32) / np.float32(nlevels)


def _shift(x: jnp.ndarray, d: int, axis: int, reverse: bool, fill) -> jnp.ndarray:
    """Non-circular shift by static d (fill at the exposed edge)."""
    n = x.shape[axis]
    rolled = pltpu.roll(x, (n - d) if reverse else d, axis=axis)
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    keep = (idx < n - d) if reverse else (idx >= d)
    return jnp.where(keep, rolled, fill)


def _seg_min_scan(v: jnp.ndarray, o: jnp.ndarray, axis: int, reverse: bool,
                  span: int | None = None) -> jnp.ndarray:
    """Segmented prefix-min along ``axis`` (Hillis–Steele): o[i]=1 iff the
    pull window behind i is fully open (masked, no image boundary).

    ``span`` caps the scan distance: lane blocks pack several images side by
    side, and a flood can never propagate further than one image's column
    width (the boundary guard kills longer windows anyway), so scanning to
    the full block width wastes log2(block/span) doubling steps."""
    d = 1
    n = min(span, v.shape[axis]) if span is not None else v.shape[axis]
    while d < n:
        vs = _shift(v, d, axis, reverse, _BIG)
        os_ = _shift(o, d, axis, reverse, np.int32(0))
        v = jnp.minimum(v, jnp.where(o > 0, vs, _BIG))
        o = o * os_
        d *= 2
    return v


def _chaos_kernel(frac_ref, img_ref, vmax_ref, out_ref, flood_ref, *,
                  ncols: int, nlevels: int, lean: bool = False,
                  work_span: int = 0):
    """One program: IB images of shape (R, ncols) packed as (R, IB*ncols).

    Two paths, chosen by what the block holds (``flood_ref[program]`` says
    which ran: 1 flood, 0 sparse).  A block with no two 4-adjacent pixels
    above 0 is SPARSE: level 0's mask is ``img > 0`` and masks only shrink
    going up, so at every level each mask pixel is its own component and
    the level's count is the number of pixels above its threshold — no
    labels, flags or sweeps.  A pair across two packed images is excluded
    by the same boundary guard the row scans use.  Any other block floods
    labels as below.  Per-lane sums differ between the paths (mask pixels
    a lane against roots a lane); the per-image sum the caller takes is
    the same integer.

    ``lean``: rematerialize the mask/open-flag arrays inside every sweep
    instead of hoisting them per level.  Hoisting is faster (flags computed
    once per level) but keeps three extra (R, IBC) i32 arrays live across
    the fixpoint while-loop; the lean variant trades ~3 extra vector ops
    per sweep for that VMEM, which is what lets WIDE images (512x512 —
    beyond the packed budget) run in the kernel instead of falling back to
    the ~10x-slower associative-scan path (VERDICT r2 item 3)."""
    img = img_ref[:]                                   # (R, IBC) f32
    shape = img.shape
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    incol = col % ncols                                # column within image
    iota = row * ncols + incol                         # per-image pixel id
    vmax = vmax_ref[:]                                 # (1, IBC) f32, per-lane

    def level_body(li_rev, carry):
        # Levels run DESCENDING (highest threshold first): masks only GROW
        # going down, so components only MERGE and the previous level's
        # final labels are exact warm-start labels — each old component's
        # label is the iota of one of its pixels, so the flood min over a
        # merged component is still its true min-iota, and that root pixel
        # stays in the mask (root counting stays valid).  Newly exposed
        # pixels start at their own iota.  Warm starts pre-merge most of
        # the structure, cutting sweeps-to-fixpoint on the dense low levels.
        acc, prev_lab = carry
        li = nlevels - 1 - li_rev
        # threshold grid identical to the oracle: vmax * (li/nlevels), the
        # fraction from the host-divided table, ONE f32 multiply here
        thr = vmax * frac_ref[li]
        mask = img > thr

        def flags():
            mi = mask.astype(jnp.int32)
            return mi, mi * (incol != 0), mi * (incol != ncols - 1)

        if not lean:
            mi_h, o_fwd_h, o_bwd_h = flags()
        lab0 = jnp.where(mask, jnp.minimum(prev_lab, iota), _BIG)

        def sweep(lab, span=None):
            mi, o_fwd, o_bwd = flags() if lean else (mi_h, o_fwd_h, o_bwd_h)
            lab = _seg_min_scan(lab, o_fwd, 1, False,
                                span=min(span or ncols, ncols))
            lab = _seg_min_scan(lab, o_bwd, 1, True,
                                span=min(span or ncols, ncols))
            lab = _seg_min_scan(lab, mi, 0, False, span=span)
            lab = _seg_min_scan(lab, mi, 0, True, span=span)
            return jnp.where(mask, lab, _BIG)

        # Fixpoint loop with a CHEAP certificate: min-label flow moves only
        # along adjacency, so stability under a span-2 sweep (one shift per
        # direction, 4 steps) IS global stability — the expensive work
        # sweep (span ``work_span`` or full; any span is correct, the
        # certificate carries exactness) runs only when the cheap sweep
        # found motion.  Warm-started levels whose labels are already final
        # cost 4 steps instead of a full proof sweep (measured ~1.6x).
        def body(st):
            lab, _ = st
            c = sweep(lab, span=2)
            changed = jnp.any(c != lab)
            lab = lax.cond(
                changed, lambda l: sweep(l, span=work_span or None),
                lambda l: l, c)
            return lab, changed

        lab, _ = lax.while_loop(lambda st: st[1], body, (lab0, True))
        cnt = jnp.sum(((lab == iota) & mask).astype(jnp.int32), axis=0,
                      keepdims=True)                   # (1, IBC) per-lane
        return acc + cnt, lab

    def flood_counts():
        acc = jnp.zeros((1, shape[1]), jnp.int32)
        big = jnp.full(shape, _BIG, jnp.int32)
        return lax.fori_loop(0, nlevels, level_body, (acc, big))[0]

    def sparse_counts():
        # the same thresholds bit for bit (host-divided fraction, one f32
        # multiply), ONE sublane reduction after the loop
        def level(li, acc):
            return acc + (img > vmax * frac_ref[li]).astype(jnp.int32)

        acc = lax.fori_loop(0, nlevels, level, jnp.zeros(shape, jnp.int32))
        return jnp.sum(acc, axis=0, keepdims=True)

    m0 = (img > 0).astype(jnp.int32)
    left = _shift(m0, 1, 1, False, np.int32(0)) * (incol != 0)
    above = _shift(m0, 1, 0, False, np.int32(0))
    flood = jnp.any(m0 * (left + above) > 0)
    flood_ref[pl.program_id(0)] = flood.astype(jnp.int32)
    out_ref[:] = lax.cond(flood, flood_counts, sparse_counts)


# Scoped-VMEM budget for one program's block, in CELLS (rows x lanes).  The
# hoisted-flag kernel's live intermediates (labels, open flags, masks,
# shifted copies) cost ~133 B/cell against the 16 MB scoped limit (measured:
# a 256x512 block = 131072 cells OOMed at 17.46 MB), so cap blocks at
# ~13 MB.  The LEAN kernel (flags rematerialized per sweep) drops the
# per-level hoisted arrays and fits ~3x more cells — 512x512 = 262144 cells
# verified on v5e — at ~10-20% more vector ops per sweep.
_MAX_CELLS = 96 * 1024
_MAX_CELLS_LEAN = 288 * 1024

# Strip-kernel budget: cells of ONE strip block (strip_rows + 2*_HALO rows x
# padded cols).  Live arrays per strip visit: the two persistent scratches
# (image f32 + labels i32) plus the sweep transients (lab_in, shifted
# copies, flags) — leaner liveness than the packed kernel's per-level
# hoists, but two resident scratches, so the budget sits between _MAX_CELLS
# and _MAX_CELLS_LEAN.
_MAX_CELLS_STRIP = 192 * 1024
_HALO = 8                     # halo rows above/below a strip: 8 keeps every
                              # DMA row offset (s*strip and s*strip+_HALO)
                              # provably sublane-aligned for Mosaic; the
                              # extra halo rows only help propagation


def _pack_geometry(nrows: int, ncols: int, lane_width: int,
                   max_cells: int = _MAX_CELLS) -> tuple[int, int, int]:
    """(R_pad, C_pad, IB): pad cols so IB*C_pad == lane block width.

    The lane width shrinks when rows are tall so R_pad * lanes stays within
    the scoped-VMEM budget; images whose padded column span still exceeds
    the budget don't fit — callers check ``fits_vmem`` and fall back to the
    associative-scan path."""
    rp = -(-nrows // 8) * 8
    budget = max(128, (max_cells // rp) // 128 * 128)
    lane_width = min(lane_width, budget)
    if ncols <= lane_width:
        cp = ncols
        # smallest divisor layout: pad cols up until it divides the lane width
        while lane_width % cp != 0:
            cp += 1
        ib = lane_width // cp
    else:
        cp = -(-ncols // 128) * 128
        ib = 1
    return rp, cp, ib


def _packed_block(nrows: int, ncols: int,
                  lane_width: int) -> tuple[int, int, int, bool]:
    """(R_pad, C_pad, IB, lean): the block ``chaos_count_sums`` runs.  The
    hoisted-flag kernel where its budget holds the block, else the block
    re-packed against the lean kernel's larger one (wide images)."""
    rp, cp, ib = _pack_geometry(nrows, ncols, lane_width)
    lean = rp * cp * ib > _MAX_CELLS
    if lean:
        rp, cp, ib = _pack_geometry(nrows, ncols, lane_width, _MAX_CELLS_LEAN)
    return rp, cp, ib, lean


def fits_vmem(nrows: int, ncols: int, lane_width: int = 512) -> bool:
    """True when one program's block fits SOME kernel variant's budget
    (packed fast kernel, or the lean wide-image kernel)."""
    rp, cp, ib, _lean = _packed_block(nrows, ncols, lane_width)
    return rp * cp * ib <= _MAX_CELLS_LEAN


@functools.partial(jax.jit, static_argnames=(
    "nrows", "ncols", "nlevels", "lane_width", "interpret", "work_span"))
def chaos_count_sums(
    principal: jnp.ndarray,   # (N, n_pix) f32, n_pix == nrows*ncols
    *,
    nrows: int,
    ncols: int,
    nlevels: int = 30,
    lane_width: int = 512,
    interpret: bool = False,
    # 32 measured best on blob-heavy 256x256 batches (1377 -> 1010 ms/512
    # ions vs full-span; spans are result-invariant — the span-2 certificate
    # carries exactness, work sweeps only accelerate)
    work_span: int = 32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(sums, flood)``: (N,) f32 per-image SUM over levels of
    connected-component counts, and (programs,) i32, 1 where that program's
    block flooded labels and 0 where it took the label-free sparse path
    (``_chaos_kernel``; program ``j`` holds images ``[j*IB, (j+1)*IB)``).

    chaos = 1 - (sum/nlevels)/n_notnull is applied by the caller (exact: the
    sums are small integers, f32-representable).
    """
    n = principal.shape[0]
    rp, cp, ib, lean = _packed_block(nrows, ncols, lane_width)
    if rp * cp * ib > _MAX_CELLS_LEAN and not interpret:
        raise ValueError(
            f"chaos kernel block ({rp}x{cp * ib} cells) exceeds the scoped-"
            f"VMEM budget ({_MAX_CELLS_LEAN}); check fits_vmem() and use the "
            "associative-scan path (measure_of_chaos_batch use_pallas=False)"
        )
    n_pad = -(-n // ib) * ib
    img = jnp.zeros((n_pad, rp, cp), jnp.float32)
    img = img.at[:n, :nrows, :ncols].set(
        jnp.maximum(principal.reshape(n, nrows, ncols), 0.0))
    vmax = img.max(axis=(1, 2))                        # (n_pad,)

    # lanes-of-images layout: (R, n_pad*C); image i occupies lanes [i*C,(i+1)*C)
    img_l = img.transpose(1, 0, 2).reshape(rp, n_pad * cp)
    vmax_l = jnp.repeat(vmax, cp).reshape(1, n_pad * cp)

    grid = (n_pad // ib,)
    ibc = ib * cp
    counts, flood = pl.pallas_call(
        functools.partial(_chaos_kernel, ncols=cp, nlevels=nlevels, lean=lean,
                          work_span=work_span),
        out_shape=(jax.ShapeDtypeStruct((1, n_pad * cp), jnp.int32),
                   jax.ShapeDtypeStruct(grid, jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rp, ibc), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, ibc), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, ibc), lambda i: (0, i), memory_space=pltpu.VMEM),
            # one scalar a program, the whole vector resident in SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        interpret=interpret,
    )(_level_fracs(nlevels), img_l, vmax_l)
    # per-image count sum: reduce each image's cp lanes
    sums = counts.reshape(n_pad, cp).sum(axis=1)[:n].astype(jnp.float32)
    return sums, flood


# ---------------------------------------------------------------------------
# Strip-processed kernel: images beyond the lean whole-image budget
# (>~288k cells, e.g. 1024x1024 whole-slide DESI) — VERDICT r3 item 4b.
#
# The image and a label plane live in HBM; row strips (with _HALO read-only
# halo rows on each side) stream through VMEM, each swept to its LOCAL
# fixpoint with the same segmented min-scans as the packed kernel.  Passes
# alternate top-down / bottom-up over the strips and repeat until one
# complete pass changes no core label — a valid GLOBAL certificate: every
# halo row is some neighbor's core row, so any pixel unstable against the
# end-of-pass state would have changed during its own strip's visit.
#
# Correctness anchors:
# - labels only ever DECREASE toward the component min (min-label flood);
#   reading a STALE halo value is therefore always an upper bound of the
#   true min and can never poison a component (monotone convergence);
# - the on-load transform  lab = where(mask, min(lab, iota), BIG)  is
#   idempotent and level-monotone (masks only grow descending levels), so
#   warm starts across levels need no per-level init or write-back: a strip
#   whose sweep changed nothing is simply not written, and the count pass
#   re-applies the transform on load;
# - empty strips (per-strip max <= threshold) are skipped without DMA:
#   masks grow monotonically going down levels, so a strip empty at this
#   level was empty at every earlier level and its labels are still the
#   init-pass BIG.
# ---------------------------------------------------------------------------


def _chaos_strip_kernel(smax_ref, thr_ref, img_ref, out_ref, lab_hbm,
                        img_vmem, lab_vmem, sems, *, ncols: int,
                        nrows_pad: int, strip_rows: int, nlevels: int,
                        work_span: int):
    """One program: one image, (nrows_pad + 2*_HALO, ncols) in HBM."""
    pid = pl.program_id(0)
    n_strips = nrows_pad // strip_rows
    rb = strip_rows + 2 * _HALO                       # block rows
    shape = (rb, ncols)
    lrow = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    core = (lrow >= _HALO) & (lrow < _HALO + strip_rows)

    def load_strip(s, *, want_img: bool):
        r0 = pl.multiple_of(s * strip_rows, 8)
        cp_l = pltpu.make_async_copy(
            lab_hbm.at[pl.ds(r0, rb), :], lab_vmem, sems.at[0])
        cp_l.start()
        if want_img:
            cp_i = pltpu.make_async_copy(
                img_ref.at[pid, pl.ds(r0, rb), :], img_vmem, sems.at[1])
            cp_i.start()
            cp_i.wait()
        cp_l.wait()

    def giota(s):
        # global pixel id of each block cell (halo rows get their true ids
        # too — assigning a masked halo pixel its own iota is always a valid
        # upper bound of its component min, and accelerates convergence)
        return (s * strip_rows + lrow - _HALO) * ncols + col

    # ---- init: labels <- BIG everywhere (strip writes overlap on halos;
    # same value, so overlap is harmless) ----
    lab_vmem[:] = jnp.full(shape, _BIG, jnp.int32)

    def init_body(s, _):
        cp = pltpu.make_async_copy(
            lab_vmem,
            lab_hbm.at[pl.ds(pl.multiple_of(s * strip_rows, 8), rb), :],
            sems.at[0])
        cp.start()
        cp.wait()
        return _

    lax.fori_loop(0, n_strips, init_body, 0)

    def sweep_strip(mask, lab, span):
        mi = mask.astype(jnp.int32)
        lab = _seg_min_scan(lab, mi, 1, False,
                            span=min(span or ncols, ncols))
        lab = _seg_min_scan(lab, mi, 1, True,
                            span=min(span or ncols, ncols))
        lab = _seg_min_scan(lab, mi, 0, False, span=min(span or rb, rb))
        lab = _seg_min_scan(lab, mi, 0, True, span=min(span or rb, rb))
        return jnp.where(mask, lab, _BIG)

    def level_body(li_rev, acc):
        li = nlevels - 1 - li_rev                     # descending thresholds
        thr = thr_ref[pid, li]                        # vmax * (li/nlevels)

        def visit(s):
            """Returns True when the strip's core labels changed (written)."""
            load_strip(s, want_img=True)
            mask = img_vmem[:] > thr
            lab_in = jnp.where(mask, jnp.minimum(lab_vmem[:], giota(s)), _BIG)

            def body(st):
                lab, _ = st
                c = sweep_strip(mask, lab, 2)         # cheap certificate
                moved = jnp.any(c != lab)
                lab = lax.cond(
                    moved, lambda l: sweep_strip(mask, l, work_span),
                    lambda l: l, c)
                return lab, moved

            lab_fin, _ = lax.while_loop(lambda st: st[1], body,
                                        (lab_in, jnp.array(True, dtype=jnp.bool_)))
            changed = jnp.any((lab_fin != lab_in) & core)

            @pl.when(changed)
            def _():
                lab_vmem[:] = lab_fin
                cp = pltpu.make_async_copy(
                    lab_vmem.at[pl.ds(_HALO, strip_rows), :],
                    lab_hbm.at[pl.ds(
                        pl.multiple_of(s * strip_rows + _HALO, 8),
                        strip_rows), :],
                    sems.at[0])
                cp.start()
                cp.wait()

            return changed

        def pass_body(st):
            p, _ = st

            def strip_body(i, any_changed):
                # alternate top-down / bottom-up passes so flows in either
                # direction cascade across all boundaries within one pass
                s = jnp.where(p % 2 == 0, i, n_strips - 1 - i)
                nonempty = smax_ref[pid, s] > thr
                ch = lax.cond(nonempty, visit, lambda _s: jnp.array(False, dtype=jnp.bool_), s)
                return jnp.logical_or(any_changed, ch)

            changed = lax.fori_loop(0, n_strips, strip_body, jnp.array(False, dtype=jnp.bool_))
            return p + 1, changed

        lax.while_loop(lambda st: st[1], pass_body,
                       (jnp.int32(0), jnp.array(True, dtype=jnp.bool_)))

        # ---- count roots: label == own iota (transform re-applied on load
        # because converged strips skip write-back) ----
        def count_body(s, lvl_acc):
            def counted(s):
                load_strip(s, want_img=True)
                mask = img_vmem[:] > thr
                gi = giota(s)
                lab = jnp.where(mask, jnp.minimum(lab_vmem[:], gi), _BIG)
                return jnp.sum((core & mask & (lab == gi)).astype(jnp.int32))

            return lvl_acc + lax.cond(smax_ref[pid, s] > thr, counted,
                                      lambda _s: jnp.int32(0), s)

        return acc + lax.fori_loop(0, n_strips, count_body, jnp.int32(0))

    out_ref[pid, 0] = lax.fori_loop(0, nlevels, level_body, jnp.int32(0))


def _strip_geometry(nrows: int, ncols: int,
                    strip_rows: int | None = None) -> tuple[int, int, int]:
    """(nrows_pad, ncols_pad, strip_rows) for the strip kernel.

    ``strip_rows`` overrides the budget-derived strip height (multiple of 8;
    tests use it to exercise multi-strip flows on small images)."""
    cp = -(-ncols // 128) * 128
    strip = (_MAX_CELLS_STRIP // cp - 2 * _HALO) // 8 * 8
    if strip_rows is not None:
        strip = strip_rows
    if (strip < 8 or strip % 8
            or (strip + 2 * _HALO) * cp > _MAX_CELLS_STRIP):
        raise ValueError(
            f"no valid strip height for the strip chaos kernel: {ncols} "
            f"cols (padded {cp}) with strip_rows={strip} against the "
            f"{_MAX_CELLS_STRIP}-cell budget")
    strip = min(strip, -(-nrows // 8) * 8)
    rp = -(-nrows // strip) * strip
    return rp, cp, strip


class ChaosGeometry(NamedTuple):
    """Which chaos route an image shape takes and the VMEM block one
    program of it holds (what the span ``backend_build`` reports)."""

    route: str                # 'packed' | 'strips' | 'scan'
    rows_pad: int             # block rows (a strip with its halos on 'strips')
    cols_pad: int             # lanes ONE image takes in the block
    images_per_program: int   # 0 on 'scan': no Pallas program, whole batch
    lean: bool                # the flag-rematerializing packed variant
    fill_pct: float           # real image cells over the padded cells the
                              # kernel sweeps (pad rows and columns are
                              # swept like real ones)


def chaos_geometry(nrows: int, ncols: int, lane_width: int = 512, *,
                   pallas: bool = True) -> ChaosGeometry:
    """The route and block for ``(nrows, ncols)`` images: 'packed' (whole
    image(s) in VMEM), 'strips' (HBM-resident labels, strips through VMEM),
    or 'scan' (associative-scan fallback; always with ``pallas=False``, a
    platform without Mosaic).  ``measure_of_chaos_batch`` routes by it and
    ``chaos_count_sums`` packs by the same ``_packed_block``, so what a
    trace says of a backend is what its kernels run."""
    def fill(rows: int, cols: int) -> float:
        return round(100.0 * nrows * ncols / (rows * cols), 1)

    if pallas:
        rp, cp, ib, lean = _packed_block(nrows, ncols, lane_width)
        if rp * cp * ib <= _MAX_CELLS_LEAN:
            return ChaosGeometry("packed", rp, cp, ib, lean, fill(rp, cp))
        try:
            rp, cp, strip = _strip_geometry(nrows, ncols)
            return ChaosGeometry("strips", strip + 2 * _HALO, cp, 1, False,
                                 fill(rp, cp))
        except ValueError:
            pass
    return ChaosGeometry("scan", nrows, ncols, 0, False, 100.0)


@functools.partial(jax.jit, static_argnames=(
    "nrows", "ncols", "nlevels", "interpret", "work_span", "strip_rows"))
def chaos_count_sums_strips(
    principal: jnp.ndarray,   # (N, n_pix) f32, n_pix == nrows*ncols
    *,
    nrows: int,
    ncols: int,
    nlevels: int = 30,
    interpret: bool = False,
    work_span: int = 32,
    strip_rows: int | None = None,
) -> jnp.ndarray:
    """(N,) f32 per-image SUM over levels of component counts — the strip
    kernel's twin of chaos_count_sums, for images beyond the lean budget."""
    n = principal.shape[0]
    rp, cp, strip = _strip_geometry(nrows, ncols, strip_rows)
    n_strips = rp // strip
    # guard/pad fill is -1: masks are img > thr with thr >= 0, so guard
    # rows, halo overhang and col padding can never enter a component
    img = jnp.full((n, rp + 2 * _HALO, cp), -1.0, jnp.float32)
    img = img.at[:, _HALO:_HALO + nrows, :ncols].set(
        jnp.maximum(principal.reshape(n, nrows, ncols), 0.0))
    body = img[:, _HALO:_HALO + rp, :]
    smax = body.reshape(n, n_strips, strip * cp).max(axis=2)   # (N, S)
    # the oracle's threshold grid, one f32 multiply per (image, level) by
    # the host-divided fractions; the kernel only looks thresholds up
    thr = smax.max(axis=1, keepdims=True) * _level_fracs(nlevels)[None, :]

    counts, _labels = pl.pallas_call(
        functools.partial(_chaos_strip_kernel, ncols=cp, nrows_pad=rp,
                          strip_rows=strip, nlevels=nlevels,
                          work_span=work_span),
        # the label plane is an OUTPUT in compiler-managed (HBM) memory,
        # not a scratch: Mosaic only allocates vmem/smem/semaphore scratch.
        # It is shared by all (sequential) grid steps — each program
        # re-inits it — and its final value is discarded.
        out_shape=(jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rp + 2 * _HALO, cp), jnp.int32)),
        grid=(n,),
        in_specs=[
            # whole-array SMEM block (scalars): TPU lowering forbids partial
            # blocks that aren't 8x128-aligned, so index by program id
            pl.BlockSpec((n, n_strips), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, nlevels), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            # whole-array SMEM out block (scalar per program) for the same
            # TPU alignment reason; each program writes its own row
            pl.BlockSpec((n, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((strip + 2 * _HALO, cp), jnp.float32),
            pltpu.VMEM((strip + 2 * _HALO, cp), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(smax, thr, img)
    return counts.reshape(n).astype(jnp.float32)
