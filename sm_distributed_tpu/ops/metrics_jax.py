"""MSM metrics, JAX/TPU backend.

Device-side counterparts of ops/metrics_np.py (the parity oracle):

- ``measure_of_chaos``: connected components without dynamic shapes — the
  genuinely hard TPU kernel (SURVEY.md §7 hard part 1).  Implemented as
  min-label propagation via SEGMENTED MIN-SCANS: labels start as pixel
  indices; one sweep runs four ``lax.associative_scan`` passes (rows
  left/right, columns down/up) whose combine op resets at mask boundaries,
  so a label floods an entire straight run in O(log n) steps; a
  ``lax.while_loop`` sweeps to the exact fixpoint (component count =
  #pixels whose final label equals their own index), matching
  scipy.ndimage.label exactly.  Design note: an earlier pointer-jumping
  variant (gather-based label compression) was ~200x slower on TPU — VPU
  scans beat gathers by orders of magnitude; iterations-to-fixpoint equals
  the component "zigzag depth", small for real ion images.
- correlation / pattern match: masked dot products, trivially vmapped.

All functions take a whole formula batch and are designed to live inside one
fused jit with the extraction kernel (north star: one fused XLA graph).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..analysis.numerics import numerics_surface

# Declared numerics contracts (ISSUE 15, analysis/numerics.py): per-site
# drift bound vs the numpy oracle, the committed test that proves it, and
# the parameters that receive lattice-padded blocks (ISSUE 13) — the
# masked-reduction rule seeds its taint from `padded=`, so a raw
# reduction over a padded axis that skips the n_real helpers is a lint
# error here, not a silent metric corruption at scale.
NUMERICS = numerics_surface(__name__, {
    "batch_metrics":
        "contract=ulp(16); test=tests/test_jax_backend.py::"
        "test_backend_parity_metrics_and_ranks; padded=images",
    "measure_of_chaos_batch":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_chaos_batch_matches_numpy; padded=principal",
    "hotspot_clip_batch":
        "contract=bit_exact; test=tests/test_jax_backend.py::"
        "test_hotspot_clip_batch_matches_numpy; padded=images",
    "correlation_from_moments":
        "contract=ulp(16); test=tests/test_jax_backend.py::"
        "test_backend_parity_metrics_and_ranks",
    "isotope_pattern_match_batch":
        "contract=ulp(16); test=tests/test_jax_backend.py::"
        "test_backend_parity_metrics_and_ranks",
})

# numpy scalar, NOT jnp: a module-level jnp value would initialize the XLA
# backend at import time, which forbids jax.distributed.initialize later
# (multi-host processes import this module before calling initialize)
_BIG = np.int32(2**30)


def _seg_min_scan(vals: jnp.ndarray, resets: jnp.ndarray, axis: int,
                  reverse: bool) -> jnp.ndarray:
    """Segmented running minimum: the min restarts wherever ``resets`` is
    True (mask boundaries), so labels flood only within contiguous runs."""

    def comb(a, b):
        av, ar = a
        bv, br = b
        return (jnp.where(br, bv, jnp.minimum(av, bv)), ar | br)

    v, _ = lax.associative_scan(comb, (vals, resets), axis=axis, reverse=reverse)
    return v


def _cc_count(mask_flat: jnp.ndarray, nrows: int, ncols: int) -> jnp.ndarray:
    """Exact 4-connectivity component count of a boolean (nrows*ncols,) mask."""
    m = mask_flat.reshape(nrows, ncols)
    iota = jnp.arange(nrows * ncols, dtype=jnp.int32).reshape(nrows, ncols)
    labels0 = jnp.where(m, iota, _BIG)
    resets = ~m

    def sweep(lab):
        lab = _seg_min_scan(lab, resets, axis=1, reverse=False)
        lab = _seg_min_scan(lab, resets, axis=1, reverse=True)
        lab = _seg_min_scan(lab, resets, axis=0, reverse=False)
        lab = _seg_min_scan(lab, resets, axis=0, reverse=True)
        return jnp.where(m, lab, _BIG)

    def cond(state):
        labels, prev = state
        return jnp.any(labels != prev)

    def body(state):
        labels, _ = state
        return sweep(labels), labels

    labels, _ = lax.while_loop(cond, body, (sweep(labels0), labels0))
    return jnp.sum((labels == iota) & m)


def refine_quotient(q: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray) -> jnp.ndarray:
    """One correction step that turns an APPROXIMATE f32 quotient ``q`` of
    ``a / b`` (a device divide: the TPU's is reciprocal-based and can land
    a unit in the last place off numpy's) into the correctly rounded one.  The residual ``a - q*b`` is formed exactly
    with Dekker's split product — only f32 add/sub/mul, which the VPU
    rounds like IEEE — so ``q + r/b`` needs ``r/b`` to a few bits only.
    A no-op where the divide is already correctly rounded (XLA-CPU)."""
    split = np.float32(4097.0)                   # 2**12 + 1

    def halves(x):
        t = split * x
        hi = t - (t - x)
        return hi, x - hi

    qh, ql = halves(q)
    bh, bl = halves(b)
    r = (((a - qh * bh) - qh * bl) - ql * bh) - ql * bl
    return q + r / b


def chaos_dispatch(nrows: int, ncols: int, use_pallas: bool | None = None):
    """The ``ChaosGeometry`` (ops/chaos_pallas.py) this process runs for
    ``(nrows, ncols)`` images: ``measure_of_chaos_batch`` routes by it (its
    docstring has the three routes and ``use_pallas``), and a backend
    reports it on its ``backend_build`` span."""
    from .chaos_pallas import chaos_geometry

    pallas = (jax.default_backend() == "tpu" if use_pallas is None
              else use_pallas)
    geometry = chaos_geometry(nrows, ncols, pallas=pallas)
    if use_pallas and geometry.route == "scan":
        raise ValueError(
            f"no pallas chaos route fits {nrows}x{ncols} images")
    return geometry


def measure_of_chaos_batch(
    principal: jnp.ndarray,   # (N, n_pix) f32, n_pix == nrows*ncols
    nrows: int,
    ncols: int,
    nlevels: int = 30,
    use_pallas: bool | None = None,
    vmax: jnp.ndarray | None = None,       # (N,) precomputed row max
    n_notnull: jnp.ndarray | None = None,  # (N,) precomputed positive count
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(chaos, programs)``: (N,) chaos scores; matches
    metrics_np.measure_of_chaos semantics: thresholds vmax * i/nlevels for i
    in 0..nlevels-1, 4-connectivity, chaos = max(0, 1 - mean(component
    counts)/n_nonzero), 0 for empty.  Beside them (2,) f32: how many
    programs of the packed kernel took its label-free sparse path and how
    many flooded labels (``chaos_pallas._chaos_kernel``); zeros on the
    routes that have no such programs.

    Three routes, all exact (the dispatch cannot change results): on TPU,
    'packed' (whole image(s) VMEM-resident, ops/chaos_pallas.py) for
    in-budget shapes or 'strips' (HBM-resident labels, halo'd row strips
    through VMEM) past the lean budget; elsewhere — and for shapes even
    strips cannot fit — the associative-scan path below.
    ``use_pallas=True`` forces a pallas route and raises ValueError when
    no pallas route fits the shape; ``False`` forces the scan path.
    """
    route = chaos_dispatch(nrows, ncols, use_pallas).route
    programs = jnp.zeros(2, jnp.float32)
    principal = jnp.maximum(principal, 0.0)
    if vmax is None:
        # smlint: masked-ok[lattice pad pixels are exact zeros, below every positive max — vmax is the real-pixel maximum]
        vmax = principal.max(axis=1)                   # (N,)
    if n_notnull is None:
        # smlint: masked-ok[zero pads are never > 0; the positive count is pad-invariant]
        n_notnull = jnp.sum(principal > 0, axis=1)     # (N,)

    if route == "packed":
        from .chaos_pallas import chaos_count_sums

        count_sums, flood = chaos_count_sums(
            principal, nrows=nrows, ncols=ncols, nlevels=nlevels)
        # smlint: masked-ok[a count of programs, not of pixels: zero pads never make a pair, so a pad can only leave a program sparse]
        n_flood = flood.sum()
        programs = jnp.stack(
            [flood.size - n_flood, n_flood]).astype(jnp.float32)
    elif route == "strips":
        from .chaos_pallas import chaos_count_sums_strips

        count_sums = chaos_count_sums_strips(
            principal, nrows=nrows, ncols=ncols, nlevels=nlevels)
    else:
        def per_level(_, frac):
            levels = vmax * frac                        # (N,)
            masks = principal > levels[:, None]         # (N, n_pix)
            counts = jax.vmap(partial(_cc_count, nrows=nrows, ncols=ncols))(masks)
            return _, counts.astype(jnp.float32)

        fracs = jnp.arange(nlevels, dtype=jnp.float32) / nlevels
        _, counts = lax.scan(per_level, None, fracs)    # (nlevels, N)
        count_sums = counts.sum(axis=0)                 # exact small integers
    # ONE division by a runtime denominator: "count_sums / nlevels" would let
    # XLA strength-reduce the constant divisor into a reciprocal multiply
    # (different rounding than numpy's true division — observed 1-ulp chaos
    # drift); nlevels * n_notnull is exact in f32 (< 2**24).  The TPU's
    # divide is itself reciprocal-based (the v5e put chaos 1 ulp off the
    # oracle, PERF.md PR 21), hence the refinement: chaos is bit-identical
    # to the oracle on every platform
    denom = (nlevels * jnp.maximum(n_notnull, 1)).astype(jnp.float32)
    chaos = 1.0 - refine_quotient(count_sums / denom, count_sums, denom)
    chaos = jnp.clip(chaos, 0.0, 1.0)
    return jnp.where((vmax > 0) & (n_notnull > 0), chaos, 0.0), programs


def correlation_from_moments(
    normsq: jnp.ndarray,      # (N, K) centered squared norms
    dots: jnp.ndarray,        # (N, K) centered dot vs principal row
    weights: jnp.ndarray,     # (N, K) theoretical intensities
    valid: jnp.ndarray,       # (N, K) bool
) -> jnp.ndarray:
    """(N,) weighted mean Pearson correlation of peaks 1..K-1 vs peak 0
    from precomputed moments (ops/moments_pallas.py), NaN-free (constant
    images count 0), clipped to [0,1]."""
    norm = jnp.sqrt(normsq)
    denom = norm[:, 0:1] * norm
    corr = jnp.where(denom > 0,
                     dots / jnp.maximum(denom, np.float32(1e-30)), 0.0)
    w = jnp.where(valid, weights, 0.0).at[:, 0].set(0.0)
    wsum = w.sum(axis=1)
    out = jnp.where(
        wsum > 0,
        (corr * w).sum(axis=1) / jnp.maximum(wsum, np.float32(1e-30)), 0.0)
    return jnp.clip(out, 0.0, 1.0)


def isotope_pattern_match_batch(
    totals: jnp.ndarray,      # (N, K) observed total intensity per isotope image
    theor: jnp.ndarray,       # (N, K) theoretical intensities
    valid: jnp.ndarray,       # (N, K) bool
) -> jnp.ndarray:
    """(N,) cosine between masked envelopes, in [0,1]."""
    obs = jnp.where(valid, totals, 0.0)
    th = jnp.where(valid, theor, 0.0)
    on = jnp.sqrt(jnp.sum(obs * obs, axis=1))
    tn = jnp.sqrt(jnp.sum(th * th, axis=1))
    dot = jnp.sum(obs * th, axis=1)
    out = jnp.where((on > 0) & (tn > 0),
                    dot / jnp.maximum(on * tn, np.float32(1e-30)), 0.0)
    return jnp.clip(out, 0.0, 1.0)


def hotspot_clip_batch(images: jnp.ndarray, q: float) -> jnp.ndarray:
    """Device-side hot-spot removal, BIT-IDENTICAL to the numpy oracle's
    ``hotspot_percentile_f32`` (the cross-backend cutoff definition): clip
    each (ion, peak) image at the q-th linear-interpolated percentile of
    its positive pixels; images with no positive pixels pass through.

    ``images``: (..., P).  Masked percentile without dynamic shapes and
    without a sort: of the row's order the cutoff reads two elements, the
    interpolation base at index lo = floor((q/100)*(m-1)) among the m
    positives ascending, which is the (m - lo)-th LARGEST of the row, and
    its upper neighbour (clamped to the last), the max(m - lo - 1, 1)-th:
    ``_kth_largest_bits`` / ``_next_above_bits`` (below ``batch_metrics``).
    The float arithmetic is the oracle's single-op sequence, the ranks stay
    integers, and an optimization barrier keeps XLA from contracting the
    final mul+add into an FMA, which would flip clipped-pixel bits."""
    # smlint: masked-ok[zero pads are never > 0, and lie below every candidate of the selection's compare-and-count; m and the rank arithmetic are pad-count invariant by construction]
    m = jnp.sum(images > 0, axis=-1).astype(jnp.int32)     # (...,)
    t = np.float32(q) / np.float32(100.0)                  # host f32 constant
    pos = t * jnp.maximum(m - 1, 0).astype(jnp.float32)    # one rounded mul
    lo = jnp.floor(pos)                                    # exact
    frac = (pos - lo)[..., None]                           # exact
    k_lo = jnp.maximum(m - lo.astype(jnp.int32), 1)        # integer rank math
    b_lo = _kth_largest_bits(images, k_lo)                 # (m == 0: unused)
    b_hi = _next_above_bits(images, b_lo, k_lo)
    v_lo = lax.bitcast_convert_type(b_lo, jnp.float32)[..., None]
    v_hi = lax.bitcast_convert_type(b_hi, jnp.float32)[..., None]
    prod = jax.lax.optimization_barrier((v_hi - v_lo) * frac)
    cutoff = v_lo + prod                                   # (..., 1)
    clipped = jnp.minimum(images, cutoff)
    return jnp.where((m > 0)[..., None], clipped, images)


def batch_metrics(
    images: jnp.ndarray,      # (N, K, n_pix) f32 — n_pix == nrows*ncols
    theor_ints: jnp.ndarray,  # (N, K) f32
    n_valid: jnp.ndarray,     # (N,) i32
    nrows: int,
    ncols: int,
    nlevels: int = 30,
    do_preprocessing: bool = False,
    q: float = 99.0,
    n_real=None,              # traced i32 scalar: REAL pixels (lattice pad)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, 4) of (chaos, spatial, spectral, msm) for a formula batch, and
    the (2,) sparse / flood program counts of its chaos kernel
    (``measure_of_chaos_batch``).

    ``n_real`` (ISSUE 13 shape-bucket lattice): when ``nrows`` is the
    ROW-BUCKETED grid (ops/buckets.row_bucket) the trailing rows are zero
    padding and ``n_real`` carries the dataset's true pixel count as a
    TRACED scalar.  Zero pads are exactly invariant for every metric op
    except the correlation's mean over pixels — which divides by
    ``n_real`` with the centered block masked back to zero past it
    (moments_pallas.batch_moments) — and the hotspot percentile, whose
    rank arithmetic is pad-count invariant by construction (a zero lies
    below every candidate its selection counts against).  Chaos
    runs on the padded grid unmasked: zero pixels are below every
    threshold, so component counts, ``vmax`` and ``n_notnull`` are exact
    integers either way.  Result: metrics are bit-identical to unpadded
    scoring while every dataset size in a bucket shares ONE executable."""
    # the named scopes are what /debug/profile attributes device time by
    # (analysis/profiling.py); they are HLO metadata only
    k = images.shape[1]
    with jax.named_scope("sm_moments"):
        valid = jnp.arange(k, dtype=jnp.int32)[None, :] < n_valid[:, None]
        images = jnp.where(valid[:, :, None], images, 0.0)
        if do_preprocessing:
            images = hotspot_clip_batch(images, q)

        # every per-pixel reduction the metrics need, in ONE streaming pass
        # over the image block (ops/moments_pallas.py; XLA fallback
        # identical semantics) — separate XLA reductions measured ~25-30 ms
        # per 1 GB DESI batch against ~3 ms fused
        from .moments_pallas import batch_moments

        sums, normsq, dots, vmax, n_notnull = batch_moments(images,
                                                            n_real=n_real)
    with jax.named_scope("sm_chaos"):
        chaos, programs = measure_of_chaos_batch(
            images[:, 0, :], nrows, ncols, nlevels,
            vmax=vmax, n_notnull=n_notnull)
    with jax.named_scope("sm_epilogue"):
        spatial = correlation_from_moments(normsq, dots, theor_ints, valid)
        spectral = isotope_pattern_match_batch(sums, theor_ints, valid)

        alive = (n_valid > 0) & (vmax > 0)
        chaos = jnp.where(alive, chaos, 0.0)
        spatial = jnp.where(alive, spatial, 0.0)
        spectral = jnp.where(alive, spectral, 0.0)
        msm = chaos * spatial * spectral
        return jnp.stack([chaos, spatial, spectral, msm], axis=1), programs


def _kth_largest_bits(images: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """The bit pattern (i32) of the ``k``-th LARGEST element ((...,) i32,
    1-based) of every row of the f32 block ``images`` (..., P), by an exact
    selection, for ``hotspot_clip_batch``.  (It stands below
    ``batch_metrics`` so that the lines above it stay where the persistent
    compile cache's keys know them.)

    For positive f32 the bit pattern read as i32 is monotone in the value,
    so the ``k``-th largest is the greatest ``v`` with ``count(bits >= v)
    >= k``: ``v`` is built from the top, bit 30 alone and then bits (29,
    28) ... (1, 0) a PAIR a step, a step being one pass over the block that
    compares it against the three per-row candidates of the pair and sums
    each along the row.  Two bits a step because a pass is bound by its
    read of the block, not by its compares (v5e, PR 52: 0.73 ms a pass of
    537 MB with three compares, 0.71 with one; 16 passes against 31).
    Every candidate has a bit set below the sign, so it is > 0 as an
    integer, and ``bits >= candidate`` is false for zeros (the lattice's
    pads, the ``valid``-masked peaks), for ``-0.0`` and for anything
    negative: no mask pass.  What comes back is an element of the row, the
    one a sort would have put at that rank, so nothing after it can round
    differently; a row with fewer than ``k`` positives reads 0.  Bit
    identity of the clip with the numpy definition was shown on the v5e
    for the sort this replaced (PR 50, 38 jobs) and for the selection
    (PR 52, 30 jobs of 15 runs on 12 seeds: PERF.md section 6)."""
    bits = lax.bitcast_convert_type(images, jnp.int32)

    def reaches(cand):
        # smlint: masked-ok[a zero pad is below every candidate: the count is of real positive pixels only]
        n_ge = jnp.sum(bits >= cand[..., None], axis=-1, dtype=jnp.int32)
        return (n_ge >= k).astype(jnp.int32)

    def step(i, v):                  # bits (29, 28) ... (1, 0), a pair a step
        shift = jnp.int32(28) - 2 * i
        passed = sum(reaches(v | jnp.left_shift(jnp.int32(j), shift))
                     for j in (1, 2, 3))           # monotone: 0, 1, 2 or 3
        return v | jnp.left_shift(passed, shift)

    top = jnp.full(images.shape[:-1], 1 << 30, jnp.int32)
    return lax.fori_loop(0, 15, step, top * reaches(top))


def _next_above_bits(images: jnp.ndarray, v: jnp.ndarray,
                     k: jnp.ndarray) -> jnp.ndarray:
    """The (k-1)-th largest of each row (the k-th where k is 1), given its
    k-th largest ``v``: one more pass.  ``n_above = count(bits > v)`` is at
    most k - 1; where it is k - 1 the answer is the least element above
    ``v``, where it is less ``v`` is tied and is the answer itself."""
    bits = lax.bitcast_convert_type(images, jnp.int32)
    above = bits > v[..., None]
    # smlint: masked-ok[a zero pad is never above a value >= 0]
    n_above = jnp.sum(above, axis=-1, dtype=jnp.int32)
    nxt = jnp.min(jnp.where(above, bits, np.int32(2**31 - 1)), axis=-1)
    return jnp.where((k > 1) & (n_above == k - 1), nxt, v)
