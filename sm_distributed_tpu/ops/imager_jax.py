"""Ion-image extraction, JAX/TPU backend.

TPU-first reformulation of the reference hot loop (SURVEY.md §3.3,
``formula_imager_segm.compute_sf_images`` [U]).  Instead of a cluster-wide
shuffle of (ion, pixel, intensity) hits, the dataset's peaks live on device
as ONE flat, globally m/z-sorted list (pixel, intensity per peak) and an ion
image is computed with *static shapes* through a per-batch WINDOW-BOUND
HISTOGRAM:

1. Host: sort the 2·W quantized window bounds of the batch into one grid;
   record each window's (lo, hi) leftmost rank in the grid (exact, integer).
2. Host: rank each grid bound among the sorted peaks (``flat_bound_ranks``:
   G binary searches into the host copy of the m/z array).  On device every
   peak's grid bin then falls out of ONE cumsum: bins[n] = #{g: grid[g] <=
   mz[n]} = inclusive cumsum of a delta array with +1 at each bound's rank.
3. Device: weighted scatter-add histogram (pixels x grid-bins) of peak
   intensities; it touches real peaks only (no per-pixel padding slots).
4. ``img = wh @ D`` where ``D[g, w] = rank_lo(w) < g <= rank_hi(w)`` — ONE
   f32 matmul on the MXU sums each window's bins; no per-(pixel, window)
   gather at all.  Crucially this is exact-zero-preserving: an empty window
   multiplies only zero histogram bins, so the result is exactly 0.0 (a
   cumsum-then-subtract formulation is NOT — XLA's parallel-prefix cumsum
   uses different summation trees per position, leaving ~1e-4 residues that
   fabricate hit pixels).

Exactness: the grid is exact integer quantized bounds and the histogram sums
exact integers (ops/quantize.py), so images equal the numpy oracle's bit for
bit in any summation order (tests/test_jax_backend.py::
test_extraction_parity).  The pixel axis is the sharding axis; each shard
histograms its pixel slice independently (collectives only in metrics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..io.dataset import SpectralDataset
from .quantize import MZ_PAD_Q, quantize_mz

# windows per band chunk in the flat-banded extraction (each chunk's
# membership matmul covers ~2*BAND_WINDOWS grid columns)
BAND_WINDOWS = 512


def window_rank_grid(
    lo_q: np.ndarray, hi_q: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side: (grid (2W,) int32 sorted, r_lo (W,), r_hi (W,) int32).

    ``grid`` is the sorted multiset of all window bounds; ``r_*`` are each
    bound's LEFTMOST rank in the grid.  Exactness: a peak lies in window w
    iff lo_q[w] <= mz_q < hi_q[w], and #\\{mz_q < b\\} == #peaks whose grid
    bin is <= leftmost_rank(b) (strictly-below counting survives duplicate
    bounds because equal bounds share the leftmost rank)."""
    # smlint: host-sync-ok[host window-bound prep; inputs are host numpy, not device values]
    lo_flat = np.ascontiguousarray(lo_q, dtype=np.int32).ravel()
    # smlint: host-sync-ok[host window-bound prep; inputs are host numpy, not device values]
    hi_flat = np.ascontiguousarray(hi_q, dtype=np.int32).ravel()
    # NOTE: the grid keeps duplicate bounds (fixed 2W size) on purpose — a
    # deduplicated grid has a data-dependent length, and every new length is
    # a new executable (measured: tens of seconds of XLA recompiles dwarfing
    # the ~nothing saved; real batches are >99.9% unique bounds anyway).
    grid = np.sort(np.concatenate([lo_flat, hi_flat]))
    r_lo = np.searchsorted(grid, lo_flat, side="left").astype(np.int32)
    r_hi = np.searchsorted(grid, hi_flat, side="left").astype(np.int32)
    return grid, r_lo, r_hi


# -- flat globally-sorted layout ----------------------------------------------


def flat_bound_ranks(mz_sorted_host: np.ndarray, grid: np.ndarray) -> np.ndarray:
    """Host-side per-batch: rank of each grid bound among the sorted peaks,
    ``pos[g] = #{peaks with mz < grid[g]}``.  G binary searches into the
    host copy of the dataset-static sorted m/z array — sub-millisecond,
    replacing a ~10 ms device searchsorted; ships as (G,) int32 (32 KB).
    (Shipping the full per-peak bins array instead was tried: host cumsum is
    free but it costs an N-sized uint16 transfer, ~5 MB, per batch.)"""
    return np.searchsorted(mz_sorted_host, grid, side="left").astype(np.int32)


def extract_images_flat(
    pixel_sorted: jnp.ndarray,  # (N,) int32, n_pixels = overflow row
    int_sorted: jnp.ndarray,    # (N,) f32, 0 at padding
    pos: jnp.ndarray,           # (G,) int32 host-computed bound ranks
    r_lo: jnp.ndarray,          # (W,) int32 leftmost rank of each lo bound
    r_hi: jnp.ndarray,          # (W,) int32 leftmost rank of each hi bound
    *,
    n_pixels: int,
) -> jnp.ndarray:
    """(W, n_pixels) f32 ion-window images; bit-identical to extract_images.

    ``bins[j] = #{g: grid[g] <= mz[j]}`` == #bounds whose rank is <= j:
    +1 at every pos, one inclusive cumsum."""
    n = pixel_sorted.shape[0]
    g = pos.shape[0]
    # the store's re-extraction (JaxBackend.extract_ion_images) is this
    # function's one program: the scope /debug/profile attributes it by
    with jax.named_scope("sm_store_extract"):
        delta = jnp.zeros(n + 1, jnp.int32).at[pos].add(1)
        bins = jnp.cumsum(delta[:-1])
        wh = jnp.zeros((n_pixels + 1, g + 1), jnp.float32).at[
            pixel_sorted, bins].add(int_sorted)
        gg = jnp.arange(g + 1, dtype=jnp.int32)[:, None]
        d = ((gg > r_lo[None, :]) & (gg <= r_hi[None, :])).astype(jnp.float32)
        img_pw = jnp.dot(wh[:n_pixels], d,
                         precision=jax.lax.Precision.HIGHEST)
        return img_pw.T


def extract_images_flat_banded(
    pixel_sorted: jnp.ndarray,  # (N,) int32, n_pixels = overflow row
    int_sorted: jnp.ndarray,    # (N,) f32, 0 at padding
    pos: jnp.ndarray,           # (G,) int32 host-computed bound ranks
    starts: jnp.ndarray,        # (C,) int32 chunk grid offsets (window_chunks)
    r_lo_loc: jnp.ndarray,      # (C, Wc) int32 local lo ranks
    r_hi_loc: jnp.ndarray,      # (C, Wc) int32 local hi ranks
    inv: jnp.ndarray,           # (W,) int32 sorted-row -> input-order map
    *,
    gc_width: int,
    n_pixels: int,
) -> jnp.ndarray:
    """(W, n_pixels) flat extraction with a BANDED membership matmul.

    The dense membership matrix costs 2*P*(G+1)*W flops — quadratic in the
    batch size (G and W both scale with B*K), which is what forbids large
    batches even though the histogram scatter amortizes with B.  But each
    window's bins live in the narrow band (r_lo, r_hi] of the grid, so with
    windows m/z-sorted and chunked (the ``window_chunks`` plan), chunk c's
    512 windows only need grid columns [start_c, start_c + gc_width + 2):
    flops drop to 2*P*gc*W — LINEAR in the batch — times the three bf16
    passes the product takes on the MXU (see the chunk body).  The
    histogram is built ONCE at full width (its cost is per-peak, not
    per-window), then each chunk dynamic-slices its band and runs a small
    MXU matmul.  Images are bit-identical: out-of-band bins have zero
    membership in the dense form.

    ``gc_width`` is the plan's sticky maximum of the chunks' rank spans.
    ``window_chunks`` sorts the windows themselves, so 512 neighbours span
    little more than their own bounds (1536 rows at any table size) and
    the image rows come out in m/z order: ``inv`` (W,) gathers them back,
    one pass over the image block.
    """
    n = pixel_sorted.shape[0]
    g = pos.shape[0]
    delta = jnp.zeros(n + 1, jnp.int32).at[pos].add(1)
    bins = jnp.cumsum(delta[:-1])
    # Scratch width: all bins live in [0, g], so max(g+1, gc+2) columns
    # suffice — chunk slices near the top CLAMP their start and shift the
    # local window ranks by the same delta (start + span <= g+1 <= cols
    # guarantees shifted ranks stay inside the gc+2-wide band, see below).
    # The scatter's FIXED cost is the operand zero-init/copy at ~38 GB/s
    # (measured: ~12 ns/update marginal + ~28 ns/column/1k-rows fixed on
    # v5e), so the old g+1+gc+2 layout paid ~2x the necessary fixed cost
    # on every 256-ion DESI batch (G ~= gc there).  Bit-exact: each
    # window still sums exactly its own bins' integers (any order — the
    # quantized grid keeps every sum < 2**24).
    cols = max(g + 1, gc_width + 2)
    # TRANSPOSED scratch (bins-major): measured on v5e at DESI shapes,
    # the (cols, P) layout scatters ~6% faster than (P, cols), its chunk
    # slice is a row-range, and the membership matmul d.T @ band emits
    # images already (W, P) — no per-chunk output transpose (together
    # ~15 ms per 256-ion DESI batch)
    wh = jnp.zeros((cols, n_pixels + 1), jnp.float32).at[
        bins, pixel_sorted].add(int_sorted)
    whp = wh[:, :n_pixels]
    gg = jnp.arange(gc_width + 2, dtype=jnp.int32)[:, None]

    def chunk(_, data):
        start, rlo, rhi = data
        # clamp keeps the static-width slice inside the scratch; the
        # chunk's windows span global cols [start, start+span] with
        # start+span <= g+1 <= cols, so shift + span <= gc+2 always
        start_eff = jnp.minimum(start, np.int32(cols - (gc_width + 2)))
        shift = start - start_eff
        band = jax.lax.dynamic_slice(
            whp, (start_eff, jnp.int32(0)), (gc_width + 2, n_pixels))
        d = ((gg > (rlo + shift)[None, :])
             & (gg <= (rhi + shift)[None, :])).astype(jnp.float32)
        # THREE bf16 MXU passes, not HIGHEST's six: XLA:TPU folds the
        # cast of the compares away and feeds the convolution a pred
        # operand, which is one exact piece; only ``band`` is cut in three
        # (PERF.md section 6, PR 42: 90% of the matrix unit's peak for
        # three passes; explicit bf16 or int8 pieces read 8-66% slower)
        return None, jnp.dot(
            d.T, band, precision=jax.lax.Precision.HIGHEST)

    _, imgs = jax.lax.scan(chunk, None, (starts, r_lo_loc, r_hi_loc))
    imgs = imgs.reshape(-1, n_pixels)                  # (C*Wc, P) sorted order
    # (W, P) input order; ``inv`` is the plan's own permutation of the
    # sorted rows, so the gather needs no out-of-range fill pass
    return imgs.at[inv].get(mode="promise_in_bounds", unique_indices=True)


def prepare_flat_sharded_arrays(
    ds: SpectralDataset,
    ppm: float,
    n_shards: int,
    pad_to_multiple: int = 1024,
    p_loc: int | None = None,
    slot_bucket=None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side flat layout per PIXEL SHARD: (mz_q (S, Nmax) int32 ascending
    per row, px_local (S, Nmax) int32, ints (S, Nmax) f32, p_loc).

    Each shard owns a contiguous slice of ``p_loc = ceil(P/S)`` pixels and
    its peaks sorted by quantized m/z; rows pad to the max shard peak count
    (m/z -> MZ_PAD_Q sentinel, pixel -> the shard-local overflow row
    ``p_loc``, intensity 0).  Per-shard bytes track the actual peak count
    (no per-pixel padding to the longest spectrum, which ragged DESI data
    would make catastrophic).  The m/z rows stay host-side (bound
    ranks are host-computed); only pixel + intensity rows go to HBM.

    ``p_loc`` (ISSUE 13 lattice): an explicit per-shard pixel capacity
    >= ceil(P/S) — the sharded backend passes a row-bucketed whole-row
    capacity so every dataset size in the bucket shares the executable
    (trailing shards may then be partially or wholly padding, exactly the
    padded-slot shape the slice above already uses).  ``slot_bucket``
    replaces the ``pad_to_multiple`` rounding of the peak-slot capacity
    with the shared lattice (``ops/buckets.peak_bucket``)."""
    if p_loc is None:
        p_pad = -(-ds.n_pixels // n_shards) * n_shards
        p_loc = p_pad // n_shards
    elif p_loc * n_shards < ds.n_pixels:
        raise ValueError(
            f"p_loc={p_loc} x {n_shards} shards cannot hold "
            f"{ds.n_pixels} pixels")
    mz_q = quantize_mz(ds.mzs_flat)
    ints_q, _scale = ds.intensity_quantization(ppm)
    lens = ds.row_lengths()
    pixel = np.repeat(np.arange(ds.n_pixels, dtype=np.int64), lens)
    shard = (pixel // p_loc).astype(np.int32)
    counts = np.bincount(shard, minlength=n_shards)
    if slot_bucket is not None:
        n_max = int(slot_bucket(max(int(counts.max()), 1)))
    else:
        n_max = -(-max(int(counts.max()), 1)
                  // pad_to_multiple) * pad_to_multiple
    mz_s = np.full((n_shards, n_max), MZ_PAD_Q, dtype=np.int32)
    px_s = np.full((n_shards, n_max), p_loc, dtype=np.int32)
    in_s = np.zeros((n_shards, n_max), dtype=np.float32)
    for s in range(n_shards):
        m = shard == s
        order = np.argsort(mz_q[m], kind="stable")
        c = int(counts[s])
        mz_s[s, :c] = mz_q[m][order]
        px_s[s, :c] = (pixel[m] - s * p_loc).astype(np.int32)[order]
        in_s[s, :c] = ints_q[m][order]
    return mz_s, px_s, in_s, p_loc


def gc_ladder(span: int) -> int:
    """Static chunk band width for a window span: smallest {1, 1.5} x
    pow-2 point >= span."""
    cap = 2
    while cap < span:
        cap <<= 1
    mid = (cap >> 2) * 3
    return mid if span <= mid and mid >= 2 else cap


def band_bucket(width: int, floor: int = 1 << 21) -> int:
    """Static band-slice capacity for a band of ``width`` peaks: the
    smallest {1, 1.125..1.875 step 1/8} x pow-2 ladder point >= width
    (with a floor).  Each bucket is one (cached) executable; eighth
    points bound padded scatter waste at 12.5% (~6% expected — the r4
    {1, 1.5} ladder's 50% bound measured ~440M scatter slots/rep at DESI
    scale against ~318M actual band peaks; at ~12 ns per padded slot the
    finer ladder buys ~1 s/rep for ~10 one-time cached compiles; a /16
    ladder would only halve the residual ~6% while doubling the compile
    count)."""
    cap = floor
    while cap < width:
        cap <<= 1
    if cap > floor:
        for eighths in range(9, 16):
            mid = (cap >> 4) * eighths
            if width <= mid:
                return mid
    return cap


def batch_peak_band(mz_host: np.ndarray, lo_q: np.ndarray,
                    hi_q: np.ndarray) -> tuple[int, int]:
    """Host-side: the CONTIGUOUS rank band [start, start+width) of the
    sorted resident peaks spanned by a batch's window union.  For an
    m/z-ordered ion table every batch's union is m/z-localized, so the band
    is narrow; extraction can then scatter a dynamic slice of the resident
    arrays directly (no per-run gather) — see
    models/msm_jax.py::fused_score_fn_flat_banded_sliced."""
    flat = merged_window_bounds(lo_q, hi_q)
    if flat.size == 0:
        return 0, 0
    cuts = np.searchsorted(
        # smlint: host-sync-ok[host band-bound pair; mz_host is the host copy of the sorted peaks]
        mz_host, np.array([flat[0], flat[-1]], dtype=mz_host.dtype),
        side="left")
    return int(cuts[0]), int(cuts[1] - cuts[0])


def merged_window_bounds(lo_q: np.ndarray, hi_q: np.ndarray) -> np.ndarray:
    """Host-side: the union of half-open quantized windows [lo, hi) as a
    flat sorted boundary array [lo1, hi1, lo2, hi2, ...] of DISJOINT
    intervals.  Membership test: searchsorted(flat, mz, 'right') is odd."""
    # smlint: host-sync-ok[host window-bound prep; inputs are host numpy, not device values]
    lo = np.asarray(lo_q, dtype=np.int64).ravel()
    # smlint: host-sync-ok[host window-bound prep; inputs are host numpy, not device values]
    hi = np.asarray(hi_q, dtype=np.int64).ravel()
    real = lo < hi                       # drop empty windows (batch padding)
    lo, hi = lo[real], hi[real]
    if lo.size == 0:
        return np.zeros(0, dtype=np.int32)
    order = np.argsort(lo, kind="stable")
    lo, hi = lo[order], hi[order]
    run_hi = np.maximum.accumulate(hi)
    # a new disjoint interval starts where lo exceeds every prior hi
    # (touching intervals merge too, keeping the parity test valid)
    new = np.concatenate([[True], lo[1:] > run_hi[:-1]])
    starts = lo[new]
    ends = run_hi[np.concatenate([new[1:], [True]])]
    return np.stack([starts, ends], axis=1).ravel().astype(np.int32)


def window_union_member(mz_q: np.ndarray, flat_bounds: np.ndarray) -> np.ndarray:
    """Boolean mask: which quantized m/z values fall inside ANY window of
    the union (the reference's searchsorted hot loop only emits hits
    [U, formula_imager_segm]; this is the dataset-side equivalent —
    peaks outside every window of a SEARCH can never contribute and are
    dropped from the device arrays up front)."""
    if flat_bounds.size == 0:
        return np.zeros(mz_q.shape, dtype=bool)
    return (np.searchsorted(flat_bounds, mz_q, side="right") % 2) == 1


def restrict_flat_to_windows(
    mz_s: np.ndarray,    # (S, N) int32 per-shard sorted, MZ_PAD_Q padding
    px_s: np.ndarray,    # (S, N) int32
    in_s: np.ndarray,    # (S, N) f32
    lo_q: np.ndarray,    # window lo bounds (any shape; empty lo==hi dropped)
    hi_q: np.ndarray,
    overflow_row: int,
    pad_to_multiple: int = 1024,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Keep only peaks inside the union of the windows; re-pad each shard
    row to the new common length.  Returns (mz, px, ints, max_kept).

    Exact: dropped peaks match no window, so every image bit is unchanged;
    padding rows (MZ_PAD_Q sentinel) sit outside every real window and drop
    with the rest.  Table padding rows quantize to the empty window (0, 0),
    which merged_window_bounds already drops — callers pass raw bounds."""
    flat = merged_window_bounds(lo_q, hi_q)
    keeps = [window_union_member(mz_s[s], flat) for s in range(mz_s.shape[0])]
    n_eff = max((int(k.sum()) for k in keeps), default=1)
    n_pad = -(-max(n_eff, 1) // pad_to_multiple) * pad_to_multiple
    s_count = mz_s.shape[0]
    mz_k = np.full((s_count, n_pad), MZ_PAD_Q, dtype=np.int32)
    px_k = np.full((s_count, n_pad), overflow_row, dtype=np.int32)
    in_k = np.zeros((s_count, n_pad), dtype=np.float32)
    for s, k in enumerate(keeps):
        c = int(k.sum())
        mz_k[s, :c] = mz_s[s][k]
        px_k[s, :c] = px_s[s][k]
        in_k[s, :c] = in_s[s][k]
    return mz_k, px_k, in_k, n_eff


# -- per-batch peak compaction ------------------------------------------------
#
# The window-union restriction (restrict_flat_to_windows) drops peaks outside
# every window of the whole SEARCH, but the histogram scatter still touches
# every resident peak once per BATCH — with T batches, each peak is scattered
# T times while matching (typically) one batch's windows.  The reference has
# no such waste: its searchsorted loop emits only hits [U, formula_imager_segm].
# Per-batch compaction restores that property on TPU with static shapes:
#
# 1. Host, per batch: merge THIS batch's windows into disjoint m/z intervals
#    and cut the sorted peak array at their bounds -> contiguous kept RUNS
#    (run start + cumulative kept offset per run); n_b = total kept.
# 2. Device: materialize the source index of every kept slot with one small
#    scatter (one offset jump per run) + cumsum, then gather pixel/intensity
#    rows.  A host-shipped index array would be ~N_b*4 B/batch; the run
#    list is KBs.
# 3. The bound ranks are re-based to kept space (exact integer arithmetic on
#    the runs), and extraction proceeds unchanged on the compacted arrays.
#
# Exact: kept peaks are precisely those inside some window of the batch, so
# the (pixel, bin, intensity) hit multiset — and every image bit — is
# unchanged.  Scatter work drops from N_resident to ~N_resident/T per batch
# (large formula DBs run tens of batches), which is what makes the large-P
# regime (BASELINE #5) scatter-bound no more.


def batch_peak_runs(
    mz_host: np.ndarray,   # (N,) int32 sorted quantized m/z (resident peaks)
    lo_q: np.ndarray,      # batch window lo bounds (any shape)
    hi_q: np.ndarray,      # batch window hi bounds
    pos: np.ndarray,       # (G,) int32 source-space bound ranks (flat_bound_ranks)
) -> tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Host-side compaction plan: (run_kept_start (R,) i32, run_delta (R,) i32,
    n_b, pos_b (G,) i32).

    ``run_kept_start`` is each run's first index in kept space, ``run_delta``
    the jump in (source - kept) offset at that index; ``pos_b`` re-bases the
    grid bound ranks to kept space: #kept peaks strictly below the bound."""
    flat = merged_window_bounds(lo_q, hi_q)
    cuts = np.searchsorted(mz_host, flat.astype(mz_host.dtype), side="left")
    starts, ends = cuts[0::2].astype(np.int64), cuts[1::2].astype(np.int64)
    lens = ends - starts
    keep = lens > 0
    starts, lens = starts[keep], lens[keep]
    if starts.size == 0:     # batch with no real windows (all padding)
        return (np.zeros(0, np.int32), np.zeros(0, np.int32), 0,
                # smlint: host-sync-ok[pos is the host-computed bound-rank array]
                np.zeros(np.asarray(pos).shape, np.int32))
    kept_start = np.zeros(starts.size + 1, dtype=np.int64)
    np.cumsum(lens, out=kept_start[1:])
    n_b = int(kept_start[-1])
    # kept rank of a source rank s: walk back to the last run starting <= s;
    # clamp inside the run (bounds between runs — possible only for empty
    # padding windows — snap to the nearest run edge, which keeps their
    # windows empty in kept space)
    r = np.searchsorted(starts, pos, side="right") - 1
    rc = np.clip(r, 0, None)
    pos_b = np.where(
        r < 0, 0,
        kept_start[rc] + np.clip(pos - starts[rc], 0, lens[rc]))
    offsets = starts - kept_start[:-1]
    run_delta = np.diff(offsets, prepend=0)
    return (kept_start[:-1].astype(np.int32), run_delta.astype(np.int32),
            n_b, pos_b.astype(np.int32))


def compact_peaks(
    px_s: jnp.ndarray,      # (N,) int32 resident pixel rows
    in_s: jnp.ndarray,      # (N,) f32 resident intensities
    run_pos: jnp.ndarray,   # (R_pad,) i32 kept-space run starts (pad: >= n_keep)
    run_delta: jnp.ndarray, # (R_pad,) i32 offset jumps (pad: 0)
    n_b: jnp.ndarray,       # () i32 kept count this batch
    *,
    n_keep: int,
    n_pixels: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side gather of the kept peak slots: (px_b, in_b), both (n_keep,).

    Slots >= n_b are padding: pixel -> an OUT-OF-BOUNDS row so the
    histogram scatter DROPS them (default jnp scatter semantics), not the
    overflow row.  In-bounds padding was a measured pathology: every pad
    slot's bin is G (all bounds below it), so with a sticky ``n_keep``
    capacity above the batch's real keep, millions of pads scattered into
    the ONE cell (overflow_row, G) — and TPU scatter serializes colliding
    updates (~50 vs ~14 ns/peak; PERF.md mechanism 2).  Dropped
    updates write nothing, so they can't collide.  Exact either way: pads
    carry intensity 0 into a bin no window sums.

    The (pixel, intensity) rows are gathered as ONE packed (N, 2) f32
    gather, not two scalar gathers: a 2-column row gather moves the same
    slot in one descriptor, measured 483 -> 181 ms for 7.7M slots on v5e
    (the gather is this function's whole cost; ``indices_are_sorted``
    hints measured no effect).  Exact while pixel ids < 2**24 (f32
    integer range) — the scale guard in models/msm_jax.py caps the flat
    path far below that; the sharded path's ids are shard-local."""
    j = jnp.arange(n_keep, dtype=jnp.int32)
    d = jnp.zeros(n_keep, jnp.int32).at[run_pos].add(run_delta, mode="drop")
    src = jnp.clip(j + jnp.cumsum(d), 0, px_s.shape[0] - 1)
    valid = j < n_b
    if n_pixels < 2**24:
        pk = jnp.stack([px_s.astype(jnp.float32), in_s], axis=1)
        got = pk[src]
        px_b = jnp.where(valid, got[:, 0].astype(jnp.int32), jnp.int32(2**30))
        in_b = jnp.where(valid, got[:, 1], jnp.float32(0.0))
    else:
        px_b = jnp.where(valid, px_s[src], jnp.int32(2**30))
        in_b = jnp.where(valid, in_s[src], jnp.float32(0.0))
    return px_b, in_b


# -- window-chunk plans --------------------------------------------------------
#
# The histogram scratch is (P, 2*B*K+1) f32, and a membership matmul over all
# of it does work quadratic in the batch.  Windows are therefore sorted by m/z
# and cut into chunks whose LOCAL slice of the bound grid is gc_width wide:
# each chunk's matmul reads only its band.  ``window_chunks`` is the plan of
# both backends' extraction.  Images are bit-identical to an unchunked
# extraction: hit sets are exact integer-grid matches and sums are exact
# integers (ops/quantize.py) in any grouping.


def window_chunks(
    r_lo: np.ndarray, r_hi: np.ndarray, windows_per_chunk: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side chunk plan: (starts (C,), r_lo_loc (C, Wc), r_hi_loc (C, Wc),
    inv (W,), gc_width).

    Windows are ordered by lo rank and cut every ``windows_per_chunk`` windows; a
    chunk's grid offset is its first window's lo rank; ``gc_width`` (the
    max local rank span, rounded up to a power of two so recompiles are
    rare) sizes the scratch.  ``inv`` maps sorted rows back to input order.
    """
    w = int(r_lo.size)
    wc = max(1, int(windows_per_chunk))
    c = max(1, -(-w // wc))
    # EMPTY windows (lo == hi: batch padding quantized to (0,0), or windows
    # collapsed by quantization) sort LAST, not by their rank-0 bounds —
    # otherwise a partially-padded batch puts rank-0 empties and high-rank
    # real windows into one chunk whose span is the whole grid, and the
    # sticky gc_width then degrades every batch (measured: 8x band growth,
    # ~10x slowdown on the bench tail batch).  Their local ranks go
    # negative in a straddling chunk, which the membership test treats as
    # empty — exactly right.
    order = np.lexsort((r_lo, (r_lo == r_hi).astype(np.int8)))
    pad = c * wc - w
    r_lo_s = np.concatenate([r_lo[order], np.zeros(pad, r_lo.dtype)]).reshape(c, wc)
    r_hi_s = np.concatenate([r_hi[order], np.zeros(pad, r_hi.dtype)]).reshape(c, wc)
    starts = r_lo_s[:, 0].astype(np.int32)
    # padded tail windows: snap to the chunk offset -> empty local window
    if pad:
        r_lo_s[-1, wc - pad:] = starts[-1]
        r_hi_s[-1, wc - pad:] = starts[-1]
    r_lo_loc = (r_lo_s - starts[:, None]).astype(np.int32)
    r_hi_loc = (r_hi_s - starts[:, None]).astype(np.int32)
    # {1, 1.5} x pow-2 ladder (floor wc): gc is a STATIC matmul/slice width
    # shared by every chunk, so rounding 1026 -> 2048 (the old pure-pow-2
    # rule) paid ~33% extra membership-matmul flops and band-slice reads
    # on typical 512-window chunks; the half-point bounds that at 50% while
    # the sticky per-stream max keeps one executable per stream either way
    gc_width = gc_ladder(max(int(r_hi_loc.max()) if w else 1, wc, 2))
    inv = np.empty(w, dtype=np.int32)
    inv[order] = np.arange(w, dtype=np.int32)
    return starts, r_lo_loc, r_hi_loc, inv, gc_width


# -- roofline cost model ------------------------------------------------------

def fused_score_cost_model(
    n_pixels: int,
    resident_peaks: int,
    n_ions: int,
    max_peaks: int,
    formula_batch: int,
    nlevels: int = 30,
    ordered: bool = True,
    cube_dtype: str = "f32",
) -> dict:
    """Minimum-work estimate of one full scoring rep (all ions once), for
    the roofline probe (scripts/roofline_probe.py, ISSUE 3 satellite).

    Counts the traffic/flops the fused graph CANNOT avoid under its current
    algorithm, priced from the extraction design (this module) and the
    mechanism notes in PERF.md:

    - histogram scatter: every scored peak slot is one 4 B intensity read,
      one index read, and one f32 read-modify-write on the scratch (~12 B).
      Ordered streams scatter each resident peak ~once in total (band-slice
      per-batch bands); unordered streams re-touch the residents per batch.
    - scratch zero-init: XLA scatter's fixed cost is the operand
      zero-init/copy (ROADMAP A3: to be re-measured on the chip) — one
      (P+1) x max(G+1, gc+2) f32 block per batch.
    - membership matmul: wh (P, G+1) @ D (G+1, B) per batch, f32 at
      ``Precision.HIGHEST``: THREE bf16 MXU passes on the TPU, not six
      (the compiler feeds the 0/1 side as a pred operand, one exact
      piece; measured at 90% of the matrix unit's peak for three, PERF.md
      section 6, PR 42).  ``matmul_flops`` stays the plain 2mnk:
      scripts/roofline_probe.py prices it against the device's MEASURED
      f32-HIGHEST matmul rate, which holds the passes.
    - image block: (n_ions, K, P) f32 written by extraction, then read by
      the moments pass (1x) and the chaos sweeps (>= ~2 effective passes of
      the label plane at span-32 with the cheap certificate).

    Returns bytes/flops totals; ``min_seconds(bw, flops)`` against measured
    device peaks is the roofline floor.  This is a LOWER bound on work (it
    prices no padding, no recompiles, no host/dispatch), so
    measured/modeled is an upper bound on remaining headroom.

    ``cube_dtype`` prices the resident intensity read of the histogram
    scatter at the compacted width (ops/quantize.py: bf16 2 B per peak).
    """
    n_batches = max(1, -(-n_ions // formula_batch))
    g = 2 * formula_batch * max_peaks
    scratch_cols = max(g + 1, 4098)
    scatter_slots = (resident_peaks if ordered
                     else resident_peaks * n_batches)
    int_bytes = {"f32": 4, "bf16": 2}[cube_dtype]
    # per slot: intensity read + index read + f32 scratch read-modify-write
    scatter_bytes = (int_bytes + 8) * scatter_slots
    init_bytes = 4 * n_batches * (n_pixels + 1) * scratch_cols
    image_bytes = 4 * n_ions * max_peaks * n_pixels
    metric_read_bytes = 3 * image_bytes    # moments 1x + chaos ~2 passes
    matmul_flops = 2.0 * n_batches * n_pixels * (g + 1) * formula_batch
    total_bytes = scatter_bytes + init_bytes + image_bytes + metric_read_bytes
    return dict(
        n_batches=n_batches,
        scatter_slots=int(scatter_slots),
        scatter_bytes=int(scatter_bytes),
        scratch_init_bytes=int(init_bytes),
        image_bytes=int(image_bytes),
        metric_read_bytes=int(metric_read_bytes),
        total_bytes=int(total_bytes),
        matmul_flops=float(matmul_flops),
    )


# -- the store's image export ---------------------------------------------------

def export_image_chunks(
    pixel_sorted: jnp.ndarray,  # (N,) int32, n_pixels = overflow row
    int_sorted: jnp.ndarray,    # (N,) f32, 0 at padding
    pos: jnp.ndarray,           # (G,) int32 host-computed bound ranks
    r_lo: jnp.ndarray,          # (W,) int32 leftmost rank of each lo bound
    r_hi: jnp.ndarray,          # (W,) int32 leftmost rank of each hi bound
    row_scale: jnp.ndarray,     # (W,) f32: 1 / int_scale, 0 where a row is padding
    *,
    n_pixels: int,
    chunk_rows: int,
) -> tuple[jnp.ndarray, ...]:
    """The store's export, as the writer takes it: ``extract_images_flat``
    cut into pieces of ``chunk_rows`` flat rows, each an output of its own
    (so each leaves for the host by itself), then the (W,) i32 count of
    non-zero pixels a row.

    ``row_scale`` does on the device what the host did over the whole
    array: the de-quantization (a multiply by the exact reciprocal of the
    power-of-two ``int_scale``: the same bits as the division) and the
    zeroing of the isotope peaks past ``n_valid`` and of the rows that pad
    the bucket (every sum is a non-negative integer, so ``x * 0`` is
    ``+0.0``).  The scatter runs once, whatever the number of pieces."""
    imgs = extract_images_flat(
        pixel_sorted, int_sorted, pos, r_lo, r_hi, n_pixels=n_pixels)
    with jax.named_scope("sm_store_extract"):
        chunks = tuple(
            imgs[s:s + chunk_rows] * row_scale[s:s + chunk_rows, None]
            for s in range(0, imgs.shape[0], chunk_rows))
        nnz = jnp.concatenate([
            jnp.count_nonzero(c, axis=1).astype(jnp.int32) for c in chunks])
        return (*chunks, nnz)
