"""Mesh-sharded fused extract+score graph (multi-chip path).

TPU-native replacement for the reference's distributed runtime (SURVEY.md
§5.8): where the reference broadcasts peak tables and runs a cluster-wide
``groupByKey`` shuffle of (ion, pixel, intensity) hits
(``formula_imager_segm.compute_sf_images`` [U], §3.3), here:

- the spectral data is resident in HBM as per-pixel-shard FLAT sorted peak
  lists sharded over the ``"pixels"`` mesh axis — the RDD-partition analog.
  (Round-2 switch from the padded cube: per-shard bytes track the actual
  peak count instead of pixels x max-spectrum-length, which is what a
  ragged >200k-pixel DESI slide needs, and extraction uses the same
  flat-banded kernel as the single-device path);
- the isotope window/intensity tables are sharded over ``"formulas"`` and
  replicated over ``"pixels"`` — the broadcast analog (XLA materializes it as
  an all-gather over ICI);
- the shuffle is ONE ``all_to_all`` along the pixel axis: each device trades
  its pixel slice of most ions for ALL pixels of a 1/n_pix ion sub-batch.
  This is the round-2 comms redesign (VERDICT r1 item 3): the round-1 step
  ``all_gather``-ed every device a full (B_loc, K, P_full) image block, so
  per-device memory grew with TOTAL pixels and (n_pix-1)/n_pix of the metric
  compute was redundant.  Now per-device image bytes are B_loc*K*P_full/n_pix
  — constant in the shard count for a fixed total batch — metric compute is
  partitioned (no redundancy), and because image pixel values are exact
  integers on the shared intensity grid (ops/quantize.py), each ion's full
  image is bit-identical to the single-device path, so metrics are computed
  by the SAME code on the SAME bits.  A final tiny ``all_gather`` of the
  (B_loc/n_pix, 4) metric rows reassembles the formula shard's output.

The whole step stays a single jitted program per dataset (static shapes), so
multi-chip keeps the north star's one-fused-graph property per batch.

ISSUE 18 scope note: the mesh step adopts the bf16 resident-cube
compaction (per-shard rows cast on host, expanded to f32 in-graph at the
top of the step), but NOT the fused Pallas scoring kernel — the step's
all_to_all trades materialized image blocks between pixel shards, and the
correlation moments need the post-shuffle global-pixel mean, so the fused
kernel's image-free partials cannot cross the shuffle without a second
collective pass.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..analysis.numerics import numerics_surface
from ..analysis.surface import compile_surface
from ..io.dataset import SpectralDataset
from ..ops import buckets as shape_buckets
from ..ops.imager_jax import (
    BAND_WINDOWS as _BAND_WINDOWS,
)
from ..ops.imager_jax import (
    batch_peak_band,
    batch_peak_runs,
    compact_peaks,
    extract_images_flat_banded,
    flat_bound_ranks,
    prepare_flat_sharded_arrays,
    window_chunks,
    window_rank_grid,
)
from ..ops.isocalc import IsotopePatternTable
from ..ops.metrics_jax import batch_metrics
from ..utils import tracing
from ..ops.quantize import compact_cube, expand_cube_jnp, quantize_window
from ..utils.config import DSConfig, SMConfig
from ..utils.logger import logger
from .mesh import FORMULAS_AXIS, PIXELS_AXIS, make_mesh, resolve_axis_sizes

# Declared compile surface (ISSUE 12, analysis/surface.py): the sharded
# step's statics ride in through make()'s partial closure, so the whole
# mesh path mints ONE executable per (gc_width, n_keep, w_cap) triple —
# sticky stream-fixpoint capacities keep the triple set closed per stream.
COMPILE_SURFACE = compile_surface(__name__, {
    "step":
        "statics=closure(gc_width,n_keep,w_cap); buckets=one executable per "
        "(gc_width, n_keep, w_cap) triple — sticky _grow_static_shapes "
        "fixpoint + band_bucket ladder bound the triple set per stream; "
        "per-shard pixel rows and resident peak slots snap to the "
        "ops/buckets lattice with a traced real-pixel count (ISSUE 13), "
        "so dataset sizes sharing a bucket share the executable; the "
        "extract_ion_images step is a second, statics-free export program",
    "sharded":
        "statics=closure(gc_width,n_keep,w_cap); buckets=jit of the "
        "shard_mapped step, cached per triple in ShardedJaxBackend._fns",
})

# Declared numerics contracts (ISSUE 15): the sharded step slices its
# all_to_all concat to the SAME row bucket the single-device path uses
# (ISSUE 13), so sharded scoring is BIT-equal to the single-device fused
# graph — the strongest cross-variant contract in the tree.  The shard
# rows ride the lattice, hence `padded=px_s,in_s` for the
# masked-reduction taint.
NUMERICS = numerics_surface(__name__, {
    "step":
        "contract=bit_exact; test=tests/test_parallel.py::"
        "test_sharded_matches_single_device; padded=px_s,in_s",
    "sharded":
        "contract=bit_exact; test=tests/test_parallel.py::"
        "test_sharded_peak_compaction_bit_exact",
})


def build_sharded_score_factory(
    mesh: Mesh,
    *,
    p_loc: int,
    nrows: int,
    ncols: int,
    nlevels: int,
    do_preprocessing: bool,
    q: float,
):
    """Returns ``make(gc_width) -> jitted sharded step``: the step maps
    (flat peak shards, window shards) -> (B, 4) metrics; the factory exists
    because the band width is a static shape (ShardedJaxBackend caches one
    executable per gc_width, normally exactly one thanks to the sticky
    pre-sized band).

    Layouts: the flat peak arrays (pixel + intensity rows, one row per pixel
    shard) are sharded P("pixels", None); the per-(pixel-shard x formula-
    shard) bound ranks P("pixels", "formulas"); the window-chunk plan per
    formula shard P("formulas", ...); output P("formulas", None).  The
    extraction inside each device block is exactly the single-device
    flat-banded kernel on the shard's pixel slice.
    """

    n_pix = mesh.shape[PIXELS_AXIS]

    def step(px_s, in_s, pos, starts, r_lo_loc, r_hi_loc, inv,
             theor_ints, n_valid, run_pos, run_delta, n_b, n_real,
             *, gc_width, n_keep, w_cap):
        # Per-device blocks: px_s/in_s (1, Nmax); pos (1, G_loc); plan
        # (C, Wc)/(C,)/(W_loc,); theor (B_loc, K); n_valid (B_loc,);
        # compaction runs (1, R_pad)/(1, R_pad)/(1, 1) per (pixel-shard x
        # formula-shard).  Exactly one of n_keep/w_cap is nonzero: n_keep
        # selects the compaction path, w_cap the band-slice path (scatter a
        # contiguous dynamic slice of this shard's sorted peaks — the cell's
        # window-union rank band; run_pos doubles as the (1, 1) per-cell
        # band start), 0/0 the plain path.  One executable per
        # (gc_width, n_keep, w_cap) triple, mirroring JaxBackend._VARIANTS.
        b, k = theor_ints.shape
        # f32 view of a (possibly bf16-compacted) shard row — a no-op for
        # legacy f32 residents, so that HLO is byte-identical (ISSUE 18)
        in_s = expand_cube_jnp(in_s)
        if n_keep:
            px_loc, in_loc = compact_peaks(
                px_s[0], in_s[0], run_pos[0], run_delta[0], n_b[0, 0],
                n_keep=n_keep, n_pixels=p_loc)
        elif w_cap:
            w_start = run_pos[0, 0]
            px_loc = jax.lax.dynamic_slice(px_s[0], (w_start,), (w_cap,))
            in_loc = jax.lax.dynamic_slice(in_s[0], (w_start,), (w_cap,))
        else:
            px_loc, in_loc = px_s[0], in_s[0]
        imgs_loc = extract_images_flat_banded(
            px_loc, in_loc, pos[0], starts, r_lo_loc, r_hi_loc, inv,
            gc_width=gc_width, n_pixels=p_loc)
        # materialize before the metric consumers (see models/msm_jax.py:
        # measured 3.4x fusion regression at 65k pixels without it)
        imgs_loc = jax.lax.optimization_barrier(imgs_loc)
        imgs_loc = imgs_loc.reshape(b, k, -1)            # (B_loc, K, P_loc)
        # The "shuffle": trade pixel slices for full-pixel ion sub-batches.
        # Device j of the pixel group ends with (B_loc/n_pix, K, P_full).
        imgs_mine = jax.lax.all_to_all(
            imgs_loc, PIXELS_AXIS, split_axis=0, concat_axis=2, tiled=True)
        imgs_mine = imgs_mine[:, :, : nrows * ncols]
        ti = theor_ints.reshape(n_pix, b // n_pix, k)
        nv = n_valid.reshape(n_pix, b // n_pix)
        my = jax.lax.axis_index(PIXELS_AXIS)
        # ``nrows`` is the (possibly row-bucketed) metric grid; ``n_real``
        # carries the dataset's true pixel count as a traced scalar so the
        # masked centering stays bit-identical on lattice padding
        # the chaos kernel's program counts stay on the shards: only the
        # single-device programs carry them to the host (models/msm_jax.py)
        out_mine, _programs = batch_metrics(
            imgs_mine, ti[my], nv[my], nrows, ncols, nlevels,
            do_preprocessing=do_preprocessing, q=q, n_real=n_real[0],
        )                                                # (B_loc/n_pix, 4)
        # reassemble the formula shard's rows (ion chunks are in pixel-shard
        # order, matching the original ion order)
        return jax.lax.all_gather(out_mine, PIXELS_AXIS, axis=0, tiled=True)

    def make(gc_width, n_keep=0, w_cap=0):
        from functools import partial

        sharded = jax.shard_map(
            partial(step, gc_width=gc_width, n_keep=n_keep, w_cap=w_cap),
            mesh=mesh,
            in_specs=(
                P(PIXELS_AXIS, None),             # px_s (S, Nmax)
                P(PIXELS_AXIS, None),             # in_s (S, Nmax)
                P(PIXELS_AXIS, FORMULAS_AXIS),    # pos (S, F*G_loc)
                P(FORMULAS_AXIS),                 # starts (F*C,)
                P(FORMULAS_AXIS, None),           # r_lo_loc (F*C, Wc)
                P(FORMULAS_AXIS, None),           # r_hi_loc (F*C, Wc)
                P(FORMULAS_AXIS),                 # inv (F*W_loc,)
                P(FORMULAS_AXIS, None),           # theor_ints
                P(FORMULAS_AXIS),                 # n_valid
                P(PIXELS_AXIS, FORMULAS_AXIS),    # run_pos (S, F*R_pad)
                P(PIXELS_AXIS, FORMULAS_AXIS),    # run_delta (S, F*R_pad)
                P(PIXELS_AXIS, FORMULAS_AXIS),    # n_b (S, F)
                P(None),                          # n_real (1,) replicated
            ),
            out_specs=P(FORMULAS_AXIS, None),
            # The output IS replicated over "pixels" (tiled all_gather of the
            # per-shard metric rows).  JAX's VMA type system can't infer
            # replication through tiled all_gather (no all_gather_invariant
            # in jax 0.9), so the static check is disabled.
            check_vma=False,
        )
        return jax.jit(sharded)

    return make


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class ShardedJaxBackend:
    """Multi-chip scorer: same interface/semantics as models.msm_jax.JaxBackend,
    data sharded over the ("pixels", "formulas") mesh."""

    name = "jax_tpu"

    def __init__(
        self,
        ds: SpectralDataset,
        ds_config: DSConfig,
        sm_config: SMConfig,
        mesh: Mesh | None = None,
        restrict_table: IsotopePatternTable | None = None,
    ):
        from .distributed import enable_compile_cache

        self.ds = ds
        self.ds_config = ds_config
        enable_compile_cache(sm_config)
        self.mesh = mesh if mesh is not None else make_mesh(sm_config.parallel)
        n_pix_shards = self.mesh.shape[PIXELS_AXIS]
        n_form_shards = self.mesh.shape[FORMULAS_AXIS]
        # shape-bucket lattice (ISSUE 13, ops/buckets.py): the pad-to
        # batch snaps to a lattice point first, then to the mesh granule
        self._buckets = shape_buckets.buckets_enabled(sm_config.parallel)
        from .distributed import compile_cache_path

        shape_buckets.bind_manifest_dir(compile_cache_path(sm_config))
        # Static batch padded so each formula shard's block further splits
        # evenly across the pixel shards (the all_to_all ion sub-batches).
        self.batch = _round_up(
            shape_buckets.effective_batch(sm_config.parallel),
            n_form_shards * n_pix_shards)
        img_cfg = ds_config.image_generation
        self.ppm = img_cfg.ppm
        # HBM guard, per-shard arithmetic (the single-device backend fails
        # early with guidance — msm_jax.py — and an 8-GiB-per-shard scatter
        # scratch OOMs just as opaquely on a mesh; VERDICT r2 weak #3)
        k_est = ds_config.isotope_generation.n_peaks
        b_loc = self.batch // n_form_shards
        p_loc_est = -(-ds.n_pixels // n_pix_shards)
        # same clamped-scratch formula as the single-device guard
        scratch = 4 * (p_loc_est + 1) * max(2 * b_loc * k_est + 1, 4098)
        if scratch > (8 << 30):
            raise ValueError(
                f"per-shard histogram scratch would be ~{scratch / 2**30:.0f}"
                f" GiB ({p_loc_est} pixels/shard x {b_loc} ions/formula-shard"
                f" x {k_est} peaks); reduce parallel.formula_batch, grow the"
                " pixels mesh axis, or add formula shards")

        if self._buckets:
            # per-shard pixel capacity = lattice WHOLE rows (each shard
            # owns complete image rows, so the concatenated padding stays
            # a contiguous tail) and peak slots on the shared lattice.
            # The metric grid is the SAME row bucket the single-device
            # path uses — the step slices its concat down to it — so
            # sharded metrics reduce over the identical padded length and
            # stay BIT-EQUAL to the single-device fused graph, while every
            # dataset size in the bucket shares the step executable
            nrows_b = shape_buckets.row_bucket(ds.nrows)
            r_loc_b = shape_buckets.pow2ish(
                -(-nrows_b // n_pix_shards), 1)
            mz_s, px_s, in_s, self._p_loc = prepare_flat_sharded_arrays(
                ds, self.ppm, n_pix_shards, p_loc=r_loc_b * ds.ncols,
                slot_bucket=shape_buckets.peak_bucket)
            self._nrows_metric = nrows_b
        else:
            mz_s, px_s, in_s, self._p_loc = prepare_flat_sharded_arrays(
                ds, self.ppm, n_pix_shards)
            self._nrows_metric = ds.nrows
        # the dataset's true pixel count, shipped replicated to every
        # device for the masked metric centering (lattice, ISSUE 13)
        self._n_real_host = np.full(1, ds.n_pixels, np.int32)
        if restrict_table is not None:
            mz_s, px_s, in_s = self._restrict_shards(
                mz_s, px_s, in_s, restrict_table)
        # bf16 halves the per-shard HBM rows (expanded to f32 in-graph at
        # the top of the step)
        self._cube_dtype = sm_config.parallel.cube_dtype
        in_s = compact_cube(in_s, self._cube_dtype)
        self._compaction = sm_config.parallel.peak_compaction
        self._band_mode = sm_config.parallel.band_slice
        self._n_keep = 0          # sticky compacted capacity (see JaxBackend)
        self._r_pad = 0           # sticky run-list capacity
        self.int_scale = ds.intensity_quantization(self.ppm)[1]
        flat_sharding = NamedSharding(self.mesh, P(PIXELS_AXIS, None))
        self._mz_shards = mz_s                 # host-side, for bound ranks
        self._px_s = jax.device_put(px_s, flat_sharding)
        self._in_s = jax.device_put(in_s, flat_sharding)
        self._pos_sharding = NamedSharding(
            self.mesh, P(PIXELS_AXIS, FORMULAS_AXIS))
        self._form_sharding = NamedSharding(self.mesh, P(FORMULAS_AXIS, None))
        self._nv_sharding = NamedSharding(self.mesh, P(FORMULAS_AXIS))
        self._rep_sharding = NamedSharding(self.mesh, P(None))
        self._n_form_shards = n_form_shards
        logger.info(
            "jax_tpu sharded flat peaks resident: %s over mesh %s "
            "(pixels=%d, formulas=%d, p_loc=%d)",
            px_s.shape, dict(self.mesh.shape), n_pix_shards, n_form_shards,
            self._p_loc,
        )
        self._make_fn = build_sharded_score_factory(
            self.mesh,
            p_loc=self._p_loc,
            nrows=self._nrows_metric,
            ncols=ds.ncols,
            nlevels=img_cfg.nlevels,
            do_preprocessing=img_cfg.do_preprocessing,
            q=img_cfg.q,
        )
        self._fns: dict[int, object] = {}      # gc_width -> jitted step
        self._gc_width = 0                     # sticky (see JaxBackend)
        # the smallest legal batch: each formula shard's block must still
        # split evenly across the pixel shards (see __init__ padding)
        self._batch_granule = n_form_shards * n_pix_shards

    def shrink_batch(self, batch: int) -> None:
        """HBM-OOM backoff hook (ISSUE 10, models/oom.py) — same contract
        as ``JaxBackend.shrink_batch`` but clamped to the mesh's batch
        granule (formula shards × pixel shards): below that, padding
        cannot shrink and memory relief must come from the mesh geometry
        instead (more pixel shards)."""
        new = max(self._batch_granule,
                  _round_up(max(1, int(batch)), self._batch_granule))
        if new < self.batch:
            logger.warning("sharded jax_tpu backend: formula batch %d -> %d "
                           "(OOM backoff, granule %d)", self.batch, new,
                           self._batch_granule)
            self.batch = new

    def _restrict_shards(self, mz_s, px_s, in_s, table):
        """Drop peaks outside the union of ``table``'s windows from every
        pixel shard's row and re-pad rows to the new common length (exact —
        ops/imager_jax.restrict_flat_to_windows)."""
        from ..ops.imager_jax import restrict_flat_to_windows

        lo_q, hi_q = quantize_window(table.mzs, self.ppm)
        mz_k, px_k, in_k, n_eff = restrict_flat_to_windows(
            mz_s, px_s, in_s, lo_q, hi_q, overflow_row=self._p_loc)
        logger.info(
            "window-union restriction: %d -> %d peaks/shard max",
            mz_s.shape[1], n_eff)
        return mz_k, px_k, in_k

    def _flat_plan(self, table: IsotopePatternTable):
        """Host prep: per-formula-shard bound grids + chunk plans + the
        per-(pixel-shard, formula-shard) bound ranks."""
        n = table.n_ions
        b = self.batch
        if n > b:
            raise ValueError(f"batch of {n} ions exceeds formula_batch={b}")
        k = table.max_peaks
        lo_q, hi_q = quantize_window(table.mzs, self.ppm)
        lo_p = np.zeros((b, k), dtype=np.int32)
        hi_p = np.zeros((b, k), dtype=np.int32)
        ints_p = np.zeros((b, k), dtype=np.float32)
        nv_p = np.zeros(b, dtype=np.int32)
        lo_p[:n], hi_p[:n] = lo_q, hi_q
        ints_p[:n] = table.ints
        nv_p[:n] = table.n_valid
        # Per-formula-shard bound grids: shard f histograms only its windows.
        n_px = self._mz_shards.shape[0]
        poss, starts_l, rlo_l, rhi_l, invs, gc = [], [], [], [], [], 0
        runs_sf: list[list] = [[] for _ in range(n_px)]  # [s][f] run plans
        bands_sf: list[list] = [[] for _ in range(n_px)]  # [s][f] rank bands
        for sl, _grid, rl, rh, pos_rows in self._shard_grids(lo_p, hi_p):
            st, rll, rhl, inv, gcs = window_chunks(rl, rh, _BAND_WINDOWS)
            gc = max(gc, gcs)
            starts_l.append(st)
            rlo_l.append(rll)
            rhi_l.append(rhl)
            invs.append(inv)
            if self._compaction != "off":
                for px in range(n_px):
                    runs_sf[px].append(batch_peak_runs(
                        self._mz_shards[px], lo_p[sl], hi_p[sl],
                        pos_rows[px]))
            if self._band_mode != "off":
                # each (pixel-shard, formula-shard) cell's contiguous rank
                # band of the shard's sorted peaks under THIS formula
                # shard's window union — with an m/z-ordered table the
                # formula shards are m/z sub-ranges of the batch, so cells
                # are even narrower than the whole batch's band
                for px in range(n_px):
                    bands_sf[px].append(batch_peak_band(
                        self._mz_shards[px], lo_p[sl], hi_p[sl]))
            poss.append(np.stack(pos_rows))
        runs = runs_sf if self._compaction != "off" else None
        bands = bands_sf if self._band_mode != "off" else None
        return (np.concatenate(poss, axis=1), np.concatenate(starts_l),
                np.concatenate(rlo_l), np.concatenate(rhi_l),
                np.concatenate(invs), ints_p, nv_p, gc, runs, bands)

    def _shard_grids(self, lo_p: np.ndarray, hi_p: np.ndarray):
        """Per formula shard: (row slice, bound grid, r_lo, r_hi, and each
        pixel shard's bound ranks) — the shared host prep of the score and
        image-export paths (they must stay in lockstep or the bit-identical
        contract breaks)."""
        f = self._n_form_shards
        n_px = self._mz_shards.shape[0]
        b_loc = lo_p.shape[0] // f
        for fi in range(f):
            sl = slice(fi * b_loc, (fi + 1) * b_loc)
            grid, rl, rh = window_rank_grid(lo_p[sl], hi_p[sl])
            pos_rows = [flat_bound_ranks(self._mz_shards[px], grid)
                        for px in range(n_px)]
            yield sl, grid, rl, rh, pos_rows

    def _variant_for(self, runs, bands) -> str:
        """Per-batch MESH-WIDE extraction variant (all devices run one
        program, so the decision keys on the busiest cell): 'band', 'compact'
        or 'plain' — the same measured-rate estimator as
        JaxBackend._variant_for (scatter ~14 ns/slot, packed-run gather ~23
        ns -> compact ~37 ns per capacity slot), on per-device work.  'on'
        modes force a variant for tests, band first.  Capacities are grown
        to a stream fixpoint first (_grow_static_shapes), so decisions are
        order-independent for a planned stream."""
        if self._band_mode == "on" and bands is not None:
            return "band"
        if self._compaction == "on" and runs is not None:
            return "compact"
        n = int(self._px_s.shape[1])
        est = {"plain": 14.0 * n}
        if runs is not None and self._compaction != "off":
            max_keep = max((r[2] for row in runs for r in row), default=1)
            cap_c = max(-(-max(max_keep, 1) // (1 << 16)) * (1 << 16),
                        self._n_keep)
            est["compact"] = 37.0 * min(cap_c, n)
        if bands is not None and self._band_mode != "off":
            cap = self._band_cap(bands)
            if cap < n:
                est["band"] = 14.0 * cap
        return min(est, key=est.get)

    def _band_cap(self, bands) -> int:
        """Static band-slice width for one batch: the bucketed max cell
        width (every cell slices the same static width; narrower cells'
        extra slice peaks land in gap bins with zero membership — exact)."""
        from ..ops.imager_jax import band_bucket

        w = max((b[1] for row in bands for b in row), default=0)
        return min(band_bucket(w), int(self._px_s.shape[1]))

    def _grow_compact_capacity(self, runs) -> None:
        # capacity clamps at the per-shard resident row length: padding
        # slots still gather/scatter, so a 64k rounding floor on a 10k-peak
        # shard would cost MORE than the plain path
        cap = max(1, int(self._px_s.shape[1]))
        rnd = 1 << 16
        max_keep = max((r[2] for row in runs for r in row), default=1)
        max_runs = max((r[0].size for row in runs for r in row), default=1)
        want = min(-(-max(max_keep, 1) // rnd) * rnd, cap)
        self._n_keep = max(self._n_keep, want)
        self._r_pad = max(self._r_pad, -(-max(max_runs, 1) // 4096) * 4096)

    def _pack_runs(self, runs):
        """(run_pos (S, F*R_pad), run_delta (S, F*R_pad), n_b (S, F),
        pos_b (S, F*G_loc)) padded to the sticky capacities."""
        n_px, f = len(runs), len(runs[0])
        rp = np.full((n_px, f * self._r_pad), self._n_keep, np.int32)
        rd = np.zeros((n_px, f * self._r_pad), np.int32)
        nb = np.zeros((n_px, f), np.int32)
        posb = []
        for s in range(n_px):
            row_pos = []
            for fi in range(f):
                run_pos, run_delta, n_b, pos_b = runs[s][fi]
                o = fi * self._r_pad
                rp[s, o : o + run_pos.size] = run_pos
                rd[s, o : o + run_delta.size] = run_delta
                nb[s, fi] = n_b
                row_pos.append(pos_b)
            posb.append(np.concatenate(row_pos))
        return rp, rd, nb, np.stack(posb)

    def _pack_bands(self, bands, pos, w_cap):
        """(w_start (S, F) i32, pos_b (S, F*G_loc) band-space bound ranks).

        Mirrors JaxBackend's band dispatch: each cell's start is clamped so
        the static-width slice stays inside the shard row; bounds outside
        the slice clip to 0/w_cap, exactly how the full plain path treats
        peaks before/after the band (see
        models/msm_jax.py::fused_score_fn_flat_banded_sliced)."""
        n_px, f = len(bands), len(bands[0])
        n = int(self._px_s.shape[1])
        g_loc = pos.shape[1] // f
        ws = np.zeros((n_px, f), np.int32)
        pos_b = np.empty_like(pos)
        for s in range(n_px):
            for fi in range(f):
                b_lo, _w = bands[s][fi]
                start = max(0, min(b_lo, n - w_cap))
                ws[s, fi] = start
                sl = slice(fi * g_loc, (fi + 1) * g_loc)
                pos_b[s, sl] = np.clip(pos[s, sl] - start, 0, w_cap)
        return ws, pos_b.astype(np.int32)

    def _dispatch(self, table: IsotopePatternTable, flat_plan=None):
        """Async: enqueue one padded sharded batch, return (device_out, n)."""
        if flat_plan is None:
            flat_plan = self._flat_plan(table)
        pos, starts, rlo, rhi, inv, ints_p, nv_p, gc, runs, bands = flat_plan
        self._gc_width = max(self._gc_width, gc)
        gc = self._gc_width
        n_px = self._mz_shards.shape[0]
        f = self._n_form_shards
        variant = self._variant_for(runs, bands)
        n_keep = w_cap = 0
        if variant == "compact":
            self._grow_compact_capacity(runs)
            n_keep = self._n_keep
            rp, rd, nb, posb = self._pack_runs(runs)
            pos = posb                 # kept-space bound ranks
        elif variant == "band":
            w_cap = self._band_cap(bands)
            rp, pos = self._pack_bands(bands, pos, w_cap)  # rp = band starts
            rd = np.zeros((n_px, f), np.int32)
            nb = np.zeros((n_px, f), np.int32)
        else:
            rp = np.zeros((n_px, f), np.int32)   # unused dummies, (1,1) blocks
            rd = np.zeros((n_px, f), np.int32)
            nb = np.zeros((n_px, f), np.int32)
        key = (gc, n_keep, w_cap)
        tracing.event("batch_variant", variant=variant, b=int(self.batch))
        if key not in self._fns:
            self._fns[key] = self._make_fn(gc, n_keep, w_cap)
        pos_d = jax.device_put(pos, self._pos_sharding)
        starts_d = jax.device_put(starts, self._nv_sharding)
        rlo_d = jax.device_put(rlo, self._form_sharding)
        rhi_d = jax.device_put(rhi, self._form_sharding)
        inv_d = jax.device_put(inv, self._nv_sharding)
        ints_d = jax.device_put(ints_p, self._form_sharding)
        nv_d = jax.device_put(nv_p, self._nv_sharding)
        rp_d = jax.device_put(rp, self._pos_sharding)
        rd_d = jax.device_put(rd, self._pos_sharding)
        nb_d = jax.device_put(nb, self._pos_sharding)
        nr_d = jax.device_put(self._n_real_host, self._rep_sharding)
        if self._buckets:
            shape_buckets.record_spec(
                self._sharded_spec(variant, key, pos, starts, rlo, inv,
                                   ints_p))
        out = self._fns[key](self._px_s, self._in_s, pos_d, starts_d,
                             rlo_d, rhi_d, inv_d, ints_d, nv_d,
                             rp_d, rd_d, nb_d, nr_d)
        return out, table.n_ions

    def _sharded_spec(self, variant: str, key: tuple, pos, starts, rlo,
                      inv, ints_p) -> dict:
        """BucketSpec of one sharded step executable (ops/buckets.py) —
        recorded for the /debug/compile lattice view AND for the AOT
        primer (service/primer.py), which since ISSUE 14 rebuilds the
        byte-identical mesh-shaped program from it on any host whose
        visible device count covers the mesh.  The spec therefore carries
        the full lease topology (mesh axes, per-shard pixel capacity) and
        every host-plan shape the step's avals depend on — a
        post-quarantine SHRUNKEN mesh records its own spec at first
        dispatch and is warm for every later job of that lease shape."""
        gc, n_keep, w_cap = key
        img = self.ds_config.image_generation
        spec = {
            "kind": "sharded", "variant": variant,
            "nrows": int(self._nrows_metric), "ncols": int(self.ds.ncols),
            "nlevels": int(img.nlevels),
            "do_preprocessing": bool(img.do_preprocessing),
            "q": float(img.q),
            "n_resident": int(self._px_s.shape[1]),
            "b": int(self.batch), "k": int(ints_p.shape[1]),
            "gc_width": int(gc), "n_keep": int(n_keep),
            "r_pad": int(self._r_pad), "w_cap": int(w_cap),
            "g": int(pos.shape[1]), "c": int(starts.shape[0]),
            "wc": int(rlo.shape[1]), "w": int(inv.shape[0]),
            "devices": int(self.mesh.size),
            "mesh_pix": int(self.mesh.shape[PIXELS_AXIS]),
            "mesh_form": int(self.mesh.shape[FORMULAS_AXIS]),
            "p_loc": int(self._p_loc),
        }
        # recorded only when compacted, like JaxBackend._bucket_spec —
        # legacy spec strings stay byte-stable
        if self._cube_dtype != "f32":
            spec["cube_dtype"] = self._cube_dtype
        return spec

    def score_batch(self, table: IsotopePatternTable) -> np.ndarray:
        from ..models.msm_jax import to_numpy_global

        out, n = self._dispatch(table)
        return to_numpy_global(out)[:n].astype(np.float64)

    def extract_ion_images(self, table: IsotopePatternTable) -> np.ndarray:
        """(n_ions, K, n_pix) de-quantized ion images off the DEVICE shards —
        the mesh-path analog of JaxBackend.extract_ion_images, so annotated
        image export needs no CPU re-extraction on multi-chip runs either.

        Collective-free: each device extracts its (formula-shard window
        block x pixel-shard slice); the output is sharded over BOTH mesh
        axes and assembled on host (to_numpy_global).  Bit-identical to the
        numpy extractor via the shared integer grids."""
        from ..models.msm_jax import to_numpy_global
        from ..ops.imager_jax import extract_images_flat

        n, b = table.n_ions, self.batch
        if n > b:
            from ..models.msm_basic import _slice_table

            out = [self.extract_ion_images(_slice_table(table, s, min(s + b, n)))
                   for s in range(0, n, b)]
            return np.concatenate(out)
        k = table.max_peaks
        lo_q, hi_q = quantize_window(table.mzs, self.ppm)
        lo_p = np.zeros((b, k), dtype=np.int32)
        hi_p = np.zeros((b, k), dtype=np.int32)
        lo_p[:n], hi_p[:n] = lo_q, hi_q
        rlo_l, rhi_l, poss = [], [], []
        for _sl, _grid, rl, rh, pos_rows in self._shard_grids(lo_p, hi_p):
            rlo_l.append(rl)
            rhi_l.append(rh)
            poss.append(np.stack(pos_rows))
        p_loc = self._p_loc

        def step(px_s, in_s, pos, rlo, rhi):
            return extract_images_flat(
                px_s[0], expand_cube_jnp(in_s[0]), pos[0], rlo, rhi,
                n_pixels=p_loc)

        if not hasattr(self, "_extract_fn"):
            self._extract_fn = jax.jit(jax.shard_map(
                step,
                mesh=self.mesh,
                in_specs=(
                    P(PIXELS_AXIS, None),             # px_s (S, Nmax)
                    P(PIXELS_AXIS, None),             # in_s (S, Nmax)
                    P(PIXELS_AXIS, FORMULAS_AXIS),    # pos (S, F*G_loc)
                    P(FORMULAS_AXIS),                 # r_lo (F*W_loc,)
                    P(FORMULAS_AXIS),                 # r_hi (F*W_loc,)
                ),
                out_specs=P(FORMULAS_AXIS, PIXELS_AXIS),
                check_vma=False,
            ))
        out = self._extract_fn(
            self._px_s, self._in_s,
            jax.device_put(np.concatenate(poss, axis=1), self._pos_sharding),
            jax.device_put(np.concatenate(rlo_l), self._nv_sharding),
            jax.device_put(np.concatenate(rhi_l), self._nv_sharding))
        # smlint: host-sync-ok[image EXPORT; assembling the both-axes-sharded output on host is the method's product]
        imgs = np.array(
            to_numpy_global(out)).reshape(b, k, -1)[:n, :, : self.ds.n_pixels]
        imgs /= np.float32(self.int_scale)   # exact power-of-two division
        valid = np.arange(k)[None, :] < table.n_valid[:, None]
        imgs[~valid] = 0.0
        return imgs

    def score_batches(self, tables, cancel=None) -> list[np.ndarray]:
        """Pipelined like the single-device backend: every batch enqueued
        (async dispatch + sharded device_put) before any result is synced;
        results fetched concurrently (models/msm_jax.fetch_scored_batches).
        Plans are built up front so the band width (and hence the ONE
        executable) is fixed before the first dispatch.  ``cancel`` is
        checked once before the group enqueues (checkpoint-group grain —
        multi-host collectives must stay in lockstep, so no per-batch
        bail-out mid-pipeline)."""
        from ..models.msm_jax import fetch_scored_batches

        tables = list(tables)
        if cancel is not None:
            cancel.check("score_batches")
        with tracing.span("score_plan", batches=len(tables)):
            plans = [self._flat_plan(t) for t in tables]
            self._grow_static_shapes(plans)
        pending = []
        mesh_ids = [int(d.id) for d in self.mesh.devices.flat]
        for t, plan in zip(tables, plans):
            with tracing.span("score_batch", backend="jax_tpu_sharded",
                              ions=int(t.n_ions), enqueue=True,
                              mesh=dict(self.mesh.shape)):
                pending.append(self._dispatch(t, plan))
        # the device_sync span carries the sub-mesh's chip ids, so a trace
        # shows WHICH chips a sharded group occupied (the PR 5 tracer's
        # per-device view of the pool lease)
        with tracing.span("device_sync", batches=len(pending),
                          devices=mesh_ids):
            out = fetch_scored_batches(pending)
        self._trace_mesh_hbm(mesh_ids)
        return out

    def _trace_mesh_hbm(self, mesh_ids: list[int]) -> None:
        """Per-chip HBM of THIS mesh's devices onto the ambient trace (the
        PR 6 telemetry, scoped to the lease) — no-op on platforms without
        memory stats (CPU)."""
        from ..utils import devicemem

        per = {
            str(s["id"]): s["bytes_in_use"]
            for s in devicemem.device_stats()
            if s["id"] in set(mesh_ids) and s["bytes_in_use"] is not None
        }
        if per:
            tracing.event("mesh_hbm", devices=per)

    def _grow_static_shapes(self, plans) -> None:
        # fixpoint, like JaxBackend._grow_for_stream: growing the compact
        # capacity can flip a batch's variant, so repeat until stable
        # (monotone + bounded -> terminates; 2 passes in practice)
        while True:
            before = (self._gc_width, self._n_keep, self._r_pad)
            for plan in plans:
                self._gc_width = max(self._gc_width, plan[7])
                if self._variant_for(plan[8], plan[9]) == "compact":
                    self._grow_compact_capacity(plan[8])
            if before == (self._gc_width, self._n_keep, self._r_pad):
                return

    def presize(self, tables) -> None:
        """Grow the sticky static shapes to cover ``tables`` without scoring
        (see JaxBackend.presize — avoids mid-search recompiles when the
        orchestrator scores in checkpoint groups)."""
        self._grow_static_shapes([self._flat_plan(t) for t in tables])

    def warmup(self, tables) -> None:
        """Compile every executable variant the stream will use: one
        representative batch per (plain | compaction) kind, pre-sized
        (mirrors JaxBackend.warmup for bench/daemon callers)."""
        from ..models.msm_jax import to_numpy_global

        tables = list(tables)
        plans = [self._flat_plan(t) for t in tables]
        self._grow_static_shapes(plans)
        seen: set[tuple] = set()
        for t, plan in zip(tables, plans):
            variant = self._variant_for(plan[8], plan[9])
            # each band w_cap bucket is its own executable
            bucket = self._band_cap(plan[9]) if variant == "band" else 0
            kind = (variant, bucket)
            if kind not in seen:
                seen.add(kind)
                # reuse the precomputed plan — _flat_plan is the expensive
                # host pass (per-cell searchsorted over the shard peaks)
                to_numpy_global(self._dispatch(t, plan)[0])


def builds_single_device(sm_config: SMConfig, n_devices: int | None) -> bool:
    """Does a job over ``n_devices`` chips (a lease's; ``None``: no pool,
    every device of the runtime) score on the single-device ``JaxBackend``?
    One chip does; so do several under a config mesh that resolves to 1x1.
    ``make_jax_backend``'s rule, also asked BEFORE the lease exists by the
    job that prepares the single-device layout ahead of it
    (engine/search_job.py)."""
    if n_devices is None:
        from .distributed import maybe_initialize_distributed

        # before the first jax.devices(): it latches the runtime
        maybe_initialize_distributed(sm_config.parallel)  # no-op single-process
        n_devices = len(jax.devices())
    if n_devices == 1:
        return True
    pix, form = resolve_axis_sizes(n_devices, sm_config.parallel)
    return pix * form == 1


def make_jax_backend(ds: SpectralDataset, ds_config: DSConfig,
                     sm_config: SMConfig, restrict_table=None,
                     device_indices=None):
    """Pick single-device fused graph or the mesh-sharded variant.

    ``device_indices`` (ISSUE 7): a device-pool lease's chip indices.  A
    1-chip lease gets the single-device fused graph PINNED to that chip
    (so two 1-chip jobs score on distinct chips concurrently); an N-chip
    lease gets the pjit/GSPMD-sharded path over a sub-mesh of exactly
    those chips.  ``None`` keeps the pre-pool behavior: mesh geometry from
    ``SMConfig.parallel`` over all local devices (1x1 mesh -> single
    device, no collectives).

    ``restrict_table``: the search's full ion table — peaks outside the
    union of its windows are dropped from the device arrays (exact)."""
    from .distributed import maybe_initialize_distributed
    from .mesh import lease_devices

    maybe_initialize_distributed(sm_config.parallel)  # no-op single-process
    devices = lease_devices(device_indices)
    # host×chip topology of the lease (ISSUE 11): the pool hands out chip
    # indices host-major, so the sub-mesh can confine cross-host (DCN)
    # traffic to pixel-axis boundaries; `hosts` here is how many host
    # failure domains THIS lease spans, not the whole pool's
    hosts = 1
    pool_hosts = max(1, int(getattr(sm_config.service,
                                    "device_pool_hosts", 1)))
    if devices is not None and device_indices is not None and pool_hosts > 1:
        from ..service.device_pool import resolve_pool_size
        from ..service.health import split_host_ranges
        from .mesh import host_topology

        # explicit per-host ranges (ISSUE 17): ragged pools attribute every
        # chip to its real host instead of skipping topology entirely
        pool_size = resolve_pool_size(sm_config.service)
        hosts = max(1, len(host_topology(
            device_indices, split_host_ranges(pool_size, pool_hosts))))
    if builds_single_device(sm_config, len(devices) if devices else None):
        from ..models.msm_jax import JaxBackend

        return JaxBackend(ds, ds_config, sm_config,
                          restrict_table=restrict_table,
                          device=devices[0] if devices else None)
    mesh = make_mesh(sm_config.parallel, devices=devices, hosts=hosts)
    return ShardedJaxBackend(ds, ds_config, sm_config, mesh=mesh,
                             restrict_table=restrict_table)
