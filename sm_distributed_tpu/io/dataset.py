"""Dataset: ragged spectra -> device-friendly spectral-cube layouts.

Reference: ``sm/engine/dataset.py::Dataset`` [U] (SURVEY.md #5) reads the
converted text dump into an ``RDD[(sp_id, mzs, ints)]``, maps scattered (x,y)
scan coordinates to a dense row-major pixel index (``_define_pixels_order``),
and exposes the sample-area mask.  Here the same responsibilities are
TPU-first: spectra land in a flat CSR layout over the *dense* pixel grid
(empty pixels = empty rows), sorted by m/z within each pixel, plus a
prefix-sum array.  The device layout is built from it in
ops/imager_jax.py (``prepare_flat_sharded_arrays``): per pixel shard, one
flat m/z-sorted peak list; the pixel axis is the sharding axis.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from pathlib import Path
from typing import NamedTuple

import numpy as np

from ..utils import tracing
from .imzml import ImzMLReader


class FlatSortedPeaks(NamedTuple):
    """The single-device resident layout before restriction and lattice
    padding: every peak of the dataset in ascending order of its quantized
    m/z, equal m/z in CSR order (the order a stable sort gives), rounded up
    to 1024 slots (tail: ``MZ_PAD_Q``, the overflow pixel ``n_pixels``,
    intensity 0).  12 B a slot."""

    mz_q: np.ndarray      # (N,) int32 ascending
    pixel: np.ndarray     # (N,) int32
    ints_q: np.ndarray    # (N,) f32 on the integer grid, in that order
    int_scale: float


# Where each lookup of ``SpectralDataset.flat_sorted`` was answered: a miss
# counts at the caller's site (``pre_lease``: SearchJob, before it asks for
# the chip; ``under_lease``: JaxBackend.__init__), a hit as ``cached``.
# Process-wide (scheduler workers share it); the service's metrics
# collector pulls it as sm_backend_prepare_total{site=}.
_FLAT_SORTED_EVENTS = {"pre_lease": 0, "under_lease": 0, "cached": 0}
# Which road measured each dataset's window occupancy, one per intensity
# grid computed (``ops/quantize.window_occupancy``: ``walk`` = shifted
# compares, ``search`` = the binary search past the walk's cap); pulled
# beside the sites as sm_prepare_occupancy_total{route=}.
_OCCUPANCY_EVENTS = {"walk": 0, "search": 0}
_FLAT_SORTED_EVENTS_LOCK = threading.Lock()


def _count(events: dict, label: str) -> None:
    with _FLAT_SORTED_EVENTS_LOCK:
        events[label] += 1


def flat_sorted_events() -> dict:
    with _FLAT_SORTED_EVENTS_LOCK:
        return dict(_FLAT_SORTED_EVENTS)


def occupancy_events() -> dict:
    with _FLAT_SORTED_EVENTS_LOCK:
        return dict(_OCCUPANCY_EVENTS)


@dataclass
class SpectralDataset:
    """Host-side dataset in flat-CSR-over-dense-pixel-grid layout."""

    nrows: int
    ncols: int
    pixel_inds: np.ndarray    # (n_spectra,) i64 — dense row-major pixel index per spectrum
    mask: np.ndarray          # (nrows, ncols) bool — sample-area mask (pixels with spectra)
    mzs_flat: np.ndarray      # (P,) f64 — all peaks, grouped by pixel, m/z-sorted per pixel
    ints_flat: np.ndarray     # (P,) f32
    row_ptr: np.ndarray       # (n_pixels+1,) i64 — CSR offsets over dense pixel grid

    @property
    def n_pixels(self) -> int:
        return self.nrows * self.ncols

    # -- order-free exact intensity grid (ops/quantize.py) ---------------

    def intensity_quantization(self, ppm: float) -> tuple[np.ndarray, float]:
        """(integer-valued f32 intensities, power-of-two scale) for ``ppm``.

        Both backends extract ion images from this shared grid, which makes
        image pixel values bit-identical regardless of summation order,
        backend, or shard count (the exact-FDR-rank requirement).  Cached
        per ppm.
        """
        return self._intensity_quantization(ppm)[:2]

    def _pixel_of_peak(self) -> np.ndarray:
        return np.repeat(
            np.arange(self.n_pixels, dtype=np.int32), self.row_lengths())

    def _intensity_quantization(self, ppm: float, mz_q=None, pixel_of_peak=None):
        """(grid, scale, hmax, occupancy route), cached per ppm.  ``mz_q`` /
        ``pixel_of_peak``: the caller's own ``quantize_mz(mzs_flat)`` /
        ``_pixel_of_peak()``, so a miss does not make them a second time."""
        from ..ops.quantize import intensity_scale, quantize_intensities

        cache = self.__dict__.setdefault("_int_q_cache", {})
        if ppm not in cache:
            if pixel_of_peak is None:
                pixel_of_peak = self._pixel_of_peak()
            scale, hmax, route = intensity_scale(
                self.mzs_flat, self.ints_flat, pixel_of_peak, ppm, mz_q=mz_q)
            _count(_OCCUPANCY_EVENTS, route)
            cache[ppm] = (quantize_intensities(self.ints_flat, scale), scale,
                          hmax, route)
        return cache[ppm]

    # -- the dataset-only half of the jax backend build ------------------

    def flat_sorted_cached(self, ppm: float) -> bool:
        return ppm in self.__dict__.get("_flat_sorted_cache", ())

    def flat_sorted(self, ppm: float, site: str = "under_lease") -> FlatSortedPeaks:
        """The single-device flat layout for ``ppm``: the 1-shard case of
        ``ops/imager_jax.prepare_flat_sharded_arrays`` plus the intensity
        scale, byte for byte, made in a few linear passes and ONE sort of
        values: the key ``mz_q << 32 | index`` orders equal m/z by index,
        which is the stable order, and carries the permutation in its low
        half (``n < 2**32``).  A function of the dataset and ``ppm`` alone,
        so a job computes it BEFORE it asks for the chip
        (``site="pre_lease"``, engine/search_job.py) and
        ``JaxBackend.__init__`` finds it here.  Cached per ppm for the
        dataset's residency, like the intensity grid; a miss computes it in
        place, whoever asks."""
        from ..ops.quantize import MZ_PAD_Q, quantize_mz

        cache = self.__dict__.setdefault("_flat_sorted_cache", {})
        hit = cache.get(ppm)
        if hit is not None:
            _count(_FLAT_SORTED_EVENTS, "cached")
            return hit
        _count(_FLAT_SORTED_EVENTS, site)
        with tracing.span("prepare_quantize"):
            mz_q = quantize_mz(self.mzs_flat)
            pixel = self._pixel_of_peak()
            ints_q, scale, hmax, route = self._intensity_quantization(
                ppm, mz_q, pixel)
            tracing.annotate(hmax=hmax, occupancy=route)
        with tracing.span("prepare_sort", sort="packed"):
            n = int(mz_q.size)
            packed = mz_q.astype(np.int64)
            packed <<= 32
            packed |= np.arange(n, dtype=np.uint32)
            packed.sort()
            n_max = -(-max(n, 1) // 1024) * 1024
            mz_s = np.empty(n_max, dtype=np.int32)
            px_s = np.empty(n_max, dtype=np.int32)
            in_s = np.empty(n_max, dtype=np.float32)
            mz_s[n:], px_s[n:], in_s[n:] = MZ_PAD_Q, self.n_pixels, 0.0
            np.right_shift(packed, 32, out=mz_s[:n], casting="unsafe")
            packed &= 0xFFFFFFFF                  # the permutation
            # every index is in range: "clip" only spares take's copy of out
            np.take(pixel, packed, out=px_s[:n], mode="clip")
            np.take(ints_q, packed, out=in_s[:n], mode="clip")
            for a in (mz_s, px_s, in_s):
                a.setflags(write=False)     # shared by every backend built on it
        cache[ppm] = FlatSortedPeaks(mz_s, px_s, in_s, scale)
        return cache[ppm]

    def resident_bytes(self) -> int:
        """Host bytes this dataset weighs in a residency once a job has
        prepared it at one ppm: the CSR arrays, the intensity grid (4 B a
        peak) and the flat sorted layout (12 B a slot).  Reckoned whether or
        not the layout is made yet: a job makes it right after the lookup
        that admits the dataset (``SearchJob._prepare_resident``)."""
        csr = sum(int(a.nbytes) for a in (
            self.pixel_inds, self.mask, self.mzs_flat, self.ints_flat,
            self.row_ptr))
        slots = -(-max(self.n_peaks, 1) // 1024) * 1024
        return csr + 4 * self.n_peaks + 12 * slots

    @property
    def n_spectra(self) -> int:
        return int(self.pixel_inds.size)

    @property
    def n_peaks(self) -> int:
        return int(self.mzs_flat.size)

    # -- construction ----------------------------------------------------

    @staticmethod
    def _pixel_grid(coords: np.ndarray, n_spectra: int):
        """(nrows, ncols, pixel_inds, mask) from raw scan coordinates.

        Pixel-order normalization mirrors the reference's
        ``_define_pixels_order`` [U]: coordinates are mapped through their
        sorted unique values (robust to offsets and uniform step sizes), and
        the dense pixel index is row-major ``row * ncols + col``.
        """
        coords = np.asarray(coords, dtype=np.int64)
        if coords.ndim != 2 or coords.shape[1] != 2 or coords.shape[0] != n_spectra:
            raise ValueError("coords must be (n_spectra, 2) matching spectra list")
        ux = np.unique(coords[:, 0])
        uy = np.unique(coords[:, 1])
        ncols, nrows = ux.size, uy.size
        col = np.searchsorted(ux, coords[:, 0])
        row = np.searchsorted(uy, coords[:, 1])
        pixel_inds = row * ncols + col
        if np.unique(pixel_inds).size != pixel_inds.size:
            raise ValueError("duplicate scan coordinates map to the same pixel")
        mask = np.zeros(nrows * ncols, dtype=bool)
        mask[pixel_inds] = True
        return nrows, ncols, pixel_inds, mask.reshape(nrows, ncols)

    @staticmethod
    def _row_ptr(n_pixels: int, pixel_inds: np.ndarray, lens: np.ndarray):
        counts = np.zeros(n_pixels, dtype=np.int64)
        counts[pixel_inds] = lens
        row_ptr = np.zeros(n_pixels + 1, dtype=np.int64)
        np.cumsum(counts, out=row_ptr[1:])
        return row_ptr

    @staticmethod
    def _sort_rows_inplace(mzs_flat, ints_flat, row_ptr) -> None:
        """Ensure ascending m/z within each CSR row, touching only rows that
        need it.  Centroided imzML stores m/z ascending in practice, so the
        vectorized violation scan usually finds nothing and this is O(N)
        with no extra copies (vs a full-array lexsort at ~2.5x N bytes)."""
        if mzs_flat.size < 2:
            return
        viol = mzs_flat[1:] < mzs_flat[:-1]
        # a drop across a row boundary is not a violation
        starts = row_ptr[1:-1]
        viol[starts[(starts > 0) & (starts < mzs_flat.size)] - 1] = False
        if not viol.any():
            return
        bad = np.unique(
            np.searchsorted(row_ptr, np.nonzero(viol)[0] + 1, side="right") - 1)
        for r in bad:
            s, e = row_ptr[r], row_ptr[r + 1]
            order = np.argsort(mzs_flat[s:e], kind="stable")
            mzs_flat[s:e] = mzs_flat[s:e][order]
            ints_flat[s:e] = ints_flat[s:e][order]

    @classmethod
    def from_arrays(
        cls,
        coords: np.ndarray,
        spectra: list[tuple[np.ndarray, np.ndarray]],
    ) -> "SpectralDataset":
        """Build from raw (x, y) scan coords + per-spectrum (mzs, ints)."""
        nrows, ncols, pixel_inds, mask = cls._pixel_grid(coords, len(spectra))
        lens = np.fromiter((len(m) for m, _ in spectra), dtype=np.int64,
                           count=len(spectra))
        row_ptr = cls._row_ptr(nrows * ncols, pixel_inds, lens)

        # vectorized flat build: concatenate everything, then ONE lexsort
        # keyed on (pixel, mz) groups peaks by dense pixel and m/z-sorts
        mz_all = (np.concatenate([np.asarray(m, np.float64) for m, _ in spectra])
                  if spectra else np.empty(0, np.float64))
        int_all = (np.concatenate([np.asarray(i, np.float32) for _, i in spectra])
                   if spectra else np.empty(0, np.float32))
        pix_all = np.repeat(pixel_inds, lens)
        order = np.lexsort((mz_all, pix_all))
        mzs_flat = mz_all[order]
        ints_flat = int_all[order]

        return cls(
            nrows=nrows,
            ncols=ncols,
            pixel_inds=pixel_inds,
            mask=mask,
            mzs_flat=mzs_flat,
            ints_flat=ints_flat,
            row_ptr=row_ptr,
        )

    @classmethod
    def from_imzml(cls, path: str | Path) -> "SpectralDataset":
        """BULK ingest: peak host memory stays ~(12 bytes x total peaks)
        plus one bounded read chunk (at most ``io/imzml._IBD_CHUNK_BYTES``
        or an eighth of the data, whichever is less), with no second copy of
        the dataset, instead of the eager build's ~4x.

        The reference streams spectrum-by-spectrum through its converter and
        reader (``imzml_txt_converter``/``dataset_reader`` [U], SURVEY.md
        #4-5); a >200k-pixel DESI slide (BASELINE #5) can exceed host RAM
        under an eager whole-dataset materialization long before HBM matters.
        Here: the reader's index gives every spectrum's peak COUNT and the
        place of its arrays in the ibd without touching it; the exact CSR
        arrays are preallocated from the counts and filled by
        ``ImzMLReader.read_into`` in a few large reads (no intermediate
        list, no concat, no full-array lexsort, no read call a spectrum:
        a thread that gives up the interpreter twice a spectrum queues for
        it behind every other ingest; per-row m/z order is verified and
        repaired only where violated).  Bit-identical to from_arrays."""
        with ImzMLReader(path) as rd:
            lens = rd.spectrum_lengths()
            nrows, ncols, pixel_inds, mask = cls._pixel_grid(
                rd.coordinates, rd.n_spectra)
            row_ptr = cls._row_ptr(nrows * ncols, pixel_inds, lens)
            total = int(lens.sum())
            mzs_flat = np.empty(total, dtype=np.float64)
            ints_flat = np.empty(total, dtype=np.float32)
            rd.read_into(mzs_flat, ints_flat, row_ptr[pixel_inds])
            cls._sort_rows_inplace(mzs_flat, ints_flat, row_ptr)
            return cls(
                nrows=nrows,
                ncols=ncols,
                pixel_inds=pixel_inds,
                mask=mask,
                mzs_flat=mzs_flat,
                ints_flat=ints_flat,
                row_ptr=row_ptr,
            )

    # -- device layouts --------------------------------------------------

    def row_lengths(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    def norm_img_pixel_inds(self) -> np.ndarray:
        """Dense pixel index per spectrum (reference:
        ``Dataset.get_norm_img_pixel_inds`` [U])."""
        return self.pixel_inds

    def get_dims(self) -> tuple[int, int]:
        """(nrows, ncols), as the reference's ``Dataset.get_dims`` [U]."""
        return self.nrows, self.ncols

    def get_sample_area_mask(self) -> np.ndarray:
        return self.mask
