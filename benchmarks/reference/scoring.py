"""The plain reference of the annotation semantics: imzML in, per-ion
(chaos, spatial, spectral, msm) and FDR levels out.  numpy + scipy only; it
imports nothing of the program and reads nothing the program has made except
the list of (formula, adduct, is_target) ions a job scored — the decoy adducts
are a seeded random sample by design, as served tokens are for a model.

The semantics are the engine's documented ones (Palmer et al. 2017; the
shared grids of ``ops/quantize.py``), copied at PR 23 from ``ops/quantize.py``,
``ops/imager_np.py``, ``ops/metrics_np.py`` and ``ops/fdr.py``:

- m/z and the ppm window bounds are matched on an int32 grid of 1e-5 Da,
  window = [mz(1-ppm*1e-6), mz(1+ppm*1e-6));
- intensities are snapped to an integer grid (a power-of-two scale chosen so
  every per-pixel window sum stays below 2**24), so an ion image is the same
  bits in any summation order;
- chaos uses an f32 threshold grid and 4-connectivity; spatial is the
  intensity-weighted mean Pearson correlation against the principal image;
  spectral is the cosine against the theoretical envelope; msm their product;
- FDR: q-value of a target = (#decoys >= t / decoys per target) / #targets
  >= t, ties counting the decoy first, monotonised, snapped to the levels.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np
from scipy import ndimage

from .isocalc import isotope_peaks

MZ_SCALE = 1e5
MZ_PAD_Q = np.int32(2**31 - 1)
INT_SUM_BITS = 24
FDR_LEVELS = (0.05, 0.1, 0.2, 0.5)
_STRUCTURE4 = np.array([[0, 1, 0], [1, 1, 1], [0, 1, 0]], dtype=int)


# ------------------------------------------------------------------ imzML
_SPECTRUM = re.compile(
    rb'accession="IMS:1000050"[^>]*value="(\d+)".*?'
    rb'accession="IMS:1000051"[^>]*value="(\d+)".*?'
    rb'accession="IMS:1000102"[^>]*value="(\d+)".*?'
    rb'accession="IMS:1000103"[^>]*value="(\d+)".*?'
    rb'accession="IMS:1000102"[^>]*value="(\d+)".*?'
    rb'accession="IMS:1000103"[^>]*value="(\d+)"', re.S)


def read_imzml(path: Path):
    """Processed-mode imzML with f64 m/z and f32 intensity arrays (what the
    benchmark's generator writes; anything else is an error).  Returns
    (nrows, ncols, pixel_of_peak i64, mzs f64, ints f32), peaks grouped by
    dense row-major pixel."""
    xml = Path(path).read_bytes()
    if b"IMS:1000031" not in xml or b"MS:1000523" not in xml \
            or b"MS:1000521" not in xml:
        raise ValueError(f"{path}: not processed-mode f64/f32 imzML")
    rows = np.array([[int(g) for g in m.groups()] for m in
                     (_SPECTRUM.search(s) for s in xml.split(b"<spectrum ")[1:])
                     ], dtype=np.int64)
    xs, ys, mz_off, mz_len, int_off, int_len = rows.T
    if not np.array_equal(mz_len, int_len):
        raise ValueError(f"{path}: m/z and intensity lengths differ")
    xs, ys = xs - xs.min(), ys - ys.min()
    nrows, ncols = int(ys.max()) + 1, int(xs.max()) + 1
    ibd = np.fromfile(Path(path).with_suffix(".ibd"), dtype=np.uint8)
    pix = ys * ncols + xs
    mzs = [ibd[o:o + 8 * n].view("<f8") for o, n in zip(mz_off, mz_len)]
    ints = [ibd[o:o + 4 * n].view("<f4") for o, n in zip(int_off, int_len)]
    pixel_of_peak = np.repeat(pix, mz_len)
    mzs, ints = np.concatenate(mzs), np.concatenate(ints)
    order = np.lexsort((mzs, pixel_of_peak))
    return nrows, ncols, pixel_of_peak[order], mzs[order], ints[order]


# ------------------------------------------------------------- the grids
def quantize_mz(mz) -> np.ndarray:
    q = np.rint(np.asarray(mz, dtype=np.float64) * MZ_SCALE)
    return np.where(q >= MZ_PAD_Q, MZ_PAD_Q, q).astype(np.int32)


def intensity_scale(mzs, ints, pixel_of_peak, ppm: float) -> float:
    """Power-of-two 2**k with hmax * max(rint(i * 2**k)) < 2**24, hmax the
    most peaks of one pixel inside any ppm window."""
    max_raw = float(np.max(ints)) if ints.size else 0.0
    if max_raw <= 0:
        return 1.0
    key = pixel_of_peak.astype(np.int64) * (1 << 32) + quantize_mz(mzs)
    width = np.ceil(np.asarray(mzs, np.float64)
                    * (2.5 * ppm * 1e-6) * MZ_SCALE).astype(np.int64)
    hi = np.searchsorted(key, key + width, side="right")
    hmax = int(np.max(hi - np.arange(key.size)))
    target = (2**INT_SUM_BITS - 1) / (max(hmax, 1) + 1) / max_raw
    return float(2.0 ** np.floor(np.log2(target)))


class Dataset:
    """All peaks of one dataset, globally sorted on the quantized m/z grid."""

    def __init__(self, path: Path, ppm: float):
        self.nrows, self.ncols, pix, mzs, ints = read_imzml(path)
        self.n_pixels = self.nrows * self.ncols
        self.n_peaks = int(mzs.size)
        self.ppm = ppm
        self.scale = intensity_scale(mzs, ints, pix, ppm)
        ints_q = np.rint(ints.astype(np.float64) * self.scale).astype(
            np.float32)
        mz_q = quantize_mz(mzs)
        order = np.argsort(mz_q, kind="stable")
        self.mz_q, self.ints_q, self.pix = mz_q[order], ints_q[order], \
            pix[order]

    def ion_images(self, mzs: np.ndarray) -> np.ndarray:
        """(len(mzs), n_pixels) f32 images of the ppm windows at ``mzs``."""
        lo = quantize_mz(mzs * (1.0 - self.ppm * 1e-6))
        hi = quantize_mz(mzs * (1.0 + self.ppm * 1e-6))
        start = np.searchsorted(self.mz_q, lo, side="left")
        end = np.searchsorted(self.mz_q, hi, side="left")
        out = np.zeros((len(mzs), self.n_pixels), dtype=np.float32)
        for k, (s, e) in enumerate(zip(start, end)):
            if e > s:
                out[k] = np.bincount(self.pix[s:e], weights=self.ints_q[s:e],
                                     minlength=self.n_pixels)
        return out / np.float32(self.scale)


# --------------------------------------------------------------- metrics
def measure_of_chaos(img: np.ndarray, nlevels: int) -> float:
    img = np.where(img > 0, img, np.float32(0.0)).astype(np.float32)
    vmax = np.float32(img.max())
    n_notnull = int((img > 0).sum())
    if vmax <= 0 or n_notnull == 0:
        return 0.0
    count_sum = 0
    for i in range(nlevels):
        lev = vmax * (np.float32(i) / np.float32(nlevels))
        count_sum += ndimage.label(img > lev, structure=_STRUCTURE4)[1]
    chaos = np.float32(1.0) - np.float32(count_sum) / np.float32(
        nlevels * n_notnull)
    return float(np.clip(chaos, np.float32(0.0), np.float32(1.0)))


def image_correlation(imgs: np.ndarray, weights: np.ndarray) -> float:
    if imgs.shape[0] < 2:
        return 0.0
    cent = imgs - imgs.mean(axis=1, keepdims=True)
    norms = np.sqrt((cent * cent).sum(axis=1))
    corrs = np.zeros(imgs.shape[0] - 1)
    for k in range(1, imgs.shape[0]):
        if norms[0] > 0 and norms[k] > 0:
            corrs[k - 1] = (cent[0] * cent[k]).sum() / (norms[0] * norms[k])
    wsum = weights.sum()
    if wsum <= 0:
        return 0.0
    return float(np.clip((corrs * weights).sum() / wsum, 0.0, 1.0))


def pattern_match(obs: np.ndarray, theor: np.ndarray) -> float:
    on, tn = np.linalg.norm(obs), np.linalg.norm(theor)
    if on == 0 or tn == 0:
        return 0.0
    return float(np.clip(np.dot(obs, theor) / (on * tn), 0.0, 1.0))


def score_ions(ds: Dataset, ions, iso: dict, nlevels: int) -> np.ndarray:
    """(len(ions), 4) f64 of (chaos, spatial, spectral, msm) for
    ``ions`` = [(sf, adduct), ...]."""
    out = np.zeros((len(ions), 4))
    for i, (sf, adduct) in enumerate(ions):
        peaks = isotope_peaks(sf, adduct, iso["charge"], iso["isocalc_sigma"],
                              iso["isocalc_pts_per_mz"], iso["n_peaks"])
        if peaks is None:
            continue
        mzs, theor = peaks
        imgs = ds.ion_images(mzs).astype(np.float64)
        if imgs[0].max() <= 0:
            continue
        chaos = measure_of_chaos(imgs[0].reshape(ds.nrows, ds.ncols), nlevels)
        spatial = image_correlation(imgs, theor[1:])
        spectral = pattern_match(imgs.sum(axis=1), theor)
        out[i] = chaos, spatial, spectral, chaos * spatial * spectral
    return out


# ------------------------------------------------------------------- FDR
def fdr_levels(target_msm: np.ndarray, decoy_msm: np.ndarray,
               decoys_per_target: int) -> np.ndarray:
    """FDR level per target (1.0 where none passes)."""
    n_t = target_msm.size
    scores = np.concatenate([target_msm, decoy_msm]).astype(np.float64)
    is_target = np.arange(scores.size) < n_t
    order = np.lexsort((is_target, -scores))
    s_target = is_target[order]
    cum_t, cum_d = np.cumsum(s_target), np.cumsum(~s_target)
    fdr = (cum_d / decoys_per_target) / np.maximum(cum_t, 1)
    q = np.minimum.accumulate(fdr[::-1])[::-1]
    out = np.empty(n_t)
    out[order[s_target]] = q[s_target]
    return np.select([out <= lv for lv in FDR_LEVELS], FDR_LEVELS,
                     default=1.0)
