"""imzML parser/writer + dataset-layout tests (reference analogs:
tests/test_imzml_txt_converter_db.py and the Dataset parts of SURVEY.md §4)."""

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.io.imzml import ImzMLParseError, ImzMLReader, ImzMLWriter


def _roundtrip(tmp_path, continuous, mz_dtype=np.float64, int_dtype=np.float32):
    rng = np.random.default_rng(1)
    path = tmp_path / ("c.imzML" if continuous else "p.imzML")
    spectra = []
    shared_mz = np.sort(rng.uniform(100, 500, size=64))
    with ImzMLWriter(path, continuous=continuous, mz_dtype=mz_dtype, int_dtype=int_dtype) as wr:
        for i, (x, y) in enumerate([(1, 1), (2, 1), (1, 2), (2, 2), (3, 1)]):
            if continuous:
                mzs = shared_mz
            else:
                mzs = np.sort(rng.uniform(100, 500, size=32 + i))
            ints = rng.exponential(5.0, size=len(mzs))
            spectra.append((x, y, mzs, ints))
            wr.add_spectrum(x, y, mzs, ints)
    return path, spectra


@pytest.mark.parametrize("continuous", [False, True])
def test_imzml_roundtrip(tmp_path, continuous):
    path, spectra = _roundtrip(tmp_path, continuous)
    with ImzMLReader(path) as rd:
        assert rd.continuous is continuous
        assert rd.n_spectra == len(spectra)
        for i, (x, y, mzs, ints) in enumerate(spectra):
            assert tuple(rd.coordinates[i]) == (x, y)
            got_mz, got_int = rd.read_spectrum(i)
            np.testing.assert_allclose(got_mz, mzs, rtol=0, atol=0)
            np.testing.assert_allclose(got_int, ints.astype(np.float32), rtol=1e-6)


def test_imzml_f32_mz_roundtrip(tmp_path):
    path, spectra = _roundtrip(tmp_path, False, mz_dtype=np.float32)
    with ImzMLReader(path) as rd:
        got_mz, _ = rd.read_spectrum(0)
        assert got_mz.dtype == np.float64  # reader normalizes dtypes
        np.testing.assert_allclose(got_mz, spectra[0][2].astype(np.float32))


def test_imzml_uuid_mismatch_detected(tmp_path):
    path, _ = _roundtrip(tmp_path, False)
    ibd = path.with_suffix(".ibd")
    raw = bytearray(ibd.read_bytes())
    raw[3] ^= 0xFF
    ibd.write_bytes(bytes(raw))
    with pytest.raises(ImzMLParseError, match="UUID"):
        ImzMLReader(path)


def test_imzml_truncated_ibd(tmp_path):
    path, _ = _roundtrip(tmp_path, False)
    ibd = path.with_suffix(".ibd")
    ibd.write_bytes(ibd.read_bytes()[:40])
    rd = ImzMLReader(path)
    with pytest.raises(ImzMLParseError, match="truncated"):
        rd.read_spectrum(4)


def test_dataset_pixel_grid():
    # scattered coords with an offset and a missing pixel (2,2)
    coords = np.array([[10, 5], [11, 5], [12, 5], [10, 6], [11, 6], [10, 7], [12, 7]])
    spectra = [
        (np.array([100.0, 200.0]), np.array([1.0, 2.0])),
        (np.array([150.0]), np.array([3.0])),
        (np.array([], dtype=float), np.array([], dtype=float)),
        (np.array([120.0, 130.0, 140.0]), np.array([1.0, 1.0, 1.0])),
        (np.array([100.0]), np.array([5.0])),
        (np.array([300.0]), np.array([7.0])),
        (np.array([400.0]), np.array([8.0])),
    ]
    ds = SpectralDataset.from_arrays(coords, spectra)
    assert ds.get_dims() == (3, 3)
    assert ds.n_spectra == 7
    assert ds.n_peaks == 9
    mask = ds.get_sample_area_mask()
    assert mask.sum() == 7
    assert not mask[2, 1]  # (x=11,y=7) missing
    # CSR rows align with dense pixel order; (x=10,y=5) -> pixel 0
    s, e = ds.row_ptr[0], ds.row_ptr[1]
    np.testing.assert_array_equal(ds.mzs_flat[s:e], [100.0, 200.0])
    # m/z sorted within every pixel
    for p in range(ds.n_pixels):
        row = ds.mzs_flat[ds.row_ptr[p]:ds.row_ptr[p + 1]]
        assert np.all(np.diff(row) >= 0)


def test_dataset_unsorted_spectrum_gets_sorted():
    coords = np.array([[1, 1]])
    spectra = [(np.array([300.0, 100.0, 200.0]), np.array([3.0, 1.0, 2.0]))]
    ds = SpectralDataset.from_arrays(coords, spectra)
    np.testing.assert_array_equal(ds.mzs_flat, [100.0, 200.0, 300.0])
    np.testing.assert_array_equal(ds.ints_flat, [1.0, 2.0, 3.0])


def test_synthetic_dataset_end_to_end(tmp_path):
    path, truth = generate_synthetic_dataset(
        tmp_path, nrows=8, ncols=8, formulas=["C6H12O6", "C5H5N5", "C27H46O", "C3H4O3"],
        present_fraction=0.5, noise_peaks=30,
    )
    assert len(truth.present) == 2
    ds = SpectralDataset.from_imzml(path)
    assert ds.get_dims() == (8, 8)
    assert ds.n_spectra == 64
    assert ds.get_sample_area_mask().all()
    # present-ion principal peaks must be findable within +-1 ppm somewhere
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    for sf in truth.present:
        mz0 = calc.isotope_peaks(sf, "+H")[0][0]
        lo = np.searchsorted(np.sort(ds.mzs_flat), mz0 * (1 - 2e-6))
        hi = np.searchsorted(np.sort(ds.mzs_flat), mz0 * (1 + 2e-6))
        assert hi - lo > 10, f"{sf} signal missing from dataset"


def test_streaming_ingest_bit_identical_and_bounded(tmp_path):
    """from_imzml streams spectra into preallocated CSR arrays (VERDICT r2
    item 5): bits identical to the eager from_arrays build, per-spectrum
    lengths come from XML metadata without touching the ibd, and peak
    working memory stays near the final array size (vs ~4x for the eager
    concat+lexsort build)."""
    import tracemalloc

    rng = np.random.default_rng(9)
    path = tmp_path / "s.imzML"
    spectra, coords = [], []
    with ImzMLWriter(path, continuous=False) as wr:
        for i in range(60):                   # many spectra, ragged lengths
            x, y = i % 10 + 1, i // 10 + 1
            mzs = np.sort(rng.uniform(100, 900, size=200 + (i * 37) % 300))
            ints = rng.exponential(5.0, size=len(mzs))
            if i == 17:                       # one out-of-order spectrum
                mzs = mzs[::-1].copy()
            wr.add_spectrum(x, y, mzs, ints)
            spectra.append((mzs, ints))
            coords.append((x, y))

    with ImzMLReader(path) as rd:
        lens = rd.spectrum_lengths()
        np.testing.assert_array_equal(
            lens, [len(m) for m, _ in spectra])

    eager = SpectralDataset.from_arrays(
        np.array(coords), [(m.astype(np.float64), i.astype(np.float32))
                           for m, i in spectra])
    tracemalloc.start()
    streamed = SpectralDataset.from_imzml(path)
    _cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    np.testing.assert_array_equal(streamed.mzs_flat, eager.mzs_flat)
    np.testing.assert_array_equal(streamed.ints_flat, eager.ints_flat)
    np.testing.assert_array_equal(streamed.row_ptr, eager.row_ptr)
    np.testing.assert_array_equal(streamed.pixel_inds, eager.pixel_inds)
    np.testing.assert_array_equal(streamed.mask, eager.mask)
    assert np.all(np.diff(streamed.mzs_flat[
        streamed.row_ptr[0]:streamed.row_ptr[1]]) >= 0)

    # bounded: peak tracked memory ~ the two final arrays (+1 small
    # violation mask), far from the eager path's transient ~4x
    final_bytes = streamed.mzs_flat.nbytes + streamed.ints_flat.nbytes
    assert peak < 2.2 * final_bytes, (peak, final_bytes)
