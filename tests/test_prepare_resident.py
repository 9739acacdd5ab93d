"""The dataset-only half of the jax backend build runs before the job asks
for the chip (ISSUE 29): ``SpectralDataset.flat_sorted`` is the sharded
builder's one-shard layout byte for byte, ``SearchJob`` makes it inside
``pre_lease`` (span ``prepare_resident``) exactly when it will build the
single-device ``JaxBackend``, ``JaxBackend.__init__`` finds it there
(``backend_build {prepared: true}``), and nothing a job stores depends on
which side of the lease computed it.  Since ISSUE 36 the layout is made in a
few linear passes (window occupancy by shifted compares, m/z order by one
packed-key sort): section (h) holds it to the binary search and the stable
``argsort`` it replaced, which live on here and not in the package.
"""

import hashlib
import threading
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from sm_distributed_tpu.engine.residency import DatasetResidency
from sm_distributed_tpu.engine.search_job import SearchJob
from sm_distributed_tpu.engine.storage import JobLedger, read_result_tables
from sm_distributed_tpu.engine.stream import (
    ChunkLog,
    StreamSearchJob,
    stream_root,
)
from sm_distributed_tpu.io.dataset import (
    SpectralDataset,
    flat_sorted_events,
    occupancy_events,
)
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.io.imzml import ImzMLReader
from sm_distributed_tpu.models import breaker as breaker_mod
from sm_distributed_tpu.ops.imager_jax import prepare_flat_sharded_arrays
from sm_distributed_tpu.ops.quantize import (
    INT_SUM_BITS,
    MZ_MAX,
    MZ_PAD_Q,
    MZ_SCALE,
    OCCUPANCY_WALK_CAP,
    intensity_scale,
    quantize_mz,
)
from sm_distributed_tpu.service.device_pool import DevicePool
from sm_distributed_tpu.utils import tracing
from sm_distributed_tpu.utils.cancel import CancelToken, JobCancelledError
from sm_distributed_tpu.utils.config import DSConfig, SMConfig

PPM = 3.0
DS_CONFIG = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                                "image_generation": {"ppm": PPM}})


@pytest.fixture(scope="module")
def fixture_path(tmp_path_factory):
    out = tmp_path_factory.mktemp("ds_prepare")
    return generate_synthetic_dataset(
        out, nrows=8, ncols=8, formulas=None, present_fraction=0.5,
        noise_peaks=40, seed=29)


def _sm(tmp_path, name, backend="jax_tpu", **over):
    return SMConfig.from_dict({
        "backend": backend,
        "fdr": {"decoy_sample_size": 3, "seed": 2},
        "storage": {"results_dir": str(tmp_path / name / "res")},
        "work_dir": str(tmp_path / name / "work"),
        # a 1x1 config mesh: with no pool the job builds the single-device
        # backend whatever the host's device count
        "parallel": {"formula_batch": 32, "pixels_axis": 1,
                     "formulas_axis": 1, "overlap_isocalc": "off"},
        **over})


def _traced(job, tmp_path, name):
    """Run ``job`` under a root trace; its span / event records."""
    ctx = tracing.new_trace(job_id=name, trace_dir=tmp_path / name / "traces")
    with tracing.attach(ctx):
        job.run()
    tracing.close_file(ctx.file)
    return tracing.read_trace(ctx.file)


def _spans(records, name):
    return [r for r in records if r["kind"] == "span" and r["name"] == name]


def _stored_tables(sm, ds_id):
    """annotations, all metrics and the decoy assignment they were ranked
    by (``RESULT_TABLES``)."""
    tables = read_result_tables(Path(sm.storage.results_dir) / ds_id)
    assert len(tables) == 3 and len(tables[2]) > 0
    return tables


# ---------------------------------------------- (a) the product, byte for byte
def _ragged(kind):
    """Scan coordinates + spectra with empty pixels, pixels off the scan
    list, and peaks that share one quantized m/z inside and across pixels."""
    rng = np.random.default_rng(7)
    pool = np.round(rng.uniform(100.0, 900.0, 60), 4)
    coords, spectra = [], []
    for y in range(7):
        for x in range(9):
            if (x + y) % 5 == 0:
                continue                          # never scanned
            n = 0 if kind == "no_peaks" or (x * y) % 7 == 3 \
                else int(rng.integers(1, 40))
            mz = np.sort(rng.choice(pool, n))     # duplicates within a pixel
            coords.append((x, y))
            spectra.append((mz, rng.uniform(1.0, 1e4, n).astype(np.float32)))
    if kind == "one_bucket":
        # exactly 1024 peaks: the layout has no padding slot at all
        have = sum(len(m) for m, _ in spectra)
        mz = np.sort(rng.choice(pool, 1024 - have + len(spectra[0][0])))
        spectra[0] = (mz, rng.uniform(1.0, 1e4, mz.size).astype(np.float32))
    return np.array(coords), spectra


@pytest.mark.parametrize("kind", ["ragged", "no_peaks", "one_bucket"])
def test_flat_sorted_is_the_sharded_builders_one_shard_byte_for_byte(kind):
    coords, spectra = _ragged(kind)
    # the parent's path, untouched: the sharded builder at one shard
    ref_ds = SpectralDataset.from_arrays(coords, spectra)
    mz_r, px_r, in_r, _p_loc = prepare_flat_sharded_arrays(
        ref_ds, PPM, n_shards=1)
    ds = SpectralDataset.from_arrays(coords, spectra)
    if kind == "ragged":
        assert (np.diff(ds.row_ptr) == 0).any()             # empty pixels
        q = quantize_mz(ds.mzs_flat)
        assert np.unique(q).size < q.size                   # duplicate mz_q
    if kind == "one_bucket":
        assert ds.n_peaks == 1024
    before = flat_sorted_events()
    got = ds.flat_sorted(PPM)
    for want, have in zip((mz_r[0], px_r[0], in_r[0]), got[:3]):
        assert have.dtype == want.dtype and have.shape == want.shape
        assert have.tobytes() == want.tobytes()
    assert got.mz_q.size == -(-max(ds.n_peaks, 1) // 1024) * 1024
    assert got.int_scale == ref_ds.intensity_quantization(PPM)[1]
    # the intensity grid the numpy backend reads is the same one
    assert ds.intensity_quantization(PPM)[0].tobytes() == \
        ref_ds.intensity_quantization(PPM)[0].tobytes()
    # cached per ppm: the second lookup is the same object, counted as such
    assert ds.flat_sorted(PPM) is got
    assert ds.flat_sorted(PPM, site="pre_lease") is got
    after = flat_sorted_events()
    assert after["under_lease"] == before["under_lease"] + 1
    assert after["cached"] == before["cached"] + 2    # whatever site asks
    assert after["pre_lease"] == before["pre_lease"]
    assert not ds.flat_sorted_cached(1.0) and ds.flat_sorted_cached(PPM)
    with pytest.raises(ValueError):
        got.mz_q[0] = 0            # shared by every backend built on it


# ------------------------- (b) what a job stores does not depend on the site
class _ClearedBeforeTheLease(SearchJob):
    """Prepares like any job, then loses the product before ``device_hold``:
    the backend build computes it again under the lease, as the parent did."""

    def _prepare_resident(self, ds):
        super()._prepare_resident(ds)
        ds.__dict__.pop("_flat_sorted_cache")


def test_stored_bytes_do_not_depend_on_where_the_layout_was_made(
        fixture_path, tmp_path):
    path, truth = fixture_path
    stored, prepared = {}, {}
    for name, cls in (("pre", SearchJob), ("under", _ClearedBeforeTheLease)):
        sm = _sm(tmp_path, name, storage={
            "results_dir": str(tmp_path / name / "res"),
            "store_images": True})
        before = flat_sorted_events()
        records = _traced(cls("ds", name, path, DS_CONFIG, sm,
                              formulas=truth.formulas), tmp_path, name)
        after = flat_sorted_events()
        (build,) = _spans(records, "backend_build")
        prepared[name] = build["attrs"]["prepared"]
        assert after["pre_lease"] == before["pre_lease"] + 1
        assert after["under_lease"] - before["under_lease"] == \
            (name == "under")
        stored[name] = (
            (Path(sm.storage.results_dir) / "ds" / "ion_images.npz")
            .read_bytes(), _stored_tables(sm, "ds"))
    assert prepared == {"pre": True, "under": False}
    assert len(stored["pre"][0]) > 1000
    assert hashlib.sha256(stored["pre"][0]).hexdigest() == \
        hashlib.sha256(stored["under"][0]).hexdigest()
    for got, want in zip(stored["pre"][1], stored["under"][1]):
        pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert len(stored["pre"][1][0]) > 0


# --------------------- (c) (g) a resident dataset is not prepared a second time
def test_resident_dataset_is_a_lookup_and_the_trace_orders_the_spans(
        fixture_path, tmp_path):
    path, truth = fixture_path
    sm = _sm(tmp_path, "res")
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    traces, counts = [], [flat_sorted_events()]
    for i in range(2):
        job = SearchJob("resident", "r", path, DS_CONFIG, sm,
                        formulas=truth.formulas, residency=residency)
        traces.append(_traced(job, tmp_path, f"res{i}"))
        counts.append(flat_sorted_events())
    assert residency.stats["dataset_hits"] == 1
    assert residency.stats["backend_hits"] == 1
    first, second = traces
    # the upload: computed in pre_lease, split by its two children, found by
    # the build; the quantization and the sort end before the lease is asked for
    (prep,) = _spans(first, "prepare_resident")
    (pre,) = _spans(first, "pre_lease")
    (hold,) = _spans(first, "device_hold")
    (build,) = _spans(first, "backend_build")
    (read,) = _spans(first, "read_dataset")
    (sort,) = _spans(first, "build_sort")
    # beside its own two attrs, what the residency held after the lookup
    assert prep["attrs"] == {
        "peaks": prep["attrs"]["peaks"], "cached": False,
        "residency_entries": 1, "residency_evicted": 0,
        "residency_bytes": prep["attrs"]["residency_bytes"]}
    assert prep["attrs"]["peaks"] > 0
    assert prep["attrs"]["residency_bytes"] > 12 * prep["attrs"]["peaks"]
    assert prep["parent_id"] == pre["span_id"] == read["parent_id"]
    assert read["ts"] + read["dur"] <= prep["ts"] + 1e-3
    assert prep["ts"] + prep["dur"] <= hold["ts"] + 1e-3
    for child in ("prepare_quantize", "prepare_sort"):
        (c,) = _spans(first, child)
        assert c["parent_id"] == prep["span_id"]
        assert c["ts"] + c["dur"] <= hold["ts"] + 1e-3
    assert build["attrs"]["prepared"] is True
    assert build["attrs"]["cache_hit"] is False
    assert sort["parent_id"] != prep["span_id"] and sort["dur"] < prep["dur"]
    assert counts[1]["pre_lease"] == counts[0]["pre_lease"] + 1
    assert counts[1]["under_lease"] == counts[0]["under_lease"]
    # the re-annotation: a dict lookup, no numpy, no counter at the site
    (prep2,) = _spans(second, "prepare_resident")
    assert prep2["attrs"]["cached"] is True
    assert not _spans(second, "prepare_quantize")
    assert not _spans(second, "prepare_sort")
    assert counts[2]["pre_lease"] == counts[1]["pre_lease"]
    assert counts[2]["under_lease"] == counts[1]["under_lease"]
    assert counts[2]["cached"] == counts[1]["cached"] + 1
    (build2,) = _spans(second, "backend_build")
    assert build2["attrs"]["cache_hit"] is True
    assert "prepared" not in build2["attrs"]


# ----------------------- (d) a cancel after the prepare never takes the chip
class _CancelAt(CancelToken):
    def __init__(self, phase):
        super().__init__()
        self.at, self.phases = phase, []

    def check(self, phase=""):
        self.phases.append(phase)
        if phase == self.at:
            self.cancel("user")
        super().check(phase)


class _CountingLock:
    def __init__(self):
        self._lock, self.acquired = threading.Lock(), 0

    def acquire(self, *a, **k):
        got = self._lock.acquire(*a, **k)
        self.acquired += bool(got)
        return got

    def release(self):
        self._lock.release()


def test_cancel_between_the_prepare_and_the_lease_stores_nothing(
        fixture_path, tmp_path):
    path, truth = fixture_path
    sm = _sm(tmp_path, "cancel")
    token, cancel = _CountingLock(), _CancelAt("prepare_resident")
    job = SearchJob("cancelled", "c", path, DS_CONFIG, sm,
                    formulas=truth.formulas, device_token=token,
                    cancel=cancel)
    before = flat_sorted_events()
    with pytest.raises(JobCancelledError):
        job.run()
    assert flat_sorted_events()["pre_lease"] == before["pre_lease"] + 1
    assert cancel.phases[-1] == "prepare_resident"
    assert cancel.phases.index("read_dataset") < \
        cancel.phases.index("prepare_resident")
    assert token.acquired == 0
    root = Path(sm.storage.results_dir) / "cancelled"
    assert not list(root.glob("*.parquet")) and not list(root.glob("*.npz"))
    assert list(JobLedger(sm.storage.results_dir).jobs("cancelled").status) \
        == ["FAILED"]


# ------------------------------------- (e) the jobs that must not prepare
@pytest.mark.parametrize("case", ["numpy_ref", "breaker_open", "lease_of_two"])
def test_jobs_that_build_no_single_device_backend_do_not_prepare(
        fixture_path, tmp_path, monkeypatch, case):
    path, truth = fixture_path
    over, token = {}, None
    if case == "breaker_open":
        over["service"] = {"breaker_threshold": 1, "breaker_cooldown_s": 600.0}
        brk = breaker_mod.get_device_breaker(
            SMConfig.from_dict(over).service)
        assert brk.record_failure() and brk.state == "open"
        assert breaker_mod.every_chip_refuses() is True
    if case == "lease_of_two":
        # the shipped mesh rule: every leased chip on the pixels axis
        token = DevicePool(2).lease(2, "two")
        over["parallel"] = {"formula_batch": 32, "overlap_isocalc": "off"}
    sm = _sm(tmp_path, case,
             backend="numpy_ref" if case == "numpy_ref" else "jax_tpu", **over)
    calls = []
    real = SpectralDataset.flat_sorted
    monkeypatch.setattr(
        SpectralDataset, "flat_sorted",
        lambda self, ppm, site="under_lease": calls.append(site)
        or real(self, ppm, site))
    job = SearchJob(case, case, path, DS_CONFIG, sm,
                    formulas=truth.formulas[:6], device_token=token)
    records = _traced(job, tmp_path, case)
    assert calls == []
    assert not _spans(records, "prepare_resident")
    assert list(JobLedger(sm.storage.results_dir).jobs(case).status) \
        == ["FINISHED"]
    if case == "breaker_open":
        # degraded from the start, and the peek admitted no probe
        assert brk.state == "open"
        assert not _spans(records, "backend_build")
    if case == "lease_of_two":
        (acq,) = [r for r in records if r["name"] == "device_token_acquired"]
        assert acq["attrs"]["devices"] == [0, 1]


def test_breaker_peek_reads_the_pool_chip_by_chip():
    cfg = SMConfig.from_dict(
        {"service": {"breaker_threshold": 1, "breaker_cooldown_s": 600.0}})
    assert breaker_mod.every_chip_refuses(range(2)) is False
    one = breaker_mod.get_device_breaker(cfg.service, devices=(0,))
    assert one.record_failure()
    # chip 1 could still be granted: the prepare is worth making
    assert breaker_mod.every_chip_refuses(range(2)) is False
    assert breaker_mod.every_chip_refuses(range(1)) is True
    breaker_mod.get_device_breaker(cfg.service, devices=(1,)).record_failure()
    assert breaker_mod.every_chip_refuses(range(2)) is True
    # past its cooldown a breaker admits a probe: nothing refuses any more
    breaker_mod.breaker_for("0").cooldown_s = 0.0
    assert breaker_mod.every_chip_refuses(range(2)) is False
    assert breaker_mod.breaker_for("0").state == "open"    # only read


# --------------------------- (f) the stream's end-of-acquisition pass inherits it
def test_stream_batch_pass_prepares_and_matches_the_batch_report(
        fixture_path, tmp_path):
    path, truth = fixture_path
    formulas = truth.formulas[:8]
    sm_batch = _sm(tmp_path, "batch")
    SearchJob("live", "b", path, DS_CONFIG, sm_batch, formulas=formulas).run()

    sm = _sm(tmp_path, "stream")
    with ImzMLReader(path) as rd:
        coords = rd.coordinates.tolist()
        spectra = [rd.read_spectrum(i) for i in range(rd.n_spectra)]
    log = ChunkLog(stream_root(sm), "live")
    edges = np.linspace(0, len(coords), 4).astype(int)
    for seq in (2, 0, 1):                        # commits in any order
        lo, hi = edges[seq], edges[seq + 1]
        log.append(seq, coords[lo:hi], spectra[lo:hi])
    log.finish()
    before = flat_sorted_events()
    records = _traced(
        StreamSearchJob("live", "s", "", DS_CONFIG, sm, formulas=formulas),
        tmp_path, "stream")
    after = flat_sorted_events()
    (prep,) = _spans(records, "prepare_resident")
    (hold,) = _spans(records, "device_hold")
    assert prep["attrs"]["cached"] is False
    assert prep["ts"] + prep["dur"] <= hold["ts"] + 1e-3
    assert _spans(records, "backend_build")[0]["attrs"]["prepared"] is True
    assert after["pre_lease"] == before["pre_lease"] + 1
    assert after["under_lease"] == before["under_lease"]
    for got, want in zip(_stored_tables(sm, "live"),
                         _stored_tables(sm_batch, "live")):
        pd.testing.assert_frame_equal(got, want, check_exact=True)



# ------------------- the served path: a pool lease, /metrics, the job's trace
def test_served_upload_prepares_before_its_lease_and_counts_it(
        fixture_path, tmp_path):
    import json
    import sys
    import urllib.request

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts.load_sweep import Harness

    path, truth = fixture_path
    h = Harness(tmp_path, "served", sm_overrides={
        "backend": "jax_tpu",
        "parallel": {"formula_batch": 32, "overlap_isocalc": "off"},
        "service": {"device_pool_size": 2, "job_timeout_s": 120.0,
                    "max_attempts": 1}})
    try:
        def count(text, site, family="sm_backend_prepare_total", label="site"):
            line = f'{family}{{{label}="{site}"}} '
            return float(text.split(line)[1].split()[0])

        before = h.metrics_text()
        ids = ["up-0", "up-1"]
        for msg_id in ids:                # the same section, resubmitted
            status, _hd, body = h.submit({
                "ds_id": "served", "msg_id": msg_id, "input_path": str(path),
                "formulas": truth.formulas[:8],
                "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
            assert status == 202, body
            rows = h.wait_terminal([msg_id], timeout_s=120.0)
            assert rows[msg_id]["state"] == "done", rows[msg_id]
        after = h.metrics_text()
        traces = []
        for msg_id in ids:
            with urllib.request.urlopen(
                    f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
                traces.append(json.loads(r.read())["records"])
    finally:
        h.shutdown()
    # one miss, at the pre_lease site; never under the lease; the build of
    # the first job and the whole second job are lookups
    assert count(after, "pre_lease") - count(before, "pre_lease") == 1
    assert count(after, "under_lease") - count(before, "under_lease") == 0
    assert count(after, "cached") - count(before, "cached") == 2
    # the one miss measured its window occupancy once, by the walk
    roads = {r: count(after, r, "sm_prepare_occupancy_total", "route")
             - count(before, r, "sm_prepare_occupancy_total", "route")
             for r in ("walk", "search")}
    assert roads == {"walk": 1, "search": 0}
    for records, cached in zip(traces, (False, True)):
        (prep,) = _spans(records, "prepare_resident")
        (hold,) = _spans(records, "device_hold")
        (acq,) = [r for r in records if r["name"] == "device_token_acquired"]
        assert prep["attrs"]["cached"] is cached
        assert prep["ts"] + prep["dur"] <= hold["ts"] + 1e-3
        assert len(acq["attrs"]["devices"]) == 1


# ------- (h) the linear passes against the search and the sort they replaced
def _searched_hmax(mzs_flat, pixel_of_peak, ppm):
    """The parent's occupancy: every key searched into the keys."""
    if mzs_flat.size == 0:
        return 0
    key = pixel_of_peak.astype(np.int64) * (1 << 32) \
        + quantize_mz(mzs_flat).astype(np.int64)
    width = np.ceil(np.asarray(mzs_flat, np.float64)
                    * (2.5 * ppm * 1e-6) * MZ_SCALE).astype(np.int64)
    hi = np.searchsorted(key, key + width, side="right")
    return int(np.max(hi - np.arange(key.size)))


def _searched_scale(mzs_flat, ints_flat, pixel_of_peak, ppm):
    """The parent's ``intensity_scale``, early returns and all."""
    if ints_flat.size == 0:
        return 1.0
    max_raw = float(np.max(ints_flat))
    if max_raw <= 0:
        return 1.0
    hmax = _searched_hmax(mzs_flat, pixel_of_peak, ppm)
    target = (2**INT_SUM_BITS - 1) / (max(hmax, 1) + 1) / max_raw
    return float(2.0 ** np.floor(np.log2(target)))


def _width_q(mz):
    return int(np.ceil(mz * (2.5 * PPM * 1e-6) * MZ_SCALE))


def _occupancy_case(kind, tmp_path):
    """(dataset, hmax it must report or None, route)."""
    rng = np.random.default_rng(36)

    def grid(spectra, ncols=3):
        coords = [(i % ncols, i // ncols) for i in range(len(spectra))]
        return SpectralDataset.from_arrays(
            np.array(coords), [(np.asarray(m, np.float64),
                                np.asarray(i, np.float32))
                               for m, i in spectra])

    if kind == "fixture_9x11":
        path, _truth = generate_synthetic_dataset(
            tmp_path, nrows=9, ncols=11, present_fraction=0.5,
            noise_peaks=60, seed=36)
        return SpectralDataset.from_imzml(path), None, "walk"
    if kind == "equal_mz":
        # one m/z five times inside a pixel, and again in the next pixels
        same = [250.0] * 5 + [600.0, 600.0]
        return grid([(same, rng.uniform(1, 9, 7))] * 4
                    + [([250.0], [3.0])]), 5, "walk"
    if kind == "window_ends_on_a_key":
        # the second peak sits on the last grid step the first one's window
        # reaches (counted: side="right"), the third one step past it
        lo = 400.0
        edge = (int(quantize_mz(lo)) + _width_q(lo)) / MZ_SCALE
        past = (int(quantize_mz(lo)) + _width_q(lo) + 1) / MZ_SCALE
        assert int(quantize_mz(edge)) - int(quantize_mz(lo)) == _width_q(lo)
        assert int(quantize_mz(past)) - int(quantize_mz(edge)) == 1
        return grid([([lo, edge], [5.0, 6.0]), ([lo, past], [7.0, 8.0]),
                     ([lo], [1.0])]), 2, "walk"
    if kind == "dense_pixel":
        # a profile-like pixel: more peaks in one window than the walk takes
        n = OCCUPANCY_WALK_CAP + 9
        dense = 500.0 + np.arange(n) / MZ_SCALE
        assert _width_q(500.0) > n
        return grid([(dense, rng.uniform(1, 9, n)),
                     (np.sort(rng.uniform(100, 900, 30)),
                      rng.uniform(1, 9, 30))]), n, "search"
    if kind == "at_the_cap":
        # the last occupancy the walk still answers itself
        n = OCCUPANCY_WALK_CAP
        return grid([(500.0 + np.arange(n) / MZ_SCALE,
                      rng.uniform(1, 9, n))]), n, "walk"
    if kind == "one_peak":
        return grid([([321.5], [4.0])]), 1, "walk"
    if kind == "no_peaks":
        return grid([([], [])] * 4), 0, "walk"
    if kind == "all_zero_intensities":
        return grid([([100.0, 100.0002, 100.0004], [0.0, 0.0, 0.0]),
                     ([200.0], [0.0])]), 3, "walk"
    if kind == "saturated_mz":
        # beyond MZ_MAX every peak sits on the padding sentinel
        far = [MZ_MAX + 1.0, 2 * MZ_MAX, 5 * MZ_MAX]
        ds = grid([(far, [1.0, 2.0, 3.0]), ([150.0] + far[:2], [4.0, 5.0, 6.0])])
        assert (quantize_mz(ds.mzs_flat) == MZ_PAD_Q).sum() == 5
        return ds, 3, "walk"
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", [
    "fixture_9x11", "equal_mz", "window_ends_on_a_key", "dense_pixel",
    "at_the_cap", "one_peak", "no_peaks", "all_zero_intensities",
    "saturated_mz"])
def test_walked_occupancy_and_scale_are_the_searched_ones(kind, tmp_path):
    ds, want_hmax, want_route = _occupancy_case(kind, tmp_path)
    pixel = np.repeat(np.arange(ds.n_pixels), ds.row_lengths())
    ref_hmax = _searched_hmax(ds.mzs_flat, pixel, PPM)
    ref_scale = _searched_scale(ds.mzs_flat, ds.ints_flat, pixel, PPM)
    if want_hmax is not None:
        assert ref_hmax == want_hmax             # the case is what it says
    for px in (pixel, pixel.astype(np.int32)):
        for mz_q in (None, quantize_mz(ds.mzs_flat)):
            scale, hmax, route = intensity_scale(
                ds.mzs_flat, ds.ints_flat, px, PPM, mz_q=mz_q)
            assert (hmax, route) == (ref_hmax, want_route)
            assert scale == ref_scale and isinstance(scale, float)
    # through the dataset: the layout's scale, the numpy backend's grid
    before = occupancy_events()
    got = ds.flat_sorted(PPM)
    ints_q, scale = ds.intensity_quantization(PPM)
    after = occupancy_events()
    assert got.int_scale == scale == ref_scale
    assert ints_q.tobytes() == np.rint(
        ds.ints_flat.astype(np.float64) * ref_scale).astype(np.float32).tobytes()
    other = "search" if want_route == "walk" else "walk"
    assert after[want_route] == before[want_route] + 1     # once a dataset
    assert after[other] == before[other]


@pytest.mark.parametrize("pool,n_peaks", [(3, 5000), (40, 3000), (1, 1024)])
def test_packed_order_is_the_stable_argsort_with_many_ties(pool, n_peaks):
    rng = np.random.default_rng(pool)
    values = np.round(rng.uniform(100.0, 900.0, pool), 4)
    coords = [(x, y) for y in range(6) for x in range(5)]
    cuts = np.sort(rng.integers(0, n_peaks + 1, len(coords) - 1))
    lens = np.diff(np.concatenate([[0], cuts, [n_peaks]]))
    spectra = [(np.sort(rng.choice(values, n)),
                rng.uniform(1.0, 1e4, n).astype(np.float32)) for n in lens]
    ds = SpectralDataset.from_arrays(np.array(coords), spectra)
    mz_q = quantize_mz(ds.mzs_flat)
    assert np.unique(mz_q).size <= pool < ds.n_peaks == n_peaks
    order = np.argsort(mz_q, kind="stable")
    pixel = np.repeat(np.arange(ds.n_pixels, dtype=np.int32),
                      ds.row_lengths())
    got = ds.flat_sorted(PPM)
    n_max = -(-n_peaks // 1024) * 1024
    for have, want, pad in ((got.mz_q, mz_q[order], MZ_PAD_Q),
                            (got.pixel, pixel[order], ds.n_pixels),
                            (got.ints_q,
                             ds.intensity_quantization(PPM)[0][order], 0.0)):
        assert have.shape == (n_max,) and have.dtype == want.dtype
        assert have[:n_peaks].tobytes() == want.tobytes()
        assert (have[n_peaks:] == pad).all()
        assert not have.flags.writeable
        with pytest.raises(ValueError):
            have[0] = 0


def test_a_miss_says_which_roads_it_took_and_the_report_prints_them(tmp_path):
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts import trace_report

    ds, _hmax, _route = _occupancy_case("equal_mz", tmp_path)
    dense, n_dense, _route = _occupancy_case("dense_pixel", tmp_path)
    ctx = tracing.new_trace(job_id="roads", trace_dir=tmp_path / "traces")
    before = occupancy_events()
    with tracing.attach(ctx):
        for d in (ds, dense, ds):            # the third lookup is a hit
            with tracing.span("prepare_resident", peaks=d.n_peaks,
                              cached=d.flat_sorted_cached(PPM)):
                d.flat_sorted(PPM, site="pre_lease")
    tracing.close_file(ctx.file)
    records = tracing.read_trace(ctx.file)
    after = occupancy_events()
    assert {r: after[r] - before[r] for r in after} == {"walk": 1, "search": 1}
    quant, sort = (_spans(records, "prepare_quantize"),
                   _spans(records, "prepare_sort"))
    assert [q["attrs"] for q in quant] == [
        {"hmax": 5, "occupancy": "walk"},
        {"hmax": n_dense, "occupancy": "search"}]
    assert [s["attrs"] for s in sort] == [{"sort": "packed"}] * 2
    preps = _spans(records, "prepare_resident")
    assert [p["attrs"]["cached"] for p in preps] == [False, False, True]
    for q, s, p in zip(quant, sort, preps):
        assert q["parent_id"] == s["parent_id"] == p["span_id"]
    text = trace_report.render(trace_report.summarize(records))
    # in the phase breakdown, under prepare_resident (the span table below
    # it lists the two names once more, without attrs)
    q_line, s_line = (
        next(ln for ln in text.splitlines() if ln.strip().startswith(name))
        for name in ("prepare_quantize", "prepare_sort"))
    assert "x2" in q_line and "hmax=5" in q_line and "occupancy=walk" in q_line
    assert "x2" in s_line and "sort=packed" in s_line
