"""The image export as a stream of row chunks (ISSUE 49): the device hands
the store's images over in pieces of ``ops/buckets.EXPORT_CHUNK_BYTES``
(``JaxBackend.iter_ion_images``) and the writer compresses and writes one
while the next lands (``engine/storage.py::store_ion_images``).  Held here:
the pieces are the export bit for bit, the file a chunked writer makes loads
to what the whole-array writer's does, a count that does not add up or a
write that fails fails the job and leaves the previous file alone, and the
spans and the counter say which path a job took."""

from __future__ import annotations

import dataclasses
import threading
import zipfile

import numpy as np
import pytest

from sm_distributed_tpu.engine import storage
from sm_distributed_tpu.engine.search_job import SearchJob
from sm_distributed_tpu.engine.storage import (
    ImageExportError,
    JobLedger,
    SearchResultsStore,
)
from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models import image_export
from sm_distributed_tpu.models.msm_basic import _slice_table
from sm_distributed_tpu.models.msm_jax import JaxBackend
from sm_distributed_tpu.ops import buckets
from sm_distributed_tpu.ops.imager_np import extract_ion_images
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.utils import tracing
from sm_distributed_tpu.utils.config import (
    DSConfig,
    IsotopeGenerationConfig,
    SMConfig,
)

DC = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                         "image_generation": {"ppm": 3.0}})
N_IONS = 40                 # 160 flat rows of a 64-ion bucket's 256


@pytest.fixture(scope="module")
def section64(tmp_path_factory):
    path, truth = generate_synthetic_dataset(
        tmp_path_factory.mktemp("ds64"), nrows=64, ncols=64, formulas=None,
        present_fraction=0.5, noise_peaks=12, seed=49)
    return SpectralDataset.from_imzml(path), truth


@pytest.fixture(scope="module")
def datasets(offgrid_ds, section64):
    """9x11 = 99 px (not a multiple of 8, in a 110-px bucket) and 64x64."""
    return {"9x11": offgrid_ds, "64x64": section64}


def _table(truth):
    """``N_IONS`` ions: every third with ``n_valid`` 2 of k, ion 1 with
    windows that hold no peak at all."""
    adducts = ("+H", "+Na", "+K")
    table = IsocalcWrapper(IsotopeGenerationConfig(adducts=adducts)
                           ).pattern_table(
        [(sf, ad) for sf in truth.formulas for ad in adducts])
    assert table.n_ions >= N_IONS
    n_valid, mzs = table.n_valid.copy(), table.mzs.copy()
    n_valid[::3] = 2
    mzs[1] = 5000.0 + np.arange(table.max_peaks)
    table = dataclasses.replace(table, n_valid=n_valid, mzs=mzs)
    return _slice_table(table, 0, N_IONS)


def _backend(ds, monkeypatch, chunk_rows):
    """A backend whose export cuts chunks of ``chunk_rows`` flat rows (None:
    the constant as it is, one chunk at these sizes)."""
    backend = JaxBackend(ds, DC, SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 128}}))
    if chunk_rows is not None:
        monkeypatch.setattr(buckets, "EXPORT_CHUNK_BYTES",
                            4 * backend._n_pix_b * chunk_rows)
        assert buckets.export_chunk_rows(backend._n_pix_b) == chunk_rows
    return backend


# flat rows a chunk -> chunks that hold one of the 160 kept rows
CHUNKINGS = [(None, 1), (96, 2), (64, 3), (8, 20)]


@pytest.mark.parametrize("chunk_rows, n_chunks", CHUNKINGS)
@pytest.mark.parametrize("size", ["9x11", "64x64"])
def test_chunks_are_the_export_bit_for_bit(datasets, monkeypatch, size,
                                           chunk_rows, n_chunks):
    ds, truth = datasets[size]
    table = _table(truth)
    backend = _backend(ds, monkeypatch, chunk_rows)
    stream = backend.iter_ion_images(table)
    assert stream.shape == (N_IONS, table.max_peaks, ds.n_pixels)
    assert stream.n_chunks == n_chunks and stream.nnz is None
    pieces = list(stream)
    assert len(pieces) == n_chunks
    assert all(p.dtype == np.float32 and p.shape[1] == ds.n_pixels
               for p in pieces)
    # every piece but the last ends its bit mask on a byte
    assert all(p.shape[0] % 8 == 0 for p in pieces[:-1])
    flat = np.concatenate(pieces)
    whole = backend.extract_ion_images(table)
    oracle = extract_ion_images(ds, table, ppm=3.0)
    assert whole.shape == oracle.shape == stream.shape
    assert whole.flags.writeable
    assert flat.tobytes() == whole.tobytes() == oracle.tobytes()
    # the device's own count, landed with the first piece
    assert stream.nnz == np.count_nonzero(oracle) > 0
    images = flat.reshape(stream.shape)
    # n_valid 2 of k on every third ion: its later peaks are zeroed, and
    # their windows do hold signal
    assert images[::3, :2].any() and not images[::3, 2:].any()
    unmasked = dataclasses.replace(table, n_valid=np.full_like(
        table.n_valid, table.max_peaks))
    assert extract_ion_images(ds, unmasked, ppm=3.0)[::3, 2:].any()
    assert not images[1].any()                               # empty windows
    assert not np.signbit(images).any()                      # +0.0, never -0.0


@pytest.mark.parametrize("batch", [16, 15])
def test_a_table_above_the_batch_takes_calls_in_turn(
        tmp_path, datasets, monkeypatch, batch):
    """Three device calls, one after the other: the pieces are still the
    export, and the count, which the later calls bring too late, is None.
    An odd batch (off the lattice, which would snap it down to 12) at 99 px
    ends each call's last piece off a byte (12 rows of 99 bits): the
    writer's file is the whole-array writer's all the same."""
    ds, truth = datasets["9x11"]
    table = _table(truth)
    backend = JaxBackend(ds, DC, SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {
            "formula_batch": batch,
            "shape_buckets": "off" if batch % 2 else "auto"}}))
    assert backend.batch == batch
    monkeypatch.setattr(buckets, "EXPORT_CHUNK_BYTES",
                        4 * backend._n_pix_b * 24)
    stream = backend.iter_ion_images(table)
    # 16 + 16 + 8 (15 + 15 + 10) ions x 4 rows, in pieces of 24 rows: 3 + 3 + 2
    assert stream.n_chunks == 8
    pieces = list(stream)
    assert stream.nnz is None
    assert (batch == 15) == any(p.size % 8 for p in pieces[:-1])
    oracle = extract_ion_images(ds, table, ppm=3.0)
    assert np.concatenate(pieces).tobytes() == oracle.tobytes()
    ions = list(zip(table.sfs, table.adducts))
    whole = _store(tmp_path, "whole").store_ion_images(
        "ds", oracle, ions, ds.nrows, ds.ncols)
    chunked = _store(tmp_path, "chunked").store_ion_images(
        "ds", backend.iter_ion_images(table), ions, ds.nrows, ds.ncols)
    _same_file(chunked, whole, ions)


class _Chunks:
    """An array handed to the writer in pieces of ``rows`` flat rows, as
    ``IonImageChunks`` hands the export over."""

    def __init__(self, images, rows, nnz="count"):
        self.shape = images.shape
        flat = images.reshape(images.shape[0] * images.shape[1], -1)
        self._pieces = [flat[s:s + rows] for s in range(0, len(flat), rows)]
        self.n_chunks = len(self._pieces)
        self.nnz = int(np.count_nonzero(flat != 0)) if nnz == "count" else nnz

    def __iter__(self):
        return iter(self._pieces)


def _handmade():
    """(6, 4, 99) at 70% density with a ``-0.0``, ``NaN``s, an all-zero image
    and an all-zero ion."""
    rng = np.random.default_rng(49)
    imgs = rng.random((6, 4, 99), dtype=np.float32) + 0.5
    imgs[rng.random(imgs.shape) >= 0.7] = 0.0
    imgs[0, 0, :5] = [-0.0, np.nan, 0.0, -np.nan, -1.5]
    imgs[2, 1] = 0.0
    imgs[4] = 0.0
    imgs[-1, -1, -1] = -0.0
    return imgs


def _store(tmp_path, name) -> SearchResultsStore:
    return SearchResultsStore(JobLedger(tmp_path / name))


def _members(path):
    with zipfile.ZipFile(path) as zf:
        assert zf.testzip() is None
        assert zf.getinfo("data.npy").compress_type == zipfile.ZIP_STORED
        assert zf.getinfo("mask.npy").compress_type == zipfile.ZIP_DEFLATED
        return {name: zf.read(name) for name in zf.namelist()}


def _same_file(chunked, whole, ions):
    got, got_ions = SearchResultsStore.load_ion_images(chunked)
    want, want_ions = SearchResultsStore.load_ion_images(whole)
    assert got_ions == want_ions == ions
    assert got.shape == want.shape
    assert got.view(np.uint32).tobytes() == want.view(np.uint32).tobytes()
    a, b = _members(chunked), _members(whole)
    assert sorted(a) == sorted(b) == [
        "data.npy", "ions.npy", "layout.npy", "mask.npy", "shape.npy"]
    assert a == b                 # every member byte for byte, headers too
    assert not list(chunked.parent.glob("*.tmp"))
    return got


@pytest.mark.parametrize("nnz", ["count", None], ids=["counted", "uncounted"])
@pytest.mark.parametrize("rows", [5, 8, 16, 24], ids=lambda r: f"rows{r}")
def test_handmade_chunks_write_the_whole_writers_file(tmp_path, rows, nnz):
    """PR 25's contract through both writers: ``-0.0`` is a zero and reads
    back ``+0.0``, ``NaN`` is a value with its sign and payload.  5 rows of
    99 px end every piece off a byte: its last bits ride with the next."""
    imgs = _handmade()
    ions = [(f"C{i}H{2 * i}", "+H") for i in range(imgs.shape[0])]
    whole = _store(tmp_path, "whole").store_ion_images(
        "ds", imgs, ions, 9, 11)
    stream = _Chunks(imgs, rows, nnz)
    assert stream.n_chunks == -(-24 // rows)
    chunked = _store(tmp_path, "chunked").store_ion_images(
        "ds", stream, ions, 9, 11)
    got = _same_file(chunked, whole, ions)
    want = imgs.copy()
    want[want == 0] = 0.0
    assert got.reshape(imgs.shape).view(np.uint32).tobytes() \
        == want.view(np.uint32).tobytes()


@pytest.mark.parametrize("chunk_rows, n_chunks", CHUNKINGS)
@pytest.mark.parametrize("size", ["9x11", "64x64"])
def test_exported_chunks_write_the_whole_writers_file(
        tmp_path, datasets, monkeypatch, size, chunk_rows, n_chunks):
    ds, truth = datasets[size]
    table = _table(truth)
    backend = _backend(ds, monkeypatch, chunk_rows)
    ions = list(zip(table.sfs, table.adducts))
    before = storage.store_export_events()
    whole = _store(tmp_path, "whole").store_ion_images(
        "ds", backend.extract_ion_images(table), ions, ds.nrows, ds.ncols)
    stream = backend.iter_ion_images(table)
    stream.wait_first()
    chunked = _store(tmp_path, "chunked").store_ion_images(
        "ds", stream, ions, ds.nrows, ds.ncols)
    got = _same_file(chunked, whole, ions)
    assert got.reshape(stream.shape).tobytes() \
        == extract_ion_images(ds, table, ppm=3.0).tobytes()
    after = storage.store_export_events()
    assert {p: after[p] - before[p] for p in after} == (
        {"whole": 2, "streamed": 0} if n_chunks == 1
        else {"whole": 1, "streamed": 1})


def _previous_file(tmp_path):
    """A store that holds a finished job's images: (store, path, bytes)."""
    store = _store(tmp_path, "res")
    old = np.ones((1, 4, 99), np.float32)
    path = store.store_ion_images("ds", old, [("C1", "+H")], 9, 11)
    return store, path, path.read_bytes()


@pytest.mark.parametrize("off", [-1, 1, 5000])
def test_a_count_that_does_not_add_up_is_refused(tmp_path, off):
    store, path, before = _previous_file(tmp_path)
    imgs = _handmade()
    stream = _Chunks(imgs, 8)
    stream.nnz += off
    with pytest.raises(ImageExportError, match="non-zero pixels"):
        store.store_ion_images(
            "ds", stream, [(f"C{i}", "+H") for i in range(6)], 9, 11)
    assert path.read_bytes() == before
    assert not list(path.parent.glob("*.tmp"))


def test_chunks_short_of_the_shape_are_refused(tmp_path):
    """A producer that hands over fewer rows than its shape says."""
    store, path, before = _previous_file(tmp_path)
    stream = _Chunks(_handmade(), 8, nnz=None)
    stream._pieces.pop()
    with pytest.raises(ImageExportError, match="do not add up"):
        store.store_ion_images(
            "ds", stream, [(f"C{i}", "+H") for i in range(6)], 9, 11)
    assert path.read_bytes() == before
    assert not list(path.parent.glob("*.tmp"))


def test_a_write_that_fails_keeps_the_previous_file(tmp_path, monkeypatch):
    store, path, before = _previous_file(tmp_path)
    real_open, writes = zipfile.ZipFile.open, []

    class FullDisk:
        def __init__(self, fid):
            self._fid = fid

        def write(self, data):
            writes.append(len(data))
            if len(writes) == 3:          # the header, one chunk, then this
                raise OSError(28, "No space left on device")
            return self._fid.write(data)

        def close(self):
            return self._fid.close()

    monkeypatch.setattr(
        zipfile.ZipFile, "open",
        lambda self, *a, **kw: FullDisk(real_open(self, *a, **kw)))
    with pytest.raises(OSError, match="No space left"):
        store.store_ion_images(
            "ds", _Chunks(_handmade(), 8),
            [(f"C{i}", "+H") for i in range(6)], 9, 11)
    assert len(writes) == 3
    assert path.read_bytes() == before
    assert not list(path.parent.glob("*.tmp"))


def test_a_producer_that_fails_midway_fails_the_store(tmp_path):
    store, path, before = _previous_file(tmp_path)
    stream = _Chunks(_handmade(), 8)

    def pieces():
        yield stream._pieces[0]
        raise RuntimeError("the link went away")

    stream.__class__ = type("Broken", (_Chunks,), {
        "__iter__": lambda self: pieces()})
    with pytest.raises(RuntimeError, match="the link went away"):
        store.store_ion_images(
            "ds", stream, [(f"C{i}", "+H") for i in range(6)], 9, 11)
    assert path.read_bytes() == before
    assert not list(path.parent.glob("*.tmp"))


def test_eight_jobs_stream_at_once(tmp_path):
    """Scheduler workers store different datasets at the same time: more
    threads than cores, a short switch interval, every file whole and the
    counter short of none."""
    import sys

    imgs = _handmade()
    ions = [(f"C{i}", "+H") for i in range(6)]
    store = _store(tmp_path, "res")
    want = _members(_store(tmp_path, "whole").store_ion_images(
        "ds", imgs, ions, 9, 11))
    before = storage.store_export_events()
    failed, interval = [], sys.getswitchinterval()

    def job(i):
        try:
            for _ in range(5):
                path = store.store_ion_images(
                    f"ds{i}", _Chunks(imgs, 8), ions, 9, 11)
                assert _members(path) == want
        except BaseException as exc:       # the assertion too: report it
            failed.append(exc)

    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=job, args=(i,)) for i in range(8)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not failed, failed
    assert not any(w.is_alive() for w in workers)
    after = storage.store_export_events()
    assert after["streamed"] - before["streamed"] == 40


# -- through a job ---------------------------------------------------------------


@pytest.fixture(scope="module")
def upload(tmp_path_factory):
    return generate_synthetic_dataset(
        tmp_path_factory.mktemp("up"), nrows=8, ncols=8,
        present_fraction=0.5, noise_peaks=40, seed=3)


def _job(tmp_path, upload, ds_id="ds", image_format="npz") -> SearchJob:
    path, truth = upload
    sm = SMConfig.from_dict({
        "backend": "jax_tpu", "work_dir": str(tmp_path / "work"),
        "storage": {"results_dir": str(tmp_path / "store"),
                    "image_format": image_format},
        "fdr": {"decoy_sample_size": 4},
        "parallel": {"formula_batch": 32, "pixels_axis": 1,
                     "formulas_axis": 1}})
    return SearchJob(ds_id, "d", str(path), DC, sm_config=sm,
                     formulas=truth.formulas)


def _traced_run(job, tmp_path, name):
    ctx = tracing.new_trace(name, trace_dir=tmp_path / "traces")
    with tracing.attach(ctx):
        job.run()
    return list(tracing.read_trace(
        tracing.trace_path(tmp_path / "traces", ctx.trace_id)))


# 8x8 px: 64 ions pad to 256 flat rows; pieces of 64 rows stream, the
# constant as it is leaves one piece
@pytest.mark.parametrize("chunk_rows, path", [
    (None, "whole"), (64, "streamed")])
def test_spans_abut_and_the_counter_names_the_path(
        tmp_path, upload, monkeypatch, chunk_rows, path):
    if chunk_rows is not None:
        monkeypatch.setattr(buckets, "EXPORT_CHUNK_BYTES", 4 * 64 * chunk_rows)
    before = storage.store_export_events()
    records = _traced_run(_job(tmp_path, upload), tmp_path, "export")
    after = storage.store_export_events()
    assert {p: after[p] - before[p] for p in after} == {
        "whole": 0, "streamed": 0, path: 1}

    def one(name):
        (rec,) = [r for r in records if r.get("name") == name
                  and r["kind"] == "span"]
        return rec

    extract, write = one("store_extract_images"), one("store_write_images")
    assert extract["tid"] == write["tid"] == one("store_results")["tid"]
    # they abut without overlapping: their sum is the export's wall time
    gap = write["ts"] - (extract["ts"] + extract["dur"])
    assert 0 <= gap < 0.005
    assert set(extract["attrs"]) == {
        "ions", "rows", "calls", "fetched_bytes", "bytes"}
    kept_rows = extract["attrs"]["ions"] * 4
    assert write["attrs"]["chunks"] == (
        1 if chunk_rows is None else -(-kept_rows // chunk_rows))
    assert write["attrs"]["layout"] == "bitmask_v1"
    assert write["attrs"]["bytes"] == extract["attrs"]["bytes"] \
        == kept_rows * 64 * 4
    # only the pieces that hold a kept row leave the device
    assert extract["attrs"]["fetched_bytes"] == 64 * 4 * min(
        extract["attrs"]["rows"] * 4,
        write["attrs"]["chunks"] * (chunk_rows or 1 << 30))
    # what the job stored is the numpy extraction of the ions it kept
    imgs, ions = SearchResultsStore.load_ion_images(
        tmp_path / "store" / "ds" / "ion_images.npz")
    ds = SpectralDataset.from_imzml(upload[0])
    table = IsocalcWrapper(DC.isotope_generation).pattern_table(
        [tuple(i) for i in ions])
    assert imgs.reshape(len(ions), 4, -1).tobytes() \
        == extract_ion_images(ds, table, ppm=3.0).tobytes()
    assert write["attrs"]["nnz"] == np.count_nonzero(imgs)


def test_png_keeps_the_whole_array_path(tmp_path, upload, monkeypatch):
    monkeypatch.setattr(buckets, "EXPORT_CHUNK_BYTES", 4 * 64 * 64)
    monkeypatch.setattr(
        image_export.IonImageChunks, "wait_first",
        lambda self: pytest.fail("the PNG writer takes whole arrays"))
    before = storage.store_export_events()
    _job(tmp_path, upload, image_format="png").run()
    after = storage.store_export_events()
    assert (after["whole"] - before["whole"],
            after["streamed"] - before["streamed"]) == (1, 0)
    assert list((tmp_path / "store" / "ds" / "ion_images").glob("*_0.png"))


def test_a_lying_count_fails_the_job_and_keeps_the_last_file(
        tmp_path, upload, monkeypatch):
    monkeypatch.setattr(buckets, "EXPORT_CHUNK_BYTES", 4 * 64 * 64)
    _job(tmp_path, upload).run()
    npz = tmp_path / "store" / "ds" / "ion_images.npz"
    before = npz.read_bytes()
    real = JaxBackend.iter_ion_images

    def lying(self, table):
        stream = real(self, table)
        stream.wait_first()
        stream.nnz += 1
        return stream

    monkeypatch.setattr(JaxBackend, "iter_ion_images", lying)
    job = _job(tmp_path, upload)
    with pytest.raises(ImageExportError, match="non-zero pixels"):
        job.run()
    jobs = job.ledger.jobs("ds")
    assert list(jobs.status) == ["FINISHED", "FAILED"]
    assert npz.read_bytes() == before
    assert not list(npz.parent.glob("*.tmp"))


def test_the_reader_reads_the_window_share():
    import importlib.util
    import json
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    import sys

    sys.path.insert(0, str(repo / "benchmarks"))
    try:
        spec = importlib.util.spec_from_file_location(
            "layer_export_streamed_pct",
            repo / "benchmarks" / "layers" / "export_streamed_pct.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(str(repo / "benchmarks"))
    counter = "sm_store_exports_total"

    def exposed(streamed, whole):
        return (f"# HELP {counter} jobs\n# TYPE {counter} counter\n"
                f'{counter}{{path="streamed"}} {float(streamed)}\n'
                f'{counter}{{path="whole"}} {float(whole)}\n')

    run = {"metrics_before": exposed(2, 1), "metrics_after": exposed(11, 1)}
    assert mod.read(run) == pytest.approx(100.0)
    assert mod.read({"metrics_before": exposed(0, 3),
                     "metrics_after": exposed(0, 15)}) == 0.0
    assert mod.read({"metrics_before": exposed(0, 0),
                     "metrics_after": exposed(3, 9)}) == pytest.approx(25.0)
    # nothing to read: a program without the counter (the parent commit's),
    # a window in which no job stored images
    assert mod.read({"metrics_before": "", "metrics_after": ""}) is None
    assert mod.read({**run, "metrics_before": run["metrics_after"]}) is None
    manifest = json.loads((repo / "BENCHMARK.json").read_text())
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "export_streamed_pct"]
    assert entry == {
        "name": "export_streamed_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "fdr store",
        "moves": "report_s",
        # the three cells with large images, where it reads 100, and one
        # 64x64 control, where one chunk holds the export and it reads 0
        "workloads": ["section64-uploads", "section128-reannotate",
                      "slide256-reannotate", "hmdb-section128-reannotate"]}


def test_the_scoring_call_sites_stay_where_the_compile_cache_knows_them():
    """A tripwire, not a contract: the Mosaic payloads of the moments and
    chaos kernels carry the file and LINE of the frames that traced them,
    these among them, so a line added above any of them in
    ``models/msm_jax.py`` re-keys every scoring executable in the
    persistent compile cache (40-80 s of set-up a cell and machine; ROADMAP
    A6 f).  The export's host side lives in ``models/image_export.py`` for
    that reason.  Whoever has to move them: move the pins, and say in
    ``CHANGES.md`` that the scoring executables are re-keyed."""
    from sm_distributed_tpu.models import msm_jax

    where = {name: fn.__code__.co_firstlineno for name, fn in {
        "fused_score_fn_flat_banded": msm_jax.fused_score_fn_flat_banded,
        "fused_score_fn_flat_banded_compact":
            msm_jax.fused_score_fn_flat_banded_compact,
        "fused_score_fn_flat_banded_sliced":
            msm_jax.fused_score_fn_flat_banded_sliced,
        "_dispatch": JaxBackend._dispatch,
        "score_batches": JaxBackend.score_batches,
        "_enqueue_traced": JaxBackend._enqueue_traced}.items()}
    assert where == {
        "fused_score_fn_flat_banded": 162,
        "fused_score_fn_flat_banded_compact": 296,
        "fused_score_fn_flat_banded_sliced": 231,
        "_dispatch": 1011, "score_batches": 1337, "_enqueue_traced": 1360}


def test_the_service_exposes_the_counter():
    from sm_distributed_tpu.service.metrics import MetricsRegistry
    from sm_distributed_tpu.service.server import AnnotationService

    m = MetricsRegistry()
    m.add_collector(AnnotationService._collect_store_exports)
    events = storage.store_export_events()
    lines = m.expose().splitlines()
    for path, n in events.items():
        assert f'sm_store_exports_total{{path="{path}"}} {n}' in lines
    assert set(events) == {"streamed", "whole"}
