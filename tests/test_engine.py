"""Engine-layer tests: ledger, result store, annotation index, work dir,
mol DB, queue daemon, SearchJob, CLI — mirroring the reference's
DB-integration + end-to-end test tier (SURVEY.md §4) against the local
sqlite/parquet/file-queue stand-ins."""

import json
import zipfile
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from sm_distributed_tpu.engine.daemon import (
    QueueConsumer,
    QueuePublisher,
    annotate_callback,
)
from sm_distributed_tpu.engine.moldb import MolecularDB
from sm_distributed_tpu.engine.search_job import SearchJob
from sm_distributed_tpu.engine.storage import (
    AnnotationIndex,
    JobLedger,
    SearchResultsStore,
)
from sm_distributed_tpu.engine.work_dir import WorkDirManager
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models.msm_basic import SearchResultsBundle
from sm_distributed_tpu.utils.config import DSConfig, SMConfig


@pytest.fixture(scope="module")
def fixture_path(tmp_path_factory):
    out = tmp_path_factory.mktemp("dse")
    path, truth = generate_synthetic_dataset(
        out, nrows=8, ncols=8, formulas=None, present_fraction=0.5,
        noise_peaks=40, seed=5,
    )
    return path, truth


def _ann_df():
    return pd.DataFrame({
        "sf": ["C6H12O6", "C5H5N5"],
        "adduct": ["+H", "+H"],
        "msm": [0.9, 0.4],
        "fdr": [0.01, 0.3],
        "fdr_level": [0.05, 0.5],
        "chaos": [0.95, 0.6],
        "spatial": [0.97, 0.7],
        "spectral": [0.98, 0.95],
    })


def test_ledger_job_lifecycle(tmp_path):
    ledger = JobLedger(tmp_path / "res")
    ledger.upsert_dataset("ds1", "my ds", "/in", {"k": 1})
    job = ledger.start_job("ds1")
    assert ledger.job_status(job) == "STARTED"
    ledger.finish_job(job)
    assert ledger.job_status(job) == "FINISHED"
    job2 = ledger.start_job("ds1")
    ledger.fail_job(job2, "boom")
    jobs = ledger.jobs("ds1")
    assert list(jobs.status) == ["FINISHED", "FAILED"]
    assert "boom" in jobs.error.iloc[1]


def test_annotation_index_roundtrip_and_job_scoped_delete(tmp_path):
    ledger = JobLedger(tmp_path / "res")
    index = AnnotationIndex(ledger)
    n = index.index_ds("ds1", 1, _ann_df(), ion_mzs={("C6H12O6", "+H"): 181.07})
    assert n == 2
    hits = index.search(ds_id="ds1", max_fdr_level=0.1)
    assert list(hits.sf) == ["C6H12O6"]
    assert hits.mz.iloc[0] == pytest.approx(181.07)
    # m/z-range query (the reference webapp's search-by-mass on the ES index)
    assert list(index.search(mz_min=181.0, mz_max=181.1).sf) == ["C6H12O6"]
    assert index.search(mz_min=200.0).empty
    # job-scoped delete must not erase other jobs' rows
    index._conn.execute(
        "INSERT INTO annotation VALUES('ds1',2,'X','+H',1,0.5,0.1,0.2,0.5,0.5,0.5)"
    )
    index.delete_ds("ds1", job_id=2)
    assert len(index.search(ds_id="ds1")) == 2
    index.delete_ds("ds1")
    assert index.search(ds_id="ds1").empty


def test_results_store_parquet_and_images(tmp_path):
    ledger = JobLedger(tmp_path / "res")
    store = SearchResultsStore(ledger)
    bundle = SearchResultsBundle(
        annotations=_ann_df(),
        all_metrics=_ann_df()[["sf", "adduct", "chaos", "spatial", "spectral", "msm"]],
        timings={"score": 1.0},
    )
    d = store.store("ds1", 1, bundle)
    assert (d / "annotations.parquet").exists()
    back = pd.read_parquet(d / "annotations.parquet")
    assert list(back.sf) == ["C6H12O6", "C5H5N5"]
    # sparse npz round-trip
    rng = np.random.default_rng(0)
    imgs = rng.random((2, 4, 12)).astype(np.float32)
    imgs[imgs < 0.5] = 0.0
    path = store.store_ion_images("ds1", imgs, [("A", "+H"), ("B", "+Na")], 3, 4)
    dense, ions = SearchResultsStore.load_ion_images(path)
    assert ions == [("A", "+H"), ("B", "+Na")]
    np.testing.assert_allclose(dense.reshape(2, 4, 12), imgs)


def _image_store(tmp_path) -> SearchResultsStore:
    return SearchResultsStore(JobLedger(tmp_path / "res"))


def _image_case(name: str) -> tuple[np.ndarray, int, int]:
    """(images (n_ions, K, n_pix), nrows, ncols) of one round-trip case."""
    rng = np.random.default_rng(25)
    n_ions, k, nrows, ncols = {
        "npix_not_multiple_of_8": (3, 4, 7, 9),      # 63 px, 756 bits
        "section_128x128": (5, 4, 128, 128),
    }.get(name, (6, 4, 16, 20))
    imgs = rng.random((n_ions, k, nrows * ncols), dtype=np.float32) + 0.5
    density = {"density_0": 0.0, "density_3pct": 0.03, "density_100": 1.0,
               }.get(name, 0.7)
    imgs[rng.random(imgs.shape) >= density] = 0.0
    if name == "negative_zero_and_nan":
        imgs[0, 0, :5] = [-0.0, np.nan, 0.0, -np.nan, -1.5]
        imgs[-1, -1, -1] = -0.0
    return imgs, nrows, ncols


@pytest.mark.parametrize("case", [
    "density_0", "density_3pct", "density_70pct", "density_100",
    "negative_zero_and_nan", "npix_not_multiple_of_8", "section_128x128"])
def test_ion_images_npz_round_trip_bit_identical(tmp_path, case):
    imgs, nrows, ncols = _image_case(case)
    ions = [(f"C{i}H{2 * i}", "+H") for i in range(imgs.shape[0])]
    store = _image_store(tmp_path)
    path = store.store_ion_images("ds1", imgs, ions, nrows, ncols)
    dense, got_ions = SearchResultsStore.load_ion_images(path)
    assert got_ions == ions
    assert dense.shape == (*imgs.shape[:2], nrows, ncols)
    # `flat != 0` decides what is a value: -0.0 is a zero (reads back +0.0),
    # NaN is a value and keeps its sign and payload bits
    want = imgs.copy()
    want[want == 0] = 0.0
    assert np.array_equal(dense.reshape(imgs.shape).view(np.uint32),
                          want.view(np.uint32))
    if case != "negative_zero_and_nan":
        assert np.array_equal(dense.reshape(imgs.shape), imgs)
    assert path.name == "ion_images.npz"
    assert not list(path.parent.glob("*.tmp"))
    with np.load(path, allow_pickle=False) as z:
        assert sorted(z.files) == ["data", "ions", "layout", "mask", "shape"]
        assert z["data"].dtype == np.float32
        assert z["data"].size == np.count_nonzero(imgs != 0)
        assert z["mask"].size == -(-imgs.size // 8)


def test_ion_images_npz_size_bound_and_values_not_deflated(tmp_path):
    """The benchmark's 128x128 store: 302 ions x 4 peaks x 16,384 px at its
    `_spatial_patterns`-like 72% density.  The file is the values, one bit a
    pixel and headers - and `data` is never deflated (11 s of one core
    under the device lease, PERF.md PR 25)."""
    rng = np.random.default_rng(128)
    rows, n_pix = 302 * 4, 128 * 128
    imgs = rng.random((rows, n_pix), dtype=np.float32)
    imgs[imgs < 0.28] = 0.0
    imgs = imgs.reshape(302, 4, n_pix)
    ions = [(f"C{i}", "+H") for i in range(302)]
    path = _image_store(tmp_path).store_ion_images("ds1", imgs, ions, 128, 128)
    nnz = int(np.count_nonzero(imgs))
    assert 0.70 < nnz / imgs.size < 0.74
    assert path.stat().st_size <= 4 * nnz + rows * n_pix // 8 + 65536
    with zipfile.ZipFile(path) as zf:
        assert zf.getinfo("data.npy").compress_type == zipfile.ZIP_STORED
        assert zf.getinfo("data.npy").file_size >= 4 * nnz
    dense, _ions = SearchResultsStore.load_ion_images(path)
    assert np.array_equal(dense.reshape(imgs.shape), imgs)


def test_ion_images_npz_unknown_layout_is_refused(tmp_path):
    path = tmp_path / "ion_images.npz"
    np.savez(path, shape=np.array([1, 1, 2, 2]), ions=np.array(["A|+H"]),
             layout=np.array("bitmask_v9"), mask=np.zeros(1, np.uint8),
             data=np.zeros(0, np.float32))
    with pytest.raises(ValueError, match="bitmask_v9"):
        SearchResultsStore.load_ion_images(path)


def test_work_dir_staging_resume_and_subdirs(tmp_path):
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.imzML").write_text("x")
    (src / "sub" / "a.imzML").write_text("y")  # same basename, different subdir
    wd = WorkDirManager(tmp_path / "work", "ds1")
    dst = wd.copy_input_data(src)
    assert (dst / "a.imzML").read_text() == "x"
    assert (dst / "sub" / "a.imzML").read_text() == "y"
    # unchanged input -> staging skipped (manifest hit): mutate dst marker
    marker = dst / "marker"
    marker.write_text("m")
    assert wd.copy_input_data(src) == dst
    assert marker.exists(), "unchanged input must not re-stage"
    # changed input -> re-staged, marker gone
    (src / "a.imzML").write_text("xx")
    wd.copy_input_data(src)
    assert not marker.exists()
    assert wd.imzml_path().name == "a.imzML"
    wd.clean()
    assert not wd.path.exists()


def test_moldb_import_and_lookup(tmp_path):
    csv = tmp_path / "db.csv"
    csv.write_text("id,name,formula\n1,Glucose,C6H12O6\n2,Dup,C6H12O6\n3,Adenine,C5H5N5\n")
    db = MolecularDB(JobLedger(tmp_path / "res"))
    assert db.import_csv(csv, "HMDB", "v1") == 3
    assert db.formulas("HMDB", "v1") == ["C6H12O6", "C5H5N5"]  # deduped, ordered
    assert db.databases() == [("HMDB", "v1")]
    # re-import replaces
    csv.write_text("sf\nC16H32O2\n")
    assert db.import_csv(csv, "HMDB", "v1") == 1
    assert db.formulas("HMDB") == ["C16H32O2"]
    with pytest.raises(KeyError):
        db.formulas("nope")
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.csv"
        bad.write_text("x,y\n1,2\n")
        db.import_csv(bad, "B", "1")


def test_search_job_end_to_end_and_failure(fixture_path, tmp_path):
    path, truth = fixture_path
    sm = SMConfig.from_dict({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 3, "seed": 2},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
    })
    ds_config = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    formulas = truth.formulas[:8]
    job = SearchJob("dsE", "e2e", path, ds_config, sm, formulas=formulas)
    bundle = job.run()
    assert len(bundle.annotations) == 8
    ledger = JobLedger(tmp_path / "res")
    assert (ledger.jobs("dsE").status == "FINISHED").all()
    index = AnnotationIndex(ledger)
    ok_rows = index.search(ds_id="dsE")
    assert len(ok_rows) == 8 and ok_rows.mz.notna().all()
    # failed second job must not wipe the first job's index rows
    bad = SearchJob("dsE", "e2e", tmp_path / "missing.imzML", ds_config, sm,
                    formulas=formulas)
    with pytest.raises(FileNotFoundError):
        bad.run()
    jobs = ledger.jobs("dsE")
    assert list(jobs.status) == ["FINISHED", "FAILED"]
    assert len(AnnotationIndex(ledger).search(ds_id="dsE")) == 8


def test_daemon_queue_success_failure_poison(fixture_path, tmp_path):
    path, truth = fixture_path
    sm = SMConfig.from_dict({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 2, "seed": 1},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
    })
    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "q1", "input_path": str(path),
                 "formulas": truth.formulas[:3],
                 "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
    pub.publish({"ds_id": "q2", "input_path": "/nope.imzML"})
    # poison message: invalid JSON dropped into pending by a foreign producer
    (tmp_path / "q" / "sm_annotate" / "pending" / "zz_poison.json").write_text("{broken")
    consumer = QueueConsumer(tmp_path / "q", annotate_callback(sm))
    consumer.run(max_messages=3)
    root = tmp_path / "q" / "sm_annotate"
    assert len(list(root.glob("done/*.json"))) == 1
    assert len(list(root.glob("failed/*.json"))) == 2
    assert not list(root.glob("pending/*.json"))
    # requeue_stale moves crashed messages back
    (root / "running" / "stuck.json").write_text(json.dumps({"ds_id": "s"}))
    assert consumer.requeue_stale() == 1
    assert (root / "pending" / "stuck.json").exists()


def test_3d_stack_campaign(tmp_path):
    """BASELINE config #4 analog: a 3-D stack is a campaign of per-slice
    datasets through ONE queue + ledger (the reference treats a stack as a
    series of jobs over shared infra).  Each slice gets its own dataset row,
    FINISHED job, and queryable annotations; the shared isocalc pattern
    cache is populated by slice 0 and only read by later slices."""
    slices = []
    for z in range(3):
        path, truth = generate_synthetic_dataset(
            tmp_path / f"slice{z}", nrows=8, ncols=8, formulas=None,
            present_fraction=0.5, noise_peaks=30, seed=100 + z)
        slices.append((path, truth))
    sm = SMConfig.from_dict({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 2, "seed": 5},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
    })
    pub = QueuePublisher(tmp_path / "q")
    for z, (path, truth) in enumerate(slices):
        pub.publish({"ds_id": f"stack_z{z}", "input_path": str(path),
                     "formulas": truth.formulas[:6],
                     "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
    consumer = QueueConsumer(tmp_path / "q", annotate_callback(sm))
    consumer.run(max_messages=1)           # slice 0 populates the cache
    cache_shards = sorted((tmp_path / "work" / "isocalc_cache").glob("*.npz"))
    assert cache_shards, "slice 0 must persist isotope patterns"
    consumer.run(max_messages=2)           # slices 1-2: cache hits only
    assert sorted((tmp_path / "work" / "isocalc_cache").glob("*.npz")) == \
        cache_shards, "later slices must reuse slice 0's pattern cache"

    root = tmp_path / "q" / "sm_annotate"
    assert len(list(root.glob("done/*.json"))) == 3
    ledger = JobLedger(tmp_path / "res")
    index = AnnotationIndex(ledger)
    for z in range(3):
        assert (ledger.jobs(f"stack_z{z}").status == "FINISHED").all()
        rows = index.search(ds_id=f"stack_z{z}")
        assert len(rows) == 6
    # slices are independently queryable; a cross-stack query sees all three
    all_rows = index.search()
    assert set(all_rows.ds_id) >= {f"stack_z{z}" for z in range(3)}


def test_cli_import_run_search(fixture_path, tmp_path, capsys):
    from sm_distributed_tpu.engine.cli import main

    path, truth = fixture_path
    sm_json = tmp_path / "sm.json"
    sm_json.write_text(json.dumps({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 2, "seed": 1},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
    }))
    ds_json = tmp_path / "ds.json"
    ds_json.write_text(json.dumps({
        "database": {"name": "mini", "version": "t"},
        "isotope_generation": {"adducts": ["+H"]},
    }))
    csv = tmp_path / "mini.csv"
    csv.write_text("formula\n" + "\n".join(truth.formulas[:4]) + "\n")
    assert main(["import-db", str(csv), "mini", "t", "--sm-config", str(sm_json)]) == 0
    assert main(["run", "cli ds", str(path), "--ds-id", "cli1",
                 "--ds-config", str(ds_json), "--sm-config", str(sm_json)]) == 0
    assert main(["search", "--ds-id", "cli1", "--sm-config", str(sm_json)]) == 0
    out = capsys.readouterr().out
    assert any(sf in out for sf in truth.formulas[:4])


def test_png_generator(tmp_path):
    from sm_distributed_tpu.engine.png import PngGenerator

    img = np.zeros((8, 10))
    img[2:5, 3:7] = np.arange(12).reshape(3, 4)
    mask = img > -1
    mask[0, 0] = False
    gen = PngGenerator(mask=mask)
    data = gen.render(img)
    assert data[:8] == b"\x89PNG\r\n\x1a\n"
    p = gen.save(img, tmp_path / "ion.png")
    from PIL import Image

    arr = np.asarray(Image.open(p))
    assert arr.shape == (8, 10, 4)
    assert arr[0, 0, 3] == 0          # masked pixel transparent
    assert arr[3, 4, 3] == 255


def test_jax_path_stores_device_images_without_cpu_reextraction(tmp_path, monkeypatch):
    """VERDICT r1 item 9: on the jax backend the annotation ion images come
    off the device cube; the numpy extractor must NOT run."""
    import numpy as np

    from sm_distributed_tpu.engine.search_job import SearchJob
    from sm_distributed_tpu.engine.storage import SearchResultsStore
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.ops import imager_np
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, present_fraction=0.5,
        noise_peaks=40, seed=3)
    sm = SMConfig.from_dict({
        "backend": "jax_tpu", "work_dir": str(tmp_path / "work"),
        "storage": {"results_dir": str(tmp_path / "store")},
        "fdr": {"decoy_sample_size": 4},
        "parallel": {"formula_batch": 32, "pixels_axis": 1, "formulas_axis": 1},
    })
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]}, "image_generation": {"ppm": 3.0}})

    real_extract = imager_np.extract_ion_images
    calls = []

    def tracking(*a, **k):
        calls.append(1)
        return real_extract(*a, **k)

    monkeypatch.setattr(imager_np, "extract_ion_images", tracking)
    job = SearchJob("devimg_ds", "d", str(path), ds_config, sm_config=sm,
                    formulas=truth.formulas)
    job.run()
    assert calls == [], "numpy re-extraction ran on the jax path"
    # and the stored images match a (post-hoc) numpy extraction bit for bit
    store_dir = tmp_path / "store" / "devimg_ds"
    imgs, ions = SearchResultsStore.load_ion_images(store_dir / "ion_images.npz")
    assert imgs.shape[0] == len(ions) and imgs.shape[0] > 0
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch  # noqa: F401
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper

    ds = SpectralDataset.from_imzml(path)
    calc = IsocalcWrapper(ds_config.isotope_generation)
    table = calc.pattern_table([tuple(i) for i in ions])
    want = real_extract(ds, table, ppm=3.0)
    np.testing.assert_array_equal(
        imgs.reshape(imgs.shape[0], imgs.shape[1], -1), want)


class _FakeRemote:
    """Fetcher test double simulating an object store (SURVEY #3 S3 seam):
    in-memory {relpath: (bytes, version)}, optional failure injection after
    N fetches to exercise resume-after-partial-fetch."""

    def __init__(self, objects, fail_after=None):
        self.objects = dict(objects)
        self.fail_after = fail_after
        self.fetch_log = []

    def list_files(self, src):
        return {rel: [len(data), ver] for rel, (data, ver) in self.objects.items()}

    def fetch_file(self, src, rel, dst):
        if self.fail_after is not None and len(self.fetch_log) >= self.fail_after:
            raise ConnectionError(f"fake remote dropped while fetching {rel}")
        self.fetch_log.append(rel)
        dst.write_bytes(self.objects[rel][0])


def test_work_dir_fake_remote_staging_and_partial_resume(tmp_path):
    objs = {f"f{i}.bin": (bytes([i]) * (10 + i), f"v{i}") for i in range(6)}
    # first attempt dies after 3 files
    flaky = _FakeRemote(objs, fail_after=3)
    wd = WorkDirManager(tmp_path / "work", "dsr", fetcher=flaky)
    with pytest.raises(ConnectionError):
        wd.copy_input_data("fake://bucket/ds")
    assert len(flaky.fetch_log) == 3
    # resume with a healthy connection: only the missing files transfer
    healthy = _FakeRemote(objs)
    wd2 = WorkDirManager(tmp_path / "work", "dsr", fetcher=healthy)
    dst = wd2.copy_input_data("fake://bucket/ds")
    assert sorted(healthy.fetch_log) == sorted(
        set(objs) - set(flaky.fetch_log)), "already-staged files refetched"
    for rel, (data, _v) in objs.items():
        assert (dst / rel).read_bytes() == data
    # steady state: nothing transfers
    quiet = _FakeRemote(objs)
    WorkDirManager(tmp_path / "work", "dsr", fetcher=quiet).copy_input_data(
        "fake://bucket/ds")
    assert quiet.fetch_log == []
    # a changed remote version refetches exactly that file
    objs2 = dict(objs)
    objs2["f2.bin"] = (b"NEW", "v2b")
    upd = _FakeRemote(objs2)
    WorkDirManager(tmp_path / "work", "dsr", fetcher=upd).copy_input_data(
        "fake://bucket/ds")
    assert upd.fetch_log == ["f2.bin"]
    assert (dst / "f2.bin").read_bytes() == b"NEW"


def test_work_dir_s3_scheme_guidance(tmp_path):
    from sm_distributed_tpu.engine.work_dir import resolve_fetcher

    with pytest.raises(ImportError, match="boto3"):
        resolve_fetcher("s3://bucket/ds")
    with pytest.raises(ValueError, match="unsupported input scheme"):
        resolve_fetcher("gopher://x")


class _FakeS3ClientError(Exception):
    def __init__(self, status):
        self.response = {"ResponseMetadata": {"HTTPStatusCode": status}}


class _FakeS3Client:
    """boto3-shaped double: head_object / list_objects_v2 pagination /
    download_file over an in-memory {key: bytes} store, so S3Fetcher's
    listing + sibling logic actually executes in this offline image."""

    class exceptions:  # noqa: N801 — boto3 client namespace shape
        ClientError = _FakeS3ClientError

    def __init__(self, objects):
        self.objects = dict(objects)
        self.head_calls, self.list_calls = [], []

    def head_object(self, Bucket, Key):
        self.head_calls.append(Key)
        if Key not in self.objects:
            raise _FakeS3ClientError(404)
        return {"ContentLength": len(self.objects[Key]),
                "ETag": f'"etag-{Key}"'}

    def get_paginator(self, op):
        assert op == "list_objects_v2"
        client = self

        class _Pager:
            def paginate(self, Bucket, Prefix):
                client.list_calls.append(Prefix)
                contents = [
                    {"Key": k, "Size": len(v), "ETag": f'"etag-{k}"'}
                    for k, v in sorted(client.objects.items())
                    if k.startswith(Prefix)
                ]
                yield {"Contents": contents} if contents else {}

        return _Pager()

    def download_file(self, bucket, key, dst):
        Path(dst).write_bytes(self.objects[key])


def test_s3_fetcher_exact_key_stages_ibd_sibling(tmp_path):
    """Advisor r3 (medium): an exact .imzML key must stage the .ibd pair."""
    from sm_distributed_tpu.engine.work_dir import S3Fetcher

    client = _FakeS3Client({
        "data/ds1.imzML": b"imzml-bytes",
        "data/ds1.ibd": b"ibd-bytes",
        "data/ds10.imzML": b"other",
    })
    f = S3Fetcher(client=client)
    listing = f.list_files("s3://bucket/data/ds1.imzML")
    assert sorted(listing) == ["ds1.ibd", "ds1.imzML"]
    # exact-key detection is HEAD requests, not a prefix scan (advisor r3)
    assert client.list_calls == []
    wd = WorkDirManager(tmp_path / "work", "s3ds", fetcher=f)
    dst = wd.copy_input_data("s3://bucket/data/ds1.imzML")
    assert (dst / "ds1.imzML").read_bytes() == b"imzml-bytes"
    assert (dst / "ds1.ibd").read_bytes() == b"ibd-bytes"
    # a lone imzML (no sibling uploaded) still stages — the reader reports
    # the missing .ibd later with its own clear error
    lone = S3Fetcher(client=_FakeS3Client({"d/solo.imzML": b"x"}))
    assert sorted(lone.list_files("s3://bucket/d/solo.imzML")) == ["solo.imzML"]
    # uppercase extension pair stages via the shared sibling rule
    up = S3Fetcher(client=_FakeS3Client({"d/DS1.IMZML": b"i", "d/DS1.IBD": b"b"}))
    assert sorted(up.list_files("s3://bucket/d/DS1.IMZML")) == [
        "DS1.IBD", "DS1.IMZML"]


def test_s3_fetcher_head_denied_surfaces_permission_error():
    from sm_distributed_tpu.engine.work_dir import S3Fetcher

    class _DeniedClient(_FakeS3Client):
        def head_object(self, Bucket, Key):
            raise _FakeS3ClientError(403)

    # denied HEAD + nothing listable -> a permissions diagnosis, not a
    # misleading "no objects" (code-review r4)
    f = S3Fetcher(client=_DeniedClient({}))
    with pytest.raises(PermissionError, match="403"):
        f.list_files("s3://bucket/data/ds1.imzML")
    # denied HEAD but the directory listing works -> staging proceeds
    ok = S3Fetcher(client=_DeniedClient({"data/ds1/a.imzML": b"A"}))
    assert sorted(ok.list_files("s3://bucket/data/ds1")) == ["a.imzML"]


def test_s3_fetcher_directory_listing_skips_markers_and_siblings(tmp_path):
    from sm_distributed_tpu.engine.work_dir import S3Fetcher

    client = _FakeS3Client({
        "data/ds1/": b"",                    # console folder marker
        "data/ds1/a.imzML": b"A",
        "data/ds1/sub/b.ibd": b"B",
        "data/ds10/c.imzML": b"C",           # sibling prefix must not leak
    })
    f = S3Fetcher(client=client)
    listing = f.list_files("s3://bucket/data/ds1")
    assert sorted(listing) == ["a.imzML", "sub/b.ibd"]
    # one directory pagination only (advisor r3: was two full listings)
    assert client.list_calls == ["data/ds1/"]
    dst = WorkDirManager(tmp_path / "work", "s3dir", fetcher=f).copy_input_data(
        "s3://bucket/data/ds1")
    assert (dst / "sub" / "b.ibd").read_bytes() == b"B"


def test_work_dir_skip_path_refetches_deleted_files(tmp_path):
    """Advisor r3: a file deleted from dst after a complete staging must be
    refetched even though the manifest still matches the listing."""
    objs = {f"f{i}.bin": (bytes([i]) * 8, "v") for i in range(3)}
    wd = WorkDirManager(tmp_path / "work", "dsx", fetcher=_FakeRemote(objs))
    dst = wd.copy_input_data("fake://bucket/ds")
    (dst / "f1.bin").unlink()
    healer = _FakeRemote(objs)
    WorkDirManager(tmp_path / "work", "dsx", fetcher=healer).copy_input_data(
        "fake://bucket/ds")
    assert healer.fetch_log == ["f1.bin"]
    assert (dst / "f1.bin").read_bytes() == objs["f1.bin"][0]


def test_daemon_residency_second_job_skips_prepare_and_compile(fixture_path, tmp_path):
    """Service mode (VERDICT r2 item 7): a second queue message on the SAME
    dataset/config must reuse the resident parsed dataset and the compiled
    backend — residency cache hits, and the second job's read_dataset phase
    collapses to ~zero in timings.json."""
    from sm_distributed_tpu.engine.residency import DatasetResidency

    path, truth = fixture_path
    sm = SMConfig.from_dict({
        "backend": "jax_tpu",
        "fdr": {"decoy_sample_size": 2, "seed": 1},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
        "parallel": {"formula_batch": 16, "pixels_axis": 1,
                     "formulas_axis": 1},
    })
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    pub = QueuePublisher(tmp_path / "q")
    msg = {"ds_id": "warm", "input_path": str(path),
           "formulas": truth.formulas[:5],
           "ds_config": {"isotope_generation": {"adducts": ["+H"]}}}
    pub.publish(msg)
    pub.publish(msg)
    consumer = QueueConsumer(
        tmp_path / "q", annotate_callback(sm, residency=residency))

    consumer.run(max_messages=1)
    t1 = json.loads((tmp_path / "res" / "warm" / "timings.json").read_text())
    def lookups():
        return {k: v for k, v in residency.stats.items()
                if k.endswith(("_hits", "_misses"))}

    assert lookups() == {"dataset_hits": 0, "dataset_misses": 1,
                         "backend_hits": 0, "backend_misses": 1,
                         "ion_table_hits": 0, "ion_table_misses": 1}
    consumer.run(max_messages=1)
    t2 = json.loads((tmp_path / "res" / "warm" / "timings.json").read_text())
    assert lookups() == {"dataset_hits": 1, "dataset_misses": 1,
                         "backend_hits": 1, "backend_misses": 1,
                         "ion_table_hits": 1, "ion_table_misses": 1}
    # one of each held, each weighed, nothing evicted, no budget by count
    stats = residency.stats
    assert [stats[f"{c}_entries"] for c in ("dataset", "backend", "ion_table")] \
        == [1, 1, 1]
    assert stats["dataset_bytes"] > 0 and stats["ion_table_bytes"] > 0
    assert stats["evictions"] == {}
    assert stats["budget_bytes"] == {"device": None, "host": None}
    # warm job: no parse — the phase is a cache lookup (generous absolute
    # bound; the substantive reuse proof is the stats assert above)
    assert t1["read_dataset"] > t2["read_dataset"]
    assert t2["read_dataset"] < 0.1
    # a DIFFERENT formula list must miss the backend cache (fingerprint)
    pub.publish({**msg, "formulas": truth.formulas[:4]})
    consumer.run(max_messages=1)
    assert residency.stats["backend_misses"] == 2
    assert residency.stats["dataset_hits"] == 2


def test_work_dir_file_uri(tmp_path):
    src = tmp_path / "in"
    src.mkdir()
    (src / "a.imzML").write_text("x")
    wd = WorkDirManager(tmp_path / "work", "dsf")
    dst = wd.copy_input_data(f"file://{src}")
    assert (dst / "a.imzML").read_text() == "x"
    # SearchJob must not round-trip URIs through Path (":" mangling)
    job = SearchJob("u1", "u", f"file://{src}/a.imzML", DSConfig(),
                    SMConfig.from_dict({
                        "storage": {"results_dir": str(tmp_path / "res")},
                        "work_dir": str(tmp_path / "work")}))
    assert job.input_path == f"file://{src}/a.imzML"
