"""Result storage + job ledger + annotation index — the L0 state plane.

TPU-native/offline replacements for the reference's service stack (SURVEY.md
#2 ``db.py::DB`` Postgres, #14 ``search_results.py::SearchResults``, #15
``es_export.py::ESExporter``, #21 SQL schema):

- ``JobLedger``     — sqlite tables ``dataset`` / ``job`` with status rows
  (STARTED/FINISHED/FAILED), the reference's job bookkeeping.
- ``SearchResultsStore`` — per-job parquet files (annotations, all metrics,
  the decoy assignment they were ranked by) plus sparse ion images as npz,
  the reference's ``iso_image_metrics`` / ``target_decoy_add`` /
  ``iso_image`` tables.
- ``AnnotationIndex`` — a searchable sqlite table of flattened annotations
  (ds, sf, adduct, msm, fdr, mz), the reference's Elasticsearch index:
  ``index_ds`` / ``delete_ds`` / ``search`` with the same flattening.

Everything lives under ``StorageConfig.results_dir``; all writers are
idempotent per (ds_id, job_id) so failed jobs can simply be re-run
(SURVEY.md §5.3: idempotent re-run as the recovery model).
"""

from __future__ import annotations

import json
import sqlite3
import threading
import time
import zipfile
from pathlib import Path

import numpy as np
import pandas as pd

from ..ops.fdr import ASSIGNMENT_COLUMNS
from ..utils import tracing
from ..utils.failpoints import failpoint, record_recovery, register_failpoint
from ..utils.logger import logger

FP_RESULTS_RENAME = register_failpoint(
    "storage.results_rename",
    "between results tmp writes and their atomic renames into place")
FP_INDEX_COMMIT = register_failpoint(
    "storage.index_commit",
    "inside the annotation index delete+insert, before the commit")
FP_LEDGER_FINISH = register_failpoint(
    "ledger.finish_job", "before the job row flips STARTED -> FINISHED")

# the tables of a finished job, in the order ``SearchResultsStore.store``
# writes them; what compares two jobs' results byte for byte reads this
RESULT_TABLES = ("annotations.parquet", "all_metrics.parquet",
                 "target_decoy_add.parquet")


def read_result_tables(ds_dir: str | Path) -> tuple[pd.DataFrame, ...]:
    """A finished job's ``RESULT_TABLES`` with their rows in one order
    (each table's ion or triple columns are a key), for comparing two jobs'
    results bit for bit."""
    out = []
    for name in RESULT_TABLES:
        df = pd.read_parquet(Path(ds_dir) / name)
        key = [c for c in ("sf", "adduct", *ASSIGNMENT_COLUMNS[1:])
               if c in df.columns]
        out.append(df.sort_values(key).reset_index(drop=True))
    return tuple(out)


# layout marker of ion_images.npz (store_ion_images); files without one are
# the CSR triple written before PR 25
IMAGE_LAYOUT = "bitmask_v1"

# image exports stored, by how the images reached the writer: "streamed" in
# more than one chunk, "whole" in one.  One a job that stores images;
# scheduler workers share it, hence the lock; the service pulls it at scrape
# as sm_store_exports_total{path=}.
_STORE_EXPORTS = {"streamed": 0, "whole": 0}
_STORE_EXPORTS_LOCK = threading.Lock()


def _count_export(path: str) -> None:
    with _STORE_EXPORTS_LOCK:
        _STORE_EXPORTS[path] += 1


def store_export_events() -> dict:
    with _STORE_EXPORTS_LOCK:
        return dict(_STORE_EXPORTS)


class ImageExportError(OSError):
    """The image file could not be made from what the export handed over."""


class _WholeImages:
    """A dense ``(n_ions, K, n_pix)`` array as the one-chunk case of what
    ``store_ion_images`` consumes (``models/image_export.IonImageChunks``)."""

    n_chunks, nnz = 1, None

    def __init__(self, images: np.ndarray):
        self.shape = images.shape
        self._flat = images.reshape(images.shape[0] * images.shape[1], -1)

    def __iter__(self):
        yield self._flat


JOB_STARTED = "STARTED"
JOB_FINISHED = "FINISHED"
JOB_FAILED = "FAILED"

_SCHEMA = """
CREATE TABLE IF NOT EXISTS dataset (
    id TEXT PRIMARY KEY,
    name TEXT,
    input_path TEXT,
    ds_config TEXT,
    created_at REAL
);
CREATE TABLE IF NOT EXISTS job (
    id INTEGER PRIMARY KEY AUTOINCREMENT,
    ds_id TEXT REFERENCES dataset(id),
    status TEXT,
    started_at REAL,
    finished_at REAL,
    error TEXT
);
CREATE TABLE IF NOT EXISTS annotation (
    ds_id TEXT,
    job_id INTEGER,
    sf TEXT,
    adduct TEXT,
    mz REAL,
    msm REAL,
    fdr REAL,
    fdr_level REAL,
    chaos REAL,
    spatial REAL,
    spectral REAL
);
CREATE INDEX IF NOT EXISTS annotation_ds ON annotation(ds_id);
CREATE INDEX IF NOT EXISTS annotation_sf ON annotation(sf);
"""


class JobLedger:
    """Job/dataset status bookkeeping (reference: ``job``/``dataset`` rows in
    Postgres written by SearchJob [U])."""

    # Concurrent scheduler workers each open their own connection to the one
    # ledger file; without a busy timeout a writer collision dies instantly
    # with "database is locked" (ISSUE 2 satellite).
    BUSY_TIMEOUT_S = 30.0

    def __init__(self, results_dir: str | Path):
        self.root = Path(results_dir)
        self.root.mkdir(parents=True, exist_ok=True)
        self.db_path = self.root / "engine.sqlite"
        self._conn = sqlite3.connect(self.db_path, timeout=self.BUSY_TIMEOUT_S)
        self._conn.execute(
            f"PRAGMA busy_timeout={int(self.BUSY_TIMEOUT_S * 1000)}")
        # WAL lets readers proceed under a writer (index replace vs /jobs
        # queries); falls back gracefully where the filesystem can't do WAL
        mode = self._set_wal()
        if str(mode).lower() != "wal":
            logger.warning(
                "ledger %s: journal_mode=WAL unavailable (got %r); "
                "concurrent access falls back to rollback-journal locking",
                self.db_path, mode)
        else:
            self._conn.execute("PRAGMA synchronous=NORMAL")
        self._conn.executescript(_SCHEMA)
        self._conn.commit()

    def _set_wal(self) -> str:
        """``PRAGMA journal_mode=WAL``, waited for.  The switch of a fresh
        database needs it to itself, and sqlite answers a second connection
        that arrives meanwhile with "database is locked" at once, whatever
        the busy timeout says: two jobs that start together on a new results
        directory, and one of them died of it."""
        deadline = time.monotonic() + self.BUSY_TIMEOUT_S
        while True:
            try:
                return self._conn.execute(
                    "PRAGMA journal_mode=WAL").fetchone()[0]
            except sqlite3.OperationalError as exc:
                if "locked" not in str(exc) or time.monotonic() > deadline:
                    raise
                time.sleep(0.01)

    def upsert_dataset(self, ds_id: str, name: str, input_path: str,
                       ds_config: dict) -> None:
        self._conn.execute(
            "INSERT INTO dataset(id, name, input_path, ds_config, created_at) "
            "VALUES(?,?,?,?,?) ON CONFLICT(id) DO UPDATE SET "
            "name=excluded.name, input_path=excluded.input_path, "
            "ds_config=excluded.ds_config",
            (ds_id, name, input_path, json.dumps(ds_config), time.time()),
        )
        self._conn.commit()

    def start_job(self, ds_id: str) -> int:
        cur = self._conn.execute(
            "INSERT INTO job(ds_id, status, started_at) VALUES(?,?,?)",
            (ds_id, JOB_STARTED, time.time()),
        )
        self._conn.commit()
        return int(cur.lastrowid)

    def finish_job(self, job_id: int) -> None:
        failpoint(FP_LEDGER_FINISH)
        self._conn.execute(
            "UPDATE job SET status=?, finished_at=? WHERE id=?",
            (JOB_FINISHED, time.time(), job_id),
        )
        self._conn.commit()

    def fail_job(self, job_id: int, error: str) -> None:
        self._conn.execute(
            "UPDATE job SET status=?, finished_at=?, error=? WHERE id=?",
            (JOB_FAILED, time.time(), error[:4000], job_id),
        )
        self._conn.commit()

    def job_status(self, job_id: int) -> str | None:
        row = self._conn.execute(
            "SELECT status FROM job WHERE id=?", (job_id,)
        ).fetchone()
        return row[0] if row else None

    def fail_stale_started(self, ds_id: str | None = None,
                           error: str = "orphaned by process crash",
                           ds_ids=None, before: float | None = None) -> int:
        """Crash reconciliation: mark STARTED job rows FAILED.  A row stuck in
        STARTED means the owning process died between start_job and its
        terminal update — rerunning is idempotent, but the ledger must not
        report a dead job as live forever.  With ``ds_id`` the sweep is
        scoped to one dataset.

        Multi-replica scoping (ISSUE 8 satellite): a takeover replica must
        not reap a LIVE peer's in-flight rows.  ``ds_ids`` restricts the
        sweep to the datasets whose spool messages the takeover actually
        fenced + requeued (the dead replica's shard contents), and
        ``before`` restricts it to rows started before the takeover
        timestamp — a row a live peer started afterwards survives even if
        its dataset collides."""
        q = "UPDATE job SET status=?, finished_at=?, error=? WHERE status=?"
        args: list = [JOB_FAILED, time.time(), error, JOB_STARTED]
        if ds_id is not None:
            q += " AND ds_id=?"
            args.append(ds_id)
        if ds_ids is not None:
            ids = sorted({str(d) for d in ds_ids})
            if not ids:
                return 0
            q += f" AND ds_id IN ({','.join('?' * len(ids))})"
            args.extend(ids)
        if before is not None:
            q += " AND started_at < ?"
            args.append(float(before))
        cur = self._conn.execute(q, args)
        self._conn.commit()
        n = cur.rowcount if cur.rowcount and cur.rowcount > 0 else 0
        if n:
            record_recovery("ledger.stale_started")
            logger.warning("ledger: marked %d orphaned STARTED job(s) FAILED", n)
        return n

    def jobs(self, ds_id: str | None = None) -> pd.DataFrame:
        q = "SELECT * FROM job"
        args: tuple = ()
        if ds_id is not None:
            q += " WHERE ds_id=?"
            args = (ds_id,)
        return pd.read_sql_query(q + " ORDER BY id", self._conn, params=args)

    def close(self) -> None:
        self._conn.close()


class AnnotationIndex:
    """The Elasticsearch-equivalent searchable annotation index
    (reference: ``ESExporter.index_ds/delete_ds`` [U], SURVEY.md #15)."""

    def __init__(self, ledger: JobLedger):
        self._conn = ledger._conn

    def index_ds(self, ds_id: str, job_id: int, annotations: pd.DataFrame,
                 ion_mzs: dict[tuple[str, str], float] | None = None) -> int:
        """Flatten + index annotations; re-indexing a dataset replaces its
        rows (idempotent, like delete+index in the reference).  Delete and
        insert commit as ONE transaction, so a failure mid-replace leaves
        the previous successful job's rows queryable (ADVICE r1)."""
        rows = [
            (
                ds_id, job_id, r.sf, r.adduct,
                float(ion_mzs.get((r.sf, r.adduct), np.nan)) if ion_mzs else np.nan,
                float(r.msm), float(r.fdr), float(r.fdr_level),
                float(r.chaos), float(r.spatial), float(r.spectral),
            )
            for r in annotations.itertuples()
        ]
        try:
            self._conn.execute("DELETE FROM annotation WHERE ds_id=?", (ds_id,))
            self._conn.executemany(
                "INSERT INTO annotation VALUES(?,?,?,?,?,?,?,?,?,?,?)", rows
            )
            # a crash HERE rolls the whole replace back on the next open —
            # the previous job's rows stay queryable (the invariant the
            # chaos sweep's storage.index_commit scenario checks)
            failpoint(FP_INDEX_COMMIT)
        except Exception:
            self._conn.rollback()
            raise
        self._conn.commit()
        return len(rows)

    def delete_ds(self, ds_id: str, job_id: int | None = None) -> None:
        """Drop a dataset's index rows; with ``job_id``, only that job's rows
        (failure cleanup must not erase a previous successful job's index)."""
        if job_id is None:
            self._conn.execute("DELETE FROM annotation WHERE ds_id=?", (ds_id,))
        else:
            self._conn.execute(
                "DELETE FROM annotation WHERE ds_id=? AND job_id=?", (ds_id, job_id)
            )
        self._conn.commit()

    def search(
        self,
        ds_id: str | None = None,
        sf: str | None = None,
        adduct: str | None = None,
        max_fdr_level: float | None = None,
        min_msm: float | None = None,
        mz_min: float | None = None,
        mz_max: float | None = None,
    ) -> pd.DataFrame:
        """Query annotations; mz_min/mz_max cover the reference webapp's
        search-by-mass use of the ES index (principal-peak ion m/z)."""
        clauses, args = [], []
        for col, val in (("ds_id", ds_id), ("sf", sf), ("adduct", adduct)):
            if val is not None:
                clauses.append(f"{col}=?")
                args.append(val)
        if max_fdr_level is not None:
            clauses.append("fdr_level<=?")
            args.append(max_fdr_level)
        if min_msm is not None:
            clauses.append("msm>=?")
            args.append(min_msm)
        if mz_min is not None:
            clauses.append("mz>=?")
            args.append(mz_min)
        if mz_max is not None:
            clauses.append("mz<=?")
            args.append(mz_max)
        q = "SELECT * FROM annotation"
        if clauses:
            q += " WHERE " + " AND ".join(clauses)
        return pd.read_sql_query(q + " ORDER BY msm DESC", self._conn, params=args)


class SearchResultsStore:
    """Persist a finished search (reference: ``SearchResults.store`` →
    ``iso_image_metrics`` + ``iso_image`` + ES trigger [U], SURVEY.md #14)."""

    def __init__(self, ledger: JobLedger, store_images: bool = True,
                 image_format: str = "npz"):
        self.ledger = ledger
        self.index = AnnotationIndex(ledger)
        self.store_images = store_images
        self.image_format = image_format

    def ds_dir(self, ds_id: str) -> Path:
        d = self.ledger.root / ds_id
        d.mkdir(parents=True, exist_ok=True)
        return d

    def store(self, ds_id: str, job_id: int, bundle,
              ion_mzs: dict[tuple[str, str], float] | None = None) -> Path:
        """Write ``RESULT_TABLES`` (annotations, metrics, the decoy
        assignment they were ranked by), index annotations. Returns the
        dataset results dir.

        Write order protects the previous successful job (ADVICE r1/r2):
        files land under temp names and are atomically renamed into place
        BEFORE the index replace commits — a crash before the renames leaves
        the old results fully intact, and a crash between the renames and
        the index transaction leaves new parquet with the old index rows,
        which the next successful ``store`` (or a re-index) repairs; the
        index never references annotations that are not on disk.
        """
        d = self.ds_dir(ds_id)
        # the draw the job ranked by (upstream's target_decoy_add [U]): its
        # columns were made once, with the draw, so a resident
        # re-annotation pays the write alone
        assignment = (bundle.assignment.frame
                      if bundle.assignment is not None
                      else pd.DataFrame(columns=list(ASSIGNMENT_COLUMNS),
                                        dtype=str))
        # disk-budget preflight (ISSUE 10, service/resources.py): deny the
        # store up front — before any tmp write — when the headroom floor
        # would be breached; rough estimate, refined by the GC rescan
        from ..service import resources as _resources

        _resources.preflight(
            "storage.results_store",
            256 * (len(bundle.annotations) + len(bundle.all_metrics))
            + 32 * len(assignment) + 8192)
        # sweep tmp debris a crashed previous store left behind: the rerun
        # overwrites the same names, but a FAILED-then-abandoned dataset
        # must not leak .tmp files forever
        stale = list(d.glob("*.tmp"))
        for p in stale:
            p.unlink(missing_ok=True)
        if stale:
            record_recovery("storage.stale_tmp")
        tmps = [(d / (name + ".tmp"), d / name) for name in RESULT_TABLES]
        bundle.annotations.to_parquet(tmps[0][0], index=False)
        bundle.all_metrics.to_parquet(tmps[1][0], index=False)
        # onto the caller's store_tables span
        tracing.annotate(
            rows=len(bundle.annotations) + len(bundle.all_metrics),
            bytes=sum(tmp.stat().st_size for tmp, _dst in tmps[:2]))
        with tracing.span("store_assignment", rows=len(assignment)):
            assignment.to_parquet(tmps[2][0], index=False)
            tracing.annotate(bytes=tmps[2][0].stat().st_size)
        tmp_t = d / "timings.json.tmp"
        tmp_t.write_text(json.dumps(bundle.timings, indent=2))
        tmps.append((tmp_t, d / "timings.json"))
        failpoint(FP_RESULTS_RENAME, path=tmps[0][0])
        for tmp, dst in tmps:
            tmp.replace(dst)
        n = self.index.index_ds(ds_id, job_id, bundle.annotations, ion_mzs)
        # read-plane publish (ISSUE 16): swap the dataset's columnar read
        # segment LAST, behind the same caller-held fence as the rest of the
        # store — readers see the previous complete segment until this commits
        from .index import publish_segment

        publish_segment(d, ds_id, job_id, bundle.annotations, ion_mzs)
        logger.info("stored %d annotations for ds %s under %s", n, ds_id, d)
        return d

    def store_ion_images(
        self,
        ds_id: str,
        images,                      # (n_ions, max_peaks, n_pix) dense, or its chunks
        ions: list[tuple[str, str]],
        nrows: int,
        ncols: int,
        mask: np.ndarray | None = None,
    ) -> Path:
        """Sparse-store ion images (reference keeps scipy CSR blobs in the
        ``iso_image`` table [U]; dense tiles live on TPU, sparsity only at
        host egress — SURVEY.md §2c).  PNG mode writes ALL isotope-peak
        images (suffix _0.._K-1, like the reference's per-isotope PNGs [U])
        with the sample-area mask rendered transparent.

        npz layout ``IMAGE_LAYOUT`` (PR 25), with ``flat`` the images as
        ``(n_ions * K, n_pix)`` in C order:

        - ``mask``: ``np.packbits(flat != 0)`` over the whole of ``flat``
          (uint8, ``bitorder="big"``: pixel ``8 * i`` is the top bit of byte
          ``i``; the last byte is zero-padded) — one bit a pixel where a CSR
          column index spent 32.  Deflated: it is small, and at densities
          under 1/32 that is what keeps it below an index list.
        - ``data``: ``flat[flat != 0]`` as f32, i.e. the non-zero values in
          row-major order.  STORED, never deflated: deflate took 10-27% off
          them for seconds of one core under the device lease (11 s of a
          13 s job at 128x128, PERF.md PR 25).
        - ``shape`` ``[n_ions, K, nrows, ncols]``, ``ions`` ``"sf|adduct"``,
          ``layout`` the marker ``load_ion_images`` branches on.

        ``-0.0`` is a zero and reads back ``+0.0``; ``NaN`` is a value.

        ``images`` is the whole array or, for the npz format, the export as
        it leaves the device (``models/image_export.IonImageChunks``: ``shape``,
        ``n_chunks``, ``nnz`` and the flat row chunks in order).  The same
        loop writes both on the caller's thread, a chunk at a time, while
        the later chunks are still on the link: a whole array is the
        one-chunk case.  Member order and the file's bytes may differ
        between the two; what loads may not."""
        d = self.ds_dir(ds_id)
        if self.image_format == "png":
            from .png import PngGenerator

            gen = PngGenerator(mask=mask)
            img_dir = d / "ion_images"
            img_dir.mkdir(exist_ok=True)
            for (sf, adduct), ion_imgs in zip(ions, images):
                name = f"{sf}{adduct}".replace("+", "p").replace("-", "m")
                for k in range(ion_imgs.shape[0]):
                    gen.save(ion_imgs[k].reshape(nrows, ncols),
                             img_dir / f"{name}_{k}.png")
            _count_export("whole")
            return img_dir
        if isinstance(images, np.ndarray):
            images = _WholeImages(images)
        n_ions, k, n_pix = images.shape
        # tmp + atomic rename: the tile service (ISSUE 16) reads this file
        # under concurrent re-annotation — readers must see the previous
        # complete npz or the new one, never a partial write
        tmp = d / "ion_images.npz.tmp"
        try:
            with zipfile.ZipFile(tmp, "w", zipfile.ZIP_STORED) as zf:
                nnz, mask = self._write_image_values(zf, images)
                if mask.size != (n_ions * k * n_pix + 7) // 8:
                    raise ImageExportError(
                        f"{tmp}: the chunks do not add up to {n_ions * k} "
                        f"rows of {n_pix} pixels")
                members = {
                    "mask": mask,
                    "shape": np.array([n_ions, k, nrows, ncols]),
                    "ions": np.array([f"{sf}|{adduct}" for sf, adduct in ions]),
                    "layout": np.array(IMAGE_LAYOUT),
                }
                for name, arr in members.items():
                    info = zipfile.ZipInfo(name + ".npy")
                    if name == "mask":
                        info.compress_type = zipfile.ZIP_DEFLATED
                    with zf.open(info, "w", force_zip64=True) as fid:
                        np.lib.format.write_array(fid, arr, allow_pickle=False)
            # onto the caller's store_write_images span: what the writer was
            # handed and what it put on disk
            tracing.annotate(layout=IMAGE_LAYOUT, nnz=nnz,
                             file_bytes=tmp.stat().st_size,
                             chunks=images.n_chunks)
            tmp.replace(d / "ion_images.npz")
        except BaseException:
            # whatever stage failed: no partial file, and the previous
            # job's images stay in place
            tmp.unlink(missing_ok=True)
            raise
        _count_export("streamed" if images.n_chunks > 1 else "whole")
        return d / "ion_images.npz"

    @staticmethod
    def _write_image_values(zf, images):
        """Stream ``images``' chunks into ``data.npy``: (non-zeros written,
        the packed bit mask).  Per chunk: the non-zero mask, the values
        under it, the packed bits.  The values go to the zip as they come
        where the producer knows their total count up front (``images.nnz``,
        the device's own: the header needs it, and the chunks' counts must
        add up to it); else they wait for the last chunk, whose end is when
        the count is known.  A chunk that ends off a byte (a producer's
        own cut: the device's are whole bytes) leaves its last bits to the
        next."""
        fid, bits, waiting, count = None, [], [], 0
        carry = np.zeros(0, bool)

        def member(nnz: int):
            fid = zf.open(zipfile.ZipInfo("data.npy"), "w", force_zip64=True)
            header = np.lib.format.header_data_from_array_1_0(
                np.empty(0, np.float32))
            np.lib.format.write_array_header_1_0(
                fid, {**header, "shape": (int(nnz),)})
            return fid

        try:
            for chunk in images:
                nz = chunk != 0
                vals = chunk[nz].astype(np.float32, copy=False)
                count += vals.size
                nz = nz.reshape(-1)
                if carry.size or nz.size % 8:
                    nz = np.concatenate([carry, nz])
                    nz, carry = np.split(nz, [nz.size // 8 * 8])
                bits.append(np.packbits(nz))
                if fid is None and images.nnz is not None:
                    fid = member(images.nnz)
                if fid is None:
                    waiting.append(vals)
                else:
                    fid.write(vals.data)
            if fid is None:
                fid = member(count)
                for vals in waiting:
                    fid.write(vals.data)
        finally:
            if fid is not None:
                fid.close()
        if images.nnz is not None and count != images.nnz:
            raise ImageExportError(
                f"the image chunks hold {count} non-zero pixels, the "
                f"export's own count says {int(images.nnz)}")
        return count, np.concatenate([*bits, np.packbits(carry)])

    @staticmethod
    def load_ion_images(path: str | Path) -> tuple[np.ndarray, list[tuple[str, str]]]:
        """Inverse of ``store_ion_images`` (npz format): dense (n_ions, K,
        nrows, ncols) + ion list.  Reads by the file's own content: the
        mask + values layout, or the CSR triple (``data`` / ``indices`` /
        ``indptr``, deflated) that every store before PR 25 wrote."""
        with np.load(path, allow_pickle=False) as z:
            n_ions, k, nrows, ncols = (int(x) for x in z["shape"])
            flat = np.zeros((n_ions * k, nrows * ncols), dtype=np.float32)
            if "indptr" in z.files:
                rows = np.repeat(np.arange(flat.shape[0]),
                                 np.diff(z["indptr"]))
                flat[rows, z["indices"]] = z["data"]
            elif str(z["layout"]) == IMAGE_LAYOUT:
                nz = np.unpackbits(z["mask"], count=flat.size)
                flat[nz.view(bool).reshape(flat.shape)] = z["data"]
            else:
                raise ValueError(
                    f"{path}: unknown ion image layout {z['layout']!r}")
            ions = [tuple(s.split("|", 1)) for s in z["ions"].tolist()]
        return flat.reshape(n_ions, k, nrows, ncols), ions
