"""SearchJob — the end-to-end annotation job orchestrator (L5).

Reference: ``sm/engine/search_job.py::SearchJob`` [U] (SURVEY.md #13, call
stack §3.1): the one place that touches every layer — config, work-dir
staging, conversion, distributed context, theor-peak generation, search,
result storage, cleanup, with job status rows (STARTED/FINISHED/FAILED).

TPU-native differences: no imzML→txt conversion step (the native reader
parses straight into the device-friendly CSR layout); the Spark context is
replaced by the jitted backend (mesh-aware via SMConfig.parallel); results go
to parquet + sqlite instead of Postgres/ES.  Failure model per SURVEY.md
§5.3: any exception marks the job FAILED with the error recorded, partial
index entries for the dataset are removed, and re-running is idempotent.
"""

from __future__ import annotations

import time
import traceback
from pathlib import Path

from ..analysis import retrace
from ..io.dataset import SpectralDataset
from ..models.msm_basic import IsotopePrefetch, MSMBasicSearch, SearchResultsBundle
from ..utils import devicemem, tracing
from ..utils.cancel import JobCancelledError, hold_cancellable
from ..utils.config import DSConfig, SMConfig
from ..utils.logger import logger, phase_timer
from .moldb import MolecularDB
from .storage import JobLedger, SearchResultsStore
from .work_dir import WorkDirManager


class SearchJob:
    """Run a full annotation job for one dataset."""

    def __init__(
        self,
        ds_id: str,
        ds_name: str,
        input_path: str | Path,
        ds_config: DSConfig,
        sm_config: SMConfig | None = None,
        formulas: list[str] | None = None,
        profile_dir: str | None = None,
        residency=None,
        device_token=None,
        cancel=None,
        fence=None,
        on_partial=None,
        workers_busy: int | None = None,
    ):
        self.ds_id = ds_id
        self.ds_name = ds_name
        # URIs (file://, s3://) must NOT round-trip through Path — it
        # collapses "://" to ":/" before the staging fetcher can parse it
        s = str(input_path)
        self.input_path: str | Path = s if "://" in s else Path(s)
        self.ds_config = ds_config
        self.sm_config = sm_config or SMConfig.get_conf()
        self.formulas = formulas      # explicit list overrides the mol DB
        self.profile_dir = profile_dir
        # service mode: this job's hold (what it is handed stays pinned until
        # run() lets go) on the shared engine/residency.DatasetResidency
        self.residency = residency.job() if residency is not None else None
        # service scheduler's device lease (service/device_pool.py — still
        # Lock-protocol compatible, so a plain threading.Lock works too):
        # when set, the device-bound compile+search+store phase runs under
        # the lease's 1..N chips while staging/parse phases overlap across
        # jobs.  A 1-chip lease pins scoring to its chip; an N-chip lease
        # scores through the pjit-sharded sub-mesh (parallel/sharded.py).
        self.device_token = device_token
        # cooperative cancellation (utils/cancel.CancelToken): checked at
        # phase boundaries here and at checkpoint-group boundaries inside
        # the search, so a timed-out/cancelled job releases the device
        # token and stores no partial results
        self.cancel = cancel
        # multi-replica fence gate (service/leases.py): a callable raising
        # FenceRejectedError when a peer replica fenced this job's claim
        # out.  Checked immediately before results become durable and
        # before the ledger commit — the two writes that would otherwise
        # double-complete under a split-brain takeover.
        self.fence = fence
        # streamed first results (ISSUE 13): provisional-annotation
        # payloads from the search's first FDR-rankable group — recorded
        # on ``last_partial`` and forwarded to ``on_partial`` (the service
        # passes ``ctx.set_partial`` so GET /jobs shows the preview)
        self.on_partial = on_partial
        self.last_partial: dict = {}
        # service mode: attempts in flight in this process when this one
        # started (``JobContext.workers_busy``), the ``pre_lease`` span's attr
        self.workers_busy = workers_busy
        self.ledger = JobLedger(self.sm_config.storage.results_dir)
        # generation stats of the last completed run (workers, patterns/s,
        # device flag) — read by probes/benches (scripts/cold_path_bench.py)
        self.last_isocalc_stats: dict = {}
        # device-memory high-water mark of the last completed run (ISSUE 6):
        # {device_kind, hbm_peak_bytes, ...}; byte fields None on platforms
        # without memory stats (utils/devicemem.py)
        self.last_hbm: dict = {}
        self.store = SearchResultsStore(
            self.ledger,
            store_images=self.sm_config.storage.store_images,
            image_format=self.sm_config.storage.image_format,
        )
        self.work_dir = WorkDirManager(self.sm_config.work_dir, ds_id)

    def _load_formulas(self) -> list[str]:
        if self.formulas is not None:
            return list(self.formulas)
        db_cfg = self.ds_config.database
        return MolecularDB(self.ledger).formulas(db_cfg.name, db_cfg.version)

    def run(self, clean: bool = False) -> SearchResultsBundle:
        """Stage → read → search → store; job row tracks status."""
        import dataclasses

        # the ledger's two rows: what run() does before pre_lease opens
        # (the callback's own preamble is span attempt_setup, daemon.py)
        with tracing.span("job_start"):
            self.ledger.upsert_dataset(
                self.ds_id, self.ds_name, str(self.input_path),
                dataclasses.asdict(self.ds_config),
            )
            job_id = self.ledger.start_job(self.ds_id)
        logger.info("job %d started for ds %s (%s)", job_id, self.ds_id, self.ds_name)
        prof = None
        succeeded = False
        prefetch = None
        try:
            timings: dict[str, float] = {}
            # pre_lease: the attempt's host-only work, from here to the
            # moment the job asks the pool for a chip (device_hold opens
            # next); the phase spans below are its children
            busy = {} if self.workers_busy is None else \
                {"workers_busy": int(self.workers_busy)}
            with tracing.span("pre_lease", **busy):
                # ISSUE 3 layer 3: isotope-pattern generation needs only the
                # formula list + configs, and it dominates the cold path (94.5%
                # of the BASELINE #3 wall) — start it FIRST, so staging + parse
                # below overlap it instead of queueing behind it
                formulas = self._load_formulas()
                if self.cancel is not None:
                    self.cancel.check("load_formulas")
                if self.sm_config.parallel.overlap_isocalc != "off":
                    prefetch = IsotopePrefetch(
                        formulas, self.ds_config, self.sm_config,
                        str(Path(self.sm_config.work_dir) / "isocalc_cache"),
                        residency=self.residency)
                ds = self._prepare_dataset(timings)
                logger.info(
                    "dataset %s: %dx%d px, %d spectra, %d peaks",
                    self.ds_id, ds.nrows, ds.ncols, ds.n_spectra, ds.n_peaks,
                )
                self._prepare_resident(ds)
                if self.profile_dir:
                    from ..analysis.profiling import ProfileSession

                    prof = ProfileSession(self.profile_dir)
                    prof.start()
                    # correlate the jax.profiler trace dir into the job trace:
                    # /jobs/<id>/trace surfaces it in otherData.jax_profile_dir
                    tracing.event("jax_profile", dir=str(self.profile_dir))
            import contextlib

            # everything up to here needs no chip (staging, parse, formula
            # lookup, and the half of the backend build that depends on the
            # dataset alone: _prepare_resident) and overlaps freely across
            # scheduler workers.  What is left of the build needs the ion
            # table or the chip (window restriction, padding, device_put),
            # and from there through result storage the job holds its
            # lease, so concurrent service jobs serialize on the TPU token.
            # The acquisition stays cancellable: a cancelled job must not
            # sit in the device queue, and the ``with`` exit releases the
            # token on the cooperative JobCancelledError unwind.
            if self.device_token is None and self.cancel is None:
                token = contextlib.nullcontext()
            else:
                token = hold_cancellable(self.device_token, self.cancel)
            # trace accounting: the device_hold span covers token WAIT +
            # HOLD; the acquired event inside marks the boundary, so
            # trace_report can split queue-wait vs token-wait vs compute.
            # wait_cpu_s on the event is the CPU this thread used waiting
            # (hold_cancellable's polling): device_hold.cpu less it is the
            # CPU the thread used UNDER the lease
            wait_c0 = time.thread_time()
            with tracing.span("device_hold"), token, \
                    retrace.lease(self.device_token):
                # a DeviceLease exposes the granted chip indices; a plain
                # Lock (legacy callers) has none — the event then matches
                # the pre-pool shape and the search meshes over all devices
                lease_devs = getattr(self.device_token, "devices", None)
                tracing.event(
                    "device_token_acquired",
                    wait_cpu_s=time.thread_time() - wait_c0,
                    **({"devices": [int(i) for i in lease_devs]}
                       if lease_devs else {}))
                with tracing.span("search_init"):
                    search = MSMBasicSearch(
                        ds, formulas, self.ds_config, self.sm_config,
                        isocalc_cache_dir=str(
                            Path(self.sm_config.work_dir) / "isocalc_cache"),
                        checkpoint_dir=str(self.work_dir.path),
                        backend_cache=self.residency,
                        prefetch=prefetch,
                        cancel=self.cancel,
                        device_indices=lease_devs,
                        partial_observer=self._note_partial,
                    )
                prefetch = None   # ownership passed: search() consumes/cancels
                bundle = search.search()
                if search.isocalc is not None:
                    self.last_isocalc_stats = dict(search.isocalc.last_stats)
                if prof:
                    prof.stop()
                    prof = None
                    logger.info("profile trace written to %s", self.profile_dir)
                bundle.timings.update(timings)
                if self.cancel is not None:
                    # last cooperative gate before results become durable: a
                    # cancelled/expired job must store NOTHING partial
                    self.cancel.check("store_results")
                if self.fence is not None:
                    # last FENCE gate before results become durable: a claim
                    # lost to a peer takeover must store NOTHING (the peer's
                    # rerun owns the results now)
                    self.fence()
                with phase_timer("store_results", bundle.timings):
                    ion_mzs = {
                        (table_sf, table_ad): mz
                        for table_sf, table_ad, mz in zip(
                            search.last_table.sfs,
                            search.last_table.adducts,
                            search.last_table.mzs[:, 0],
                        )
                    } if search.last_table is not None else None
                    # images first, index/parquet swap last: a failure anywhere
                    # in storage leaves the previous successful job's results
                    # fully queryable (ADVICE r1)
                    if self.sm_config.storage.store_images:
                        self._store_annotation_images(ds, search, bundle)
                    with tracing.span("store_tables"):
                        self.store.store(self.ds_id, job_id, bundle, ion_mzs)
                # pin the device high-water mark while this job's arrays
                # are still resident; the trace gets it as an event so
                # every per-phase hbm sample has a job-level roll-up
                self.last_hbm = devicemem.hbm_summary()
                if self.last_hbm.get("hbm_peak_bytes") is not None:
                    tracing.event("hbm_job_peak", **self.last_hbm)
            if self.fence is not None:
                # ledger-commit fence: a stale replica must not flip the
                # job row FINISHED under the takeover replica's run
                self.fence()
            with tracing.span("finish_job"):
                self.ledger.finish_job(job_id)
            if search.last_checkpoint is not None:
                # only after results are durably persisted: a storage failure
                # above must leave the checkpoint for the rerun to resume
                # from; and a failed cleanup must not FAIL a finished job
                try:
                    with tracing.span("checkpoint_finalize"):
                        search.last_checkpoint.finalize()
                except OSError:
                    logger.warning(
                        "could not remove search checkpoint shards under %s",
                        search.last_checkpoint.dir, exc_info=True)
            logger.info("job %d FINISHED (%d annotations)", job_id, len(bundle.annotations))
            succeeded = True
            return bundle
        except Exception as exc:
            if prefetch is not None:
                # job died between prefetch start and search(): stop the
                # background generation before reporting failure
                try:
                    prefetch.cancel()
                except Exception:
                    logger.warning("isotope prefetch cancel failed",
                                   exc_info=True)
            if prof:
                prof.stop()
            self.ledger.fail_job(job_id, f"{exc}\n{traceback.format_exc()}")
            # remove THIS job's partial index entries (the reference's ES
            # cleanup [U]); earlier successful jobs' rows stay queryable
            self.store.index.delete_ds(self.ds_id, job_id=job_id)
            if isinstance(exc, JobCancelledError):
                logger.info("job %d CANCELLED: %s", job_id, exc)
            else:
                logger.error("job %d FAILED: %s", job_id, exc)
            raise
        finally:
            if self.residency is not None:
                self.residency.release()
            # on failure the work dir survives even with clean=True: it holds
            # the checkpoint shards + staged input the rerun resumes from
            if clean and succeeded:
                with tracing.span("workdir_clean"):
                    self.work_dir.clean()
            elif clean:
                logger.info(
                    "job failed: keeping work dir %s for resume",
                    self.work_dir.path)

    def _note_partial(self, payload: dict) -> None:
        """Provisional annotations landed (ISSUE 13): remember the latest
        payload and forward it to the service's ``on_partial`` (exception-
        safe — a preview consumer can never fail the job)."""
        self.last_partial = dict(payload or {})
        if self.on_partial is None:
            return
        try:
            self.on_partial(self.last_partial)
        except Exception:
            logger.warning("on_partial consumer failed", exc_info=True)

    def _prepare_dataset(self, timings: dict[str, float]) -> SpectralDataset:
        """Stage the input + parse it into the canonical CSR layout.  The
        one overridable seam between job bookkeeping and scoring: a stream
        job (engine/stream.py) assembles its dataset from the committed
        chunk log instead of a staged imzML file, and everything else in
        ``run`` — ledger rows, device hold, search, fences, storage — is
        shared verbatim (which is what makes the end-of-acquisition pass
        bit-identical to a batch submit)."""
        with phase_timer("stage_input", timings):
            self.work_dir.copy_input_data(self.input_path)
        if self.cancel is not None:
            self.cancel.check("stage_input")
        with phase_timer("read_dataset", timings):
            ds = self._read_dataset()
        if self.cancel is not None:
            self.cancel.check("read_dataset")
        return ds

    def _prepare_resident(self, ds: SpectralDataset) -> None:
        """The dataset-only half of the jax backend build (``ds.flat_sorted``:
        m/z quantization, the intensity grid from the walked window
        occupancy, and m/z order by one sort of packed ``mz_q << 32 | index``
        keys, which is the stable order), made here, BEFORE the job asks for
        the chip, so the lease does not sit idle through it;
        ``JaxBackend.__init__`` then finds it cached on the dataset.  Only for a job that will build the single-device
        layout: the jax backend, one chip (or no pool and a 1x1 mesh), and
        not every chip it could be granted refused by its breaker.  On a
        resident dataset it is a dict lookup."""
        if self.sm_config.backend != "jax_tpu":
            return
        from ..models.breaker import every_chip_refuses
        from ..parallel.sharded import builds_single_device

        if not builds_single_device(
                self.sm_config, getattr(self.device_token, "n", None)):
            return
        pool = getattr(self.device_token, "pool", None)
        if every_chip_refuses(None if pool is None else range(pool.size)):
            return
        ppm = self.ds_config.image_generation.ppm
        # the dataset's lookup was read_dataset's; this span says what the
        # residency held after it and what the lookup evicted
        held = {} if self.residency is None else \
            self.residency.span_attrs("dataset")
        with tracing.span("prepare_resident", peaks=int(ds.n_peaks),
                          cached=ds.flat_sorted_cached(ppm), **held):
            ds.flat_sorted(ppm, site="pre_lease")
        if self.cancel is not None:
            self.cancel.check("prepare_resident")

    def _read_dataset(self) -> SpectralDataset:
        """Parse the staged imzML — or reuse the residency cache's copy,
        keyed on the staging manifest so a restaged DIFFERENT input misses."""
        path = self.work_dir.imzml_path()
        if self.residency is None:
            return SpectralDataset.from_imzml(path)
        import hashlib

        manifest = self.work_dir.file("input.manifest.json")
        content = manifest.read_text() if manifest.exists() else str(path)
        key = (self.ds_id, hashlib.sha256(content.encode()).hexdigest())
        return self.residency.dataset(
            key, lambda: SpectralDataset.from_imzml(path))

    def _store_annotation_images(
        self, ds: SpectralDataset, search: MSMBasicSearch, bundle: SearchResultsBundle
    ) -> None:
        """Persist ion images for annotations at FDR <= 0.5 (the reference
        stores images for scored target ions — ``store_sf_iso_images`` [U]).

        On the jax paths — single-device AND mesh-sharded — the images come
        off the DEVICE arrays (bit-identical to the numpy extraction via the
        shared integer grids) instead of being re-extracted on CPU (VERDICT
        r1 item 9); numpy_ref uses the numpy extractor.
        """
        import numpy as np

        table = search.last_table
        if table is None or bundle.annotations.empty:
            return
        # store_select / store_extract_images / store_write_images (and
        # store_tables in run) split the store_results phase: PERF.md
        # section 3, store_images_s
        with tracing.span("store_select"):
            keep = bundle.annotations[bundle.annotations.fdr_level <= 0.5]
            want = set(zip(keep.sf, keep.adduct))
            idx = [
                i for i, (sf, ad) in enumerate(zip(table.sfs, table.adducts))
                if (sf, ad) in want
            ]
            if not idx:
                return
            sub = table.__class__(
                sfs=[table.sfs[i] for i in idx],
                adducts=[table.adducts[i] for i in idx],
                mzs=table.mzs[idx],
                ints=table.ints[idx],
                n_valid=table.n_valid[idx],
                targets=table.targets[idx],
            )
        # the two spans abut on this thread and add up to the export's wall
        # time: the first ends when the FIRST chunk of the images is on the
        # host (a whole array is its own only chunk), the second runs from
        # there to the file's rename, the rest of the chunks landing under it
        image_format = self.sm_config.storage.image_format
        with tracing.span("store_extract_images", ions=len(idx)):
            backend = search.last_backend
            if image_format == "npz" and hasattr(backend, "iter_ion_images"):
                images = backend.iter_ion_images(sub)
                images.wait_first()
            elif backend is not None and hasattr(backend, "extract_ion_images"):
                images = np.asarray(backend.extract_ion_images(sub))
            else:
                from ..ops.imager_np import SortedPeakView, extract_ion_images

                view = SortedPeakView.prepare(ds, self.ds_config.image_generation.ppm)
                images = np.asarray(extract_ion_images(
                    view, sub, self.ds_config.image_generation.ppm))
            nbytes = int(images.nbytes)
            tracing.annotate(bytes=nbytes)
        with tracing.span("store_write_images", format=image_format,
                          bytes=nbytes):
            path = self.store.store_ion_images(
                self.ds_id, images,
                list(zip(sub.sfs, sub.adducts)), ds.nrows, ds.ncols,
                mask=ds.get_sample_area_mask(),
            )
        logger.info("stored %d ion image sets -> %s", len(idx), path)
