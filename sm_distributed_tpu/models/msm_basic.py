"""MSM basic search — the framework's "model": images -> metrics -> FDR.

Reference: ``sm/engine/msm_basic/msm_basic_search.py::MSMBasicSearch.search``
[U] (SURVEY.md #12, call stack §3.1): compute_sf_images -> sf_image_metrics ->
FDR.estimate_fdr.  Here the pipeline streams formula batches through a
backend's fused score function; the backend is selected by
``SMConfig.backend`` (numpy_ref | jax_tpu) per the north star.
"""

from __future__ import annotations

import hashlib
import os
import zlib
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np
import pandas as pd

from ..io.dataset import SpectralDataset
from ..ops import buckets as shape_buckets
from ..ops import metrics_np
from ..ops.fdr import FDR, DecoyAssignment, count_ranked
from ..ops.imager_np import SortedPeakView, extract_ion_images
from ..ops.isocalc import (
    ISOCALC_PATTERN_VERSION,
    IsocalcWrapper,
    IsotopePatternTable,
    resolve_device_blur,
)
from ..utils import tracing
from ..utils.cancel import JobCancelledError
from ..utils.config import DSConfig, SMConfig
from ..utils.failpoints import failpoint, record_recovery, register_failpoint
from ..utils.logger import logger, phase_timer
from . import faults, oom
from .breaker import get_device_breaker, record_degraded
from .faults import FP_CHIP_FAULT

FP_SHARD_WRITE = register_failpoint(
    "ckpt.shard_write",
    "between a checkpoint shard's tmp savez and its os.replace (torn/crash)")
FP_SHARD_LOAD = register_failpoint(
    "ckpt.shard_load", "per-shard checkpoint read (I/O error on resume)")
FP_DEVICE_SCORE = register_failpoint(
    "device.score_batch",
    "before scoring a batch group (TPU preemption / XLA failure mid-search)")
FP_DEVICE_ERROR = register_failpoint(
    "backend.device_error",
    "inside a device score_batches call — the consecutive-error seam the "
    "circuit breaker counts (open -> degrade to numpy -> half-open probe); "
    "raise:MemoryError injects an HBM RESOURCE_EXHAUSTED, which is a "
    "SIZING signal: batch backoff, no breaker trip (models/oom.py)")


# Checkpoint partition format version, hashed into the search fingerprint:
# bump whenever the group-partition RULE changes (a resume under a
# different partition would leave unscored zero rows).  v2 = the leading
# group is split to a single batch so the first FDR-rankable annotations
# land while later batches still run (ISSUE 13 streamed first results).
_PARTITION_VERSION = 2


# First-annotation observers (ISSUE 6): called once per search when the
# first checkpoint group's metrics land — the earliest moment FDR-rankable
# results exist.  Same producer-side pattern as logger phase observers /
# isocalc attach_metrics: the service's SLOTracker subscribes without this
# module importing the service layer.
_first_annotation_observers: list = []


def add_first_annotation_observer(fn) -> None:
    if fn not in _first_annotation_observers:
        _first_annotation_observers.append(fn)


def remove_first_annotation_observer(fn) -> None:
    if fn in _first_annotation_observers:
        _first_annotation_observers.remove(fn)


def _notify_first_annotation() -> None:
    """Exception-safe dispatch (observability never fails the pipeline)."""
    for fn in list(_first_annotation_observers):
        try:
            fn()
        except Exception:
            logger.warning("first-annotation observer %r failed", fn,
                           exc_info=True)


def _slice_table(table: IsotopePatternTable, s: int, e: int) -> IsotopePatternTable:
    return IsotopePatternTable(
        sfs=table.sfs[s:e],
        adducts=table.adducts[s:e],
        mzs=table.mzs[s:e],
        ints=table.ints[s:e],
        n_valid=table.n_valid[s:e],
        targets=table.targets[s:e],
    )


def maybe_order_table(table: IsotopePatternTable, order_ions: str,
                      formula_batch: int) -> IsotopePatternTable:
    """Apply parallel.order_ions: "mz" always orders, "table" never, "auto"
    orders when the stream has >=6 batches — the measured crossover: m/z
    locality won +20% at 6 batches (65k px) and 8.3x at 41 batches
    (262k px), but lost 17% at 3 batches where there is no locality to win
    and ordering spreads the blob-heavy target images' chaos cost across
    every batch (PERF.md ledger)."""
    if order_ions == "mz":
        return order_table_by_mz(table)
    if order_ions == "table":
        return table
    n_batches = -(-table.n_ions // max(1, formula_batch))
    return order_table_by_mz(table) if n_batches >= 6 else table


def order_table_by_mz(table: IsotopePatternTable) -> IsotopePatternTable:
    """Reorder ions by principal-peak m/z (stable), targets and decoys
    interleaved.  Per-ion metrics are identical in any order (the window-
    bound histogram is exact per ion); what changes is BATCH COMPOSITION:
    a formula_batch slice of an m/z-sorted table has an m/z-LOCALIZED
    window union, so per-batch peak compaction (ops/imager_jax.py) keeps
    only that narrow band's peaks for every batch — total histogram-
    scatter work across a T-batch stream drops from ~T x N_resident
    (every batch touching most resident peaks) toward ~N_resident (each
    peak scattered where its band is scored).  The effect grows with
    batch count, i.e. exactly in the BASELINE #5 regime where the HBM
    guard forces small batches (VERDICT r3 item 3)."""
    order = np.argsort(table.mzs[:, 0], kind="stable")
    return IsotopePatternTable(
        sfs=[table.sfs[i] for i in order],
        adducts=[table.adducts[i] for i in order],
        mzs=table.mzs[order],
        ints=table.ints[order],
        n_valid=table.n_valid[order],
        targets=table.targets[order],
    )


class NumpyBackend:
    """The reference-semantics CPU backend (stand-in for the Spark-RDD
    executor; also the parity oracle for jax_tpu)."""

    name = "numpy_ref"

    def __init__(self, ds: SpectralDataset, ds_config: DSConfig):
        self.ds = ds
        self.ds_config = ds_config
        # sort once, reuse per batch; ppm selects the shared integer
        # intensity grid (exact cross-backend image parity)
        self._view = SortedPeakView.prepare(ds, ds_config.image_generation.ppm)

    def score_batches(self, tables, cancel=None) -> list[np.ndarray]:
        """Score an iterable of batches one at a time (no pipelining on CPU;
        accepts a lazy generator so only one slice is live at once).
        ``cancel`` is checked between batches — the host path's finest
        cooperative-cancellation grain."""
        out = []
        for t in tables:
            if cancel is not None:
                cancel.check("score_batch")
            out.append(self.score_batch(t))
        return out

    def score_batch(self, table: IsotopePatternTable) -> np.ndarray:
        """(n_ions, 4) array of (chaos, spatial, spectral, msm)."""
        with tracing.span("score_batch", backend=self.name,
                          ions=int(table.n_ions)):
            return self._score_batch(table)

    def _score_batch(self, table: IsotopePatternTable) -> np.ndarray:
        img_cfg = self.ds_config.image_generation
        images = extract_ion_images(self._view, table, img_cfg.ppm)
        out = np.zeros((table.n_ions, 4))
        for i in range(table.n_ions):
            out[i] = metrics_np.ion_metrics(
                images[i],
                table.ints[i],
                int(table.n_valid[i]),
                self.ds.nrows,
                self.ds.ncols,
                nlevels=img_cfg.nlevels,
                do_preprocessing=img_cfg.do_preprocessing,
                q=img_cfg.q,
            )
        return out


def make_backend(name: str, ds: SpectralDataset, ds_config: DSConfig,
                 sm_config: SMConfig, table: IsotopePatternTable | None = None,
                 device_indices=None):
    """``table``: the search's full ion table, when known up front — the jax
    backends drop dataset peaks outside the union of its windows (exact;
    the reference's "only hits shuffle" property).

    ``device_indices`` (ISSUE 7): the job's device-pool lease chips — 1
    chip pins the single-device fused graph to it, N chips score through
    the pjit-sharded sub-mesh; None = config-mesh over all devices."""
    if name == "numpy_ref":
        return NumpyBackend(ds, ds_config)
    if name == "jax_tpu":
        from ..parallel.sharded import make_jax_backend  # deferred: jax import is heavy

        return make_jax_backend(ds, ds_config, sm_config, restrict_table=table,
                                device_indices=device_indices)
    raise ValueError(f"unknown backend {name!r}")


def isocalc_device_blur(sm_config: SMConfig) -> bool:
    """The oracle-or-device mode the engine's wrappers run in."""
    # "on" forces the device stage; "off" leaves the decision to the
    # SM_ISOCALC_DEVICE env (None), so ad-hoc probes can opt in without
    # a config edit
    return resolve_device_blur(
        True if sm_config.parallel.isocalc_device == "on" else None)


def make_isocalc(ds_config: DSConfig, sm_config: SMConfig,
                 cache_dir: str | None) -> IsocalcWrapper:
    """IsocalcWrapper wired to the engine's parallel.* isocalc knobs."""
    par = sm_config.parallel
    return IsocalcWrapper(
        ds_config.isotope_generation,
        cache_dir=cache_dir,
        n_procs=par.isocalc_workers or None,
        device_blur=isocalc_device_blur(sm_config),
        chunk_size=par.isocalc_chunk,
    )


@dataclass(frozen=True)
class ResidentIonTable:
    """A finished ion table as ``engine/residency.DatasetResidency`` keeps it
    across jobs of one parameter set: what ``MSMBasicSearch.search()`` needs
    of an ``IsotopePrefetch`` and nothing else.  The table's arrays are
    read-only (two workers may score it at once); ``device_blur`` is the
    mode its patterns were made in, which the pairs fingerprint hashes."""

    fdr: FDR
    assignment: DecoyAssignment
    table: IsotopePatternTable
    device_blur: bool


def ion_table_key(formulas: list[str], ds_config: DSConfig,
                  sm_config: SMConfig) -> tuple:
    """Content identity of the ion table a job scores: the de-duplicated
    formula list in order, and everything the decoy draw (target adducts,
    ``decoy_sample_size``, ``seed``) and the patterns (charge, sigma,
    pts_per_mz, n_peaks, oracle-or-device mode, pattern version) are a
    function of.  Nothing of the dataset: every upload against one
    database shares the entry."""
    iso = ds_config.isotope_generation
    fdr = sm_config.fdr
    return ("ion_table",
            hashlib.sha256("\x00".join(formulas).encode()).hexdigest(),
            len(formulas), tuple(iso.adducts), iso.charge, iso.isocalc_sigma,
            iso.isocalc_pts_per_mz, iso.n_peaks,
            fdr.decoy_sample_size, fdr.seed,
            isocalc_device_blur(sm_config),
            ISOCALC_PATTERN_VERSION)


def _table_bytes(table: IsotopePatternTable) -> int:
    return int(table.mzs.nbytes + table.ints.nbytes + table.n_valid.nbytes
               + table.targets.nbytes)


class ResidentStream:
    """The consumer side of a ``PatternStream`` over a table that is whole
    already (a residency hit): nothing to wait for, to cancel or to count."""

    gen_seconds = 0.0
    cold_patterns = 0

    def __init__(self, table: IsotopePatternTable):
        self._table = table
        self.n_ions = table.n_ions

    def wait_rows(self, n: int, timeout: float | None = None) -> int:
        return self.n_ions

    def table_view(self) -> IsotopePatternTable:
        return self._table

    result_table = table_view

    def cancel(self) -> None:
        pass


class IsotopePrefetch:
    """Background decoy selection + isotope-pattern generation (ISSUE 3
    layer 3).  SearchJob starts this BEFORE staging/parsing the input, so
    the dominant cold-path cost — pattern generation — overlaps the input
    pipeline instead of following it.  Everything here depends only on the
    formula list and configs, never on the dataset - so with a ``residency``
    the finished table of an earlier job of the same parameter set is asked
    for first (``ion_table_key``).  A hit starts no thread and builds no
    wrapper and no stream: ``result()`` returns at once, ``isocalc`` stays
    None.  A miss runs as without a residency, and ``keep()`` offers the
    table once its stream is whole.

    ``result()`` joins the setup thread (decoy sampling + cache-shard load +
    stream start — the generation itself keeps running inside the returned
    ``PatternStream``) and re-raises any setup failure.  ``cancel()`` tears
    the stream down when the job dies before consuming it.
    """

    def __init__(self, formulas: list[str], ds_config: DSConfig,
                 sm_config: SMConfig, cache_dir: str | None,
                 residency=None):
        import threading

        self.formulas = list(dict.fromkeys(formulas))
        self.ds_config = ds_config
        self.sm_config = sm_config
        self.cache_dir = cache_dir
        self.residency = residency
        self.timings: dict[str, float] = {}
        self.fdr: FDR | None = None
        self.assignment: DecoyAssignment | None = None
        self.isocalc: IsocalcWrapper | None = None
        self.device_blur = isocalc_device_blur(sm_config)
        self.stream = None
        self._error: BaseException | None = None
        self._thread = None
        if residency is not None and self._take_resident():
            return
        # thread hop: capture the caller's (SearchJob attempt) trace context
        # so prefetch setup + the generation stream trace into the job
        self._trace = tracing.current()
        self._thread = threading.Thread(
            target=self._run, name="isotope-prefetch", daemon=True)
        self._thread.start()

    def _take_resident(self) -> bool:
        """Ask the residency for this parameter set's table.  On a hit this
        is the job's whole ``isotope_prefetch_setup``: the span is emitted
        here with explicit timing, since only a hit has it on this thread
        (a miss opens it around ``_setup``, on the prefetch thread)."""
        import time

        ts, t0, c0 = time.time(), time.perf_counter(), time.thread_time()
        self._key = ion_table_key(self.formulas, self.ds_config,
                                  self.sm_config)
        entry = self.residency.ion_table(self._key)
        if entry is None:
            return False
        self.fdr, self.assignment = entry.fdr, entry.assignment
        self.device_blur = entry.device_blur
        self.stream = ResidentStream(entry.table)
        ctx = tracing.current()
        if ctx is not None:
            tracing.emit_span(
                ctx, "isotope_prefetch_setup", ts=ts,
                cpu=time.thread_time() - c0,
                dur=time.perf_counter() - t0, parent_id=ctx.span_id,
                formulas=len(self.formulas), ions=entry.table.n_ions,
                cache="resident", table_bytes=_table_bytes(entry.table))
        return True

    def keep(self) -> None:
        """Offer a miss's table to the residency - only once its stream is
        whole: a failed, cancelled or partial table is never kept.  From
        here on the arrays are read-only, for this job's scoring too."""
        if (self.residency is None or self._thread is None
                or not self.stream.complete()):
            return
        table = self.stream.table_view()
        for arr in (table.mzs, table.ints, table.n_valid, table.targets):
            arr.flags.writeable = False
        self.residency.keep_ion_table(self._key, ResidentIonTable(
            self.fdr, self.assignment, table, self.device_blur))

    def _run(self) -> None:
        try:
            with tracing.attach(self._trace), \
                    tracing.span("isotope_prefetch_setup"):
                self._setup()
        except BaseException as exc:  # noqa: BLE001 — result() re-raises
            self._error = exc

    def _setup(self) -> None:
        import time

        iso_cfg = self.ds_config.isotope_generation
        fdr_cfg = self.sm_config.fdr
        self.fdr = FDR(
            decoy_sample_size=fdr_cfg.decoy_sample_size,
            target_adducts=iso_cfg.adducts,
            seed=fdr_cfg.seed,
        )
        t0 = time.perf_counter()
        with tracing.span("decoy_selection", formulas=len(self.formulas),
                          decoys=fdr_cfg.decoy_sample_size):
            self.assignment = self.fdr.decoy_adduct_selection(self.formulas)
            self.pairs, self.flags = self.assignment.all_ion_tuples(
                self.formulas, iso_cfg.adducts)
            tracing.annotate(
                target_adducts=len(iso_cfg.adducts),
                triples=self.assignment.n_triples,
                distinct_decoys=self.assignment.n_distinct_decoys)
        self.timings["decoy_selection"] = time.perf_counter() - t0
        # wrapper construction loads the cache shards (warm: seconds at
        # 1.68M ions; span pattern_cache_load) — deliberately inside this
        # thread too
        self.isocalc = make_isocalc(
            self.ds_config, self.sm_config, self.cache_dir)
        self.stream = self.isocalc.stream_table(self.pairs, self.flags)
        # onto isotope_prefetch_setup: were all, none or some of the
        # table's patterns in the shards
        missing, ions = self.stream.n_missing, self.stream.n_ions
        tracing.annotate(
            formulas=len(self.formulas), ions=ions,
            cache="warm" if not missing else
            "cold" if missing == ions else "partial",
            table_bytes=_table_bytes(self.stream.table_view()))

    def result(self):
        """(fdr, assignment, stream) — blocks on setup only."""
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            raise self._error
        return self.fdr, self.assignment, self.stream

    def cancel(self) -> None:
        if self._thread is not None:
            self._thread.join()
        if self.stream is not None:
            self.stream.cancel()


class SearchCheckpoint:
    """Mid-search checkpoint of scored metrics (SURVEY §5.4: the reference has
    only coarse resume — theor_peaks cache + work-dir skips [U]; at BASELINE
    config #3/#5 scale a multi-hour search needs a finer grain).

    Append-style: one small npz shard per completed batch group (only that
    group's metric rows), so total checkpoint I/O is linear in ions — a single
    monolithic file rewritten per group would be quadratic and stall the
    device pipeline at every group boundary.  Shards are keyed by a
    fingerprint of (ion table, batch partition, image config, dataset
    content); a resume trusts only the contiguous shard prefix g0..gk.
    Metrics are backend-independent (cross-backend parity is bit-exact), so a
    search may resume under a different backend than it started with.
    """

    def __init__(self, directory: str | Path, fingerprint: str,
                 process_id: int = 0):
        # per-process filenames: co-located processes (or a shared work_dir
        # mount) must not race on one tmp/ckpt inode
        self.dir = Path(directory)
        self.prefix = f"msm_search.p{process_id}"
        self.fingerprint = fingerprint
        self.dir.mkdir(parents=True, exist_ok=True)

    def _shard(self, gi: int) -> Path:
        return self.dir / f"{self.prefix}.g{gi:05d}.ckpt.npz"

    def load(self, metrics: np.ndarray, n_groups: int,
             row_ranges: list[tuple[int, int]]) -> int:
        """Restore ``metrics`` rows in place from the contiguous shard
        prefix; return # of completed batch groups (0 if absent/stale).

        A shard that is unreadable, truncated, shape-mismatched, or fails its
        CRC32 checksum is treated as MISSING — the prefix ends there and the
        groups recompute — never as fatal: a torn checkpoint write must
        degrade to extra work, not crash the resume path."""
        done = 0
        for gi in range(n_groups):
            path = self._shard(gi)
            if not path.exists():
                break
            try:
                failpoint(FP_SHARD_LOAD, path=path)
                with np.load(path, allow_pickle=False) as z:
                    if (str(z["fingerprint"]) != self.fingerprint
                            or int(z["n_groups"]) != n_groups):
                        break             # stale checkpoint — normal miss
                    s, e = row_ranges[gi]
                    rows = z["rows"]
                    if rows.shape != (e - s, metrics.shape[1]):
                        raise ValueError("shard row shape mismatch")
                    # np.load happily returns rows from a zip whose payload
                    # bytes were silently corrupted in place; the checksum
                    # catches what the container format does not
                    if int(z["checksum"]) != zlib.crc32(
                            np.ascontiguousarray(rows).tobytes()):
                        raise ValueError("shard checksum mismatch")
                    metrics[s:e] = rows
            except Exception as exc:
                # unreadable/corrupt shard: trust only the prefix before it
                record_recovery("ckpt.corrupt_shard")
                logger.warning(
                    "checkpoint shard %s rejected (%s); resuming from the "
                    "%d-group prefix before it", path.name, exc, done)
                break
            done = gi + 1
        return done

    def save(self, metrics: np.ndarray, gi: int, n_groups: int,
             row_ranges: list[tuple[int, int]]) -> None:
        s, e = row_ranges[gi]
        rows = np.ascontiguousarray(metrics[s:e])
        # disk-budget preflight (ISSUE 10, service/resources.py): a full
        # disk fails the shard BEFORE a torn write, with headroom reserved
        # for the seams below this one.  No-op outside the service.
        from ..service import resources as _resources

        _resources.preflight("ckpt.shard_write", rows.nbytes + 4096)
        tmp = self._shard(gi).with_suffix(".tmp.npz")  # same dir -> atomic
        np.savez(tmp, fingerprint=np.str_(self.fingerprint),
                 rows=rows, n_groups=n_groups,
                 checksum=zlib.crc32(rows.tobytes()))
        failpoint(FP_SHARD_WRITE, path=tmp)
        os.replace(tmp, self._shard(gi))

    def finalize(self) -> None:
        # shards AND any orphaned tmp from a kill between savez and replace
        for path in self.dir.glob(f"{self.prefix}.g*"):
            path.unlink(missing_ok=True)


@dataclass
class SearchResultsBundle:
    """Everything the orchestrator persists (reference: metrics df + sparse
    ion images handed to SearchResults.store [U])."""

    annotations: pd.DataFrame      # target ions with fdr/fdr_level
    all_metrics: pd.DataFrame      # every scored ion incl. decoys
    timings: dict[str, float] = field(default_factory=dict)
    # the draw the annotations were ranked by, stored beside them as
    # target_decoy_add.parquet (None: nothing was ranked)
    assignment: DecoyAssignment | None = None


class MSMBasicSearch:
    """End-to-end search over a dataset + formula list (class name kept)."""

    def __init__(
        self,
        ds: SpectralDataset,
        formulas: list[str],
        ds_config: DSConfig,
        sm_config: SMConfig | None = None,
        isocalc_cache_dir: str | None = None,
        checkpoint_dir: str | None = None,
        backend_cache=None,
        prefetch: IsotopePrefetch | None = None,
        cancel=None,
        device_indices=None,
        partial_observer=None,
    ):
        self.ds = ds
        self.formulas = list(dict.fromkeys(formulas))  # dedup, keep order
        self.ds_config = ds_config
        self.sm_config = sm_config or SMConfig.get_conf()
        self.checkpoint_dir = checkpoint_dir
        # service mode (engine/residency.DatasetResidency): reuse a compiled
        # backend across jobs when the search fingerprint + backend-shaping
        # knobs all match — the second job skips device transfer AND compile
        self.backend_cache = backend_cache
        # orchestrator-started generation (SearchJob overlap): decoys +
        # isocalc already running — search() consumes its stream instead of
        # starting one
        self.prefetch = prefetch
        # cooperative cancellation (utils/cancel.CancelToken or None):
        # checked at checkpoint-group boundaries and inside the host
        # backend's per-batch loop
        self.cancel = cancel
        # the job's device-pool lease chips (ISSUE 7): forwarded into
        # make_backend so a 1-chip job pins to its chip and an N-chip job
        # scores through the pjit-sharded sub-mesh; None = all devices
        self.device_indices = (tuple(int(i) for i in device_indices)
                               if device_indices else None)
        self.isocalc_cache_dir = isocalc_cache_dir
        # the prefetch's wrapper, once search() has joined it: None on an
        # ion-table residency hit, which builds none
        self.isocalc: IsocalcWrapper | None = None
        # populated by search(); the orchestrator reads these to persist ion
        # images / m/z values for annotated ions (engine/search_job.py) —
        # last_backend lets the jax path export DEVICE images instead of
        # re-extracting on CPU
        self.last_table: IsotopePatternTable | None = None
        self.last_backend = None
        self.last_checkpoint: SearchCheckpoint | None = None
        # streamed first results (ISSUE 13): called once per search with a
        # provisional-annotation payload when the first FDR-rankable group
        # lands (the service threads it to the job record's `partial`
        # field); None = no consumer
        self.partial_observer = partial_observer
        # effective scoring batch (ISSUE 10/13): the config formula_batch
        # snapped to the shape-bucket lattice (ops/buckets.effective_batch
        # — the jax backends pad with the same snap, so slicing and
        # padding can never disagree), capped by a previously LEARNED
        # proven-safe size for this (dataset shape, backend, lease) — set
        # in _score_and_rank before the fingerprint (the checkpoint
        # partition depends on it)
        self._batch_eff = shape_buckets.effective_batch(
            self.sm_config.parallel)
        # in-flight OOM backoff cap: once a group halves its way to a
        # fitting size, every LATER group of this search starts capped
        # there (the device backend's padding batch already shrank)
        self._oom_cap = 0

    def _fingerprint(self, table: IsotopePatternTable) -> str:
        """Identity of a search for checkpoint validity: the exact ion table
        (decoys included — they depend on the FDR seed), image-config knobs,
        the batch partition (groups_done counts groups under a specific
        (formula_batch, checkpoint_every) split — resuming under a different
        split would leave unscored zero rows), and dataset content (strided
        peak sample + exact intensity sum, so a restaged same-shape dataset
        invalidates the checkpoint)."""
        img = self.ds_config.image_generation
        par = self.sm_config.parallel
        h = hashlib.sha256()
        h.update(repr((self.ds.nrows, self.ds.ncols, int(self.ds.n_peaks),
                       img.ppm, img.nlevels, img.do_preprocessing, img.q,
                       # the EFFECTIVE batch (== the lattice-snapped
                       # formula_batch unless an OOM-learned safe size caps
                       # it): the checkpoint partition is keyed on what
                       # actually ran, under the current partition format
                       self._batch_eff, par.checkpoint_every,
                       _PARTITION_VERSION)).encode())
        stride = max(1, self.ds.mzs_flat.size // 65536)
        h.update(np.ascontiguousarray(self.ds.mzs_flat[::stride]).tobytes())
        h.update(np.ascontiguousarray(self.ds.ints_flat[::stride]).tobytes())
        h.update(np.float64(
            self.ds.ints_flat.sum(dtype=np.float64)).tobytes())
        h.update("\x00".join(table.sfs).encode())
        h.update("\x00".join(table.adducts).encode())
        h.update(np.ascontiguousarray(table.mzs).tobytes())
        return h.hexdigest()

    def _fingerprint_pairs(self, table: IsotopePatternTable) -> str:
        """Checkpoint fingerprint computable BEFORE patterns exist (the
        overlapped path scores leading groups while generation runs, so it
        cannot hash the pattern m/z block like ``_fingerprint``).  Instead
        of pattern bits it hashes what determines them: the exact ion list,
        the isotope-generation params, and ``ISOCALC_PATTERN_VERSION`` —
        which MUST be bumped when pattern math changes result bits, or a
        stale checkpoint would resume against different patterns."""
        img = self.ds_config.image_generation
        par = self.sm_config.parallel
        iso = self.ds_config.isotope_generation
        h = hashlib.sha256()
        h.update(repr((self.ds.nrows, self.ds.ncols, int(self.ds.n_peaks),
                       img.ppm, img.nlevels, img.do_preprocessing, img.q,
                       # the EFFECTIVE batch (== the lattice-snapped
                       # formula_batch unless an OOM-learned safe size caps
                       # it): the checkpoint partition is keyed on what
                       # actually ran, under the current partition format
                       self._batch_eff, par.checkpoint_every,
                       _PARTITION_VERSION)).encode())
        stride = max(1, self.ds.mzs_flat.size // 65536)
        h.update(np.ascontiguousarray(self.ds.mzs_flat[::stride]).tobytes())
        h.update(np.ascontiguousarray(self.ds.ints_flat[::stride]).tobytes())
        h.update(np.float64(
            self.ds.ints_flat.sum(dtype=np.float64)).tobytes())
        h.update("\x00".join(table.sfs).encode())
        h.update("\x00".join(table.adducts).encode())
        h.update(repr((iso.charge, iso.isocalc_sigma, iso.isocalc_pts_per_mz,
                       iso.n_peaks, ISOCALC_PATTERN_VERSION,
                       self._device_blur)).encode())
        return h.hexdigest()

    def _agree_resume_point(self, done: int) -> int:
        """Multi-host: every process must resume from the SAME batch group,
        else they issue different collective sequences and the SPMD program
        deadlocks.  Checkpoints are per-process local files, so agree on
        min(done) across processes (rows below min are valid everywhere)."""
        if self.sm_config.backend != "jax_tpu":
            return done
        import jax

        if jax.process_count() == 1:
            return done
        from jax.experimental import multihost_utils

        all_done = multihost_utils.process_allgather(np.int64(done))
        agreed = int(np.min(all_done))
        if agreed != done:
            logger.info(
                "checkpoint resume point lowered %d -> %d to agree with "
                "other processes", done, agreed)
        return agreed

    _ANN_COLUMNS = ["sf", "adduct", "msm", "fdr", "fdr_level",
                    "chaos", "spatial", "spectral"]
    _ALL_COLUMNS = ["sf", "adduct", "is_target", "chaos", "spatial",
                    "spectral", "msm"]

    def _reduced_slices(self, group: list[tuple[int, int]]) -> list[tuple[int, int]]:
        """Re-split a checkpoint group's batch slices at the degraded
        (breaker-open) batch size.  Group row ranges — and therefore the
        checkpoint partition — are untouched; only the host scoring grain
        shrinks."""
        cap = max(1, self.sm_config.service.breaker_degraded_batch)
        return [(a, min(a + cap, e))
                for s, e in group for a in range(s, e, cap)]

    def _oom_key(self) -> str:
        """Safe-batch registry key: what a batch's HBM footprint depends
        on (models/oom.py).  Keyed on the PIXEL BUCKET, not the raw count
        (ISSUE 13): every dataset size in a lattice bucket runs the same
        executables at the same scratch shapes, so a learned safe batch
        transfers across them."""
        return oom.shape_key(self.ds.n_pixels, self.sm_config.backend,
                             self.device_indices)

    @staticmethod
    def _capped_slices(slices: list[tuple[int, int]],
                       cap: int) -> list[tuple[int, int]]:
        """Re-split scoring slices at ``cap`` ions.  The checkpoint
        partition (group row ranges) is untouched — only the per-call
        scoring grain shrinks, exactly like ``_reduced_slices``.  Callers
        pass lattice-point caps (``_oom_backoff`` snaps them down), so a
        shrunk batch lands on a primer-enumerated executable instead of
        minting a one-off size."""
        return [(a, min(a + cap, e))
                for s, e in slices for a in range(s, e, cap)]

    def _oom_backoff(self, backend, slices: list[tuple[int, int]],
                     cap: int, exc: BaseException) -> int:
        """HBM OOM recovery (ISSUE 10): halve the scoring batch and tell
        the device backend to shrink its static padding size.  Returns the
        new cap, or 0 when the batch is already a single ion (nothing left
        to shrink — the OOM is then a real failure for the retry policy,
        but still NOT a breaker signal)."""
        cur = cap or max(e - s for s, e in slices)
        new = cur // 2
        if new >= 1 and shape_buckets.buckets_enabled(
                self.sm_config.parallel):
            # snap the shrunk cap DOWN to the lattice so the backoff lands
            # on a primer-enumerated executable (ISSUE 13)
            new = shape_buckets.batch_bucket_down(new)
        oom.record_oom_event("score_group", str(exc))
        if new < 1:
            logger.error(
                "device OOM at a single-ion batch — cannot back off "
                "further: %s", exc)
            return 0
        if hasattr(backend, "shrink_batch"):
            backend.shrink_batch(new)
        logger.warning(
            "device OOM while scoring — a SIZING signal, not a device "
            "fault (no breaker count): halving batch %d -> %d and "
            "retrying in place (%s)", cur, new, exc)
        tracing.event("oom_backoff", from_batch=cur, to_batch=new,
                      error=str(exc)[:300])
        return new

    def _score_group(self, backend, table, metrics: np.ndarray,
                     group: list[tuple[int, int]], breaker, use_device: bool,
                     degraded: bool):
        """Score one checkpoint group through the circuit breaker.  Device
        errors feed ``record_failure``; below threshold they fail the
        attempt (the retry may find a healthy device), at threshold the
        breaker OPENS and this group — and the rest of the job — degrades
        in place to the numpy oracle at reduced batch.  Metrics are
        backend-independent (bit-exact parity), so a mid-job switch is
        invisible in the results.

        Every non-cancel exception routes through the ONE fault taxonomy
        (models/faults.py, ISSUE 14).  A *program* fault (raised while
        tracing, lowering or compiling — a Mosaic refusal, a kernel past
        its scoped VMEM, a removed API) fails the job untouched by any of
        the machinery below.  HBM ``RESOURCE_EXHAUSTED`` is a
        *sizing* signal: the batch halves and the group rescores in place,
        the breaker never counts it, and the converged size is remembered
        so the next job on this shape starts there.  A *transient* fault
        (collective timeout, connection reset) fails the attempt into the
        retry policy — same chip, backoff, no breaker count, no
        quarantine.  A *sticky* fault reports the lease chips to the
        health tracker (quarantine / probe attribution) AND counts on the
        per-chip breaker.  Returns the (possibly swapped) backend and
        degraded flag."""
        on_device = use_device and not degraded
        slices = self._reduced_slices(group) if degraded else group
        if self._oom_cap:
            # an earlier group already backed off: the backend's padding
            # batch is shrunk, so later groups must arrive pre-capped
            slices = self._capped_slices(slices, self._oom_cap)
        oom_cap = 0
        while True:
            try:
                if on_device:
                    # injected consecutive-device-error seam (chaos sweep:
                    # breaker opens mid-job, degrades, converges to golden)
                    failpoint(FP_DEVICE_ERROR)
                    # classified chip-fault seam (ISSUE 14): the injected
                    # exception class selects the taxonomy — see faults.py
                    failpoint(FP_CHIP_FAULT)
                # lazy slices: every backend exposes score_batches; the jax
                # one pipelines (async-enqueues all batches in the group
                # before syncing any), the numpy one consumes one at a time
                outs = backend.score_batches(
                    (_slice_table(table, s, e) for s, e in slices),
                    cancel=self.cancel)
            except JobCancelledError:
                raise
            except Exception as exc:
                injected = ("backend.device_error" in str(exc)
                            or "backend.chip_fault" in str(exc))
                if not (on_device or injected):
                    raise             # a host-backend bug is not a device fault
                kind = faults.classify(exc)
                if kind == faults.FAULT_PROGRAM:
                    # raised while tracing, lowering or compiling: the
                    # program is wrong for this installation or shape, the
                    # chip is fine.  Fail the job with the compiler's
                    # words — counting it on the breaker would end in a
                    # numpy degrade that reports `done` with the device
                    # unused, and batch backoff would hunt for a size at
                    # which a broken kernel happens to compile
                    logger.error(
                        "program fault while scoring (not a device fault: "
                        "no breaker count, no degrade, no backoff): "
                        "%s: %s", type(exc).__name__, exc)
                    tracing.event("program_fault",
                                  error=f"{type(exc).__name__}: {exc}"[:300])
                    raise
                if kind == faults.FAULT_OOM:
                    new_cap = self._oom_backoff(backend, slices, oom_cap, exc)
                    if not new_cap:
                        raise         # single-ion batch still OOMs: let the
                                      # retry policy handle it — no breaker
                    oom_cap = new_cap
                    slices = self._capped_slices(slices, new_cap)
                    continue
                faults.report_device_fault(self.device_indices, kind, exc)
                if kind == faults.FAULT_TRANSIENT:
                    # known-recoverable runtime hiccup: the retry policy
                    # probes the SAME chip after backoff — no breaker
                    # count, no quarantine (the regression the old breaker
                    # test caused: a collective timeout opened it)
                    logger.warning(
                        "transient device fault while scoring — retrying "
                        "via the job retry policy (no breaker count): %s",
                        exc)
                    raise
                now_open = breaker.record_failure()
                logger.warning(
                    "sticky device fault while scoring (breaker %s after "
                    "it; lease chips reported for quarantine): %s",
                    breaker.state, exc)
                if not now_open:
                    raise             # below threshold: let the retry policy
                                      # probe the device again
                record_degraded()
                logger.warning(
                    "device breaker opened mid-job: degrading to the numpy "
                    "backend at batch %d",
                    self.sm_config.service.breaker_degraded_batch)
                backend = NumpyBackend(self.ds, self.ds_config)
                self.last_backend = backend
                degraded = True
                slices = self._reduced_slices(group)
                outs = backend.score_batches(
                    (_slice_table(table, s, e) for s, e in slices),
                    cancel=self.cancel)
                break
            else:
                if on_device:
                    # a cleanly scored device group closes a half-open probe
                    # and resets the consecutive-error count — and clears
                    # the lease chips' suspect state (ISSUE 14)
                    breaker.record_success()
                    faults.report_device_ok(self.device_indices)
                break
        if oom_cap:
            # the group converged at oom_cap: proven-safe — later groups
            # of THIS search stay capped, and later jobs on this
            # (dataset shape, backend, lease) start there
            self._oom_cap = oom_cap
            oom.record_safe_batch(self._oom_key(), oom_cap)
        for (s, e), out in zip(slices, outs):
            metrics[s:e] = out
        return backend, degraded

    def _emit_partial(self, fdr: FDR, assignment: DecoyAssignment,
                      table: IsotopePatternTable, metrics: np.ndarray,
                      n_scored: int, gi: int) -> None:
        """Provisional annotations over the scored prefix (ISSUE 13
        streamed first results): rank the first ``n_scored`` ions' msm
        through the REAL FDR estimator (the decoy set is the prefix's —
        provisional by construction, converging to the final ranking as
        groups land) and publish a small summary to the job trace and the
        ``partial_observer`` (the service threads it into the job record's
        ``partial`` field).  Best-effort: a failure here degrades to no
        preview, never a failed search."""
        if n_scored >= table.n_ions or n_scored <= 0:
            return                    # single group: final results imminent
        if self.partial_observer is None and not tracing.enabled():
            return
        try:
            sub = pd.DataFrame({
                "sf": table.sfs[:n_scored],
                "adduct": table.adducts[:n_scored],
                "msm": metrics[:n_scored, 3],
            })
            ann = fdr.estimate_fdr(sub, assignment)
            top = ann.sort_values("msm", ascending=False).head(5)
            payload = {
                "provisional": True,
                "group": int(gi),
                "n_scored": int(n_scored),
                "n_ions": int(table.n_ions),
                "annotations": int(len(ann)),
                "fdr_10pct": int((ann["fdr"] <= 0.1).sum()),
                "top": [
                    {"sf": str(r.sf), "adduct": str(r.adduct),
                     "msm": round(float(r.msm), 6),
                     "fdr": round(float(r.fdr), 6)}
                    for r in top.itertuples()
                ],
            }
        except Exception:
            logger.warning("provisional partial annotations failed",
                           exc_info=True)
            return
        tracing.event("partial_annotations",
                      **{k: v for k, v in payload.items() if k != "top"})
        obs = self.partial_observer
        if obs is not None:
            try:
                obs(payload)
            except Exception:
                logger.warning("partial-results observer %r failed", obs,
                               exc_info=True)

    def search(self) -> SearchResultsBundle:
        timings: dict[str, float] = {}
        if not self.formulas:
            return SearchResultsBundle(
                annotations=pd.DataFrame(columns=self._ANN_COLUMNS),
                all_metrics=pd.DataFrame(columns=self._ALL_COLUMNS),
            )
        # SearchJob started decoys + generation before staging: by the time
        # search() runs, the stream has been computing all along.  Without
        # one (overlap_isocalc "off", direct callers) the same prefetch is
        # made here and joined at once: one way to get a table, and one
        # lookup of the resident one in front of it
        prefetch = self.prefetch or IsotopePrefetch(
            self.formulas, self.ds_config, self.sm_config,
            self.isocalc_cache_dir, residency=self.backend_cache)
        with tracing.span("prefetch_join"):
            fdr, assignment, stream = prefetch.result()
        self.isocalc = prefetch.isocalc
        self._device_blur = prefetch.device_blur
        timings.update(prefetch.timings)
        try:
            return self._score_and_rank(stream, fdr, assignment, timings,
                                        keep=prefetch.keep)
        except BaseException:
            stream.cancel()
            raise

    def _score_and_rank(self, stream, fdr: FDR, assignment: DecoyAssignment,
                        timings: dict[str, float],
                        keep) -> SearchResultsBundle:
        # Overlapped scoring (ISSUE 3 layer 3): with the host backend, the
        # leading checkpoint groups score as soon as their pattern rows are
        # published — generation and scoring run concurrently.  The device
        # backend consumes the WHOLE table up front (window-union peak
        # restriction + executable presizing), so it waits for the stream
        # instead; its overlap is at the SearchJob level (staging/parse).
        overlap = (self.sm_config.parallel.overlap_isocalc != "off"
                   and self.sm_config.backend == "numpy_ref")
        with phase_timer("isotope_patterns", timings):
            if overlap:
                table = stream.table_view()   # rows fill in as chunks land
            else:
                table = stream.result_table()
                keep()       # whole now: the next job's residency hit
                # what the wrapper's last_stats say of this generation
                tracing.annotate(
                    ions=table.n_ions, computed=stream.cold_patterns,
                    cached=table.n_ions - stream.cold_patterns,
                    gen_s=round(stream.gen_seconds, 3))
                # m/z-localized batch unions (see maybe_order_table):
                # per-ion results are order-independent, so this only
                # changes which extraction variant each batch's plan picks
                table = maybe_order_table(
                    table, self.sm_config.parallel.order_ions,
                    self.sm_config.parallel.formula_batch)
        self.last_table = table
        n_targets = int(table.targets.sum())
        logger.info(
            "scoring %d ions (%d targets, %d decoys) with backend=%s%s",
            table.n_ions, n_targets, table.n_ions - n_targets,
            self.sm_config.backend,
            " (overlapping isocalc)" if overlap else "",
        )
        # OOM memory (ISSUE 10): a previous job on this (dataset shape,
        # backend, lease) proved a smaller batch fits in HBM — start there
        # instead of rediscovering the RESOURCE_EXHAUSTED.  Must happen
        # BEFORE the fingerprint: the checkpoint partition depends on it.
        safe = oom.safe_batch_for(self._oom_key())
        if safe and safe < self._batch_eff:
            logger.info(
                "oom: starting at learned safe batch %d (config %d) for %s",
                safe, self._batch_eff, self._oom_key())
            self._batch_eff = safe
        with tracing.span("table_fingerprint"):
            fingerprint = (self._fingerprint_pairs(table) if overlap
                           else self._fingerprint(table))

        def build():
            tracing.annotate(cache_hit=False)    # onto the backend_build span
            return make_backend(
                self.sm_config.backend, self.ds, self.ds_config,
                self.sm_config, table=table,
                device_indices=self.device_indices,
            )

        # device circuit breaker (models/breaker.py): an OPEN breaker means
        # the device backend recently produced N consecutive errors — skip
        # the build/compile entirely and score on the numpy oracle at
        # reduced batch (bit-identical results; degraded-but-correct beats
        # dead).  allow_device() admits one half-open probe after cooldown.
        use_device = self.sm_config.backend == "jax_tpu"
        # per-chip breaker view (ISSUE 14): a leased job answers to ITS
        # chips' breakers, so one bad chip's history never degrades jobs
        # holding healthy chips; un-leased runs keep the "*" singleton
        breaker = get_device_breaker(self.sm_config.service,
                                     devices=self.device_indices)
        degraded = False
        if use_device and not breaker.allow_device():
            logger.warning(
                "device breaker open: degrading job to the numpy backend "
                "at batch %d", self.sm_config.service.breaker_degraded_batch)
            record_degraded()
            backend = NumpyBackend(self.ds, self.ds_config)
            degraded = True
        else:
            # the span behind backend_build_s (PERF.md section 3); the jax
            # backend splits it into build_sort / build_restrict /
            # build_pad_compact / build_device_put, and says through
            # build_attrs which kernel geometry it runs (hit or miss)
            with tracing.span("backend_build", cache_hit=True):
                if self.backend_cache is not None:
                    par = self.sm_config.parallel
                    key = (self.sm_config.backend, fingerprint,
                           par.pixels_axis, par.formulas_axis,
                           par.peak_compaction, par.band_slice,
                           par.order_ions,
                           # a backend is pinned to its lease's chips — a
                           # cached one must never be reused by a job
                           # holding DIFFERENT chips
                           self.device_indices)
                    backend = self.backend_cache.backend(key, build)
                else:
                    backend = build()
                tracing.annotate(
                    peaks_in=int(self.ds.n_peaks),
                    peaks_resident=getattr(backend, "resident_peaks", None),
                    resident_bytes=getattr(backend, "resident_bytes", None),
                    **getattr(backend, "build_attrs", {}))
        self.last_backend = backend
        batch = self._batch_eff
        if batch < max(1, self.sm_config.parallel.formula_batch) and \
                hasattr(backend, "shrink_batch"):
            # the learned safe size also caps the device backend's static
            # padding batch (padding to the config size would re-OOM)
            backend.shrink_batch(batch)
        metrics = np.zeros((table.n_ions, 4))
        with phase_timer("score", timings):
            slices = [(s, min(s + batch, table.n_ions))
                      for s in range(0, table.n_ions, batch)]
            ckpt_every = self.sm_config.parallel.checkpoint_every
            if self.checkpoint_dir and ckpt_every > 0:
                # group batches so pipelining still happens within a
                # group.  Streamed first results (ISSUE 13,
                # _PARTITION_VERSION 2): the LEADING group is a single
                # batch, so the first FDR-rankable metrics — and the
                # provisional `partial` annotations — land after one
                # batch's compute instead of a whole group's, while later
                # groups keep the full pipelining grain
                groups = [slices[: 1]] + [
                    slices[1:][i : i + ckpt_every]
                    for i in range(0, len(slices) - 1, ckpt_every)]
                if self.sm_config.backend == "jax_tpu":
                    import jax

                    pid = jax.process_index()
                else:
                    pid = 0
                ckpt = SearchCheckpoint(
                    self.checkpoint_dir, fingerprint, process_id=pid)
                row_ranges = [(g[0][0], g[-1][1]) for g in groups]
                with tracing.span("checkpoint_load", groups=len(groups)):
                    done = self._agree_resume_point(
                        ckpt.load(metrics, len(groups), row_ranges))
                if done:
                    logger.info(
                        "resuming search from checkpoint: %d/%d batch groups "
                        "already scored", done, len(groups))
            elif overlap:
                # no checkpoint grain: publish/score per batch, so overlap
                # still engages (the host backend consumes batches one at a
                # time anyway)
                groups, ckpt, done = [[sl] for sl in slices], None, 0
                row_ranges = [sl for sl in slices]
            elif len(slices) > 1:
                # no checkpoint grain: still split the leading batch into
                # its own group so first-annotation latency is one batch,
                # not the whole stream (the tail stays one pipelined group)
                groups, ckpt, done = [slices[:1], slices[1:]], None, 0
                row_ranges = [(g[0][0], g[-1][1]) for g in groups]
            else:
                groups, ckpt, done = [slices], None, 0
                row_ranges = [(0, table.n_ions)] if slices else []
            if len(groups) > 1 and hasattr(backend, "presize"):
                # per-group score_batches calls would otherwise pre-size
                # static shapes per GROUP and recompile when a later group
                # needs a wider band (models/msm_jax.py::presize)
                with tracing.span("presize", batches=len(slices)):
                    backend.presize(
                        _slice_table(table, s, e) for s, e in slices)
            first_scored = False
            for gi, group in enumerate(groups):
                if gi < done:
                    continue
                if self.cancel is not None:
                    # THE cooperative cancellation boundary: a timed-out /
                    # deleted / past-deadline job unwinds here, after the
                    # last durable checkpoint and before any new work
                    self.cancel.check("score")
                if overlap:
                    # block until this group's pattern rows are published —
                    # in bounded slices so a cancel still lands while
                    # generation is the laggard
                    need = row_ranges[gi][1]
                    if self.cancel is None:
                        stream.wait_rows(need)
                    else:
                        while stream.wait_rows(need, timeout=0.2) < min(
                                need, stream.n_ions):
                            self.cancel.check("isotope_patterns_wait")
                # device-fault seam: a preempted TPU / failed XLA launch
                # surfaces here, after `done` groups are already durable
                failpoint(FP_DEVICE_SCORE)
                with tracing.span("score_group", group=gi,
                                  rows=list(row_ranges[gi]) if row_ranges
                                  else None, degraded=degraded):
                    backend, degraded = self._score_group(
                        backend, table, metrics, group, breaker, use_device,
                        degraded)
                if not first_scored:
                    # the first FDR-rankable metrics of this search exist
                    # now — the submit→first-annotation SLI's stop clock
                    first_scored = True
                    tracing.event("first_annotation", group=gi)
                    _notify_first_annotation()
                    # streamed first results (ISSUE 13): provisional FDR
                    # over the scored prefix, exposed on the job trace +
                    # the scheduler's `partial` field while later batches
                    # still run
                    with tracing.span("partial_fdr", group=gi):
                        self._emit_partial(
                            fdr, assignment, table, metrics,
                            row_ranges[gi][1] if row_ranges
                            else table.n_ions, gi)
                if ckpt is not None:
                    with tracing.span("checkpoint_save", group=gi):
                        ckpt.save(metrics, gi, len(groups), row_ranges)
            # NOT finalized here: downstream FDR/storage can still fail, and
            # the scored metrics must survive a rerun.  The orchestrator
            # (SearchJob) finalizes after results are durably persisted; a
            # leftover checkpoint is harmless (fingerprint-guarded) and makes
            # an identical re-search skip scoring entirely.
            self.last_checkpoint = ckpt
            if not first_scored:
                # fully resumed from checkpoint (or an empty table): the
                # first annotations were available immediately
                _notify_first_annotation()
            if overlap:
                # join generation (shard commits/compaction may trail the
                # last row) and surface any late stream error before FDR
                stream.result_table()
                keep()
        timings["isocalc_gen"] = stream.gen_seconds
        if self.cancel is not None:
            self.cancel.check("fdr")
        with phase_timer("fdr", timings):
            tracing.annotate(ions=table.n_ions, targets=n_targets,
                             decoys=table.n_ions - n_targets)
            all_df = pd.DataFrame(
                {
                    "sf": table.sfs,
                    "adduct": table.adducts,
                    "is_target": table.targets,
                    "chaos": metrics[:, 0],
                    "spatial": metrics[:, 1],
                    "spectral": metrics[:, 2],
                    "msm": metrics[:, 3],
                }
            )
            annotations = fdr.estimate_fdr(all_df[["sf", "adduct", "msm"]], assignment)
            ranked = pd.unique(annotations["adduct"]).tolist()
            tracing.annotate(rankings=len(ranked))
            count_ranked(assignment, ranked)
            annotations = annotations.merge(
                all_df[["sf", "adduct", "chaos", "spatial", "spectral"]],
                on=["sf", "adduct"],
                how="left",
            )
            # keep the declared schema authoritative for empty & non-empty paths
            annotations = annotations[self._ANN_COLUMNS]
            all_df = all_df[self._ALL_COLUMNS]
        return SearchResultsBundle(
            annotations=annotations, all_metrics=all_df, timings=timings,
            assignment=assignment,
        )
