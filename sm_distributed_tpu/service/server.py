"""AnnotationService — the long-running serving composition.

Wires together the spool (``QueuePublisher`` for ``POST /submit``), the
``JobScheduler`` worker pool, the metrics registry (phase-timer observer +
residency collector + spool depth gauges), and the ``AdminAPI``, with
POSIX-graceful shutdown: SIGTERM/SIGINT stop admission, requeue
claimed-but-unstarted messages, drain running jobs, then stop the API —
``running/`` is empty on a clean exit, so a restart resumes exactly the
pending backlog.
"""

from __future__ import annotations

import signal
import sys
import threading
import time
from pathlib import Path

from ..engine.daemon import QUEUE_ANNOTATE, QueuePublisher, _STATES
from ..models import faults, oom
from ..models.breaker import attach_metrics as attach_breaker_metrics
from ..models.breaker import get_device_breaker
from ..utils import tracing
from ..utils.config import SMConfig
from ..utils.failpoints import attach_metrics as attach_failpoint_metrics
from ..utils.logger import add_phase_observer, logger, remove_phase_observer
from .admission import AdmissionController
from .api import AdminAPI
from .device_pool import DevicePool, resolve_pool_size
from .metrics import (MetricsRegistry, build_info_collector,
                      process_collector, process_cpu_collector)
from .resources import ResourceGovernor, set_governor
from .scheduler import JobScheduler
from .telemetry import DeviceMonitor, SLOTracker


class AnnotationService:
    def __init__(
        self,
        queue_dir: str | Path,
        callback,
        sm_config: SMConfig | None = None,
        queue: str = QUEUE_ANNOTATE,
        residency=None,
        with_api: bool = True,
    ):
        self.sm_config = sm_config or SMConfig.get_conf()
        cfg = self.sm_config.service
        self.queue_dir = Path(queue_dir)
        self.queue = queue
        self.metrics = MetricsRegistry()
        self.publisher = QueuePublisher(queue_dir, queue=queue)
        # end-to-end tracing (ISSUE 5, docs/OBSERVABILITY.md): per-job JSONL
        # files + the flight-recorder ring behind /jobs/<id>/trace and
        # /debug/events.  tracing.enabled=false keeps only the no-op stubs.
        tracing.configure(enabled=self.sm_config.tracing.enabled,
                          ring_size=self.sm_config.tracing.ring_size)
        self.trace_dir = (self.sm_config.trace_dir
                          if self.sm_config.tracing.enabled else None)
        # replica identity (ISSUE 8): stamped on every trace record and
        # telemetry sample this process emits
        tracing.set_replica(cfg.replica_id)
        # overload protection in front of /submit: bounded depth, per-tenant
        # quotas, EWMA latency shedding (service/admission.py); the
        # scheduler feeds terminal outcomes + attempt latency back into it.
        # State is replica-local; the spool re-adoption and the peer view
        # are wired after the scheduler exists (it owns the shard map).
        self.admission = AdmissionController(cfg.admission, metrics=self.metrics)
        # SLO instrumentation (service/telemetry.py): queue-wait / first-
        # annotation / e2e histograms recorded at the scheduler's seams,
        # attainment served by GET /slo
        self.slo = SLOTracker(self.metrics, self.sm_config.telemetry)
        # multi-chip device pool (ISSUE 7): resolved against the configured
        # backend so a jax_tpu service leases out every visible chip, while
        # a numpy_ref service keeps the degenerate 1-chip pool (= the old
        # single-token serialization)
        pool_size = resolve_pool_size(cfg, backend=self.sm_config.backend)
        # per-chip health (ISSUE 14, service/health.py): quarantined chips
        # leave placement, lease-time probes fence dead chips before a job
        # touches them, half-open re-probes readmit recovered ones —
        # surfaced on GET /debug/devices and sm_device_* metrics
        from .health import HealthTracker

        self.device_pool = DevicePool(
            pool_size,
            max_bypass=cfg.device_pool_max_bypass,
            hosts=cfg.device_pool_hosts,
            health=HealthTracker.from_config(
                pool_size, cfg, hosts=cfg.device_pool_hosts))
        self.device_pool.attach_metrics(self.metrics)
        # resource governor (ISSUE 10, service/resources.py): disk-budget
        # preflight at every governed write seam, degrade order traces →
        # cache → 507 submits, bounded-retention GC run from the
        # scheduler's replica loop.  Installed as the process singleton so
        # the engine seams (checkpoints, results, publish, cache shards)
        # and the admission controller consult it without plumbing;
        # tracing's file gate makes trace appends the FIRST thing dropped.
        read_cache_dir = Path(self.sm_config.work_dir) / "read_cache"
        from ..engine.stream import StreamIngest, stream_root

        stream_dir = stream_root(self.sm_config)
        self.resources = ResourceGovernor(
            self.sm_config.resources,
            work_dir=self.sm_config.work_dir,
            results_dir=self.sm_config.storage.results_dir,
            queue_root=self.queue_dir / queue,
            trace_dir=self.trace_dir,
            cache_dir=Path(self.sm_config.work_dir) / "isocalc_cache",
            tracing_cfg=self.sm_config.tracing,
            metrics=self.metrics, replica_id=cfg.replica_id,
            read_cache_dir=read_cache_dir,
            read_cache_max_bytes=cfg.read.cache_disk_max_bytes,
            stream_dir=stream_dir,
            stream_retention_age_s=cfg.stream.retention_age_s,
            stream_idle_timeout_s=cfg.stream.idle_timeout_s)
        set_governor(self.resources)
        tracing.set_file_gate(self.resources.trace_gate)
        # live-acquisition ingest (ISSUE 19, engine/stream.py): the HTTP
        # chunk seam (POST /datasets/<id>/pixels|finish) appends into the
        # crash-safe chunk log that StreamSearchJob re-scores from; shared
        # work_dir means any replica can serve appends for any acquisition
        self.stream_ingest = StreamIngest(stream_dir, metrics=self.metrics)
        # result read path (ISSUE 16, service/readpath.py): governed LRU +
        # segment reader + tile renderer behind the GET endpoints; cache
        # fills consult the governor's no-read-cache degrade level
        from .readpath import ReadPath

        self.readpath = ReadPath(
            self.sm_config.storage.results_dir, cfg.read,
            governor=self.resources, metrics=self.metrics, slo=self.slo,
            disk_dir=read_cache_dir) if cfg.read.enabled else None
        # HBM-OOM adaptive-scoring telemetry (models/oom.py): events,
        # converged backoffs, and the learned safe batch on /metrics
        oom.attach_metrics(self.metrics)
        # classified device-fault telemetry (models/faults.py, ISSUE 14):
        # sm_device_faults_total{kind=} beside the oom/breaker families
        faults.attach_metrics(self.metrics)
        # compile-retrace attribution (ISSUE 12, analysis/retrace.py):
        # every XLA compilation this process pays for is attributed to its
        # call site + abstract signature (sm_compile_* on /metrics, a
        # `compile` event on the owning job's trace) — the runtime half of
        # the COMPILE_SURFACE closed-signature-set invariant
        if self.sm_config.telemetry.retrace:
            from ..analysis import retrace

            retrace.enable(metrics=self.metrics)
        self.scheduler = JobScheduler(
            queue_dir, callback, config=cfg, queue=queue, metrics=self.metrics,
            admission=self.admission, trace_dir=self.trace_dir, slo=self.slo,
            device_pool=self.device_pool, resources=self.resources)
        # ahead-of-time cache primer (ISSUE 13, service/primer.py): when
        # the spool sits idle, AOT-compile the recorded shape-bucket
        # lattice into the persistent XLA cache so a cold submit loads
        # executables instead of compiling.  Constructed even when
        # disabled — GET /debug/compile serves its primed-vs-missing view
        # either way; only the idle thread is gated on the knob.
        from .primer import CachePrimer

        self.primer = CachePrimer(
            self.sm_config, busy=self._primer_busy, metrics=self.metrics)
        # replica-scoped spool re-adoption + the registry-backed peer view:
        # each replica tracks its own shards and folds the peers' gossiped
        # summaries into its quota/shed decisions (GET /peers serves both)
        self.admission.sync_from_spool(self.queue_dir / queue,
                                       owns_msg=self.scheduler.owns_msg)
        self.admission.set_peer_view(self.scheduler.peer_admission_summaries)
        # device & memory telemetry: HBM/occupancy/cache sampler feeding
        # gauges + the GET /debug/timeseries snapshot ring
        from ..parallel.distributed import compile_cache_path

        self.telemetry = DeviceMonitor(
            self.metrics, self.sm_config.telemetry,
            device_pool=self.device_pool,
            queue_root=self.queue_dir / queue,
            compile_cache_dir=compile_cache_path(self.sm_config),
            replica_id=cfg.replica_id,
            readpath=self.readpath, stream_ingest=self.stream_ingest)
        # device-backend circuit breaker: configure the process singleton
        # from THIS service's knobs and export its state on /metrics
        get_device_breaker(cfg)
        attach_breaker_metrics(self.metrics)
        self.residency = residency
        self.started_at = time.time()
        self._stop_requested = threading.Event()
        self._shutdown_done = threading.Event()
        self._shutdown_once = threading.Lock()
        self._phase_hist = self.metrics.histogram(
            "sm_phase_seconds", "Pipeline phase wall clock by phase name",
            ("phase",))
        # chaos observability: sm_failpoints_injected_total{name=} and
        # sm_recovery_events_total{event=} surface on /metrics
        attach_failpoint_metrics(self.metrics)
        # isocalc cold-path observability (ISSUE 3): pattern counter +
        # per-generation worker/rate gauges, plus a scrape-window rate
        from ..ops import isocalc as isocalc_mod
        from .metrics import rate_collector

        isocalc_mod.attach_metrics(self.metrics)
        rate_collector(self.metrics, "sm_isocalc_patterns_scrape_rate_per_s",
                       "Isotope patterns computed per second, over the "
                       "window since the previous scrape",
                       isocalc_mod.patterns_total)
        # what the FDR ranked (ISSUE 47): sampled triples, distinct decoy
        # ions and rankings per target adduct of the jobs that reached fdr
        from ..ops import fdr as fdr_mod

        fdr_mod.attach_metrics(self.metrics)
        # build identity + process health (ISSUE 5 satellite): dashboards
        # need a version/backend join key and leak-spotting gauges (RSS,
        # threads, FDs) the load sweep only catches in tests
        build_info_collector(self.metrics, backend=self.sm_config.backend)
        process_collector(self.metrics)
        process_cpu_collector(self.metrics)
        if residency is not None:
            self.metrics.add_collector(self._collect_residency)
        self.metrics.add_collector(self._collect_prepare)
        self.metrics.add_collector(self._collect_ingest)
        self.metrics.add_collector(self._collect_chaos_images)
        self.metrics.add_collector(self._collect_chaos_programs)
        self.metrics.add_collector(self._collect_store_exports)
        self.metrics.add_collector(self._collect_extract_load)
        self.metrics.add_collector(self._collect_scoring_jits)
        self.metrics.add_collector(self._collect_interp_probe)
        self.api = AdminAPI(self, host=cfg.http_host,
                            port=cfg.http_port) if with_api else None
        # fleet observability plane (ISSUE 20, service/fleetview.py):
        # /fleet/* aggregation across live replicas + /debug/profile
        # on-demand device capture.  The admin address, pool occupancy and
        # in-flight stream count are gossiped through registry heartbeats
        # so peers can scrape this replica without another channel — the
        # API binds its socket in __init__, so the address is final here.
        from .fleetview import DeviceProfiler, FleetView

        self.fleetview = (FleetView(self, cfg.fleetview)
                          if cfg.fleetview.enabled and with_api else None)
        self.profiler = DeviceProfiler(self, self.sm_config.telemetry.profile)
        if self.api is not None:
            self.scheduler.add_gossip(
                "admin", lambda: "%s:%d" % self.api.address)
        self.scheduler.add_gossip("pool", self._gossip_pool)
        self.scheduler.add_gossip("streams_in_flight",
                                  self.stream_ingest.in_flight)

    def _gossip_pool(self) -> dict:
        """The heartbeat-sized pool summary peers fold into /fleet/status
        (the full per-chip view stays on this replica's /debug/devices)."""
        snap = self.device_pool.snapshot()
        return {"size": snap["size"], "in_use": snap["in_use"],
                "waiters": snap["waiters"]}

    # -------------------------------------------------------------- metrics
    def _observe_phase(self, phase: str, seconds: float) -> None:
        self._phase_hist.labels(phase=phase).observe(seconds)

    def _collect_residency(self, m: MetricsRegistry) -> None:
        """Scrape-time pull of ``DatasetResidency.stats`` into counters
        (the stats ARE cumulative, so exposing their current value under a
        counter type is faithful)."""
        stats = self.residency.stats
        hits = m.counter("sm_residency_hits_total",
                         "Residency cache hits", ("cache",))
        misses = m.counter("sm_residency_misses_total",
                           "Residency cache misses", ("cache",))
        # what the store holds and may hold (engine/residency.py): a
        # backend's bytes are its chips', its host-side m/z index is listed
        # as cache "backend_index"; a tier without a budget has no sample
        held = m.gauge("sm_residency_bytes",
                       "Bytes the residency holds, by cache", ("cache",))
        evictions = m.counter("sm_residency_evictions_total",
                              "Entries the residency evicted, by cache and "
                              "by the rule that chose them", ("cache", "cause"))
        for cache in ("dataset", "backend", "ion_table"):
            # counters only move forward; set via delta from the live stats
            moved = [(hits.labels(cache=cache), stats[f"{cache}_hits"]),
                     (misses.labels(cache=cache), stats[f"{cache}_misses"])]
            moved += [(evictions.labels(cache=cache, cause=cause),
                       stats["evictions"].get((cache, cause), 0))
                      for cause in ("count", "bytes")]
            for counter, now in moved:
                counter.inc(max(0.0, now - counter.value))
            held.labels(cache=cache).set(stats[f"{cache}_bytes"])
        held.labels(cache="backend_index").set(stats["backend_host_bytes"])
        budget = m.gauge("sm_residency_budget_bytes",
                         "Bytes a tier of the residency may hold (device: "
                         "the fullest chip's bytes_limit less the scoring "
                         "reserve; host: its share of the memory available "
                         "at start-up)", ("tier",))
        for tier, n in stats["budget_bytes"].items():
            if n is not None:
                budget.labels(tier=tier).set(n)

    @staticmethod
    def _collect_prepare(m: MetricsRegistry) -> None:
        """Where the dataset-only half of a backend build ran
        (``SpectralDataset.flat_sorted``): before the job asked for the
        chip, under its lease, or not at all (``cached``), and which road
        measured each dataset's window occupancy (``walk``: shifted
        compares; ``search``: the binary search past the walk's cap).
        Pulled like the residency stats above, of whose family it is."""
        from ..io.dataset import flat_sorted_events, occupancy_events

        for name, text, label, events in (
                ("sm_backend_prepare_total",
                 "Lookups of a dataset's resident flat layout, by where a "
                 "miss was computed", "site", flat_sorted_events()),
                ("sm_prepare_occupancy_total",
                 "Intensity grids computed, by the road that measured the "
                 "window occupancy", "route", occupancy_events())):
            family = m.counter(name, text, (label,))
            for value, n in events.items():
                c = family.labels(**{label: value})
                c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_ingest(m: MetricsRegistry) -> None:
        """How each imzML ingest made its index (``scan``: one pass of a
        pattern over the XML's bytes; ``xml``: the file was handed whole to
        the XML parser) and the ibd read calls issued (``io/imzml.py``).
        Pulled like the prepare sites above."""
        from ..io.imzml import ingest_events

        events = ingest_events()
        ingests = m.counter(
            "sm_imzml_ingest_total",
            "imzML files indexed, by how the index was made", ("index",))
        for index in ("scan", "xml"):
            c = ingests.labels(index=index)
            c.inc(max(0.0, events[index] - c.value))
        reads = m.counter(
            "sm_imzml_ibd_reads_total", "ibd read calls issued").labels()
        reads.inc(max(0.0, events["ibd_reads"] - reads.value))

    @staticmethod
    def _collect_chaos_images(m: MetricsRegistry) -> None:
        """Ion images sent through each chaos kernel geometry
        (``models/msm_jax.py::chaos_image_events``, counted where a batch is
        enqueued).  Pulled like the prepare sites above, and only if the jax
        backend was ever imported: a numpy-only service never pays for it."""
        images = m.counter(
            "sm_chaos_images_total",
            "Ion images sent through each measure-of-chaos route and "
            "block geometry", ("route", "images_per_program"))
        mod = sys.modules.get("sm_distributed_tpu.models.msm_jax")
        for (route, ib), n in (mod.chaos_image_events() if mod else {}).items():
            c = images.labels(route=route, images_per_program=str(ib))
            c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_chaos_programs(m: MetricsRegistry) -> None:
        """Programs of the packed chaos kernel by the path each took
        (``models/msm_jax.py::chaos_program_events``: the kernel's own
        flags, summed on the device and read off the scored blocks the
        host fetches anyway).  Pulled like the chaos images above."""
        programs = m.counter(
            "sm_chaos_programs_total",
            "Programs of the packed measure-of-chaos kernel, by path: "
            "sparse (no two adjacent pixels in the block, counted without "
            "labels) or flood", ("path",))
        mod = sys.modules.get("sm_distributed_tpu.models.msm_jax")
        for path, n in (mod.chaos_program_events() if mod else {}).items():
            c = programs.labels(path=path)
            c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_store_exports(m: MetricsRegistry) -> None:
        """Image exports stored, by how the images reached the writer
        (``engine/storage.py::store_export_events``): in more than one
        chunk, or whole.  Pulled like the chaos programs above."""
        exports = m.counter(
            "sm_store_exports_total",
            "Jobs that stored ion images, by path: streamed (the export "
            "reached the writer in more than one chunk) or whole",
            ("path",))
        mod = sys.modules.get("sm_distributed_tpu.engine.storage")
        for path, n in (mod.store_export_events() if mod else {}).items():
            c = exports.labels(path=path)
            c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_extract_load(m: MetricsRegistry) -> None:
        """Capacity slots handed to extraction and the peaks really inside
        them, by extraction variant (``models/msm_jax.py::
        extract_load_events``, counted where a batch is enqueued): their
        ratio is what the band floor, the band ladder and the sticky
        compact capacity pad.  Pulled like the chaos images above."""
        slots = m.counter(
            "sm_extract_slots_total",
            "Resident-peak capacity slots dispatched to extraction (band "
            "w_cap, sticky compact capacity, or every resident slot)",
            ("variant",))
        peaks = m.counter(
            "sm_extract_peaks_total",
            "Peaks inside the dispatched batches' bands or window-union "
            "runs", ("variant",))
        mod = sys.modules.get("sm_distributed_tpu.models.msm_jax")
        for variant, load in (mod.extract_load_events() if mod else {}).items():
            for family, n in zip((slots, peaks), load):
                c = family.labels(variant=variant)
                c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_scoring_jits(m: MetricsRegistry) -> None:
        """Backends constructed, by whether the jitted scorers of their
        geometry were already in the process (``shared``: nothing of a
        signature seen before is traced, lowered or loaded again) or had to
        be made (``built``) — ``models/msm_jax.py::scoring_jit_events``.
        Pulled like the chaos images above."""
        jits = m.counter(
            "sm_scoring_jits_total",
            "Scoring backends constructed, by whether their geometry's "
            "jitted scorers were shared or built", ("result",))
        mod = sys.modules.get("sm_distributed_tpu.models.msm_jax")
        for result, n in (mod.scoring_jit_events() if mod else {}).items():
            c = jits.labels(result=result)
            c.inc(max(0.0, n - c.value))

    @staticmethod
    def _collect_interp_probe(m: MetricsRegistry) -> None:
        """The interpreter-wait probe's totals (``analysis/profiling.py::
        InterpProbe``: one thread, alive only during a capture, that asks
        for the interpreter every 10 ms).  late / wakeups over a window is
        the mean wait for the GIL; both stand still outside a capture.
        Pulled like the prepare sites above."""
        from ..analysis.profiling import interp_probe_events

        events = interp_probe_events()
        wakeups = m.counter(
            "sm_interp_probe_wakeups_total",
            "Wakes of the interpreter-wait probe (10 ms apart, during a "
            "profile capture only)").labels()
        late = m.counter(
            "sm_interp_probe_late_seconds_total",
            "Seconds the probe's wakes came after their deadlines: its "
            "wait for the interpreter").labels()
        wakeups.inc(max(0.0, events["wakeups"] - wakeups.value))
        late.inc(max(0.0, events["late_s"] - late.value))

    def queue_depths(self) -> dict:
        root = self.queue_dir / self.queue
        return {s: len(list(root.glob(f"{s}/*.json"))) for s in _STATES}

    def _primer_busy(self) -> bool:
        """Real work in flight?  The primer only runs while this is False
        (and re-checks between specs), so priming never delays a job."""
        if self.scheduler.live_claims() > 0:
            return True
        root = self.queue_dir / self.queue
        return any(True for _ in root.glob("pending/*.json")) or \
            any(True for _ in root.glob("running/*.json"))

    def stopping(self) -> bool:
        """True once shutdown began — /submit sheds with 503 from here on."""
        return self._stop_requested.is_set()

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        # additive registration (ISSUE 5 satellite): a single slot would
        # silently evict any other observer
        add_phase_observer(self._observe_phase)
        # first-annotation SLI: msm_basic notifies once per search when the
        # first checkpoint group's metrics land (producer-side observer
        # list, same pattern as phase observers)
        from ..models.msm_basic import add_first_annotation_observer

        add_first_annotation_observer(self.slo.note_first_annotation)
        if self.sm_config.telemetry.enabled:
            self.telemetry.start()
        self.scheduler.start()
        if self.sm_config.service.prime.enabled:
            self.primer.start()
        if self.api is not None:
            self.api.start()
        logger.info("service: up (queue=%s)", self.queue_dir / self.queue)

    def shutdown(self, timeout_s: float | None = None) -> bool:
        """Drain and stop everything; safe to call more than once.  A
        concurrent caller BLOCKS until the in-flight drain finishes —
        otherwise the main thread (run_forever's finally) can exit the
        process while the signal-drain thread is still mid-retire,
        leaving registry/heartbeat debris behind (ISSUE 11: a retired
        replica must leave nothing)."""
        with self._shutdown_once:
            if self._stop_requested.is_set():
                first = False
            else:
                self._stop_requested.set()
                first = True
        if not first:
            self._shutdown_done.wait(
                timeout=(timeout_s if timeout_s is not None else
                         self.sm_config.service.drain_timeout_s) + 10.0)
            return True
        logger.info("service: shutdown requested — draining")
        self.primer.stop()
        ok = self.scheduler.shutdown(timeout_s)
        if self.api is not None:
            self.api.stop()
        self.telemetry.stop()
        from ..models.msm_basic import remove_first_annotation_observer

        remove_first_annotation_observer(self.slo.note_first_annotation)
        remove_phase_observer(self._observe_phase)
        # detach the resource governor so a later service (tests run many
        # per process) starts from its own budget, not this one's
        from .resources import get_governor

        if get_governor() is self.resources:
            tracing.set_file_gate(None)
            set_governor(None)
        self._shutdown_done.set()
        return ok

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT → graceful drain.  Only valid in the main thread."""

        def _handler(signum, frame):
            logger.info("service: received signal %d", signum)
            # handler must return fast; the drain happens in a helper thread
            threading.Thread(target=self.shutdown, daemon=True,
                             name="signal-drain").start()

        signal.signal(signal.SIGTERM, _handler)
        signal.signal(signal.SIGINT, _handler)

    def run_forever(self, max_terminal: int | None = None,
                    idle_timeout_s: float | None = None) -> int:
        """Block until shutdown (signal or programmatic).  ``max_terminal``
        stops after N jobs reach a terminal state (smoke tests);
        ``idle_timeout_s`` stops after the spool stays empty that long."""
        idle_since = None
        try:
            while not self._stop_requested.is_set():
                if self.scheduler.drain_complete():
                    # zero-loss drain (ISSUE 11): the replica acked — every
                    # claim resolved, nothing more will be written; exit so
                    # the controller can count the drain done
                    logger.info("service: drain acked — retiring")
                    break
                if max_terminal is not None and \
                        self.scheduler._terminal_count >= max_terminal:
                    break
                if idle_timeout_s is not None:
                    depths = self.queue_depths()
                    busy = depths["pending"] or depths["running"]
                    if busy:
                        idle_since = None
                    elif idle_since is None:
                        idle_since = time.time()
                    elif time.time() - idle_since >= idle_timeout_s:
                        break
                time.sleep(0.1)
        finally:
            self.shutdown()
        return 0
