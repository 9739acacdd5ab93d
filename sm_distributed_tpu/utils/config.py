"""Two-level configuration, mirroring the reference's config system.

The reference uses a process-global ``SMConfig`` singleton loading
``conf/config.json`` (services + spark + fdr settings) and a per-dataset
``ds_config.json`` (database, isotope_generation, image_generation) —
``sm/engine/util.py::SMConfig`` [U], SURVEY.md #1/#20.  Every numerical knob
keeps its reference name and default: ``ppm``, ``nlevels=30``, ``q=99``,
``do_preprocessing``, ``decoy_sample_size=20``, ``isocalc_sigma``,
``isocalc_pts_per_mz``, ``adducts``, ``charge``.

One deliberate addition, demanded by the north star (BASELINE.json): the
``backend`` selector — ``numpy_ref`` (CPU parity oracle, the stand-in for the
reference's Spark-RDD executor) or ``jax_tpu`` (the fused-XLA-graph TPU path).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, ClassVar

VALID_BACKENDS = ("numpy_ref", "jax_tpu")


def _from_dict(cls, d: dict[str, Any]):
    """Build a dataclass from a dict, recursing into dataclass fields and
    rejecting unknown keys (catches config typos early, unlike the reference's
    raw-dict access which fails deep inside a Spark task)."""
    # "__doc__"-style keys are comments (JSON has none; the shipped
    # conf/*.template files use them), skipped by load & validation
    d = {k: v for k, v in d.items() if not k.startswith("__")}
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - names
    if unknown:
        raise ValueError(f"unknown {cls.__name__} config keys: {sorted(unknown)}")
    kwargs = {}
    for key, val in d.items():
        target = _DATACLASS_FIELDS.get((cls.__name__, key))
        if target is not None and isinstance(val, dict):
            kwargs[key] = _from_dict(target, val)
        elif isinstance(val, list):
            # JSON arrays land in tuple-typed fields; keep frozen configs hashable.
            kwargs[key] = tuple(val)
        else:
            kwargs[key] = val
    return cls(**kwargs)


@dataclass(frozen=True)
class IsotopeGenerationConfig:
    """Mirrors ds_config['isotope_generation'] [U]."""
    adducts: tuple[str, ...] = ("+H", "+Na", "+K")
    charge: int = 1                      # signed; reference: {polarity:'+', n_charges:1}
    isocalc_sigma: float = 0.01          # gaussian sigma of instrument blur [Da]
    isocalc_pts_per_mz: int = 10000      # resolution of the profile grid
    n_peaks: int = 4                     # top isotope peaks kept per ion (reference: 4)

    def __post_init__(self):
        if self.charge == 0:
            raise ValueError("isotope_generation.charge must be nonzero")
        if self.isocalc_sigma <= 0 or self.isocalc_pts_per_mz <= 0 or self.n_peaks <= 0:
            raise ValueError("isotope_generation: sigma/pts_per_mz/n_peaks must be positive")


@dataclass(frozen=True)
class ImageGenerationConfig:
    """Mirrors ds_config['image_generation'] [U]."""
    ppm: float = 3.0                     # half-width of the m/z match window
    nlevels: int = 30                    # thresholds in measure_of_chaos
    do_preprocessing: bool = False       # hot-spot removal before chaos
    q: float = 99.0                      # hot-spot clipping percentile

    def __post_init__(self):
        if self.ppm <= 0 or self.nlevels <= 0 or not (0 < self.q <= 100):
            raise ValueError("image_generation: ppm/nlevels/q out of range")


@dataclass(frozen=True)
class DatabaseConfig:
    """Mirrors ds_config['database'] [U]."""
    name: str = "HMDB"
    version: str = "2016"


@dataclass(frozen=True)
class DSConfig:
    """Per-dataset config (the reference's ds_config.json [U])."""
    database: DatabaseConfig = field(default_factory=DatabaseConfig)
    isotope_generation: IsotopeGenerationConfig = field(default_factory=IsotopeGenerationConfig)
    image_generation: ImageGenerationConfig = field(default_factory=ImageGenerationConfig)

    @staticmethod
    def load(path: str | Path) -> "DSConfig":
        return _from_dict(DSConfig, json.loads(Path(path).read_text()))

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "DSConfig":
        return _from_dict(DSConfig, d)


@dataclass(frozen=True)
class FDRConfig:
    """Mirrors sm_config['fdr'] [U]."""
    decoy_sample_size: int = 20
    seed: int = 42                       # decoy sampling made explicit/seeded (SURVEY §7 hard part 3)


@dataclass(frozen=True)
class ParallelConfig:
    """TPU-native replacement for sm_config['spark'] [U]: mesh geometry instead
    of master/executor-memory. axis sizes of -1 mean 'use all devices'."""
    pixels_axis: int = -1                # mesh axis sharding the pixel dimension
    formulas_axis: int = 1               # mesh axis sharding the formula dimension
    # ions scored per fused-graph invocation.  Batches pad to this and the
    # histogram scratch grows with it (pixels x 2*batch*peaks f32), so a
    # large slide wants less; the benchmark's configurations run 2048
    formula_batch: int = 2048
    # per-batch peak compaction on the flat path: histogram only the peaks
    # inside the current batch's window union (auto = on when the planned
    # batches keep <70% of resident peaks; on/off force it)
    peak_compaction: str = "auto"
    # ion-table ordering before batching: "mz" sorts ions by principal-peak
    # m/z so each batch's window union is an m/z-LOCALIZED band (total
    # histogram-scatter work across a many-batch stream drops from
    # ~n_batches x resident toward ~resident — the BASELINE #5 regime);
    # "table" keeps the caller's order (targets first); "auto" (default)
    # orders at >=6 batches (measured: 6-batch 65k-px stream +20%, 41-batch
    # 262k-px stream +8.3x, 3-batch 4k-px stream -17%).  Per-ion results
    # are identical either way.
    order_ions: str = "auto"
    # contiguous band-slice extraction: when a batch's window union spans a
    # contiguous slice of the m/z-sorted resident peaks (ordered streams),
    # scatter a dynamic slice instead of gathering a packed run list —
    # scatter-only cost, no 23 ns/slot gather.  auto = picked per batch by
    # measured-cost estimate vs plain/compaction; on/off force or disable.
    band_slice: str = "auto"
    # multi-host (DCN) runtime — jax.distributed.initialize; the analog of
    # the reference's spark.master cluster address (SURVEY.md §5.8).  Env
    # vars SM_COORDINATOR / SM_NUM_PROCESSES / SM_PROCESS_ID override.
    coordinator_address: str = ""        # "" = single-process (no-op init)
    num_processes: int = 1
    process_id: int = -1                 # -1 = resolve from env/launcher
    # coordinator launch race (ISSUE 17): every host process races the
    # coordinator's bind at pod startup, so jax.distributed.initialize
    # retries with exponential backoff (base * 2^attempt, capped at 30 s)
    # before the failure is considered real
    init_retries: int = 5                # attempts AFTER the first; 0 = one
                                         # shot (fail fast)
    init_backoff_s: float = 1.0          # first retry delay; doubles per
                                         # attempt
    # mid-search resume (SURVEY §5.4): checkpoint scored metrics every N
    # formula batches; 0 disables.  A killed multi-hour search (BASELINE
    # configs #3/#5) resumes from the last complete group.
    checkpoint_every: int = 0
    # persistent XLA compilation cache: "" = on, "off" = disabled.  WHERE it
    # lives is not a config value: $JAX_COMPILATION_CACHE_DIR when set,
    # else <checkout>/.cache/xla_cache (parallel/distributed.py)
    compile_cache_dir: str = ""
    # --- isotope-pattern cold path (ops/isocalc.py, docs/ISOCALC.md) ---
    # process-pool size for cold pattern generation: 0 = all cores
    # (env SM_ISOCALC_PROCS overrides a 0 here)
    isocalc_workers: int = 0
    # (formula, adduct) pairs per generation chunk == per incremental cache
    # shard: 0 = default (2048; env SM_ISOCALC_CHUNK overrides a 0 here)
    isocalc_chunk: int = 0
    # batched device (XLA) blur->centroid stage: "on" routes the
    # post-convolution math through ops/isocalc_jax.py.  Results match the
    # NumPy oracle to ~1e-5 (NOT bit-exact; separate cache namespace), so
    # the default stays "off" — the pinned golden report is oracle bits.
    isocalc_device: str = "off"
    # overlap isotope generation with the rest of the job: SearchJob stages/
    # parses concurrently with isocalc, and (numpy_ref backend) scoring
    # starts on the leading checkpoint groups while later patterns are
    # still computing.  "off" restores strictly serial phases.
    overlap_isocalc: str = "auto"
    # daemon service mode: how many datasets' parsed layouts + compiled
    # backends + finished ion tables stay resident across queue messages
    # (engine/residency.py, LRU): an integer N keeps the last N of each, 0
    # disables, "auto" keeps as many as their bytes fit the chip's memory
    # and a share of the host's (budgets computed, docs/SERVICE.md)
    resident_datasets: int | str = 2
    # shape-bucket lattice (ISSUE 13, ops/buckets.py): "auto"/"on" snap
    # dataset-dependent shapes (pixel rows, resident peak slots, pad-to
    # batch) to the canonical power-of-two-ish lattice so every dataset
    # size maps into a closed, primeable signature set; "off" keeps exact
    # legacy shapes (one executable family per dataset size)
    shape_buckets: str = "auto"
    # resident intensity dtype (ops/quantize.compact_cube): "f32" is exact
    # and is what every benchmark cell serves.  "bf16" halves the resident
    # intensities but regrids them: FDR ranks held on the tier-1 fixture,
    # the metric values miss the benchmark's `correct` limits on the chip
    # (PERF.md section 2), so it is kept only as the benchmark's failing
    # control (benchmarks/tests/control_on_chip.py).
    cube_dtype: str = "f32"
    # vestigial: the fused Pallas scoring variant it routed to went in
    # PR 44.  "auto" and "off" both mean the XLA chain, the only one; the
    # field stays because benchmarks/configs/*.json spell it out and
    # from_dict rejects unknown keys (ROADMAP C4 names the PR that lets
    # it go).
    fused_metrics: str = "auto"


@dataclass(frozen=True)
class AdmissionConfig:
    """Overload protection for ``POST /submit`` (docs/SERVICE.md "Overload &
    degradation model").  A shed submit gets a structured 429/503 with a
    ``Retry-After`` header instead of joining an unbounded backlog."""
    max_queue_depth: int = 512           # admitted-but-not-terminal bound
                                         # across all tenants; 0 = unlimited
    max_tenant_inflight: int = 128       # per-tenant admitted-but-not-
                                         # terminal bound; 0 = unlimited
    ewma_alpha: float = 0.2              # weight of the newest job latency
    latency_shed_s: float = 0.0          # EWMA job latency that starts
                                         # shedding (503); 0 disables
    latency_resume_s: float = 0.0        # hysteresis floor: resume accepting
                                         # below this (0 = 0.75 * shed)
    retry_after_s: float = 1.0           # Retry-After hint on shed responses

    def __post_init__(self):
        if self.max_queue_depth < 0 or self.max_tenant_inflight < 0:
            raise ValueError("admission: depth/quota bounds must be >= 0")
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError("admission: ewma_alpha must be in (0, 1]")
        if self.latency_shed_s < 0 or self.latency_resume_s < 0:
            raise ValueError("admission: latency thresholds must be >= 0")
        if self.retry_after_s < 0:
            raise ValueError("admission: retry_after_s must be >= 0")

    @property
    def effective_resume_s(self) -> float:
        return self.latency_resume_s or 0.75 * self.latency_shed_s


@dataclass(frozen=True)
class FleetConfig:
    """Elastic replica fleet (service/fleet.py, docs/SERVICE.md "Elasticity
    model").  A FleetController supervises replica subprocesses and makes
    hysteresis-damped scale decisions between ``min_replicas`` and
    ``max_replicas`` from the live signals the service already exports:
    ``/slo`` error-budget burn, admission queue depth, and device-pool
    occupancy.  Scale-down is a zero-loss *drain*: the victim stops
    claiming, finishes or releases in-flight work, acks, and retires —
    rendezvous hashing re-owns its shards and fenced leases make the
    handoff safe by construction."""
    enabled: bool = False                # serve --fleet (or this knob) runs
                                         # the controller beside replica r0
    min_replicas: int = 1                # repair floor (crash replacement
                                         # bypasses hysteresis + cooldown)
    max_replicas: int = 4                # scale ceiling
    decide_interval_s: float = 5.0       # controller decision cadence
    cooldown_s: float = 60.0             # min gap between scale events, so
                                         # flapping traffic can't thrash
    hysteresis_ticks: int = 2            # consecutive decide ticks a signal
                                         # must hold before acting
    scale_up_burn: float = 1.0           # worst /slo error-budget burn at or
                                         # above this is scale-up pressure
    scale_down_burn: float = 0.5         # burn must be at or below this for
                                         # scale-down relief
    queue_high_per_replica: float = 8.0  # pending depth / alive replicas at
                                         # or above this is pressure
    queue_low_per_replica: float = 1.0   # ... at or below this is relief
    occupancy_high: float = 0.95         # pool occupancy at or above this is
                                         # pressure (0 disables the signal)
    spawn_timeout_s: float = 30.0        # a spawned replica must register a
                                         # heartbeat within this or count as
                                         # a failed spawn
    drain_timeout_s: float = 120.0       # drain ack + process exit deadline
                                         # before the victim is force-killed

    def __post_init__(self):
        if self.min_replicas <= 0 or self.max_replicas < self.min_replicas:
            raise ValueError("fleet: need 1 <= min_replicas <= max_replicas")
        if self.decide_interval_s <= 0 or self.cooldown_s < 0 or \
                self.hysteresis_ticks < 1:
            raise ValueError("fleet: decide_interval_s must be positive, "
                             "cooldown_s >= 0, hysteresis_ticks >= 1")
        if self.scale_up_burn <= 0 or self.scale_down_burn < 0 or \
                self.scale_down_burn > self.scale_up_burn:
            raise ValueError("fleet: need 0 <= scale_down_burn <= "
                             "scale_up_burn")
        if self.queue_high_per_replica <= 0 or \
                self.queue_low_per_replica < 0 or \
                self.queue_low_per_replica > self.queue_high_per_replica:
            raise ValueError("fleet: need 0 <= queue_low_per_replica <= "
                             "queue_high_per_replica")
        if not 0.0 <= self.occupancy_high <= 1.0:
            raise ValueError("fleet: occupancy_high must be in [0, 1]")
        if self.spawn_timeout_s <= 0 or self.drain_timeout_s <= 0:
            raise ValueError("fleet: spawn/drain timeouts must be positive")


@dataclass(frozen=True)
class PrimeConfig:
    """Ahead-of-time XLA cache priming (ISSUE 13, service/primer.py,
    PERF.md "Cold start"): a scheduler-idle background thread AOT-
    compiles the recorded (config, bucket, lease-shape) lattice into the
    persistent compilation cache, so a cold submit loads executables from
    disk instead of paying the compile.  ``GET /debug/compile`` reports
    primed vs missing buckets; ``scripts/prime_cache.py`` is the offline
    equivalent."""

    enabled: bool = False                # start the idle primer thread
    idle_after_s: float = 5.0            # spool must be idle this long
                                         # before a prime cycle starts
    interval_s: float = 30.0             # rescan cadence for new bucket
                                         # specs once everything known is
                                         # primed
    max_specs_per_cycle: int = 0         # compile at most N specs per
                                         # idle cycle (0 = no cap); the
                                         # primer re-checks idleness
                                         # between specs either way

    def __post_init__(self):
        if self.idle_after_s < 0 or self.interval_s <= 0:
            raise ValueError("prime: idle_after_s must be >= 0 and "
                             "interval_s positive")
        if self.max_specs_per_cycle < 0:
            raise ValueError("prime: max_specs_per_cycle must be >= 0")


@dataclass(frozen=True)
class ReadPathConfig:
    """Result read path (ISSUE 16, service/readpath.py, docs/SERVICE.md
    "Read path"): the queryable annotation index + ion-image tile service +
    governed LRU cache behind the ``GET /datasets*`` endpoints.  Reads shed
    independently of writes: more than ``max_concurrent`` in-flight reads
    get a structured 429 + Retry-After, and cache fills stop (reads still
    answer from the source segments) when the disk governor degrades past
    the read-cache floor."""
    enabled: bool = True                 # serve the read endpoints
    cache_max_bytes: int = 64 << 20      # in-memory LRU result/tile cache
                                         # byte cap (0 disables caching)
    cache_max_entries: int = 1024        # ... entry cap
    cache_disk_max_bytes: int = 128 << 20  # on-disk tile cache byte cap
                                         # under <work_dir>/read_cache
                                         # (0 disables the disk tier)
    max_concurrent: int = 32             # in-flight read bound; excess reads
                                         # shed with 429 (0 = unlimited)
    retry_after_s: float = 1.0           # Retry-After hint on shed reads
    page_size: int = 100                 # default annotations page length
    page_size_max: int = 1000            # hard cap on ?limit=

    def __post_init__(self):
        if min(self.cache_max_bytes, self.cache_max_entries,
               self.cache_disk_max_bytes, self.max_concurrent) < 0:
            raise ValueError("read: cache/concurrency bounds must be >= 0")
        if self.retry_after_s < 0:
            raise ValueError("read: retry_after_s must be >= 0")
        if not 0 < self.page_size <= self.page_size_max:
            raise ValueError(
                "read: need 0 < page_size <= page_size_max")


@dataclass(frozen=True)
class StreamConfig:
    """Live-acquisition streaming ingest (ISSUE 19, docs/SERVICE.md
    "Streaming model"): ``mode=stream`` submits + ``POST
    /datasets/<id>/pixels`` chunk appends into the crash-safe chunk log,
    provisional re-scoring as coverage grows, and batch-identical
    convergence at ``POST /datasets/<id>/finish``."""
    idle_timeout_s: float = 300.0        # cancel an acquisition when no NEW
                                         # chunk commits for this long (the
                                         # stream analog of deadline_s —
                                         # stream jobs are exempt from the
                                         # submit-pinned absolute deadline);
                                         # 0 waits forever
    poll_interval_s: float = 0.25        # stream attempt's manifest poll
                                         # cadence while waiting for chunks
    rescore_min_chunks: int = 1          # provisional re-scores run only
                                         # when at least this many NEW
                                         # chunks committed since the last
                                         # one (1 = re-score every commit)
    retention_age_s: float = 3600.0      # finished chunk logs idle past
                                         # this are removed by the
                                         # governor's GC sweep; abandoned
                                         # (never-finished) logs after
                                         # retention_age_s + idle_timeout_s
                                         # idle (0 = keep forever)

    def __post_init__(self):
        if self.idle_timeout_s < 0 or self.retention_age_s < 0:
            raise ValueError(
                "stream: idle_timeout_s/retention_age_s must be >= 0")
        if self.poll_interval_s <= 0 or self.rescore_min_chunks < 1:
            raise ValueError("stream: poll_interval_s must be positive and "
                             "rescore_min_chunks >= 1")


@dataclass(frozen=True)
class FleetViewConfig:
    """Fleet observability plane (ISSUE 20, service/fleetview.py,
    docs/OBSERVABILITY.md "Fleet plane"): the serving replica scrapes live
    peers (admin addresses gossiped through registry heartbeats), merges
    their exposition, and answers ``GET /fleet/metrics|slo|status`` with a
    fleet-wide view that degrades to partial-with-evidence when a peer dies
    mid-scrape."""
    enabled: bool = True                 # serve the /fleet/* endpoints
    scrape_timeout_s: float = 2.0        # per-peer HTTP scrape budget; a
                                         # peer slower than this counts as a
                                         # scrape error, not a fleet 500
    cache_ttl_s: float = 1.0             # merged-view reuse window so N
                                         # dashboard readers cost one fleet
                                         # scrape (0 = scrape every request)

    def __post_init__(self):
        if self.scrape_timeout_s <= 0 or self.cache_ttl_s < 0:
            raise ValueError("fleetview: scrape_timeout_s must be positive "
                             "and cache_ttl_s >= 0")


@dataclass(frozen=True)
class ServiceConfig:
    """Annotation-service knobs (scheduler + failure policy + admin API) —
    the serving-side analog of the reference's rabbitmq/daemon settings.
    Consumed by ``sm_distributed_tpu.service`` (the ``serve`` CLI command)."""
    workers: int = 2                     # concurrent job slots (CPU phases
                                         # overlap; device phases serialize
                                         # through the scheduler's TPU token)
    poll_interval_s: float = 0.5         # fallback scan cadence of pending/:
                                         # how late the idle dispatcher finds
                                         # what ANOTHER process published (a
                                         # peer's API, a script, a requeue)
                                         # or a retry whose back-off ended;
                                         # this process's POST /submit wakes
                                         # it at once
    job_timeout_s: float = 21600.0       # per-attempt wall clock (6 h — the
                                         # 80k-formula DESI job is 32-67 min)
    max_attempts: int = 3                # attempts before dead-letter
    backoff_base_s: float = 1.0          # retry delay = base * 2^(n-1) ...
    backoff_max_s: float = 60.0          # ... capped here ...
    backoff_jitter: float = 0.1          # ... times 1 + U[0, jitter]
    heartbeat_interval_s: float = 5.0    # claim heartbeat touch cadence
    stale_after_s: float = 30.0          # claims with no heartbeat this old
                                         # are requeued by crash recovery
    drain_timeout_s: float = 30.0        # graceful-shutdown wait for running
    http_host: str = "127.0.0.1"         # admin API bind (healthz/metrics/
    http_port: int = 8685                # jobs/submit); port 0 = ephemeral
    # --- cooperative cancellation (utils/cancel.py, docs/SERVICE.md) ---
    cancel_grace_s: float = 15.0         # after a cancel is delivered, how
                                         # long the worker waits for the
                                         # attempt thread to unwind before
                                         # declaring it abandoned
    watchdog_interval_s: float = 5.0     # stall-watchdog scan cadence
    watchdog_stall_s: float = 0.0        # cancel attempts whose progress
                                         # heartbeat is older than this;
                                         # 0 disables the watchdog
    # --- poison-job quarantine ---
    quarantine_after: int = 8            # claims without a terminal outcome
                                         # before a message moves to
                                         # quarantine/; 0 disables
    # --- multi-chip device pool (service/device_pool.py, ISSUE 7) ---
    device_pool_size: int = 0            # chips the scheduler leases out;
                                         # 0 = auto (local jax device count
                                         # when the backend uses jax, else 1
                                         # — the old single-token behavior)
    devices_per_job: int = 1             # chips a job claims by default; a
                                         # per-submit "devices" field
                                         # overrides.  1 = pack small jobs
                                         # onto distinct chips; >1 = claim a
                                         # contiguous sub-mesh and score
                                         # through the pjit-sharded path
    device_pool_max_bypass: int = 64     # grants that may jump a waiting
                                         # larger lease before it seals the
                                         # queue (anti-starvation for
                                         # sub-mesh jobs under small-job
                                         # traffic)
    device_pool_hosts: int = 1           # host dimension of the pool (a
                                         # jax.distributed-style host×chip
                                         # topology, simulated on CPU): the
                                         # pool's chips split into this many
                                         # equal failure domains; 1-host
                                         # leases are preferred, a sub-mesh
                                         # lease may span hosts and reports
                                         # them (DeviceLease.hosts)
    lease_reap_after_s: float = 300.0    # an abandoned (zombie) attempt's
                                         # device lease is reclaimed when
                                         # its thread exits, or forcibly
                                         # after this TTL; 0 = wait for the
                                         # thread forever
    # --- per-chip device health (service/health.py, ISSUE 14) ---
    health_probe_on_lease: bool = True   # probe every granted chip with a
                                         # device round-trip before the job
                                         # touches it (no-op without jax)
    health_fault_quarantine: int = 3     # consecutive transient /
                                         # unattributed-sticky strikes on a
                                         # chip before it is quarantined
                                         # (an attributed sticky fault
                                         # quarantines immediately)
    health_reprobe_after_s: float = 60.0 # quarantine -> half-open re-probe
                                         # cooldown; a passing re-probe
                                         # readmits the chip (0 = never
                                         # re-probe)
    health_host_evict_fraction: float = 0.75  # fraction of a host domain's
                                         # chips quarantined at which the
                                         # WHOLE host is evicted (>= 1.0
                                         # disables host eviction)
    # --- pod host watchdog (service/scheduler.py, ISSUE 17) ---
    host_watchdog_interval_s: float = 0.0  # cadence of the per-host process-
                                         # heartbeat scan; 0 disables the
                                         # watchdog (single-process pods)
    host_stale_after_s: float = 10.0     # a host whose EVERY process beat is
                                         # older than this is evicted: its
                                         # chips quarantine as one unit and
                                         # in-flight attempts on them cancel
                                         # into the normal retry path
    # --- multi-replica scheduling (service/leases.py, ISSUE 8) ---
    replica_id: str = "r0"               # this scheduler process's identity
                                         # (serve --replica-id); leases and
                                         # heartbeats carry it
    replicas: int = 1                    # expected replica count (serve
                                         # --replicas) — informational; the
                                         # LIVE set comes from heartbeats
    spool_shards: int = 8                # logical spool partitions; claims
                                         # filter by crc32(msg_id) % shards
                                         # and rendezvous-hash ownership
    replica_heartbeat_interval_s: float = 2.0   # registry beat cadence
    replica_stale_after_s: float = 8.0   # a peer whose beat is older drops
                                         # from the alive set (its shards
                                         # redistribute to survivors)
    takeover_interval_s: float = 2.0     # takeover/orphan scan cadence
    # --- device-backend circuit breaker (models/breaker.py) ---
    breaker_threshold: int = 3           # consecutive device errors → open
    breaker_cooldown_s: float = 30.0     # open → half-open probe delay
    breaker_degraded_batch: int = 512    # numpy-fallback formula batch while
                                         # the breaker is open (reduced from
                                         # parallel.formula_batch)
    admission: AdmissionConfig = field(default_factory=AdmissionConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    prime: PrimeConfig = field(default_factory=PrimeConfig)
    read: ReadPathConfig = field(default_factory=ReadPathConfig)
    stream: StreamConfig = field(default_factory=StreamConfig)
    fleetview: FleetViewConfig = field(default_factory=FleetViewConfig)

    def __post_init__(self):
        if self.workers <= 0 or self.max_attempts <= 0:
            raise ValueError("service: workers/max_attempts must be positive")
        if self.backoff_base_s < 0 or self.backoff_max_s < 0 or self.backoff_jitter < 0:
            raise ValueError("service: backoff knobs must be non-negative")
        if self.cancel_grace_s < 0 or self.watchdog_interval_s <= 0 or \
                self.watchdog_stall_s < 0 or self.quarantine_after < 0:
            raise ValueError("service: cancel/watchdog/quarantine knobs out of range")
        if self.breaker_threshold <= 0 or self.breaker_cooldown_s < 0 or \
                self.breaker_degraded_batch <= 0:
            raise ValueError("service: breaker knobs out of range")
        if self.device_pool_size < 0 or self.devices_per_job <= 0 or \
                self.device_pool_max_bypass < 0:
            raise ValueError("service: device-pool knobs out of range "
                             "(device_pool_size >= 0, devices_per_job >= 1, "
                             "device_pool_max_bypass >= 0)")
        if self.device_pool_hosts <= 0 or self.lease_reap_after_s < 0:
            raise ValueError("service: device_pool_hosts must be >= 1 and "
                             "lease_reap_after_s >= 0")
        if self.health_fault_quarantine < 1 or \
                self.health_reprobe_after_s < 0 or \
                not 0.0 < self.health_host_evict_fraction:
            raise ValueError(
                "service: health_fault_quarantine must be >= 1, "
                "health_reprobe_after_s >= 0, and "
                "health_host_evict_fraction > 0 (>= 1.0 disables eviction)")
        if self.host_watchdog_interval_s < 0 or self.host_stale_after_s <= 0:
            raise ValueError("service: host_watchdog_interval_s must be >= 0 "
                             "and host_stale_after_s positive")
        if not self.replica_id or self.replicas <= 0 or self.spool_shards <= 0:
            raise ValueError("service: replica_id must be non-empty and "
                             "replicas/spool_shards positive")
        if self.replica_heartbeat_interval_s <= 0 or \
                self.replica_stale_after_s <= 0 or \
                self.takeover_interval_s <= 0:
            raise ValueError("service: replica heartbeat/staleness/takeover "
                             "intervals must be positive")


@dataclass(frozen=True)
class ProfileConfig:
    """On-demand device profiling (ISSUE 20, service/fleetview.py,
    docs/OBSERVABILITY.md "Device profiles"): ``GET /debug/profile?seconds=``
    runs a ``jax.profiler`` capture around in-flight work, attributes device
    time per ``jax.named_scope`` and idle gaps per program span, and appends
    ``device_scope`` / ``device_busy`` / ``device_idle`` spans to the traces
    of the jobs it overlapped."""
    enabled: bool = True                 # serve /debug/profile
    default_seconds: float = 2.0         # capture window when ?seconds= is
                                         # omitted
    max_seconds: float = 30.0            # hard cap on a requested window (a
                                         # profile holds the single-flight
                                         # slot for its whole duration)
    dir: str = ""                        # capture dir; "" = <work_dir>/profiles

    def __post_init__(self):
        if not 0 < self.default_seconds <= self.max_seconds:
            raise ValueError("profile: need 0 < default_seconds <= "
                             "max_seconds")


@dataclass(frozen=True)
class TelemetryConfig:
    """Quantitative telemetry (service/telemetry.py, docs/OBSERVABILITY.md):
    the device/HBM monitor + metric-snapshot time-series ring behind
    ``GET /debug/timeseries``, and the SLO objectives ``GET /slo`` reports
    attainment/error-budget burn against."""
    enabled: bool = True                 # start the sampling thread
    sample_interval_s: float = 5.0       # device/occupancy sample cadence
    timeseries_len: int = 720            # snapshot ring capacity (1 h @ 5 s)
    retrace: bool = True                 # compile-attribution tracer
                                         # (analysis/retrace.py): sm_compile_*
                                         # metrics + `compile` trace events
    # SLO objectives: latency threshold (seconds) + attainment target
    # (fraction of jobs that must land under the threshold)
    slo_queue_wait_s: float = 30.0       # submit -> first attempt start
    slo_first_annotation_s: float = 120.0  # submit -> first scored group
    slo_e2e_s: float = 600.0             # submit -> terminal outcome
    slo_read_s: float = 0.25             # read request -> response (ISSUE 16)
    slo_stream_partial_s: float = 30.0   # stream chunk commit -> provisional
                                         # partial served (ISSUE 19)
    slo_target: float = 0.99
    profile: ProfileConfig = field(default_factory=ProfileConfig)

    def __post_init__(self):
        if self.sample_interval_s <= 0 or self.timeseries_len <= 0:
            raise ValueError(
                "telemetry: sample_interval_s/timeseries_len must be positive")
        if min(self.slo_queue_wait_s, self.slo_first_annotation_s,
               self.slo_e2e_s, self.slo_read_s,
               self.slo_stream_partial_s) <= 0:
            raise ValueError("telemetry: SLO thresholds must be positive")
        if not 0.0 < self.slo_target < 1.0:
            raise ValueError("telemetry: slo_target must be in (0, 1)")


@dataclass(frozen=True)
class TracingConfig:
    """End-to-end job tracing (utils/tracing.py, docs/OBSERVABILITY.md):
    per-job JSONL span logs + the in-memory flight recorder behind
    ``GET /jobs/<id>/trace`` and ``GET /debug/events``."""
    enabled: bool = True                 # span/event emission on traced jobs
    dir: str = ""                        # trace-file dir; "" = <work_dir>/traces
    ring_size: int = 2048                # flight-recorder record capacity
    # bounded retention for the ON-DISK per-job trace files (the flight-
    # recorder ring is already bounded; the files were not — ISSUE 10
    # satellite).  Enforced by the resource governor's GC sweeper
    # (service/resources.py): files older than retention_age_s are removed,
    # and when the trace dir exceeds retention_max_bytes the oldest files
    # go first.  0 disables that dimension.
    retention_age_s: float = 0.0
    retention_max_bytes: int = 0

    def __post_init__(self):
        if self.ring_size <= 0:
            raise ValueError("tracing.ring_size must be positive")
        if self.retention_age_s < 0 or self.retention_max_bytes < 0:
            raise ValueError("tracing.retention_* must be >= 0")


@dataclass(frozen=True)
class ResourcesConfig:
    """Resource-exhaustion survival (service/resources.py, docs/RECOVERY.md
    "Resource exhaustion"): disk-budget governor + bounded-retention GC.
    The governor preflights every governed write seam and degrades in a
    configured order as headroom shrinks — trace writes drop first
    (remaining < trace_floor_bytes), then isocalc cache writes
    (< cache_floor_bytes), then new submits shed with a structured 507
    (< submit_floor_bytes); essential writes (checkpoints, results, spool)
    are denied only when the floor itself would be breached."""
    min_free_bytes: int = 0              # filesystem free-space reserve the
                                         # governor protects (0 disables the
                                         # statvfs constraint)
    disk_budget_bytes: int = 0           # cap on bytes under the governed
                                         # roots (work/results/queue);
                                         # 0 = free-space constraint only
    trace_floor_bytes: int = 32 << 20    # remaining headroom below which
                                         # trace-file writes are dropped
    cache_floor_bytes: int = 16 << 20    # ... below which isocalc cache
                                         # shard writes are dropped
    read_cache_floor_bytes: int = 12 << 20  # ... below which read-path
                                         # result/tile cache fills stop
                                         # (reads answer from source)
    submit_floor_bytes: int = 8 << 20    # ... below which POST /submit
                                         # sheds with 507 + Retry-After
    gc_interval_s: float = 30.0          # retention sweep + usage rescan
                                         # cadence (scheduler replica loop)
    done_retention_age_s: float = 0.0    # spool done/ messages older than
                                         # this are removed (0 = keep)
    failed_retention_age_s: float = 0.0  # dead-letter/quarantine evidence
                                         # older than this is removed
                                         # (0 = keep)
    cache_retention_max_bytes: int = 0   # isocalc cache size cap — oldest
                                         # shards removed first (0 = keep)
    registry_retention_age_s: float = 3600.0  # crashed replicas' registry
                                         # heartbeat files older than this
                                         # are removed (they never retire)

    def __post_init__(self):
        if min(self.min_free_bytes, self.disk_budget_bytes,
               self.cache_retention_max_bytes) < 0:
            raise ValueError("resources: byte knobs must be >= 0")
        if not (self.trace_floor_bytes >= self.cache_floor_bytes
                >= self.read_cache_floor_bytes
                >= self.submit_floor_bytes >= 0):
            raise ValueError(
                "resources: degrade floors must be ordered "
                "trace_floor_bytes >= cache_floor_bytes >= "
                "read_cache_floor_bytes >= submit_floor_bytes >= 0 "
                "(traces drop first, then isocalc cache, then read-cache "
                "fills, then submits)")
        if self.gc_interval_s <= 0:
            raise ValueError("resources.gc_interval_s must be positive")
        if min(self.done_retention_age_s, self.failed_retention_age_s,
               self.registry_retention_age_s) < 0:
            raise ValueError("resources: retention ages must be >= 0")


@dataclass(frozen=True)
class LogsConfig:
    """Structured logging: ``json: true`` switches every handler to one
    JSON object per line with ``trace_id``/``job_id``/``span`` injected from
    the ambient trace context (utils/logger.py::JsonLogFormatter)."""
    json: bool = False


@dataclass(frozen=True)
class StorageConfig:
    """Replaces sm_config['db'/'elasticsearch'] service blocks: pluggable local
    sinks (parquet results + sqlite index) instead of Postgres/ES."""
    results_dir: str = "results"
    store_images: bool = True
    image_format: str = "npz"            # npz (sparse) | png


@dataclass(frozen=True)
class SMConfig:
    """Engine-global config (the reference's conf/config.json via
    sm/engine/util.py::SMConfig [U])."""
    backend: str = "jax_tpu"
    fdr: FDRConfig = field(default_factory=FDRConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    service: ServiceConfig = field(default_factory=ServiceConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    resources: ResourcesConfig = field(default_factory=ResourcesConfig)
    logs: LogsConfig = field(default_factory=LogsConfig)
    work_dir: str = "/tmp/sm_tpu_work"
    logs_dir: str = ""                   # "" = console only

    @property
    def trace_dir(self) -> str:
        """Resolved per-job trace-file directory (tracing.dir wins)."""
        return self.tracing.dir or str(Path(self.work_dir) / "traces")
    # fault injection for chaos/recovery testing (utils/failpoints.py,
    # docs/RECOVERY.md): same grammar as the SM_FAILPOINTS env var, which
    # always wins when set; "" disables.  NEVER set in production configs.
    failpoints: str = ""

    def __post_init__(self):
        if self.backend not in VALID_BACKENDS:
            raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {self.backend!r}")
        for knob, valid in (("order_ions", ("auto", "mz", "table")),
                            ("band_slice", ("auto", "on", "off")),
                            ("peak_compaction", ("auto", "on", "off")),
                            ("isocalc_device", ("on", "off")),
                            ("overlap_isocalc", ("auto", "on", "off")),
                            ("compile_cache_dir", ("", "off")),
                            ("cube_dtype", ("f32", "bf16")),
                            ("fused_metrics", ("auto", "off"))):
            v = getattr(self.parallel, knob)
            if v not in valid:
                removed = (
                    ": the fused Pallas scoring variant was removed in PR 44"
                    if (knob, v) == ("fused_metrics", "on") else "")
                raise ValueError(
                    f"parallel.{knob} must be one of {valid}, "
                    f"got {v!r}{removed}")
        n = self.parallel.resident_datasets
        if n != "auto" and not (type(n) is int and n >= 0):
            raise ValueError(
                "parallel.resident_datasets must be a count >= 0 or "
                f"\"auto\", got {n!r}")

    # -- singleton access, mirroring SMConfig.set_path()/get_conf() [U] --
    _instance: ClassVar["SMConfig | None"] = None

    @staticmethod
    def set_path(path: str | Path) -> "SMConfig":
        SMConfig._instance = _from_dict(SMConfig, json.loads(Path(path).read_text()))
        return SMConfig._instance

    @staticmethod
    def set(conf: "SMConfig") -> "SMConfig":
        SMConfig._instance = conf
        return conf

    @staticmethod
    def get_conf() -> "SMConfig":
        if SMConfig._instance is None:
            SMConfig._instance = SMConfig()
        return SMConfig._instance

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "SMConfig":
        return _from_dict(SMConfig, d)


# nested-field -> dataclass routing for _from_dict
_DATACLASS_FIELDS = {
    ("DSConfig", "database"): DatabaseConfig,
    ("DSConfig", "isotope_generation"): IsotopeGenerationConfig,
    ("DSConfig", "image_generation"): ImageGenerationConfig,
    ("SMConfig", "fdr"): FDRConfig,
    ("SMConfig", "parallel"): ParallelConfig,
    ("SMConfig", "storage"): StorageConfig,
    ("SMConfig", "service"): ServiceConfig,
    ("SMConfig", "tracing"): TracingConfig,
    ("SMConfig", "telemetry"): TelemetryConfig,
    ("SMConfig", "resources"): ResourcesConfig,
    ("SMConfig", "logs"): LogsConfig,
    ("ServiceConfig", "admission"): AdmissionConfig,
    ("ServiceConfig", "fleet"): FleetConfig,
    ("ServiceConfig", "prime"): PrimeConfig,
    ("ServiceConfig", "read"): ReadPathConfig,
    ("ServiceConfig", "stream"): StreamConfig,
    ("ServiceConfig", "fleetview"): FleetViewConfig,
    ("TelemetryConfig", "profile"): ProfileConfig,
}
