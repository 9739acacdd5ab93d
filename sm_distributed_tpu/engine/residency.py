"""Multi-dataset residency for service mode (daemon).

Reference: the daemon keeps ONE long-lived SparkContext across queue
messages, so repeat jobs skip cluster spin-up [U] (SURVEY.md #16).  The
TPU-native analog of that warm state is (a) the host-side CSR dataset
layout (the parse: ~1 s for a 64x64 section, minutes for a large slide)
with, cached on it, the dataset-only half of a backend build - intensity
grid, m/z quantization and every peak in stable m/z order
(``SpectralDataset.flat_sorted``, 12 B a peak; a job makes it BEFORE it asks
for the chip) - and (b) the backend object: the device-resident flat peak
arrays, whose build under the lease is what is left once (a) is there
(window restriction against the job's ion table, lattice padding, the
``device_put``), plus the jitted programs, which a new process loads from
the persistent compile cache or compiles.  A second job on the same
dataset/shapes skips parse, prepare, build AND compile (ROADMAP item 3,
VERDICT r2 item 7).

A third residency holds what depends on no dataset at all: (c) the finished
ion table of a parameter set - the decoy draw and every isotope pattern of
the job's (formula, adduct) list, packed (``models/msm_basic.py::
ResidentIonTable``).  It is the top of three tiers (docs/ISOCALC.md): this
table in memory, over the checksummed shards on disk, over cold generation.
A hit hands a job the table a fresh ``IsotopePrefetch`` would build, bit for
bit, and skips building it: the decoy draw, the read-back of every shard of
the parameter set, the per-ion dedup / chemistry check / row fill.  The
TABLE is kept, not the ``IsocalcWrapper``: the wrapper's cache is one pair
of small arrays an ion (what the shard read-back spends its time making, and
what a stream then copies out of, ion by ion), the table four flat arrays
(~9 MB at 126,000 ions, ~120 MB at 1.68 M) that scoring reads as they are.
Its arrays are read-only: two workers may score one table at once.

Keys carry content identity, not just names: datasets key on the staged
input manifest (so a restaged different file misses), backends key on the
search fingerprint (dataset content + image config + batch partition +
ion table) plus every backend-shaping parallel knob, ion tables key on a
digest of the formula list in order plus everything the decoys and the
patterns are a function of (``models/msm_basic.py::ion_table_key``) and on
nothing of the dataset: every upload against one database shares an entry.

All three live in ONE store (``ResidentStore``), in one order of recency,
bounded either way ``parallel.resident_datasets`` says (docs/SERVICE.md
"Residency"):

- an integer N: at most N entries of each kind, the least recently used of
  a kind leaving when an N+1st comes (what it has always meant);
- ``"auto"``: as many as the bytes allow.  Every entry is weighed where its
  bytes live - a backend's resident arrays on ITS chip (tier
  ``device:<chip>``), a dataset's CSR arrays and prepared flat layout, an
  ion table's four arrays and a backend's host-side m/z index in host
  memory (tier ``host``) - and a newcomer evicts by recency until every
  tier it weighs on is inside its budget.  A chip's budget is its
  allocator's ``bytes_limit`` (``utils/devicemem.py``) less what the
  hungriest resident backend's scoring needs beside the resident arrays
  (``JaxBackend.scoring_reserve_bytes``); the host's is
  ``HOST_BUDGET_SHARE`` of the machine's available memory when the store
  was made.  Both are computed, none is configured.

An entry a running job holds (``DatasetResidency.job()``) is pinned: never
chosen by the bytes rule, and where the count rule drops it all the same
its device buffers are freed when the job lets go, not under it.  An
unpinned backend's buffers are freed at the eviction itself
(``JaxBackend.release``), not at the next collection.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from ..utils import tracing
from ..utils.logger import logger

CACHES = ("dataset", "backend", "ion_table")
HOST = "host"
# the share of the machine's available memory (read once, when the store is
# made) that resident datasets, ion tables and backend indexes may fill
HOST_BUDGET_SHARE = 0.5


def device_tier(chip: int) -> str:
    return f"device:{int(chip)}"


class _Entry:
    """One resident thing: where it weighs (``charges``: tier -> bytes), what
    must stay free beside it on those tiers (``reserve``), who holds it."""

    __slots__ = ("cache", "key", "value", "charges", "reserve", "pins",
                 "evicted")

    def __init__(self, cache, key, value, charges, reserve):
        self.cache, self.key, self.value = cache, key, value
        self.charges = {t: int(b) for t, b in charges.items() if b}
        self.reserve = {t: int(b) for t, b in (reserve or {}).items() if b}
        self.pins = 0
        self.evicted = False

    @property
    def bytes(self) -> int:
        return sum(self.charges.values())


class ResidentStore:
    """Thread-safe LRU over ``(cache, key)`` under count caps and byte
    budgets.  The service scheduler's workers share one store across
    concurrent jobs; the lock guards only the bookkeeping, NOT a builder -
    holding it through a minutes-long parse would serialize exactly the CPU
    staging the scheduler exists to overlap.  Two workers missing on the
    same key may therefore both build; the first insert wins and the
    duplicate is dropped (device-backend builds don't race in practice
    because they run under the scheduler's device lease).

    ``caps``: cache -> most entries (0 keeps nothing, None no cap).
    ``budget_of``: tier -> bytes, or None for a tier without a budget; what
    the entries on a tier want kept free beside them (the largest
    ``reserve``) comes off it.  The plain statement of the bytes rule, a
    list and a loop, is ``tests/residency_reference.py``."""

    # shared-state registry checked by the smlint guarded-by rule
    # (docs/ANALYSIS.md): mutated only under _lock
    _GUARDED_BY = {"_entries": "_lock", "_held": "_lock",
                   "_reserve": "_lock", "hits": "_lock", "misses": "_lock",
                   "evictions": "_lock"}

    def __init__(self, caps: dict | None = None, budget_of=None):
        self._caps = dict(caps or {})
        self._budget_of = budget_of or (lambda tier: None)
        self._entries: OrderedDict = OrderedDict()   # oldest first
        self._held: dict[str, int] = {}              # tier -> bytes
        self._reserve: dict[str, int] = {}           # tier -> largest asked
        self.hits = dict.fromkeys(CACHES, 0)
        self.misses = dict.fromkeys(CACHES, 0)
        self.evictions: dict[tuple[str, str], int] = {}
        self._lock = threading.Lock()

    def _keeps(self, cache: str) -> bool:
        cap = self._caps.get(cache)
        return cap is None or cap > 0

    def get(self, cache: str, key, pin: bool = False) -> _Entry | None:
        """The entry under ``key`` or None; counts the hit or the miss."""
        with self._lock:
            entry = self._entries.get((cache, key)) \
                if self._keeps(cache) else None
            if entry is None:
                self.misses[cache] += 1
                return None
            self.hits[cache] += 1
            self._entries.move_to_end((cache, key))
            entry.pins += bool(pin)
            return entry

    def put(self, cache: str, key, value, charges: dict,
            reserve: dict | None = None,
            pin: bool = False) -> tuple[_Entry, list[_Entry]]:
        """Keep ``value`` under ``key``.  Returns the entry the store holds
        there (``value``'s, or a concurrent builder's that came first) and
        what left to make room, oldest first."""
        entry = _Entry(cache, key, value, charges, reserve)
        if not self._keeps(cache):
            return entry, []
        with self._lock:
            first = self._entries.get((cache, key))
            if first is not None:      # concurrent builder won - reuse theirs
                first.pins += bool(pin)
                return first, []
            entry.pins = int(bool(pin))
            self._entries[(cache, key)] = entry
            for tier, n in entry.charges.items():
                self._held[tier] = self._held.get(tier, 0) + n
            for tier, n in entry.reserve.items():
                if n > self._reserve.get(tier, 0):
                    self._reserve[tier] = n
                    budget = self._budget_of(tier)
                    if budget is not None:
                        logger.info(
                            "residency: %s may hold %d bytes (%d less the "
                            "%d a resident backend's scoring wants free)",
                            tier, max(0, budget - n), budget, n)
            gone = self._shrink_locked()
        self._free(gone)
        return entry, gone

    def unpin(self, entries: list[_Entry]) -> list[_Entry]:
        """Let go of what ``get`` / ``put`` pinned.  An entry the count rule
        dropped while it was held is freed now; one that stayed may be the
        room a tier over its budget was waiting for."""
        with self._lock:
            late = []
            for entry in entries:
                entry.pins -= 1
                if entry.evicted and entry.pins == 0:
                    late.append(entry)
            gone = self._shrink_locked()
        self._free(late + gone)
        return gone

    def _room_locked(self, tier: str) -> int | None:
        """Bytes ``tier`` may hold: its budget less the largest reserve an
        entry on it asks for; None where the tier has no budget."""
        budget = self._budget_of(tier)
        if budget is None:
            return None
        return max(0, budget - self._reserve.get(tier, 0))

    def _over_locked(self, tier: str) -> bool:
        room = self._room_locked(tier)
        return room is not None and self._held.get(tier, 0) > room

    def _shrink_locked(self) -> list[_Entry]:
        gone = []
        # the count rule, as the three LRUs had it: past the cap the oldest
        # of a kind leaves, held or not
        for cache, cap in self._caps.items():
            if cap:
                mine = [k for k in self._entries if k[0] == cache]
                gone += [self._drop_locked(k, "count")
                         for k in mine[:max(0, len(mine) - cap)]]
        # the bytes rule: the oldest entry nobody holds that weighs on a
        # tier over its budget, until none is over or none is left
        for tier in list(self._held):
            while self._over_locked(tier):
                k = next((k for k, e in self._entries.items()
                          if not e.pins and tier in e.charges), None)
                if k is None:
                    break
                gone.append(self._drop_locked(k, "bytes"))
        return gone

    def _drop_locked(self, k, cause: str) -> _Entry:
        entry = self._entries.pop(k)
        entry.evicted = True
        for tier, n in entry.charges.items():
            self._held[tier] -= n
        for tier in entry.reserve:
            self._reserve[tier] = max(
                (e.reserve.get(tier, 0) for e in self._entries.values()),
                default=0)
        self.evictions[(entry.cache, cause)] = \
            self.evictions.get((entry.cache, cause), 0) + 1
        logger.info("residency: evicted a %s of %d bytes (%s)%s",
                    entry.cache, entry.bytes, cause,
                    ", held by a running job" if entry.pins else "")
        return entry

    @staticmethod
    def _free(entries: list[_Entry]) -> None:
        """Give an evicted value's buffers back NOW (``JaxBackend.release``:
        two workers admit at once, and the next collection may be a job
        away); what a job still holds waits for its ``unpin``."""
        for entry in entries:
            release = getattr(entry.value, "release", None)
            if not entry.pins and callable(release):
                release()

    def held(self, cache: str) -> tuple[int, dict[str, int]]:
        """(entries, bytes per tier) of one cache."""
        with self._lock:
            mine = [e for e in self._entries.values() if e.cache == cache]
        tiers: dict[str, int] = {}
        for e in mine:
            for tier, n in e.charges.items():
                tiers[tier] = tiers.get(tier, 0) + n
        return len(mine), tiers

    def room(self, tier: str) -> int | None:
        """What ``tier`` may hold right now (budget less reserve)."""
        with self._lock:
            return self._room_locked(tier)

    def view(self, cache: str) -> dict:
        """key -> value of one cache (a copy; tests count it)."""
        with self._lock:
            return {k[1]: e.value for k, e in self._entries.items()
                    if k[0] == cache}


class _CacheView:
    """One kind's corner of the store, as the LRU of that kind showed it."""

    def __init__(self, store: ResidentStore, cache: str):
        self._store, self._cache = store, cache

    @property
    def data(self) -> dict:
        return self._store.view(self._cache)


def _backend_charges(backend) -> tuple[dict, dict]:
    """(charges, reserve) of a backend: its resident arrays on its chip and
    its m/z index on the host; beside the arrays, what its scoring takes.
    A backend that states no ``resident_bytes`` (the host oracle, the
    mesh-sharded one) weighs nothing: only a count cap bounds it."""
    tier = device_tier(getattr(getattr(backend, "device", None), "id", 0))
    return ({tier: getattr(backend, "resident_bytes", 0),
             HOST: getattr(backend, "resident_host_bytes", 0)},
            {tier: getattr(backend, "scoring_reserve_bytes", 0)})


def _dataset_bytes(ds) -> int:
    weigh = getattr(ds, "resident_bytes", None)
    return weigh() if callable(weigh) else 0


def _ion_table_bytes(entry) -> int:
    """The table's four arrays (``models/msm_basic.py::ResidentIonTable``)."""
    table = getattr(entry, "table", None)
    return sum(int(getattr(getattr(table, name, None), "nbytes", 0))
               for name in ("mzs", "ints", "n_valid", "targets"))


def home_bytes(cache: str, tiers: dict[str, int]) -> int:
    """A cache's bytes where they count: a backend's on its chips (its
    host-side index is listed apart), a dataset's and a table's on the
    host."""
    return sum(b for t, b in tiers.items()
               if (t != HOST) == (cache == "backend"))


class _Access:
    """The four lookups a job makes, over a shared store.  ``pin`` says
    whether what they return stays held until ``release()``."""

    def __init__(self, store: ResidentStore, pin: bool):
        self._store = store
        self._pin = pin
        self._pinned: list[_Entry] = []
        # evictions by this holder's last lookup, a cache
        self._evicted = dict.fromkeys(CACHES, 0)

    def _get(self, cache: str, key):
        self._evicted[cache] = 0
        entry = self._store.get(cache, key, pin=self._pin)
        if entry is None:
            return None
        if self._pin:
            self._pinned.append(entry)
        return entry.value

    def _put(self, cache: str, key, value, charges, reserve=None):
        entry, gone = self._store.put(cache, key, value, charges, reserve,
                                      pin=self._pin)
        if self._pin and entry.pins:
            self._pinned.append(entry)
        self._evicted[cache] = len(gone)
        return entry.value

    def dataset(self, key, loader):
        ds = self._get("dataset", key)
        if ds is None:
            ds = loader()
            ds = self._put("dataset", key, ds, {HOST: _dataset_bytes(ds)})
        return ds

    def backend(self, key, builder):
        backend = self._get("backend", key)
        if backend is None:
            backend = builder()
            backend = self._put("backend", key, backend,
                                *_backend_charges(backend))
        # onto the caller's backend_build span (models/msm_basic.py, whose
        # lines the compile cache's keys know: nothing is added there)
        tracing.annotate(**self.span_attrs("backend"))
        return backend

    def ion_table(self, key):
        """The resident ion table under ``key`` or None.  No builder here:
        a miss's table is made by a stream that may fail or be cancelled,
        and only its owner knows when it is whole (``keep_ion_table``)."""
        return self._get("ion_table", key)

    def keep_ion_table(self, key, entry):
        return self._put("ion_table", key, entry,
                         {HOST: _ion_table_bytes(entry)})

    def span_attrs(self, cache: str) -> dict:
        """What the span around a lookup says of the store after it: entries
        and bytes of that kind, and how many this holder's lookup evicted."""
        n, tiers = self._store.held(cache)
        return {"residency_entries": n,
                "residency_bytes": home_bytes(cache, tiers),
                "residency_evicted": self._evicted[cache]}

    def release(self) -> None:
        """The job is over (or a stream's provisional pass is): nothing this
        holder looked up is pinned any longer."""
        pinned, self._pinned = self._pinned, []
        if pinned:
            self._store.unpin(pinned)


def _host_available_bytes() -> int | None:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError):
        logger.debug("residency: /proc/meminfo unreadable", exc_info=True)
    return None


def _chip_limit_bytes(chip: int) -> int | None:
    from ..utils import devicemem

    return next((d["limit_bytes"] for d in devicemem.device_stats()
                 if d["id"] == chip), None)


class DatasetResidency(_Access):
    """Host datasets, compiled backends and finished ion tables kept across
    jobs.  ``max_datasets`` / ``max_backends``: the count caps (ion tables
    share the datasets'; None lifts a cap).  ``byte_budgets``: bound every
    tier by bytes (``parallel.resident_datasets: "auto"``, ``from_config``).
    ``device_limit_bytes`` stands in for a chip's ``bytes_limit``: for a
    test or a by-hand run that wants evictions at a small size, never from
    a config."""

    def __init__(self, max_datasets: int | None = 2,
                 max_backends: int | None = 2, byte_budgets: bool = False,
                 device_limit_bytes: int | None = None):
        self._device_limit = device_limit_bytes
        self._byte_budgets = byte_budgets
        # tier -> bytes before the reserve comes off (None: no budget)
        self._limits: dict[str, int | None] = {}
        if byte_budgets:
            avail = _host_available_bytes()
            self._limits[HOST] = None if avail is None \
                else int(avail * HOST_BUDGET_SHARE)
            logger.info(
                "residency: byte budgets; the host may hold %s bytes (%d%% "
                "of the %s available now), a chip its bytes_limit less the "
                "largest resident backend's scoring_reserve_bytes (said at "
                "its first backend)", self._limits[HOST],
                100 * HOST_BUDGET_SHARE, avail)
        store = ResidentStore(
            {"dataset": max_datasets, "backend": max_backends,
             "ion_table": max_datasets}, self._limit_of)
        super().__init__(store, pin=False)
        self._datasets = _CacheView(store, "dataset")
        self._backends = _CacheView(store, "backend")
        self._ion_tables = _CacheView(store, "ion_table")

    @classmethod
    def from_config(cls, resident_datasets) -> "DatasetResidency | None":
        """``parallel.resident_datasets`` as the service reads it: 0 keeps
        nothing (no residency at all), an integer N caps each kind at N,
        ``"auto"`` bounds the store by bytes."""
        if resident_datasets == "auto":
            return cls(max_datasets=None, max_backends=None,
                       byte_budgets=True)
        if resident_datasets > 0:
            return cls(max_datasets=resident_datasets,
                       max_backends=resident_datasets)
        return None

    def _limit_of(self, tier: str) -> int | None:
        """Bytes ``tier`` may hold before the reserve comes off: the host's
        share, a chip's forced limit or its ``bytes_limit`` (asked once a
        chip: jax is loaded by the time a backend weighs on one)."""
        if tier != HOST and self._device_limit is not None:
            return self._device_limit
        if not self._byte_budgets:
            return None
        if tier not in self._limits:
            self._limits[tier] = _chip_limit_bytes(int(tier.partition(":")[2]))
            logger.info("residency: %s bytes_limit %s%s", tier,
                        self._limits[tier], "" if self._limits[tier]
                        else ": no budget on this platform")
        return self._limits[tier]

    def job(self) -> _Access:
        """One job's hold on this residency: the same lookups, each pinning
        what it returns until the job's ``release()``."""
        return _Access(self._store, pin=True)

    @property
    def stats(self) -> dict:
        """Hits, misses, entries and bytes a cache (``backend_host_bytes``:
        the backends' host-side indexes, charged to the host), evictions by
        ``(cache, cause)``, and what each tier may hold now (``device``: the
        fullest chip's; None where a tier has no budget)."""
        store = self._store
        out, chips = {}, {}
        for cache in CACHES:
            n, tiers = store.held(cache)
            out[f"{cache}_hits"] = store.hits[cache]
            out[f"{cache}_misses"] = store.misses[cache]
            out[f"{cache}_entries"] = n
            out[f"{cache}_bytes"] = home_bytes(cache, tiers)
            if cache == "backend":
                out["backend_host_bytes"] = tiers.get(HOST, 0)
                chips = {t: b for t, b in tiers.items() if t != HOST}
        out["evictions"] = dict(store.evictions)
        fullest = max(chips, key=chips.get) if chips else None
        out["budget_bytes"] = {
            "device": store.room(fullest) if fullest else None,
            HOST: store.room(HOST)}
        return out
