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
the persistent compile cache or compiles.  This cache keeps the last N of
each across daemon messages with LRU eviction, so a second job on the same
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
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from ..utils.logger import logger


class _LRU:
    """Thread-safe LRU.  The service scheduler's workers share one residency
    across concurrent jobs; the lock guards only the dict bookkeeping, NOT
    ``builder()`` — holding it through a minutes-long parse would serialize
    exactly the CPU staging the scheduler exists to overlap.  Two workers
    missing on the same key may therefore both build; the first insert wins
    and the duplicate is dropped (device-backend builds don't race in
    practice because they run under the scheduler's TPU token)."""

    # shared-state registry checked by the smlint guarded-by rule
    # (docs/ANALYSIS.md): mutated only under _lock
    _GUARDED_BY = {"data": "_lock", "hits": "_lock", "misses": "_lock"}

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self.data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def get(self, key):
        """The value under ``key`` or None; counts the hit or the miss."""
        with self._lock:
            if self.maxsize > 0 and key in self.data:
                self.hits += 1
                self.data.move_to_end(key)
                return self.data[key]
            self.misses += 1
            return None

    def put(self, key, val):
        """Keep ``val`` under ``key`` and return what the cache holds there:
        ``val``, or a concurrent builder's value that came first."""
        if self.maxsize <= 0:
            return val
        with self._lock:
            if key in self.data:       # concurrent builder won — reuse theirs
                return self.data[key]
            self.data[key] = val
            while len(self.data) > self.maxsize:
                old_key, _old = self.data.popitem(last=False)
                logger.info("residency: evicted %s", old_key[0] if old_key else old_key)
        return val

    def get_or_build(self, key, builder):
        val = self.get(key)
        return val if val is not None else self.put(key, builder())


class DatasetResidency:
    """LRU caches for host datasets, compiled backends and finished ion
    tables across jobs."""

    def __init__(self, max_datasets: int = 2, max_backends: int = 2):
        self._datasets = _LRU(max_datasets)
        self._backends = _LRU(max_backends)
        # bounded like the datasets that are scored against them
        self._ion_tables = _LRU(max_datasets)

    def dataset(self, key, loader):
        return self._datasets.get_or_build(key, loader)

    def backend(self, key, builder):
        return self._backends.get_or_build(key, builder)

    def ion_table(self, key):
        """The resident ion table under ``key`` or None.  No builder here:
        a miss's table is made by a stream that may fail or be cancelled,
        and only its owner knows when it is whole (``keep_ion_table``)."""
        return self._ion_tables.get(key)

    def keep_ion_table(self, key, entry):
        return self._ion_tables.put(key, entry)

    @property
    def stats(self) -> dict:
        return {
            "dataset_hits": self._datasets.hits,
            "dataset_misses": self._datasets.misses,
            "backend_hits": self._backends.hits,
            "backend_misses": self._backends.misses,
            "ion_table_hits": self._ion_tables.hits,
            "ion_table_misses": self._ion_tables.misses,
        }
