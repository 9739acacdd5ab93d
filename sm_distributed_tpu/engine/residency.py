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

Keys carry content identity, not just names: datasets key on the staged
input manifest (so a restaged different file misses), backends key on the
search fingerprint (dataset content + image config + batch partition +
ion table) plus every backend-shaping parallel knob.
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

    def get_or_build(self, key, builder):
        with self._lock:
            if self.maxsize <= 0:
                self.misses += 1
            elif key in self.data:
                self.hits += 1
                self.data.move_to_end(key)
                return self.data[key]
            else:
                self.misses += 1
        val = builder()
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


class DatasetResidency:
    """LRU caches for host datasets and compiled backends across jobs."""

    def __init__(self, max_datasets: int = 2, max_backends: int = 2):
        self._datasets = _LRU(max_datasets)
        self._backends = _LRU(max_backends)

    def dataset(self, key, loader):
        return self._datasets.get_or_build(key, loader)

    def backend(self, key, builder):
        return self._backends.get_or_build(key, builder)

    @property
    def stats(self) -> dict:
        return {
            "dataset_hits": self._datasets.hits,
            "dataset_misses": self._datasets.misses,
            "backend_hits": self._backends.hits,
            "backend_misses": self._backends.misses,
        }
