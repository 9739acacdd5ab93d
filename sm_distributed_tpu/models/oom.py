"""HBM-OOM classification and proven-safe batch memory (ISSUE 10).

An XLA ``RESOURCE_EXHAUSTED`` used to be indistinguishable from any other
device error: it fed the circuit breaker's consecutive-failure count, and
three of them degraded every following job to the numpy oracle — turning a
*sizing* problem (this dataset × this batch does not fit in HBM) into a
*health* verdict about a perfectly good chip.  This module gives the
scoring seam (``models/msm_basic.py::MSMBasicSearch._score_group``) the
vocabulary to treat OOM as what it is:

- :func:`is_oom_error` — recognizes the allocator's failure shapes
  (``JaxRuntimeError: RESOURCE_EXHAUSTED``, "out of memory" texts, and
  plain ``MemoryError`` — which the ``backend.device_error`` failpoint can
  inject deterministically) and tells them from a Pallas kernel that
  overflows its scoped VMEM, which comes back under the same status;
- the **safe-batch registry** — after a backoff converges, the proven-safe
  batch size is recorded per :func:`shape_key` (dataset shape × backend ×
  device lease), so the NEXT job on the same shape starts at the size that
  fits instead of re-discovering the OOM; ``MSMBasicSearch`` consults it
  before building the backend and the checkpoint partition;
- ``sm_oom_*`` metrics through the same attach pattern as the breaker
  (``attach_metrics``; docs/OBSERVABILITY.md).

The registry is process-global plain state under one leaf lock — it is a
performance memo, not a correctness mechanism: losing it on restart only
costs one extra backoff cycle.
"""

from __future__ import annotations

import threading

from ..utils import tracing
from ..utils.logger import logger

# substrings that mark an exception as accelerator memory exhaustion: the
# XLA client raises JaxRuntimeError("RESOURCE_EXHAUSTED: Out of memory
# while trying to allocate ...") at run time, and the TPU compiler's static
# HBM plan refuses an oversized program under the same status.  MemoryError
# is the host-side (and failpoint-injectable) shape.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Resource exhausted")
# ...unless the exhausted space is a kernel's ON-CHIP scratch.  Mosaic
# reports a Pallas kernel that overflows scoped VMEM/SMEM as
# "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ... exceeded
# scoped vmem limit" (libtpu 0.0.34, verbatim in tests/test_oom.py).  That
# is a kernel that does not fit, not a batch that does not fit HBM:
# halving the batch until the kernel happens to compile would hide it.
_ON_CHIP_MARKERS = ("memory space vmem", "memory space smem", "scoped vmem",
                    "vmem limit", "smem limit")


def is_kernel_scratch_error(exc: BaseException) -> bool:
    """A Pallas kernel that does not fit its scoped VMEM/SMEM — a program
    fault (``models/faults.py``), whatever status it comes back under."""
    text = str(exc).lower()
    return any(m in text for m in _ON_CHIP_MARKERS)


def is_oom_error(exc: BaseException) -> bool:
    """Is this exception an HBM/host memory-exhaustion signal?  String-
    based for the XLA shapes: the status text is the stable part of a
    JaxRuntimeError.  On-chip kernel-scratch exhaustion is NOT one."""
    if isinstance(exc, MemoryError):
        return True
    if is_kernel_scratch_error(exc):
        return False
    return any(m in str(exc) for m in _OOM_MARKERS)


def shape_key(n_pixels: int, backend: str, device_indices=None) -> str:
    """Registry key for a (dataset-shape, mesh) combination: what the
    HBM footprint of a scoring batch actually depends on.  ``None``
    device_indices = the config mesh over all local devices.

    The pixel count keys on its LATTICE BUCKET (ISSUE 13,
    ops/buckets.pixel_bucket): under the shape-bucket lattice every
    dataset size in a bucket scores through the same executables at the
    same scratch shapes, so a learned safe batch transfers across the
    whole bucket instead of being re-discovered per size."""
    from ..ops.buckets import pixel_bucket

    devs = ",".join(str(int(i)) for i in device_indices) \
        if device_indices else "*"
    return f"pxb{pixel_bucket(int(n_pixels))}|{backend}|dev[{devs}]"


class _GuardedRegistry:
    """The module singleton's state, lock-guarded (smlint guarded-by)."""

    _GUARDED_BY = {"_safe": "_lock", "_events": "_lock",
                   "_recoveries": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._safe: dict[str, int] = {}
        self._events = 0              # OOM exceptions classified
        self._recoveries = 0          # backoffs that converged

    def record_event(self) -> None:
        with self._lock:
            self._events += 1

    def record_safe(self, key: str, batch: int) -> None:
        with self._lock:
            self._safe[key] = int(batch)
            self._recoveries += 1

    def safe_batch_for(self, key: str) -> int | None:
        with self._lock:
            return self._safe.get(key)

    def snapshot(self) -> dict:
        with self._lock:
            return {"events": self._events, "recoveries": self._recoveries,
                    "safe_batches": dict(self._safe)}

    def reset(self) -> None:
        with self._lock:
            self._safe.clear()
            self._events = 0
            self._recoveries = 0


_registry = _GuardedRegistry()
_metrics = None
_metrics_lock = threading.Lock()


def record_oom_event(where: str, error: str) -> None:
    """An OOM was classified at the scoring seam (before any retry)."""
    _registry.record_event()
    tracing.event("oom", where=where, error=error[:300])
    m = _metrics
    if m is not None:
        m.counter("sm_oom_events_total",
                  "Device/host memory-exhaustion errors classified at the "
                  "scoring seam").inc()


def record_safe_batch(key: str, batch: int) -> None:
    """A backoff converged: ``batch`` is proven to fit for ``key``; later
    jobs on the same shape start there."""
    _registry.record_safe(key, batch)
    logger.warning("oom: learned safe batch %d for %s", batch, key)
    tracing.event("oom_safe_batch", key=key, batch=int(batch))
    m = _metrics
    if m is not None:
        m.counter("sm_oom_recoveries_total",
                  "OOM backoffs that converged to a fitting batch size").inc()
        m.gauge("sm_oom_safe_batch",
                "Most recently learned proven-safe formula batch").set(batch)


def safe_batch_for(key: str) -> int | None:
    return _registry.safe_batch_for(key)


def snapshot() -> dict:
    """Registry contents for ``GET /debug/resources``."""
    return _registry.snapshot()


def reset() -> None:
    """Forget learned sizes and counts (tests)."""
    _registry.reset()


def attach_metrics(registry) -> None:
    """Export the ``sm_oom_*`` family through a service MetricsRegistry;
    counts recorded before attachment are backfilled."""
    global _metrics
    with _metrics_lock:
        _metrics = registry
    snap = _registry.snapshot()
    registry.counter(
        "sm_oom_events_total",
        "Device/host memory-exhaustion errors classified at the scoring "
        "seam").inc(snap["events"])
    registry.counter(
        "sm_oom_recoveries_total",
        "OOM backoffs that converged to a fitting batch size").inc(
        snap["recoveries"])
    g = registry.gauge("sm_oom_safe_batch",
                       "Most recently learned proven-safe formula batch")
    if snap["safe_batches"]:
        g.set(list(snap["safe_batches"].values())[-1])
