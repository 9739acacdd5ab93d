"""Device-fault taxonomy: one classification for every backend exception.

ISSUE 14 tentpole, layer 1.  Before this module the engine had THREE
uncoordinated opinions about a device exception:

- ``models/oom.py::is_oom_error`` recognized memory exhaustion (a *sizing*
  signal — batch backoff, never a health verdict);
- the circuit breaker (``models/breaker.py``) counted every non-OOM device
  error toward its consecutive-failure threshold — including known-
  transient collective timeouts that the next attempt would survive;
- the device pool had no opinion at all: a chip whose hardware died kept
  getting re-leased forever, because nothing between the scoring seam and
  the pool carried the verdict.

This module is the single classifier (the GSPMD pod-scale framing,
arXiv:2105.04663: device health is *pool state*, fed by classified
faults).  Every backend exception maps to exactly one kind:

``program``
    Raised while the scoring program was being traced, lowered or
    compiled: ``AttributeError``/``TypeError``/``ValueError``/
    ``NotImplementedError`` and their kin, a Pallas lowering or
    verification error, a Mosaic refusal, a kernel that overflows scoped
    VMEM.  The code is wrong for this installation or this shape; the chip
    is fine.  The job FAILS with the compiler's message — no breaker
    count, no quarantine, no batch backoff, and never a degrade to the
    numpy oracle, which would finish the job ``done`` with the device
    unused.
``oom``
    Memory exhaustion (``models/oom.py`` is the authority).  A sizing
    signal: the scoring batch halves and rescores in place.  NEVER a
    device fault — no breaker count, no quarantine.
``transient``
    Known-recoverable runtime hiccups: collective/DCN timeouts,
    ``DEADLINE_EXCEEDED`` / ``UNAVAILABLE`` / ``ABORTED`` status codes,
    connection resets.  The attempt fails into the normal
    retry policy (same chip, exponential backoff) — no breaker count;
    the chip is marked *suspect* and quarantined only if transients keep
    repeating (``service.health_fault_quarantine``).
``sticky``
    Everything else at the device seam — ``INTERNAL``/``DATA_LOSS`` XLA
    status, launch failures, wedged cores.  The chip (or, for a sharded
    lease, the probe-attributed culprit) is **quarantined** out of the
    device pool (``service/health.py``) and the per-chip breaker counts
    the failure, so the retry re-leases *healthy* chips instead of
    degrading the whole process to numpy.

The health tracker subscribes through :func:`set_fault_listener` (the
same producer-side pattern as breaker/oom ``attach_metrics``), so this
module never imports the service layer.  ``sm_device_faults_total{kind=}``
rides the usual attach seam; docs/RECOVERY.md "Device faults" carries the
taxonomy table.
"""

from __future__ import annotations

import threading

from ..utils import tracing
from ..utils.failpoints import register_failpoint
from ..utils.logger import logger
from . import oom

FAULT_PROGRAM = "program"
FAULT_OOM = "oom"
FAULT_TRANSIENT = "transient"
FAULT_STICKY = "sticky"

# The injectable chip-fault seam (fired in MSMBasicSearch._score_group next
# to backend.device_error): the raised exception CLASS selects the
# taxonomy — raise:ConnectionError / raise:TimeoutError inject a transient,
# raise:RuntimeError (the default classification) a sticky chip death, and
# raise:MemoryError still lands in the OOM sizing path.
FP_CHIP_FAULT = register_failpoint(
    "backend.chip_fault",
    "inside a device score_batches call — the classified chip-fault seam "
    "(models/faults.py): ConnectionError/TimeoutError = transient (retry "
    "same chip, no quarantine), other exceptions = sticky (chip "
    "quarantined out of the device pool, per-chip breaker count)")

# Exceptions Python, JAX tracing and Pallas lowering raise for a program
# that is wrong: none of them says anything about the chip.  Pallas' own
# classes are matched by name so this module stays jax-free.
_PROGRAM_TYPES = (AttributeError, TypeError, ValueError, NotImplementedError,
                  NameError, LookupError, AssertionError, ImportError)
_PROGRAM_TYPE_NAMES = ("LoweringException", "VerificationError")
# ...and what the TPU compiler says when it refuses one (a JaxRuntimeError,
# like a run-time device error: only the text tells them apart).
_PROGRAM_MARKERS = (
    "mosaic",
    "failed to compile",
    "compile permanent error",
    "unimplemented",
    "invalid_argument",
)

# Status texts that mark an exception as KNOWN-transient.  The XLA client
# surfaces gRPC/absl status codes in the message text.
_TRANSIENT_MARKERS = (
    "deadline_exceeded",
    "deadline exceeded",
    "unavailable",
    "aborted",
    "cancelled by peer",
    "collective",            # collective timeout / all-reduce stall
    "all-reduce",
    "all_reduce",
    "connection reset",
    "broken pipe",
    "temporarily unavailable",
    "too many requests",
)


def classify(exc: BaseException) -> str:
    """Map one backend exception to its fault kind.  OOM is checked FIRST
    (``models/oom.py`` stays the single memory-exhaustion authority, so
    the PR 10 contract — OOM is never a device fault — cannot regress);
    then program faults (a compiler refusal can quote any status text),
    then the known-transient markers; everything else is sticky."""
    if oom.is_oom_error(exc):
        return FAULT_OOM
    text = str(exc).lower()
    if (isinstance(exc, _PROGRAM_TYPES)
            or type(exc).__name__ in _PROGRAM_TYPE_NAMES
            or oom.is_kernel_scratch_error(exc)
            or any(m in text for m in _PROGRAM_MARKERS)):
        return FAULT_PROGRAM
    if isinstance(exc, (TimeoutError, ConnectionError)):
        return FAULT_TRANSIENT
    if any(m in text for m in _TRANSIENT_MARKERS):
        return FAULT_TRANSIENT
    return FAULT_STICKY


# ------------------------------------------------------- listener + metrics
_lock = threading.Lock()
_listener = None                       # the service's HealthTracker
_metrics = None


def set_fault_listener(listener) -> None:
    """Subscribe a health tracker (``service/health.py``): it receives
    every classified non-OOM device fault as ``report_fault(devices,
    kind, error)`` and every clean device group as ``report_ok(devices)``.
    One listener per process (last registration wins — the live
    scheduler's pool)."""
    global _listener
    with _lock:
        _listener = listener


def clear_fault_listener(listener=None) -> None:
    """Detach (tests / service shutdown).  With ``listener`` given, only
    detaches when it is still the registered one — a newer scheduler's
    registration survives an older service's teardown."""
    global _listener
    with _lock:
        if listener is None or _listener is listener:
            _listener = None


def report_device_fault(devices, kind: str, error: BaseException | str) -> None:
    """A classified device fault at the scoring seam.  ``devices`` is the
    job's lease chip tuple (None for un-leased/offline runs — nothing to
    attribute then).  Dispatches to the health listener, exports
    ``sm_device_faults_total{kind=}``, and stamps the job trace."""
    err = str(error)
    tracing.event("device_fault", kind=kind, error=err[:300],
                  **({"devices": [int(d) for d in devices]}
                     if devices else {}))
    m = _metrics
    if m is not None:
        m.counter("sm_device_faults_total",
                  "Classified device faults at the scoring seam, by kind",
                  ("kind",)).labels(kind=kind).inc()
    with _lock:
        listener = _listener
    if listener is None or not devices:
        return
    try:
        listener.report_fault(tuple(int(d) for d in devices), kind, err)
    except Exception:
        logger.warning("device-fault listener %r failed", listener,
                       exc_info=True)


def report_device_ok(devices) -> None:
    """A clean device scoring group: clears the lease chips' suspect
    state/fault counters (quarantine is only undone by a re-probe)."""
    with _lock:
        listener = _listener
    if listener is None or not devices:
        return
    try:
        listener.report_ok(tuple(int(d) for d in devices))
    except Exception:
        logger.warning("device-fault listener %r failed", listener,
                       exc_info=True)


def attach_metrics(registry) -> None:
    """Export ``sm_device_faults_total{kind=}`` through a service
    ``MetricsRegistry`` (same attach pattern as breaker/oom)."""
    global _metrics
    with _lock:
        _metrics = registry
    registry.counter("sm_device_faults_total",
                     "Classified device faults at the scoring seam, by kind",
                     ("kind",))


def reset() -> None:
    """Detach listener + metrics (tests)."""
    global _listener, _metrics
    with _lock:
        _listener = None
        _metrics = None
