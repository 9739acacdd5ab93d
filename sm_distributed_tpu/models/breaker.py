"""Device-backend circuit breaker (ISSUE 4 degradation layer; per-chip
labels since ISSUE 14).

A flaky device backend — preempted TPU, XLA launch failures —
used to be retried forever by the scheduler's failure policy, burning every
attempt of every job on the same broken path.  The breaker wraps the device
scoring seam in ``MSMBasicSearch._score_and_rank``:

- **closed**: device scoring as normal; each cleanly scored group counts as
  a success and resets the consecutive-error count;
- **open**: after ``service.breaker_threshold`` consecutive device errors.
  Jobs score on the numpy oracle at ``service.breaker_degraded_batch``
  instead (metrics are backend-independent, so results are bit-identical to
  a healthy numpy run) — degraded but correct beats dead;
- **half-open**: once ``service.breaker_cooldown_s`` has elapsed, the next
  job's device build is allowed through as a probe.  A clean group closes
  the breaker; another device error re-opens it and restarts the cooldown.

**Per-chip labelling (ISSUE 14):** PR 4's breaker was a process-global
singleton — correct when one device served the whole process, but on the
multi-chip pool one sticky chip's failures opened the ONE breaker and
degraded every job on every healthy chip to numpy.  The singleton is now a
*registry* of breakers keyed per chip: a job holding a device-pool lease
gets a :class:`LeaseBreaker` view over its chips' breakers (a failure
counts on every leased chip, a success resets them, the device is allowed
only when every chip's breaker allows it), and ``sm_breaker_state`` /
``sm_breaker_transitions_total`` carry a ``device`` label.  Un-leased
callers (offline CLI, legacy tests) keep the old single-breaker semantics
under the ``"*"`` label.  Chip-level *quarantine* (``service/health.py``)
is the first line of defense — a sticky chip leaves the pool entirely —
and the per-chip breaker is the backstop beneath it: if every healthy
chip keeps failing too, jobs still degrade to the numpy oracle instead of
dying.
"""

from __future__ import annotations

import threading
import time

from ..utils import tracing
from ..utils.logger import logger

STATE_CLOSED = "closed"
STATE_OPEN = "open"
STATE_HALF_OPEN = "half_open"
_STATE_CODE = {STATE_CLOSED: 0, STATE_HALF_OPEN: 1, STATE_OPEN: 2}

# the un-leased / process-wide breaker key (old single-device semantics)
GLOBAL_LABEL = "*"


class CircuitBreaker:
    """Consecutive-failure breaker with half-open recovery probes."""

    # shared-state registry checked by the smlint guarded-by rule
    # (docs/ANALYSIS.md): these attrs may only be mutated under _lock
    _GUARDED_BY = {"_state": "_lock", "_failures": "_lock",
                   "_opened_at": "_lock", "transitions": "_lock"}

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 label: str = GLOBAL_LABEL):
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self.label = str(label)        # chip index, or "*" for un-leased
        self._lock = threading.Lock()
        self._state = STATE_CLOSED
        self._failures = 0
        self._opened_at = 0.0
        # (monotonic time, from, to) — bounded history for probes/tests
        self.transitions: list[tuple[float, str, str]] = []

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def _transition_locked(self, to: str) -> None:
        # callers hold self._lock (the _locked suffix is the guarded-by
        # rule's caller-holds-lock convention, docs/ANALYSIS.md)
        if self._state == to:
            return
        self.transitions.append((time.monotonic(), self._state, to))
        if len(self.transitions) > 256:
            del self.transitions[:-256]
        logger.warning("device breaker[%s]: %s -> %s (%d consecutive "
                       "failures)", self.label, self._state, to,
                       self._failures)
        # trace/flight-recorder visibility (ISSUE 5): attached to the job
        # span that tripped it when one is ambient, ring-only otherwise
        tracing.event("breaker", device=self.label, from_state=self._state,
                      to_state=to, failures=self._failures)
        self._state = to
        _export_state(to, self.label)

    def allow_device(self) -> bool:
        """May the next job use the device backend?  In OPEN state this
        flips to HALF_OPEN once the cooldown has elapsed and admits that one
        caller as the recovery probe."""
        with self._lock:
            if self._state == STATE_CLOSED or self._state == STATE_HALF_OPEN:
                return True
            if time.monotonic() - self._opened_at >= self.cooldown_s:
                self._transition_locked(STATE_HALF_OPEN)
                return True
            return False

    def refuses_device(self) -> bool:
        """Would ``allow_device()`` answer False right now?  Reads the
        state and admits no probe: for callers deciding BEFORE the lease
        whether device-only preparation is worth doing."""
        with self._lock:
            return self._state == STATE_OPEN and \
                time.monotonic() - self._opened_at < self.cooldown_s

    def record_success(self) -> None:
        """A device scoring group completed cleanly."""
        with self._lock:
            self._failures = 0
            if self._state != STATE_CLOSED:
                self._transition_locked(STATE_CLOSED)

    def record_failure(self) -> bool:
        """A device error occurred; returns True when the breaker is now
        open (callers degrade to the numpy fallback)."""
        with self._lock:
            self._failures += 1
            if self._state == STATE_HALF_OPEN or (
                    self._state == STATE_CLOSED
                    and self._failures >= self.threshold):
                self._opened_at = time.monotonic()
                self._transition_locked(STATE_OPEN)
            elif self._state == STATE_OPEN:
                self._opened_at = time.monotonic()
            return self._state == STATE_OPEN

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self._state, "failures": self._failures,
                    "threshold": self.threshold,
                    "cooldown_s": self.cooldown_s}


class LeaseBreaker:
    """Per-chip breaker view over one device-pool lease (ISSUE 14).

    A failure at the scoring seam counts on EVERY leased chip's breaker
    (the seam cannot attribute deeper — the health probe does that), a
    clean group resets them all, and the device path is allowed only when
    every chip's breaker allows it.  One bad chip therefore opens only its
    own breaker; the next lease over different chips scores on the device
    as if nothing happened."""

    def __init__(self, breakers: list[CircuitBreaker]):
        self._breakers = list(breakers)

    @property
    def state(self) -> str:
        # worst state across the lease: open > half_open > closed
        states = [b.state for b in self._breakers]
        for s in (STATE_OPEN, STATE_HALF_OPEN):
            if s in states:
                return s
        return STATE_CLOSED

    def allow_device(self) -> bool:
        # note: evaluated for every chip (no short-circuit), so each
        # open-past-cooldown breaker flips to its half-open probe together
        return all([b.allow_device() for b in self._breakers])

    def record_success(self) -> None:
        for b in self._breakers:
            b.record_success()

    def record_failure(self) -> bool:
        return any([b.record_failure() for b in self._breakers])

    def snapshot(self) -> dict:
        return {b.label: b.snapshot() for b in self._breakers}


# ------------------------------------------------------- process registry
_lock = threading.Lock()
_breakers: dict[str, CircuitBreaker] = {}
_metrics = None


def _breaker_locked(label: str) -> CircuitBreaker:
    b = _breakers.get(label)
    if b is None:
        b = _breakers[label] = CircuitBreaker(label=label)
    return b


def get_device_breaker(service_cfg=None, devices=None):
    """The process-global breaker for a device scope.  ``devices`` (a
    device-pool lease's chip tuple) selects per-chip breakers wrapped in a
    :class:`LeaseBreaker`; ``None`` keeps the old un-leased singleton
    (label ``"*"``).  ``service_cfg`` (a ``ServiceConfig``) refreshes the
    thresholds in place — the state machines are untouched, so a service
    and its jobs reading the same config always agree."""
    labels = ([GLOBAL_LABEL] if not devices
              else [str(int(d)) for d in devices])
    with _lock:
        picked = [_breaker_locked(lb) for lb in labels]
        if service_cfg is not None:
            for b in picked:
                b.threshold = int(service_cfg.breaker_threshold)
                b.cooldown_s = float(service_cfg.breaker_cooldown_s)
    if not devices:
        return picked[0]
    return LeaseBreaker(picked)


def breaker_for(label) -> CircuitBreaker | None:
    """The per-chip breaker for one label (chip index or ``"*"``), or
    None if this process never touched it — test/harness introspection."""
    with _lock:
        return _breakers.get(str(label))


def every_chip_refuses(chips=None) -> bool:
    """True when a job would be degraded to numpy whichever ONE of
    ``chips`` its lease grants (``None``: the un-leased ``"*"`` scope).
    A chip this process never touched has no breaker, so it does not
    refuse; nothing is created and no half-open probe is admitted."""
    labels = [GLOBAL_LABEL] if chips is None else [str(int(c)) for c in chips]
    with _lock:
        picked = [_breakers.get(lb) for lb in labels]
    return all(b is not None and b.refuses_device() for b in picked)


def breakers_snapshot() -> dict:
    """{label: breaker snapshot} of every breaker this process has touched
    (the ``GET /debug/devices`` body's breaker half)."""
    with _lock:
        picked = list(_breakers.values())
    return {b.label: b.snapshot() for b in picked}


def reset_device_breaker() -> None:
    """Fresh breakers + detach metrics (tests)."""
    global _metrics
    with _lock:
        _breakers.clear()
        _metrics = None


def _export_state(state: str, label: str) -> None:
    m = _metrics
    if m is None:
        return
    m.gauge("sm_breaker_state",
            "Device breaker state (0=closed, 1=half_open, 2=open), per "
            "chip ('*' = the un-leased process breaker)",
            ("device",)).labels(device=label).set(_STATE_CODE[state])
    m.counter("sm_breaker_transitions_total",
              "Device breaker state transitions, by chip and destination",
              ("device", "to")).labels(device=label, to=state).inc()


def attach_metrics(registry) -> None:
    """Export breaker state through a service ``MetricsRegistry``:
    ``sm_breaker_state{device=}`` gauge + ``sm_breaker_transitions_total
    {device=,to=}`` and a degraded-scoring counter (incremented by the
    scoring seam)."""
    global _metrics
    with _lock:
        _metrics = registry
        existing = list(_breakers.values())
    g = registry.gauge(
        "sm_breaker_state",
        "Device breaker state (0=closed, 1=half_open, 2=open), per chip "
        "('*' = the un-leased process breaker)", ("device",))
    for b in existing or [CircuitBreaker()]:
        g.labels(device=b.label).set(_STATE_CODE[b.state])
    registry.counter(
        "sm_breaker_transitions_total",
        "Device breaker state transitions, by chip and destination",
        ("device", "to"))
    registry.counter("sm_breaker_degraded_total",
                     "Scoring runs degraded to the numpy fallback")


def record_degraded() -> None:
    m = _metrics
    if m is not None:
        m.counter("sm_breaker_degraded_total",
                  "Scoring runs degraded to the numpy fallback").inc()
