"""Runtime retrace tracer: attribute every XLA compilation (ISSUE 12).

The static ``jit-compile-surface`` rule proves call sites DECLARE a
bounded compile surface; this module proves the surface observed at
runtime matches.  ``enable()`` registers a ``jax.monitoring`` listener for
the backend-compile duration event — fired synchronously inside every
compile-cache miss — and, per compile:

- walks the Python stack to the innermost frame inside this repo (the
  **call site** that dispatched the jitted callable — ``_dispatch``,
  ``warmup``, a test body, ...);
- pulls the **abstract signature** from the in-flight pjit frame
  (``_pjit_call_impl_python`` carries the closed jaxpr and executable
  name as locals; absent — e.g. an AOT ``.compile()`` path — the
  signature degrades to ``<opaque>`` rather than losing the event);
- records ``(site, signature)`` into a process-global census,
  increments ``sm_compile_events_total{site=}``, updates the
  ``sm_compile_signatures{site=}`` distinct-signature gauge, and emits a
  ``compile`` trace event onto the ambient job trace (so a cold-start
  compile shows up INSIDE the job that paid for it).

``enable()`` registers the listeners exactly once per process and
``disable()`` just de-activates them (cheaper than an unregister/register
cycle per test); both are idempotent.  A listener fault must never fail a compile: the
handler catches everything and logs once per process.

``scripts/compile_census.py`` drives a real service with this tracer on
and asserts the observed surface is attributed (every site's module has a
``COMPILE_SURFACE`` registration) and CLOSED (a second same-shaped job
adds zero new signatures).
"""

from __future__ import annotations

import contextlib
import contextvars
import sys
import threading
from pathlib import Path

from ..utils import tracing
from ..utils.logger import logger

# chips of the device lease this thread's job holds: a ``compile`` event
# names them, so a job on chip 1..3 that pays a compile the chip-0 primer
# did not cover says so
_LEASE_DEVICES: contextvars.ContextVar[tuple[int, ...] | None] = \
    contextvars.ContextVar("sm_lease_devices", default=None)


@contextlib.contextmanager
def lease(token):
    """Name the chips of a GRANTED device lease (``token.devices``; a plain
    lock has none) on the compile events of this thread for the block."""
    devs = getattr(token, "devices", None)
    reset = _LEASE_DEVICES.set(tuple(int(i) for i in devs) if devs else None)
    try:
        yield
    finally:
        _LEASE_DEVICES.reset(reset)

# the jax monitoring event fired once per backend-compile REQUEST.  It
# wraps ``compile_or_get_cached``, so it fires on persistent-cache HITS
# too (checked on jax 0.9) — the hit is announced by a separate cache-hits event
# just before the duration event lands on the same thread, which is how
# the listener below tells a real compile from a cache load (ISSUE 13:
# a primed cache must show up as loads, not compiles).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# warm-start attribution (ISSUE 18): the other places a "warm" compile_s
# actually goes.  jaxpr tracing and jaxpr->MLIR lowering run on EVERY
# compile-cache miss (even when the executable then loads off the
# persistent cache — the cache key needs the lowered module), and the
# cache-retrieval event times the disk read + deserialize alone.  The
# census accumulates all four buckets so bench.py / trace_report.py can
# split warm compile seconds into trace / lower / cache-load / backend-
# compile instead of one opaque number.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# census duration buckets, keyed by the reported field name
_DURATION_KEYS = ("trace_s", "lower_s", "cache_load_s", "backend_compile_s")
_EVENT_BUCKET = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
                 CACHE_LOAD_EVENT: "cache_load_s"}

_REPO_ROOT = Path(__file__).resolve().parents[2]
_SELF = Path(__file__).resolve()

# per-site cap on STORED signature strings (the distinct count keeps
# counting past it; the census only needs the set to prove closure, and an
# unbounded-retrace bug is exactly when storage would explode)
MAX_STORED_SIGNATURES = 128


class _Census:
    """Process-global compile census (smlint guarded-by)."""

    _GUARDED_BY = {"_sites": "_lock", "_events_total": "_lock",
                   "_overflow": "_lock", "_cache_hits_total": "_lock",
                   "_durations": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._sites: dict[str, dict] = {}   # site -> {signatures:set, events:int}
        self._events_total = 0
        self._cache_hits_total = 0          # persistent-cache loads (primed)
        self._overflow = 0                  # signatures dropped past the cap
        self._durations = dict.fromkeys(_DURATION_KEYS, 0.0)

    def _entry_locked(self, site: str) -> dict:
        return self._sites.setdefault(
            site, {"signatures": set(), "events": 0, "cache_hits": 0})

    def record(self, site: str, signature: str) -> tuple[bool, int]:
        """A REAL backend compile.  Returns (is_new_signature,
        distinct_count_for_site)."""
        with self._lock:
            ent = self._entry_locked(site)
            ent["events"] += 1
            self._events_total += 1
            new = signature not in ent["signatures"]
            if new:
                if len(ent["signatures"]) >= MAX_STORED_SIGNATURES:
                    self._overflow += 1
                else:
                    ent["signatures"].add(signature)
            return new, len(ent["signatures"])

    def record_cache_hit(self, site: str) -> None:
        """A persistent-cache LOAD: the executable came off disk — the
        outcome priming buys — so it must not count as a compile."""
        with self._lock:
            self._entry_locked(site)["cache_hits"] += 1
            self._cache_hits_total += 1

    def record_duration(self, bucket: str, seconds: float) -> None:
        """Accumulate one compile-pipeline stage duration (ISSUE 18
        warm-start attribution)."""
        with self._lock:
            self._durations[bucket] += seconds

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "events_total": self._events_total,
                "cache_hits_total": self._cache_hits_total,
                "durations": {k: round(v, 6)
                              for k, v in self._durations.items()},
                "signatures_total": sum(
                    len(e["signatures"]) for e in self._sites.values()),
                "overflow": self._overflow,
                "sites": {
                    s: {"events": e["events"],
                        "cache_hits": e.get("cache_hits", 0),
                        "signatures": sorted(e["signatures"])}
                    for s, e in sorted(self._sites.items())
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._sites.clear()
            self._events_total = 0
            self._cache_hits_total = 0
            self._overflow = 0
            self._durations = dict.fromkeys(_DURATION_KEYS, 0.0)


_census = _Census()
_state_lock = threading.Lock()
_active = False
_registered = False
_metrics = None
_warned = False
# per-thread persistent-cache-hit flag: jax announces a hit with
# CACHE_HIT_EVENT just before the wrapping COMPILE_EVENT duration lands on
# the same thread; the duration listener consumes the flag to classify
_tls = threading.local()


def _site_of_frame(frame) -> str | None:
    """``relpath:function`` when ``frame`` is repo code, else None."""
    try:
        path = Path(frame.f_code.co_filename).resolve()
    except OSError:
        return None
    if path == _SELF or "site-packages" in path.parts:
        return None
    try:
        rel = path.relative_to(_REPO_ROOT)
    except ValueError:
        return None
    return f"{rel.as_posix()}:{frame.f_code.co_name}"


def _attribute() -> tuple[str, str, str]:
    """(site, executable name, abstract signature) for the in-flight
    compile, from the listener's own stack."""
    site, fn_name, sig = "<external>", "", "<opaque>"
    f = sys._getframe(2)            # skip _attribute + the listener
    while f is not None:
        if f.f_code.co_name == "_pjit_call_impl_python":
            loc = f.f_locals
            name = loc.get("name")
            if isinstance(name, str):
                fn_name = name
            jaxpr = loc.get("jaxpr")
            avals = getattr(jaxpr, "in_avals", None)
            if avals is not None:
                sig = "(" + ", ".join(str(a) for a in avals) + ")"
        if site == "<external>":
            s = _site_of_frame(f)
            if s is not None:
                site = s
        f = f.f_back
    return site, fn_name, sig


def _on_event(name: str, **_kw) -> None:
    """record_event listener: flags a persistent-cache hit for the
    duration event that follows on this thread."""
    if name == CACHE_HIT_EVENT and _active:
        _tls.cache_hit = True


def _on_event_duration(name: str, duration: float, **_kw) -> None:
    global _warned
    if not _active:
        return
    if name in _EVENT_BUCKET:
        # compile-pipeline stage durations (warm-start attribution): one
        # firing per compile-cache miss / cache read — census totals plus
        # a trace event so a job trace shows where its warm seconds went
        try:
            bucket = _EVENT_BUCKET[name]
            _census.record_duration(bucket, float(duration))
            tracing.event(f"compile_{bucket.removesuffix('_s')}",
                          dur_s=round(float(duration), 4))
        except Exception:
            if not _warned:
                _warned = True
                logger.warning("retrace tracer: attribution failed (disabled "
                               "for this event only)", exc_info=True)
        return
    if name != COMPILE_EVENT:
        return
    try:
        cached = bool(getattr(_tls, "cache_hit", False))
        _tls.cache_hit = False
        if not cached:
            # a cached firing's duration is the retrieval (already in the
            # cache_load_s bucket via CACHE_LOAD_EVENT) — only a real
            # backend compile lands here
            _census.record_duration("backend_compile_s", float(duration))
        site, fn_name, sig = _attribute()
        signature = f"{fn_name}{sig}" if fn_name else sig
        held = _LEASE_DEVICES.get()
        devices = {"devices": list(held)} if held else {}
        m = _metrics
        if cached:
            # the executable came off the persistent cache — the primed
            # outcome, NOT a compile: counted separately so the census
            # (and the coldstart smoke) can assert "loads, not compiles"
            _census.record_cache_hit(site)
            if m is not None:
                m.counter(
                    "sm_compile_cache_hits_total",
                    "Persistent-XLA-cache executable loads (primed/warm "
                    "cache) by attributed call site",
                    ("site",)).labels(site=site).inc()
            tracing.event("compile", site=site, fn=fn_name,
                          signature=sig[:500],
                          dur_s=round(float(duration), 4), cached=True,
                          **devices)
            return
        new, distinct = _census.record(site, signature)
        if m is not None:
            m.counter(
                "sm_compile_events_total",
                "XLA backend compilations (compile-cache misses) by "
                "attributed call site", ("site",)).labels(site=site).inc()
            m.gauge(
                "sm_compile_signatures",
                "Distinct abstract signatures compiled, by attributed "
                "call site", ("site",)).labels(site=site).set(distinct)
        tracing.event("compile", site=site, fn=fn_name,
                      signature=sig[:500], dur_s=round(float(duration), 4),
                      new_signature=bool(new), cached=False, **devices)
    except Exception:
        # a tracer fault must never fail the compile it observes
        if not _warned:
            _warned = True
            logger.warning("retrace tracer: attribution failed (disabled "
                           "for this event only)", exc_info=True)


def enable(metrics=None) -> None:
    """Start attributing compiles.  Idempotent; the jax listeners are
    registered once per process, so repeated enable/disable cycles only
    flip the active flag.  ``metrics``
    (a service MetricsRegistry) rebinds the ``sm_compile_*`` export —
    the latest caller wins, matching the oom/breaker attach pattern."""
    global _active, _registered, _metrics
    with _state_lock:
        if metrics is not None:
            _metrics = metrics
        if not _registered:
            try:
                from jax import monitoring
            except ImportError:
                logger.warning("retrace tracer: jax.monitoring unavailable; "
                               "compile attribution disabled")
                return
            monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            monitoring.register_event_listener(_on_event)
            _registered = True
        _active = True


def disable() -> dict:
    """Stop recording; returns the final census snapshot."""
    global _active
    with _state_lock:
        _active = False
    return _census.snapshot()


def enabled() -> bool:
    return _active


def snapshot() -> dict:
    """Census contents: ``{events_total, cache_hits_total, durations:
    {trace_s, lower_s, cache_load_s, backend_compile_s}, signatures_total,
    overflow, sites: {site: {events, cache_hits, signatures}}}``."""
    return _census.snapshot()


def reset() -> None:
    """Forget recorded compiles (tests / census phases)."""
    _census.reset()
