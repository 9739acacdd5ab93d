"""End-to-end job tracing + flight recorder (ISSUE 5 tentpole).

The reference engine's only visibility was step-level wall-clock log lines
around each SearchJob phase (SURVEY.md §5.1); nothing correlated what the
scheduler, admission controller, device backend, isocalc pool workers, spool
daemon, breaker, and failpoints did *for one job*.  This module gives every
job a **trace**: a tree of spans sharing a ``trace_id`` minted at ``POST
/submit`` (or at CLI entry for offline runs), propagated scheduler →
``JobContext`` → ``SearchJob`` → ``MSMBasicSearch`` → both scoring backends
→ isocalc pool workers (serialized across the spawn boundary, re-parented on
return) → spool publish/claim/complete, with retry / cancel / deadline /
admission-shed / breaker-transition / failpoint events attached to the
owning span.

Model
-----
Two record kinds, each one JSON object (see docs/OBSERVABILITY.md for the
schema):

- ``span``:  ``{kind, trace_id, span_id, parent_id, name, ts, dur, cpu,
  pid, tid, attrs}`` — a timed operation.  ``ts`` is epoch seconds at
  entry, ``dur`` wall seconds, ``cpu`` the seconds the span's thread was on
  a core between entry and exit (``time.thread_time``; absent on a span
  emitted with explicit timing, whose body ran elsewhere).  ``dur - cpu``
  is the time the thread was OFF a core: blocked on the chip
  (``device_sync``, the export's fetch), on a file, on a lock, or queued
  for the interpreter.
- ``event``: ``{kind, trace_id, span_id, name, ts, pid, tid, attrs}`` — an
  instant attached to its owning span (``span_id`` = the span it happened
  under; both ids empty for traceless service-level events, which still
  reach the flight recorder).

Sinks
-----
- a bounded in-memory **flight recorder** ring (``GET /debug/events?n=``),
  process-global, thread-safe;
- a per-job **JSONL file** under the trace dir (append-only, one flushed
  line per record, so a crash loses at most the line being written and a
  restarted job/attempt APPENDS to the same file — the trace id and file
  travel inside the spool message, surviving requeue and process death).

Propagation
-----------
The current span is ambient via a ``contextvars.ContextVar``.  New threads
start without a context, so every thread hop attaches explicitly::

    ctx = tracing.current()            # capture in the spawning thread
    ...
    with tracing.attach(ctx):          # in the spawned thread
        with tracing.span("phase"):
            ...

Process hops (the isocalc spawn pool) serialize ``ctx.to_wire()`` into the
worker args; the worker rebuilds the context, records its spans into a
``capture()`` buffer (no sinks exist in the worker), and returns them with
the chunk result — the driver emits them via ``emit_records`` ("re-parented
on return": the records already carry the parent ids, the driver just owns
the sinks).

Overhead
--------
``span()``/``event()`` with no ambient context and no explicit one return a
no-op immediately — untraced hot paths (bench floors, raw backend calls)
pay one ContextVar read.  A traced span costs two ``perf_counter`` and two
``thread_time`` reads, one dict, one ``json.dumps`` and one flushed line:
tens of microseconds, 35-45 spans a served job.  ``thread_time`` is the
host's: 0.3 us a read and nanosecond steps where the kernel serves it from
the vDSO; 5.9 us a read (34-48 us with eight threads reading at once) and
steps of 10 ms on the sandboxed chip host (PERF.md section 6, PR 35), where
a span's ``cpu`` is therefore a multiple of 10 ms: right in sums over many
spans or jobs, and up to one step above ``dur`` on a short span.  File
emission caches one append handle per path and writes a single flushed
line per record.

Device captures
---------------
While a ``jax.profiler`` capture runs (``analysis/profiling.py::
ProfileSession``), every span opened also enters a
``jax.profiler.TraceAnnotation("sm:<name>", trace_id=, span_id=, job_id=)``
so the raw ``.xplane.pb`` carries the program's spans on the profiler's own
timeline.  The session installs the hook with ``set_capture`` and clears it
when the capture stops; with no capture a span pays one ``is not None`` test
and this module never imports jax.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

from .failpoints import failpoint, register_failpoint

_log = logging.getLogger("sm-tpu")

FP_TRACE_APPEND = register_failpoint(
    "trace.append",
    "inside a per-job trace file append (I/O error / ENOSPC) — trace "
    "emission must never fail the pipeline")

RECORD_KINDS = ("span", "event")
# required keys per record kind (validate_records + the smoke gate)
_SPAN_KEYS = ("kind", "trace_id", "span_id", "parent_id", "name", "ts",
              "dur", "pid", "tid")
_EVENT_KEYS = ("kind", "trace_id", "span_id", "name", "ts", "pid", "tid")

_CTX: contextvars.ContextVar["TraceContext | None"] = contextvars.ContextVar(
    "sm_trace_ctx", default=None)
_CAPTURE: contextvars.ContextVar["list | None"] = contextvars.ContextVar(
    "sm_trace_capture", default=None)

_enabled = True

# set by analysis/profiling.py::ProfileSession for the length of a capture:
# called with a span's record as it opens, returns an object whose close()
# runs as the span ends.  The hook must not raise.
_capture = None


def set_capture(hook) -> None:
    """Install (or clear, with ``None``) the device-capture span hook."""
    global _capture
    _capture = hook


def new_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclass(frozen=True)
class TraceContext:
    """Position in a trace: ids + the per-job sink every child inherits."""

    trace_id: str
    span_id: str
    job_id: str = ""
    file: str = ""                # per-job JSONL sink ("" = ring only)
    # the open span's attrs, for ``annotate`` (None outside ``span``)
    attrs: dict | None = field(default=None, compare=False, repr=False)

    def child(self, span_id: str | None = None,
              attrs: dict | None = None) -> "TraceContext":
        return TraceContext(trace_id=self.trace_id,
                            span_id=span_id or new_id(),
                            job_id=self.job_id, file=self.file, attrs=attrs)

    def to_wire(self) -> dict:
        """Minimal dict for a process hop (no file — workers have no sinks)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "job_id": self.job_id}

    @staticmethod
    def from_wire(d: dict | None) -> "TraceContext | None":
        if not d or not d.get("trace_id"):
            return None
        return TraceContext(trace_id=str(d["trace_id"]),
                            span_id=str(d.get("span_id", "")),
                            job_id=str(d.get("job_id", "")))


# --------------------------------------------------------- flight recorder
class FlightRecorder:
    """Bounded ring of the most recent records, process-wide."""

    # smlint guarded-by registry (docs/ANALYSIS.md)
    _GUARDED_BY = {"_ring": "_lock"}

    def __init__(self, maxlen: int = 2048):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=maxlen)

    def record(self, rec: dict) -> None:
        with self._lock:
            self._ring.append(rec)

    def recent(self, n: int | None = None) -> list[dict]:
        with self._lock:
            items = list(self._ring)
        return items if n is None else items[-max(0, int(n)):]

    def resize(self, maxlen: int) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(1, int(maxlen)))

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    @property
    def maxlen(self) -> int:
        with self._lock:
            return self._ring.maxlen or 0


flight_recorder = FlightRecorder()


def configure(enabled: bool = True, ring_size: int | None = None) -> None:
    """Apply ``SMConfig.tracing`` knobs (service/CLI startup)."""
    global _enabled
    _enabled = bool(enabled)
    if ring_size is not None and ring_size != flight_recorder.maxlen:
        flight_recorder.resize(ring_size)


def enabled() -> bool:
    return _enabled


# replica identity (ISSUE 8): stamped on every record this process emits so
# a trace continued across a takeover shows WHICH replica ran each span
_replica_id = ""


def set_replica(replica_id: str) -> None:
    """Set the process-wide replica id (service startup; "" disables)."""
    global _replica_id
    _replica_id = str(replica_id or "")


def replica() -> str:
    return _replica_id


# pod process identity (ISSUE 17): a cross-process mesh runs one scheduler
# process per host — records carry (process_id, host) so a trace spanning a
# host loss shows which process emitted each span
_process_id = -1
_host = ""


def set_process(process_id: int, host: str = "") -> None:
    """Set the pod identity stamped on every record (-1/"" disables)."""
    global _process_id, _host
    _process_id = int(process_id)
    _host = str(host or "")


def process() -> tuple[int, str]:
    return _process_id, _host


# --------------------------------------------------------------- file sink
# cached append handles: one flushed line per record, no per-record open()
_files_lock = threading.Lock()
_files: dict[str, object] = {}

# disk-pressure gate (ISSUE 10): the resource governor installs a callable
# consulted before every FILE write — under disk pressure trace writes are
# the FIRST thing dropped (ring records keep flowing).  None = always write.
# The gate must be cheap and non-raising; it is called outside _files_lock.
_file_gate = None


def set_file_gate(fn) -> None:
    """Install (or clear, with ``None``) the trace-file write gate.  The
    service wires this to ``ResourceGovernor.trace_gate`` so a disk-budget
    breach drops trace APPENDS before anything essential degrades."""
    global _file_gate
    _file_gate = fn


def _file_handle_locked(path: str):
    """Caller holds ``_files_lock``."""
    f = _files.get(path)
    if f is None or f.closed:
        if len(_files) >= 64:         # bound fd usage across many jobs
            for stale in list(_files):
                with contextlib.suppress(OSError):
                    _files[stale].close()
                del _files[stale]
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        f = _files[path] = open(path, "a", encoding="utf-8")
    return f


def close_files() -> None:
    """Close cached trace-file handles (tests / shutdown)."""
    with _files_lock:
        for f in _files.values():
            with contextlib.suppress(OSError):
                f.close()
        _files.clear()


def close_file(path: str | Path) -> None:
    """Drop one cached append handle (the GC sweeper calls this before
    unlinking an aged trace file, so a later append to the same trace id
    reopens instead of writing to an unlinked inode)."""
    key = str(path)
    with _files_lock:
        f = _files.pop(key, None)
        if f is not None:
            with contextlib.suppress(OSError):
                f.close()


# reentrancy guard for the trace.append failpoint: a FIRED failpoint emits
# its own "failpoint" trace event, which re-enters _emit — without the
# guard an every-hit spec would recurse without bound
_appending = threading.local()


def _emit(rec: dict, file: str) -> None:
    buf = _CAPTURE.get()
    if buf is not None:               # worker-side capture: no sinks here
        buf.append(rec)
        return
    flight_recorder.record(rec)
    if file and (_file_gate is None or _file_gate()):
        line = json.dumps(rec, default=str) + "\n"
        try:
            # the governed-write seam (ISSUE 10): ENOSPC/I/O faults here
            # must degrade to a lost trace line, never a failed job
            if not getattr(_appending, "active", False):
                _appending.active = True
                try:
                    failpoint(FP_TRACE_APPEND, path=file)
                finally:
                    _appending.active = False
            with _files_lock:         # whole-line writes, never interleaved
                f = _file_handle_locked(file)
                f.write(line)
                f.flush()
        except OSError:               # tracing must never fail the pipeline
            _log.warning("trace emit to %s failed", file, exc_info=True)


# ------------------------------------------------------------ context + API
def current() -> TraceContext | None:
    return _CTX.get()


@contextlib.contextmanager
def attach(ctx: TraceContext | None):
    """Make ``ctx`` the ambient trace context for this thread/block."""
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def new_trace(job_id: str = "", trace_dir: str | Path | None = None,
              trace_id: str | None = None,
              span_id: str | None = None) -> TraceContext:
    """Mint a root context (does not emit anything).  ``trace_dir`` selects
    the per-job JSONL sink: ``<trace_dir>/<trace_id>.jsonl``."""
    tid = trace_id or new_id()
    file = str(trace_path(trace_dir, tid)) if trace_dir else ""
    return TraceContext(trace_id=tid, span_id=span_id or new_id(),
                        job_id=job_id, file=file)


def trace_path(trace_dir: str | Path, trace_id: str) -> Path:
    return Path(trace_dir) / f"{trace_id}.jsonl"


def _base(ctx: TraceContext, name: str, kind: str) -> dict:
    rec = {
        "kind": kind, "trace_id": ctx.trace_id, "span_id": ctx.span_id,
        "name": name, "ts": time.time(), "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if ctx.job_id:
        rec["job_id"] = ctx.job_id
    if _replica_id:
        rec["replica"] = _replica_id
    if _process_id >= 0:
        rec["process"] = _process_id
    if _host:
        rec["host"] = _host
    return rec


@contextlib.contextmanager
def span(name: str, /, ctx: TraceContext | None = None, **attrs):
    """Timed child span of ``ctx`` (or the ambient context).  No-op without
    either — untraced paths stay at one ContextVar read.  Yields the child
    context (ambient inside the block), emits the span record on exit; a
    raising body is recorded with ``error`` in attrs and re-raised."""
    parent = ctx if ctx is not None else _CTX.get()
    if parent is None or not _enabled:
        yield None
        return
    child = parent.child(attrs=attrs)
    rec = _base(child, name, "span")
    rec["parent_id"] = parent.span_id
    rec["attrs"] = attrs
    mark = _capture(rec) if _capture is not None else None
    token = _CTX.set(child)
    # the CPU clock is read INSIDE the wall clock's interval at both ends,
    # so cpu <= dur holds up to one step of the host's thread clock
    t0 = time.perf_counter()
    c0 = time.thread_time()
    try:
        yield child
    except BaseException as exc:
        attrs["error"] = f"{type(exc).__name__}: {exc}"
        raise
    finally:
        _CTX.reset(token)
        rec["cpu"] = time.thread_time() - c0
        rec["dur"] = time.perf_counter() - t0
        if mark is not None:
            mark.close()
        if not attrs:
            del rec["attrs"]
        _emit(rec, parent.file)


def annotate(**attrs) -> None:
    """Add attrs to the innermost span open on this thread — for counts
    known only once the work is done (a cache hit, bytes moved).  No-op on
    an untraced path."""
    ctx = _CTX.get()
    if ctx is not None and ctx.attrs is not None:
        ctx.attrs.update(attrs)


def emit_span(ctx: TraceContext, name: str, /, ts: float = 0.0,
              dur: float = 0.0,
              span_id: str | None = None, parent_id: str = "",
              cpu: float | None = None, **attrs) -> None:
    """Emit a span record with explicit timing — for spans whose body ran
    elsewhere (the scheduler's attempt span measured around a join, the
    root job span closed at the terminal outcome, bench's retroactive
    phase spans).  Such a record carries no ``cpu``: no one thread ran it.
    A caller that did run the body on its own thread, and learns only at
    its end that it was a span (a residency hit's
    ``isotope_prefetch_setup``), passes the ``thread_time`` it measured."""
    if ctx is None or not _enabled:
        return
    rec = {
        "kind": "span", "trace_id": ctx.trace_id,
        "span_id": span_id or new_id(), "parent_id": parent_id,
        "name": name, "ts": ts, "dur": dur, "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if ctx.job_id:
        rec["job_id"] = ctx.job_id
    if _replica_id:
        rec["replica"] = _replica_id
    if _process_id >= 0:
        rec["process"] = _process_id
    if _host:
        rec["host"] = _host
    if cpu is not None:
        rec["cpu"] = cpu
    if attrs:
        rec["attrs"] = attrs
    _emit(rec, ctx.file)


def event(name: str, /, ctx: TraceContext | None = None, **attrs) -> None:
    """Instant event attached to the owning span (``ctx`` or ambient).
    With neither, the event still lands in the flight recorder with empty
    ids — service-level happenings (admission sheds, breaker flips) stay
    observable without a job trace."""
    if not _enabled:
        return
    owner = ctx if ctx is not None else _CTX.get()
    if owner is None:
        owner = TraceContext(trace_id="", span_id="")
    rec = _base(owner, name, "event")
    if attrs:
        rec["attrs"] = attrs
    _emit(rec, owner.file)


# ----------------------------------------------- process-hop (pool workers)
@contextlib.contextmanager
def capture():
    """Redirect this thread's emissions into a list instead of the sinks —
    the worker side of a process hop.  Yields the list; the driver passes
    it to ``emit_records`` after the hop returns."""
    buf: list[dict] = []
    token = _CAPTURE.set(buf)
    try:
        yield buf
    finally:
        _CAPTURE.reset(token)


def emit_records(records: list[dict] | None,
                 ctx: TraceContext | None = None) -> None:
    """Emit records captured in a worker ("re-parented on return": they
    already carry trace/parent ids from the wire context — the driver owns
    the sinks the worker never had).  ``ctx`` supplies the file sink."""
    if not records or not _enabled:
        return
    file = ctx.file if ctx is not None else ""
    for rec in records:
        if isinstance(rec, dict) and rec.get("kind") in RECORD_KINDS:
            _emit(rec, file)


# ------------------------------------------------------- reading + exports
def read_trace(path: str | Path) -> list[dict]:
    """Parse a per-job JSONL trace file; tolerates a torn trailing line
    (the crash-in-flight case the append-only format exists for)."""
    out: list[dict] = []
    try:
        text = Path(path).read_text(encoding="utf-8")
    except OSError:
        return out
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue                  # torn trailing write
        if isinstance(rec, dict):
            out.append(rec)
    return out


def validate_records(records: list[dict]) -> list[str]:
    """Schema check; returns problem strings (empty = valid).  The trace
    smoke gate and tests run every emitted trace through this."""
    problems = []
    for i, rec in enumerate(records):
        if not isinstance(rec, dict):
            problems.append(f"record {i}: not an object")
            continue
        kind = rec.get("kind")
        if kind not in RECORD_KINDS:
            problems.append(f"record {i}: bad kind {kind!r}")
            continue
        keys = _SPAN_KEYS if kind == "span" else _EVENT_KEYS
        missing = [k for k in keys if k not in rec]
        if missing:
            problems.append(f"record {i} ({kind} {rec.get('name')!r}): "
                            f"missing {missing}")
        if kind == "span" and not isinstance(rec.get("dur"), (int, float)):
            problems.append(f"record {i}: span dur not numeric")
        if kind == "span" and "cpu" in rec \
                and not isinstance(rec["cpu"], (int, float)):
            problems.append(f"record {i}: span cpu not numeric")
        if "attrs" in rec and not isinstance(rec["attrs"], dict):
            problems.append(f"record {i}: attrs not an object")
    return problems


def to_chrome_trace(records: list[dict]) -> dict:
    """Convert trace records to Chrome trace-event JSON (Perfetto-loadable:
    chrome://tracing and ui.perfetto.dev both open it).  Spans become
    complete ``"X"`` events (µs timestamps), instants become thread-scoped
    ``"i"`` events; a ``jax_profile`` event surfaces the correlated
    ``jax.profiler`` trace dir in ``otherData``."""
    events: list[dict] = []
    other: dict = {}
    pids = set()
    for rec in records:
        pid = int(rec.get("pid", 0))
        pids.add(pid)
        args = dict(rec.get("attrs") or {})
        args["trace_id"] = rec.get("trace_id", "")
        args["span_id"] = rec.get("span_id", "")
        base = {
            "name": str(rec.get("name", "")),
            "cat": "span" if rec.get("kind") == "span" else "event",
            "pid": pid, "tid": int(rec.get("tid", 0)),
            "ts": round(float(rec.get("ts", 0.0)) * 1e6, 3),
            "args": args,
        }
        if rec.get("kind") == "span":
            base["ph"] = "X"
            base["dur"] = round(float(rec.get("dur", 0.0)) * 1e6, 3)
            if "cpu" in rec:
                base["args"]["cpu"] = rec["cpu"]
            if rec.get("parent_id"):
                base["args"]["parent_id"] = rec["parent_id"]
        else:
            base["ph"] = "i"
            base["s"] = "t"
            if rec.get("name") == "jax_profile" and "dir" in args:
                other["jax_profile_dir"] = args["dir"]
        events.append(base)
        if rec.get("trace_id") and "trace_id" not in other:
            other["trace_id"] = rec["trace_id"]
        if rec.get("job_id"):
            other.setdefault("job_id", rec["job_id"])
    for pid in sorted(pids):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"sm-tpu pid {pid}"}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}
