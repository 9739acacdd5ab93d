"""On-demand device profiling: one capture, one clock, one reduction.

``ProfileSession`` is the one way this program starts a ``jax.profiler``
capture (``GET /debug/profile``, ``engine.cli run --profile``, ``bench.py``):
Python tracer off (on, a 30 s capture is 134 MB and halves the host's
speed), host tracer on, so ``jax.profiler.TraceAnnotation``s are kept.  For
the length of a capture every ``tracing.span`` also enters an ``sm:<name>``
annotation (``utils/tracing.py::set_capture``), and the session emits an
``sm_clock`` annotation carrying ``time.time_ns()`` at both ends: the
reduction maps profiler time to the wall clock of the job traces through
those two events, not through the first device event or anybody's send time.

For the same length of time one daemon thread, the interpreter-wait probe
(``InterpProbe``), asks for the interpreter every 10 ms and adds up how late
it got it: what a thread that wants the GIL for microseconds waits to get
it.  CPython hands the GIL over after its 5 ms switch interval, so a mean of
~0.1 ms reads idle, ~5 ms one thread hogging, more than that a queue.
Outside a capture the thread does not exist.

``reduce_capture`` turns the ``.xplane.pb`` this JAX writes on a TPU into
device time, in a ``JAX_PLATFORMS=cpu`` helper process (``python -m
sm_distributed_tpu.analysis.profiling <request.json>``) so the serving
process's GIL is not held for seconds while jobs run.  A chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` has one event per HLO op as it ran
(ops of a loop nest under it), ``XLA Modules`` one event per executed
program.  To count nothing twice: busy time is the UNION of the ``XLA Ops``
intervals, a program's time its ``XLA Modules`` event, an op's time its SELF
time (children taken out of parents).  The arithmetic is the one
``benchmarks/trace_reduce.py`` proved on a real trace.

Device time attributes by the ``jax.named_scope`` an op was traced under
(``sm_extract``, ``sm_moments``, ``sm_chaos``, ``sm_epilogue``,
``sm_store_extract``; else ``unscoped``).  The scope path is the ``tf_op``
stat of the op's XEventMetadata (``jit(f)/jit(main)/sm_chaos/while/...``),
which ``jax.profiler.ProfileData`` does not expose, so ``op_paths`` reads
that one table from the protobuf wire format itself.

With the job traces of ``service.trace_dir`` the reduction also attributes
every idle gap of a chip to the innermost program span of the job that held
the chip's lease at the time (else ``between_jobs``), and builds, for every
job whose lease hold overlaps the capture, the ``device_scope`` /
``device_busy`` / ``device_idle`` spans ``DeviceProfiler`` appends to that
job's trace (docs/OBSERVABILITY.md "Device profiles").
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time
from functools import partial
from pathlib import Path

from ..utils import tracing

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CLOCK, SPAN_PREFIX = "sm_clock", "sm:"
UNSCOPED, BETWEEN_JOBS, HOLD = "unscoped", "between_jobs", "device_hold"
INJECTED = ("device_scope", "device_busy", "device_idle")
MAX_JOB_GAPS = 16          # device_idle spans appended per job hold
MAX_BODY_GAPS = 32         # idle_gaps rows in the response body


# ------------------------------------------------------------- the capture
class _OpenSpan:
    """One span opened under a capture: its ``sm:`` annotation, and its
    record in the session's table of spans still open."""

    def __init__(self, session: "ProfileSession", rec: dict):
        self.session, self.span_id = session, rec["span_id"]
        session.open[self.span_id] = rec
        self.annotation = session.annotation(
            SPAN_PREFIX + rec["name"], trace_id=rec["trace_id"],
            span_id=rec["span_id"], job_id=rec.get("job_id", ""))
        self.annotation.__enter__()

    def close(self) -> None:
        self.annotation.__exit__(None, None, None)
        self.session.open.pop(self.span_id, None)


# ------------------------------------------------ the interpreter-wait probe
# process totals over every capture so far: read by ``interp_probe_events``
# (``service/server.py::_collect_interp_probe`` pulls them at a scrape)
_probe_lock = threading.Lock()
_probe_totals = {"wakeups": 0, "late_s": 0.0}


def interp_probe_events() -> dict:
    """``{"wakeups", "late_s"}``: the probe's wakes and the seconds they came
    late, summed over the process's captures, the running one included."""
    with _probe_lock:
        return dict(_probe_totals)


class InterpProbe:
    """One daemon thread that sleeps ``PERIOD_S`` on an Event and measures
    how long after the deadline it is running again.  The wait releases the
    GIL and the wake has to take it back, so the lateness is the time a
    thread queues for the interpreter (plus the kernel's wake-up, ~0.1 ms).
    ``stop`` joins the thread and returns what the process's totals grew by
    since ``start`` (one probe runs at a time, as one capture does)."""

    PERIOD_S = 0.010

    def __init__(self):
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="interp-probe", daemon=True)
        self._at_start: dict = {}

    def start(self) -> None:
        self._at_start = interp_probe_events()
        self._thread.start()

    def _run(self) -> None:
        while True:
            deadline = time.perf_counter() + self.PERIOD_S
            if self._stop.wait(self.PERIOD_S):
                return
            late = max(0.0, time.perf_counter() - deadline)
            with _probe_lock:
                _probe_totals["wakeups"] += 1
                _probe_totals["late_s"] += late

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join()
        now = interp_probe_events()
        return {"wakeups": now["wakeups"] - self._at_start["wakeups"],
                "late_s": round(now["late_s"] - self._at_start["late_s"], 6)}


class ProfileSession:
    """One ``jax.profiler`` capture into ``profile_dir``.  ``start()`` raises
    ``RuntimeError`` when jax is missing — callers surface that as a
    structured error, never a crash."""

    def __init__(self, profile_dir: str | Path):
        self.dir = Path(profile_dir)
        self.t0_wall = 0.0
        self.open: dict[str, dict] = {}     # span_id -> record, still open
        self.annotation = None
        self._preexisting: set[Path] = set()
        self._probe: InterpProbe | None = None

    def _clock(self) -> None:
        with self.annotation(CLOCK, wall_ns=time.time_ns()):
            pass

    def start(self) -> None:
        try:
            import jax
        except ImportError as exc:           # pragma: no cover - jax baked in
            raise RuntimeError(f"profiling needs jax: {exc}") from exc
        self.dir.mkdir(parents=True, exist_ok=True)
        self._preexisting = set(self.dir.rglob("*.xplane.pb"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.annotation = jax.profiler.TraceAnnotation
        self._clock()
        self.t0_wall = time.time()
        tracing.set_capture(partial(_OpenSpan, self))
        self._probe = InterpProbe()
        self._probe.start()

    def stop(self) -> dict:
        """Stop the capture; returns ``{"xplane", "t0_wall", "duration_s",
        "open_spans", "interp_probe"}`` — the file the profiler wrote (""
        when it wrote none), the records of the spans still open, which no
        job trace holds yet, and the interpreter-wait probe's wakes and late
        seconds over this capture."""
        if self.annotation is None:
            raise RuntimeError("ProfileSession.stop() before start()")
        import jax

        tracing.set_capture(None)
        probe = self._probe.stop()
        self._probe = None
        open_spans = [dict(r) for r in list(self.open.values())]
        self._clock()
        t1 = time.time()
        jax.profiler.stop_trace()
        self.annotation = None
        new = sorted(set(self.dir.rglob("*.xplane.pb")) - self._preexisting,
                     key=lambda p: p.stat().st_mtime)
        return {"xplane": str(new[-1]) if new else "",
                "t0_wall": self.t0_wall,
                "duration_s": round(t1 - self.t0_wall, 6),
                "open_spans": open_spans, "interp_probe": probe}


def reduce_capture(capture: dict, trace_files=()) -> dict:
    """Reduce a stopped capture (``ProfileSession.stop()``'s dict) against
    the job traces in ``trace_files``, in a CPU-only helper process.  A
    capture that wrote no file reduces to no chips."""
    if not capture.get("xplane"):
        return reduce_planes({}, [], None, [], [])
    with tempfile.TemporaryDirectory() as tmp:
        req, out = Path(tmp) / "request.json", Path(tmp) / "reduced.json"
        req.write_text(json.dumps({
            "xplane": str(Path(capture["xplane"]).resolve()),
            "out": str(out),
            "trace_files": [str(Path(f).resolve()) for f in trace_files],
            "open_spans": capture.get("open_spans", [])}))
        proc = subprocess.run(
            [sys.executable, "-m", __name__, str(req)],
            cwd=Path(__file__).resolve().parents[2],   # the package's parent
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"profile reduction failed: {proc.stderr[-2000:]}")
        return json.loads(out.read_text())


def measured_roofline(floor_s_per_call: float, kernel_s_per_call: float) -> float:
    """The cost model's floor time for one scoring call over the MEASURED
    device time one call took.  Never clamped: a reading above 1 says the
    floor counts too much or the device time leaves out part of the work."""
    if kernel_s_per_call <= 0 or floor_s_per_call <= 0:
        return 0.0
    return floor_s_per_call / kernel_s_per_call


# ---------------------------------------------------- reading the .xplane.pb
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryview slices for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind}")
            val, i = buf[i:i + size], i + size
        yield key >> 3, val


def op_paths(path: str | Path) -> dict[str, dict[str, str]]:
    """``{plane name: {event name: tf_op path}}`` for the device planes of an
    ``.xplane.pb``: XSpace.planes=1; XPlane.name=2, event_metadata=4 and
    stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2, stats=5;
    XStat.metadata_id=1, str_value=5, ref_value=7 (the id of a stat
    metadata whose NAME is the string); XStatMetadata.name=2.  The lines,
    which are nearly all of the file, are skipped by their length."""
    out: dict[str, dict[str, str]] = {}
    space = memoryview(Path(path).read_bytes())
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, events, stat_names = "", [], {}
        for no, val in _fields(plane):
            if no == 2:
                name = bytes(val).decode()
            elif no == 4:
                events.append(dict(_fields(val))[2])
            elif no == 5:
                entry = dict(_fields(val))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        paths = out[name] = {}
        for meta in events:
            ev_name, tf_op = "", ""
            for no, val in _fields(meta):
                if no == 2:
                    ev_name = bytes(val).decode()
                elif no == 5:
                    stat = dict(_fields(val))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if tf_op:
                paths[ev_name] = tf_op
    return out


def scope_of(tf_op: str) -> str:
    """The outermost ``sm_`` component of an op-name path, else ``unscoped``."""
    for part in tf_op.split("/"):
        if part.startswith("sm_"):
            return part
    return UNSCOPED


def load(path: str | Path):
    """``(chips, annotations, capture_ns)`` of an ``.xplane.pb``: per chip
    the ``XLA Ops`` as (start, end, name, scope), scope None for an op the
    compiler gave no ``tf_op``, and the ``XLA Modules`` as (start, end, name); the ``sm_clock`` / ``sm:`` host annotations as
    (name, start, end, stats); the capture's length.  Times in ns from the
    start of the capture."""
    from jax.profiler import ProfileData

    paths = op_paths(path)
    chips: dict[int, dict] = {}
    annotations = []
    capture_ns = 0.0
    for plane in ProfileData.from_file(str(path)).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            scope = {n: scope_of(p) for n, p in paths.get(plane.name, {}).items()}
            lines = {line.name: line for line in plane.lines}
            chips[int(m.group(1))] = {
                "ops": [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         scope.get(e.name))       # None: no metadata at all
                        for e in getattr(lines.get(OPS_LINE), "events", ())],
                "modules": [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in getattr(lines.get(MODULES_LINE),
                                             "events", ())]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == CLOCK or e.name.startswith(SPAN_PREFIX):
                        annotations.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            capture_ns = float(stats.get("profile_stop_time", 0)
                               - stats.get("profile_start_time", 0))
    return chips, annotations, capture_ns


# ------------------------------------------------------------ the arithmetic
def union(intervals) -> tuple[list[list[float]], float]:
    """Merged, sorted intervals and their total length."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged, sum(b - a for a, b in merged)


def self_times(events: list[tuple]) -> list[float]:
    """Self time of each of possibly nested ``(start, end, ...)`` events,
    which must be sorted by ``(start, -end)``: an event's duration less the
    part its direct children cover."""
    out = [0.0] * len(events)
    stack: list[tuple[float, int]] = []          # (end, index)
    for i, ev in enumerate(events):
        a, b = ev[0], ev[1]
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(b, stack[-1][0]) - a
        out[i] += b - a
        stack.append((b, i))
    return out


def inherit_scopes(ops: list[tuple], runs: list[tuple]) -> list[str]:
    """The scope of each ``(start, end, name, scope)`` op, sorted by
    ``(start, -end)``.  The compiler makes ops of its own with no metadata
    at all (on the TPU a scatter becomes a ``sort`` and a custom fusion:
    half of a scoring program's device time): such an op takes the scope of
    the op it is nested under, else of the op that ran before it in the
    same program run (``runs``: the ``XLA Modules`` intervals).  An op with
    metadata but no ``sm_`` scope keeps ``unscoped``."""
    out: list[str] = []
    stack: list[tuple[float, int]] = []          # (end, index)
    starts = sorted(r[0] for r in runs)
    run, last = -1, UNSCOPED                     # last scope seen in the run
    for i, (a, b, _name, scope) in enumerate(ops):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if not stack:
            this_run = bisect.bisect_right(starts, a)
            if this_run != run:
                run, last = this_run, UNSCOPED
        if scope is None:
            scope = out[stack[-1][1]] if stack else last
        if not stack:
            last = scope
        out.append(scope)
        stack.append((b, i))
    return out


def short(name: str) -> str:
    """``%fusion.1 = f32[67129345]{0:T(1024)} fusion(...)`` -> ``fusion.1
    f32[67129345]``: the name the trace prints, without its operand list."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()[:80]


def clock_of(annotations) -> dict | None:
    """The profiler-to-wall mapping from the ``sm_clock`` annotations: the
    first pair anchors it, ``drift_us`` is what the last pair disagrees
    with the first by over the capture."""
    pairs = sorted((a, int(stats["wall_ns"])) for name, a, _b, stats
                   in annotations if name == CLOCK and "wall_ns" in stats)
    if not pairs:
        return None
    (p1, w1), (p2, w2) = pairs[0], pairs[-1]
    return {"profiler_ns": p1, "wall_ns": w1, "pairs": len(pairs),
            "drift_us": ((w2 - p2) - (w1 - p1)) / 1e3}


def job_holds(records: list[dict], file: str = "") -> list[dict]:
    """The lease holds of one job trace: ``device_token_acquired`` (lease
    granted, its chips) to the end of the ``device_hold`` span it sits in.
    ``end`` is None while the hold's span is still open.  ``spans`` are the
    spans below the hold as (start, end, depth, name, span_id)."""
    spans = [r for r in records if r.get("kind") == "span"
             and r["name"] not in INJECTED]
    by_id = {s["span_id"]: s for s in spans if not s.get("open")}
    children: dict[str, list[dict]] = {}
    for s in spans:
        children.setdefault(s.get("parent_id", ""), []).append(s)
    holds = []
    for ev in records:
        if ev.get("kind") != "event" or ev["name"] != "device_token_acquired":
            continue
        closed = by_id.get(ev["span_id"])
        below, level, depth = [], children.get(ev["span_id"], []), 1
        while level:
            below += [(s["ts"], None if s.get("open") else s["ts"] + s["dur"],
                       depth, s["name"], s["span_id"]) for s in level]
            level = [c for s in level for c in children.get(s["span_id"], [])]
            depth += 1
        holds.append({
            "job": ev.get("job_id", ""), "trace_id": ev["trace_id"],
            "file": file, "span_id": ev["span_id"],
            "chips": list((ev.get("attrs") or {}).get("devices", [])),
            "start": ev["ts"],
            "end": closed["ts"] + closed["dur"] if closed else None,
            "spans": below})
    return holds


def _timeline(hold: dict, a: float, b: float) -> list[tuple]:
    """``[(start, end, name, span_id)]`` covering [a, b]: at each moment the
    deepest span below the hold that is open then (the later-started of two
    equally deep), the hold itself where none is."""
    spans = [(max(s, a), min(b if e is None else e, b), d, n, i)
             for s, e, d, n, i in hold["spans"]]
    spans = [s for s in spans if s[1] > s[0]]
    cuts = sorted({a, b, *(s[0] for s in spans), *(s[1] for s in spans)})
    out: list[tuple] = []
    for x, y in zip(cuts, cuts[1:]):
        cover = [s for s in spans if s[0] <= x and s[1] >= y]
        top = max(cover, key=lambda s: (s[2], s[0])) if cover else None
        name, sid = (top[3], top[4]) if top else (HOLD, hold["span_id"])
        if out and out[-1][3] == sid:
            out[-1] = (out[-1][0], y, name, sid)
        else:
            out.append((x, y, name, sid))
    return out


def _holds_in(traces, open_spans, cap0: float, cap1: float):
    """The lease holds of ``traces`` that overlap the capture [cap0, cap1],
    each cut to it (``a``, ``b``), flagged ``whole`` when it needed no cut,
    with its ``timeline``; and the (start, end) of every closed span by id.
    Spans still open (``open_spans``) end where the capture does."""
    still_open: dict[str, list[dict]] = {}
    for rec in open_spans:
        still_open.setdefault(rec["trace_id"], []).append(
            {**rec, "kind": "span", "open": True})
    holds, span_ts = [], {}
    for file, records in traces:
        tid = next((r["trace_id"] for r in records if r.get("trace_id")), "")
        for r in records:
            if r.get("kind") == "span":
                span_ts[r["span_id"]] = (r["ts"], r["ts"] + r["dur"])
        for h in job_holds(records + still_open.get(tid, []), file):
            end = cap1 if h["end"] is None else h["end"]
            if h["start"] < cap1 and end > cap0:
                h["whole"] = h["end"] is not None and h["start"] >= cap0 \
                    and end <= cap1
                h["a"], h["b"] = max(h["start"], cap0), min(end, cap1)
                h["timeline"] = _timeline(h, h["a"], h["b"])
                holds.append(h)
    return holds, span_ts


def _idle_pieces(merged, holds, cap0: float, cap1: float) -> list[tuple]:
    """One chip's idle time inside the capture — the complement of its
    ``merged`` busy intervals — cut at lease and span boundaries:
    ``[(start, end, host, host span id, hold or None)]``."""
    segments, t = [], cap0
    for h in holds:
        if h["a"] > t:
            segments.append((t, h["a"], BETWEEN_JOBS, "", None))
        segments += [(x, y, n, i, h) for x, y, n, i in h["timeline"] if y > t]
        t = max(t, h["b"])
    if cap1 > t:
        segments.append((t, cap1, BETWEEN_JOBS, "", None))
    starts = [seg[0] for seg in segments]
    edges = [cap0] + [x for iv in merged for x in iv] + [cap1]
    pieces = []
    for a, b in zip(edges[::2], edges[1::2]):
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            x, y, host, sid, h = segments[i]
            if min(b, y) > max(a, x):
                pieces.append((max(a, x), min(b, y), host, sid, h))
            i += 1
    return pieces


def _hold_records(h: dict, chip: int, ops, starts, selfs, merged,
                  pieces) -> list[dict]:
    """What one lease hold on one chip gets appended to its job's trace
    (``starts``: the start of each of ``ops``, ``selfs`` their self times)."""
    i0, i1 = (bisect.bisect_left(starts, h[k]) for k in ("a", "b"))
    scopes: dict[str, list] = {}
    for (a, b, _n, scope), s in zip(ops[i0:i1], selfs[i0:i1]):
        row = scopes.setdefault(scope, [a, b, 0.0, 0])
        row[1] = max(row[1], b)
        row[2] += s
        row[3] += 1
    records = [{"name": "device_scope", "ts": a, "dur": b - a,
                "attrs": {"scope": scope, "chip": chip, "device_s": s,
                          "n_ops": n, "whole": h["whole"]}}
               for scope, (a, b, s, n) in sorted(scopes.items())]
    records.append({
        "name": "device_busy", "ts": h["a"], "dur": h["b"] - h["a"],
        "attrs": {"chip": chip, "hold_s": h["b"] - h["a"],
                  "busy_s": sum(min(b, h["b"]) - max(a, h["a"])
                                for a, b in merged
                                if b > h["a"] and a < h["b"]),
                  "whole": h["whole"]}})
    own = sorted((p for p in pieces if p[4] is h),
                 key=lambda p: p[0] - p[1])[:MAX_JOB_GAPS]
    return records + [
        {"name": "device_idle", "ts": lo, "dur": hi - lo,
         "attrs": {"chip": chip, "host": host, "host_span_id": sid}}
        for lo, hi, host, sid, _h in own]


def reduce_planes(chips: dict, annotations: list, capture_ns: float | None,
                  traces: list[tuple[str, list[dict]]],
                  open_spans: list[dict]) -> dict:
    """The whole reduction.  ``chips`` / ``annotations`` / ``capture_ns`` as
    ``load`` returns them, ``traces`` as ``[(file, job-trace records)]``,
    ``open_spans`` the span records no trace holds yet.  Every time in the
    result is seconds on the wall clock (seconds from the capture's start
    when the capture carries no ``sm_clock``, and then no job is read)."""
    clock = clock_of(annotations)
    offset = clock["wall_ns"] - clock["profiler_ns"] if clock else 0.0

    def wall(t_ns: float) -> float:
        return (t_ns + offset) / 1e9

    # the capture is what lies between the two sm_clock events: spans are
    # annotated and the table of open spans is kept only there
    marks = [t for name, a, b, _st in annotations if name == CLOCK
             for t in (a, b)]
    ends = [ev[1] for c in chips.values() for ev in c["ops"] + c["modules"]]
    cap0 = wall(min(marks, default=0.0))
    cap1 = wall(max(marks) if marks else capture_ns or max(ends, default=0.0))

    def inside(a_ns: float, b_ns: float) -> bool:
        return cap0 <= wall(a_ns) and wall(b_ns) <= cap1

    holds, span_ts = _holds_in(traces if clock else (), open_spans,
                               cap0, cap1)
    if clock:
        # the clock check: every sm: annotation against its job-trace span
        errs = [max(abs(wall(a) - span_ts[st["span_id"]][0]),
                    abs(wall(b) - span_ts[st["span_id"]][1]))
                for name, a, b, st in annotations
                if name.startswith(SPAN_PREFIX)
                and st.get("span_id") in span_ts]
        clock["annotations"] = {
            "n": sum(n.startswith(SPAN_PREFIX) for n, *_ in annotations),
            "matched": len(errs),
            "max_err_us": max(errs) * 1e6 if errs else None}

    out_chips, programs, op_rows, gaps, inject = [], {}, {}, [], []
    by_scope: dict[str, float] = {}
    idle_by_host: dict[str, float] = {}
    for chip in sorted(chips):
        ops = sorted(((wall(a), wall(b), n, s)
                      for a, b, n, s in chips[chip]["ops"] if inside(a, b)),
                     key=lambda e: (e[0], -e[1]))
        runs = [(wall(a), wall(b), n)
                for a, b, n in chips[chip]["modules"] if inside(a, b)]
        selfs = self_times(ops)
        scopes = inherit_scopes(ops, runs)
        inherited = sum(s for s, op, scope in zip(selfs, ops, scopes)
                        if op[3] is None and scope != UNSCOPED)
        ops = [(a, b, n, scope) for (a, b, n, _s), scope in zip(ops, scopes)]
        merged, busy = union([(a, b) for a, b, _n, _s in ops])
        chip_scope: dict[str, float] = {}
        for (_a, _b, name, scope), s in zip(ops, selfs):
            chip_scope[scope] = chip_scope.get(scope, 0.0) + s
            row = op_rows.setdefault((short(name), scope), [0.0, 0])
            row[0] += s
            row[1] += 1
        for scope, s in chip_scope.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + s
        for a, b, name in runs:
            row = programs.setdefault((name, chip), [0.0, 0])
            row[0] += b - a
            row[1] += 1
        out_chips.append({"chip": chip, "busy_s": busy, "n_ops": len(ops),
                          "by_scope_s": chip_scope,
                          "inherited_s": inherited})

        # a lease without chips is a plain lock: it held every chip
        mine = sorted((h for h in holds if chip in h["chips"]
                       or not h["chips"]), key=lambda h: h["a"])
        pieces = _idle_pieces(merged, mine, cap0, cap1)
        for lo, hi, host, _sid, h in pieces:
            idle_by_host[host] = idle_by_host.get(host, 0.0) + hi - lo
            gaps.append({"chip": chip, "start": lo, "dur": hi - lo,
                         "host": host, "job": h["job"] if h else ""})
        starts = [op[0] for op in ops]
        inject += [{"trace_id": h["trace_id"], "job": h["job"],
                    "file": h["file"], "parent_id": h["span_id"],
                    "records": _hold_records(h, chip, ops, starts, selfs,
                                             merged, pieces)}
                   for h in mine]

    gaps.sort(key=lambda g: -g["dur"])
    return {
        "clock": clock,
        "capture": {"start": cap0, "seconds": cap1 - cap0},
        "chips": out_chips,
        "by_scope_s": by_scope,
        "programs": sorted(
            ({"name": n, "chip": c, "device_s": s, "runs": k}
             for (n, c), (s, k) in programs.items()),
            key=lambda p: -p["device_s"]),
        "ops": sorted(
            ({"op": n, "scope": sc, "device_s": s, "n": k}
             for (n, sc), (s, k) in op_rows.items()),
            key=lambda o: -o["device_s"])[:40],
        "idle_gaps": gaps[:MAX_BODY_GAPS],
        "idle_by_host_s": idle_by_host,
        "idle_in_holds_s": sum(s for host, s in idle_by_host.items()
                               if host != BETWEEN_JOBS),
        "jobs": [{"job": h["job"], "trace_id": h["trace_id"],
                  "chips": h["chips"], "start": h["a"],
                  "hold_s": h["b"] - h["a"], "whole": h["whole"]}
                 for h in holds],
        "inject": inject,
    }


def reduce_file(xplane: str | Path, trace_files=(), open_spans=()) -> dict:
    chips, annotations, capture_ns = load(xplane)
    traces = [(str(f), tracing.read_trace(f)) for f in trace_files]
    return reduce_planes(chips, annotations, capture_ns, traces,
                         list(open_spans))


if __name__ == "__main__":
    _req = json.loads(Path(sys.argv[1]).read_text())
    Path(_req["out"]).write_text(json.dumps(reduce_file(
        _req["xplane"], _req.get("trace_files", ()),
        _req.get("open_spans", ()))))
