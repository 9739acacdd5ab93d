"""Ran, waited for the chip, or waited for the interpreter (ISSUE 35).

Every ``tracing.span`` record says how long its thread was on a core
(``cpu``) beside its wall time (``dur``); a capture runs an interpreter-wait
probe; ``/metrics`` has the process's CPU clock; and the seconds of a lease
hold that no span named have names.  The served job of the last section is
the 8x8-px fixture on the jax backend (XLA-CPU), driven once for the file.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import types
from pathlib import Path

import pytest

from sm_distributed_tpu.analysis import profiling
from sm_distributed_tpu.utils import tracing

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.configure(enabled=True, ring_size=2048)
    tracing.flight_recorder.clear()
    yield
    tracing.close_files()
    tracing.flight_recorder.clear()


def _one_span(tmp_path, body, name="work"):
    ctx = tracing.new_trace(trace_dir=tmp_path)
    with tracing.span(name, ctx=ctx):
        body()
    (rec,) = [r for r in tracing.read_trace(ctx.file) if r["name"] == name]
    return rec


def _burn(cpu_s: float) -> None:
    """Pure Python (GIL held) until this thread has used ``cpu_s`` of CPU."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        sum(range(2000))


class _Hog:
    """A thread that holds the interpreter: pure Python, no blocking call."""

    def __enter__(self):
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        return self

    def _run(self):
        while not self.stop.is_set():
            sum(range(20000))

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join()


# ------------------------------------------------------------ cpu on a span
def test_sleeping_span_has_wall_time_and_no_cpu(tmp_path):
    rec = _one_span(tmp_path, lambda: time.sleep(0.1))
    assert rec["dur"] >= 0.1
    assert 0.0 <= rec["cpu"] < 0.005


def test_busy_span_cpu_is_its_wall_time(tmp_path):
    # on a shared machine a thread can be put off its core: best of three
    ratios = []
    for _ in range(3):
        rec = _one_span(tmp_path, lambda: _burn(0.1))
        assert 0.1 <= rec["cpu"] <= rec["dur"]
        ratios.append(rec["cpu"] / rec["dur"])
        if ratios[-1] >= 0.8:
            break
    assert max(ratios) >= 0.8, ratios


def test_span_queued_behind_a_gil_hog_is_off_core(tmp_path):
    with _Hog():
        rec = _one_span(tmp_path, lambda: _burn(0.05))
    # two threads share one interpreter: about half the wall time is a wait
    assert rec["cpu"] >= 0.05
    assert rec["dur"] - rec["cpu"] > 0.01, rec


def test_phase_timer_span_has_cpu(tmp_path):
    from sm_distributed_tpu.utils.logger import phase_timer

    ctx = tracing.new_trace(trace_dir=tmp_path)
    with tracing.attach(ctx), phase_timer("fdr"):
        _burn(0.01)
    (rec,) = tracing.read_trace(ctx.file)
    assert rec["attrs"]["phase"] is True and 0.01 <= rec["cpu"] <= rec["dur"]


@pytest.mark.parametrize("cpu", [None, 0.25])
def test_emit_span_writes_no_cpu_but_the_callers_own(tmp_path, cpu):
    """``cpu``: a body the caller ran on its own thread and timed itself (a
    residency hit's ``isotope_prefetch_setup``)."""
    ctx = tracing.new_trace(trace_dir=tmp_path)
    more = {} if cpu is None else {"cpu": cpu}
    tracing.emit_span(ctx, "attempt", ts=time.time(), dur=1.5, attempt=1,
                      **more)
    (rec,) = tracing.read_trace(ctx.file)
    assert rec["dur"] == 1.5 and rec.get("cpu") == cpu
    assert ("cpu" in rec) is (cpu is not None)
    assert rec["attrs"] == {"attempt": 1}
    assert not tracing.validate_records([rec])


def test_captured_worker_span_keeps_its_cpu(tmp_path):
    root = tracing.new_trace(job_id="j1", trace_dir=tmp_path)
    wire = tracing.TraceContext.from_wire(root.to_wire())

    def worker():                         # the far side of a process hop
        with tracing.capture() as buf:
            with tracing.attach(wire), tracing.span("isocalc_chunk"):
                _burn(0.02)
        return buf

    (captured,) = worker()
    assert captured["cpu"] >= 0.02
    tracing.emit_records([captured], root)
    (rec,) = tracing.read_trace(root.file)
    assert rec["name"] == "isocalc_chunk" and rec["cpu"] == captured["cpu"]


def _span_record(**extra):
    return {"kind": "span", "trace_id": "t", "span_id": "s", "parent_id": "",
            "name": "fdr", "ts": 100.0, "dur": 0.29, "pid": 1, "tid": 2,
            **extra}


@pytest.mark.parametrize("extra,problems", [
    ({}, 0),                              # the shape before ISSUE 35
    ({"cpu": 0.03}, 0),
    ({"cpu": 0}, 0),
    ({"cpu": "0.03"}, 1),
])
def test_validate_records_takes_a_span_with_or_without_cpu(extra, problems):
    found = tracing.validate_records([_span_record(**extra)])
    assert len(found) == problems, found
    assert all("cpu" in p for p in found)


@pytest.mark.parametrize("extra", [{}, {"cpu": 0.03}])
def test_chrome_trace_passes_cpu_through_in_args(extra):
    chrome = tracing.to_chrome_trace(
        [_span_record(attrs={"phase": True}, **extra)])
    (event,) = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert event["dur"] == pytest.approx(0.29e6)
    assert event["args"].get("cpu") == extra.get("cpu")
    assert event["args"]["phase"] is True


# ------------------------------------------------ the interpreter-wait probe
def _probe_threads():
    return [t for t in threading.enumerate() if t.name == "interp-probe"]


def _probe_mean_late(seconds: float) -> float:
    probe = profiling.InterpProbe()
    probe.start()
    time.sleep(seconds)
    got = probe.stop()
    assert got["wakeups"] >= 5
    return got["late_s"] / got["wakeups"]


def test_probe_reads_late_behind_a_hog_and_not_without():
    # alone a wake is late by the kernel's timer slack; behind a thread that
    # never blocks it waits out the interpreter's 5 ms switch interval
    idle = min(_probe_mean_late(0.3) for _ in range(3))
    with _Hog():
        hogged = _probe_mean_late(0.5)
    assert idle < 0.002, idle
    assert hogged > 0.003 and hogged > 2 * idle, (idle, hogged)


def test_probe_lives_and_counts_only_inside_a_capture(tmp_path):
    assert not _probe_threads()
    before = profiling.interp_probe_events()
    time.sleep(0.05)
    assert profiling.interp_probe_events() == before   # no capture: no wakes
    session = profiling.ProfileSession(tmp_path)
    session.start()
    try:
        assert len(_probe_threads()) == 1
        time.sleep(0.2)
        during = profiling.interp_probe_events()       # live, not at stop
        assert during["wakeups"] > before["wakeups"]
    finally:
        capture = session.stop()
    assert not _probe_threads()
    own = capture["interp_probe"]
    after = profiling.interp_probe_events()
    assert 5 <= own["wakeups"] == after["wakeups"] - before["wakeups"]
    assert own["late_s"] == pytest.approx(
        after["late_s"] - before["late_s"], abs=1e-5)
    time.sleep(0.05)
    assert profiling.interp_probe_events() == after


def test_debug_profile_answer_and_metrics_carry_the_probe(tmp_path):
    """``GET /debug/profile``'s body has its own capture's totals, and the
    service's collector shows the process's on ``/metrics``."""
    from sm_distributed_tpu.service.fleetview import DeviceProfiler
    from sm_distributed_tpu.service.metrics import MetricsRegistry
    from sm_distributed_tpu.service.server import AnnotationService
    from sm_distributed_tpu.utils.config import ProfileConfig

    reg = MetricsRegistry()
    reg.add_collector(AnnotationService._collect_interp_probe)
    svc = types.SimpleNamespace(
        metrics=reg, trace_dir=None,
        scheduler=types.SimpleNamespace(jobs=lambda: []),
        sm_config=types.SimpleNamespace(work_dir=str(tmp_path)))

    def totals():
        text = reg.expose()
        return {line.split()[0]: float(line.split()[1])
                for line in text.splitlines()
                if line.startswith("sm_interp_probe_")}

    before = totals()
    assert set(before) == {"sm_interp_probe_wakeups_total",
                           "sm_interp_probe_late_seconds_total"}
    code, body = DeviceProfiler(svc, ProfileConfig()).run(0.2)
    assert code == 200, body
    probe = body["interp_probe"]
    assert probe["wakeups"] >= 5 and probe["late_s"] >= 0.0
    after = totals()
    assert after["sm_interp_probe_wakeups_total"] \
        - before["sm_interp_probe_wakeups_total"] == probe["wakeups"]
    assert after["sm_interp_probe_late_seconds_total"] \
        >= before["sm_interp_probe_late_seconds_total"]
    assert not _probe_threads()


# ------------------------------------------------- the process's CPU clock
def test_process_cpu_pair_is_monotone_and_read_in_one_scrape():
    from sm_distributed_tpu.service.metrics import (MetricsRegistry,
                                                    process_cpu_collector)

    reg = MetricsRegistry()
    process_cpu_collector(reg)
    names = ("sm_process_cpu_seconds_total", "sm_process_clock_seconds_total")

    def scrape():
        text = reg.expose()
        return [float(line.split()[1]) for name in names
                for line in text.splitlines() if line.startswith(name + " ")]

    cpu0, clock0 = scrape()
    _burn(0.2)
    # both stand still between scrapes: one collector sets them, at a scrape
    assert [reg.value(n) for n in names] == [cpu0, clock0]
    cpu1, clock1 = scrape()
    assert 0.15 <= cpu1 - cpu0          # os.times() ticks are 10 ms
    assert clock1 - clock0 >= 0.2
    # one thread burned: the process cannot have used more than its cores
    assert cpu1 - cpu0 <= (clock1 - clock0) * (
        len(os.sched_getaffinity(0)) + 1)
    cpu2, clock2 = scrape()
    assert cpu2 >= cpu1 and clock2 > clock1


# ------------------------------------------------------ a served job's trace
NEW_SPANS = ("attempt_setup", "job_start", "search_init", "prefetch_join",
             "table_fingerprint", "checkpoint_load", "presize", "partial_fdr",
             "finish_job", "checkpoint_finalize", "workdir_clean")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two jobs through the real in-process service on the jax backend: the
    first builds and compiles, the second hits the backend cache."""
    from scripts.load_sweep import Harness, _msg, build_fixtures

    tmp = tmp_path_factory.mktemp("served")
    fx = build_fixtures(tmp)
    h = Harness(tmp, "svc", {
        "backend": "jax_tpu", "storage": {"store_images": True},
        "parallel": {"formula_batch": 8, "checkpoint_every": 2},
        "service": {"job_timeout_s": 300.0}})
    traces = {}
    try:
        for msg_id in ("first", "again"):
            status, _hd, body = h.submit(
                _msg(fx, "fast", "ds", msg_id=msg_id, clean=True))
            assert status == 202
            rows = h.wait_terminal([msg_id], timeout_s=300.0)
            assert rows[msg_id]["state"] == "done", rows[msg_id]
            traces[msg_id] = tracing.read_trace(tracing.trace_path(
                h.service.trace_dir, body["trace_id"]))
        idle_threads = [t.name for t in threading.enumerate()]
    finally:
        h.shutdown()
    return {**traces, "idle_threads": idle_threads}


def _spans(records, name=None):
    return [r for r in records if r["kind"] == "span"
            and (name is None or r["name"] == name)]


@pytest.mark.parametrize("job", ["first", "again"])
def test_every_span_of_a_served_job_carries_cpu(served, job):
    records = served[job]
    assert not tracing.validate_records(records)
    explicit = {"submit", "attempt"}       # emit_span: their body ran elsewhere
    for r in _spans(records):
        if r["name"] in explicit:
            assert "cpu" not in r
        else:
            assert 0.0 <= r["cpu"] <= r["dur"] + 0.002, r
    (grant,) = [r for r in records if r["name"] == "device_token_acquired"]
    (hold,) = _spans(records, "device_hold")
    assert 0.0 <= grant["attrs"]["wait_cpu_s"] <= hold["cpu"]


@pytest.mark.parametrize("name", NEW_SPANS)
def test_each_new_span_appears_once_a_job(served, name):
    for job in ("first", "again"):
        assert len(_spans(served[job], name)) == 1, (job, name)


def test_score_plan_appears_once_a_group(served):
    for job in ("first", "again"):
        groups = _spans(served[job], "score_group")
        plans = _spans(served[job], "score_plan")
        assert len(groups) >= 2
        assert sorted(p["parent_id"] for p in plans) \
            == sorted(g["span_id"] for g in groups)
        assert sum(p["attrs"]["batches"] for p in plans) \
            == len(_spans(served[job], "score_batch"))


def test_new_spans_sit_where_the_issue_put_them(served):
    records = served["first"]

    def one(name):
        (s,) = _spans(records, name)
        return s

    attempt, hold = one("attempt"), one("device_hold")
    score = one("score")
    for name in ("attempt_setup", "job_start", "finish_job",
                 "checkpoint_finalize", "workdir_clean"):
        assert one(name)["parent_id"] == attempt["span_id"], name
    for name in ("search_init", "prefetch_join", "table_fingerprint"):
        assert one(name)["parent_id"] == hold["span_id"], name
    for name in ("checkpoint_load", "presize", "partial_fdr"):
        assert one(name)["parent_id"] == score["span_id"], name
    # before pre_lease, and after the hold
    assert one("attempt_setup")["ts"] <= one("job_start")["ts"] \
        <= one("pre_lease")["ts"]
    assert one("finish_job")["ts"] >= hold["ts"] + hold["dur"] - 1e-3
    # partial_fdr is the provisional FDR: after the first group, outside it
    first_group = min(_spans(records, "score_group"), key=lambda r: r["ts"])
    assert one("partial_fdr")["ts"] >= first_group["ts"] + first_group["dur"] \
        - 1e-3


@pytest.mark.parametrize("job", ["first", "again"])
def test_all_but_5pct_of_the_hold_lies_under_a_named_span(served, job):
    from scripts import trace_report

    split = trace_report.hold_split(served[job])
    assert split["held_s"] > 0
    assert 0.0 <= split["unnamed_s"] <= 0.05 * split["held_s"] + 0.002, split
    # ran + device_sync + stalled is the hold, by construction
    assert split["ran_s"] + split["device_sync_s"] + split["stalled_s"] \
        == pytest.approx(split["held_s"], abs=1e-5)
    assert split["ran_s"] > 0


def test_hold_split_without_cpu_still_names_the_unnamed(served):
    """A trace from before ISSUE 35 (no ``cpu``, no ``wait_cpu_s``): wall
    numbers stay, the CPU ones read None."""
    from scripts import trace_report

    old = []
    for r in served["again"]:
        r = {k: v for k, v in r.items() if k != "cpu"}
        if r["name"] == "device_token_acquired":
            r["attrs"] = {k: v for k, v in r["attrs"].items()
                          if k != "wait_cpu_s"}
        old.append(r)
    split = trace_report.hold_split(old)
    assert split["ran_s"] is None and split["stalled_s"] is None
    assert split["unnamed_s"] == trace_report.hold_split(
        served["again"])["unnamed_s"]
    text = trace_report.render(trace_report.summarize(old))
    assert " off " not in text and "stalled n/a" in text


def test_trace_report_prints_cpu_and_the_hold_split(served):
    from scripts import trace_report

    summary = trace_report.summarize(served["first"])
    text = trace_report.render(summary)
    assert "lease hold after the grant" in text and "stalled" in text
    fdr = next(line for line in text.splitlines()
               if line.strip().startswith("fdr "))
    assert " cpu " in fdr and " off " in fdr
    table = {row["name"]: row for row in summary["spans"]}
    assert table["partial_fdr"]["under_hold"] is True
    assert table["finish_job"]["under_hold"] is False
    assert table["attempt"]["cpu_s"] is None
    assert 0 < table["store_tables"]["cpu_s"] <= table["store_tables"]["seconds"]


def test_no_probe_thread_on_an_idle_serve_process(served):
    # taken while the service was up and idle, after its two jobs
    assert len(served["idle_threads"]) > 1
    assert "interp-probe" not in served["idle_threads"]
