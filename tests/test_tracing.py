"""End-to-end tracing subsystem tests (ISSUE 5).

Covers the tentpole seams: span nesting + wire round-trip, Chrome-trace
schema validity, flight-recorder bounds under concurrent writers,
cross-process worker span re-parenting (a real spawn pool), trace
continuation across attempts/restarts, JSON-log record fields, the
multi-observer phase dispatch, and the service integration acceptance
shape (root submit span → phases → batch spans → worker span →
store_results via GET /jobs/<id>/trace).
"""

from __future__ import annotations

import json
import logging
import threading
import urllib.request
from pathlib import Path

import pytest

from sm_distributed_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_tracing():
    """Isolate ring + file-handle cache + enablement between tests."""
    tracing.configure(enabled=True, ring_size=2048)
    tracing.flight_recorder.clear()
    yield
    tracing.close_files()
    tracing.configure(enabled=True, ring_size=2048)
    tracing.flight_recorder.clear()


# ------------------------------------------------------------ span basics
def test_span_nesting_and_parentage(tmp_path):
    ctx = tracing.new_trace(job_id="j1", trace_dir=tmp_path)
    with tracing.attach(ctx):
        with tracing.span("outer") as outer:
            with tracing.span("inner", depth=2) as inner:
                tracing.event("mark", note="x")
            assert inner.trace_id == ctx.trace_id
    recs = tracing.read_trace(ctx.file)
    assert [r["name"] for r in recs] == ["mark", "inner", "outer"]
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["parent_id"] == ctx.span_id
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    # the event is attached to the span it happened under
    assert by_name["mark"]["span_id"] == by_name["inner"]["span_id"]
    assert by_name["inner"]["attrs"]["depth"] == 2
    assert all(r["job_id"] == "j1" for r in recs)
    assert not tracing.validate_records(recs)


def test_span_records_error_and_reraises(tmp_path):
    ctx = tracing.new_trace(trace_dir=tmp_path)
    with pytest.raises(ValueError):
        with tracing.attach(ctx), tracing.span("boom"):
            raise ValueError("nope")
    (rec,) = tracing.read_trace(ctx.file)
    assert rec["attrs"]["error"].startswith("ValueError")


def test_span_is_noop_without_context():
    before = len(tracing.flight_recorder.recent())
    with tracing.span("untraced") as got:
        assert got is None
    assert len(tracing.flight_recorder.recent()) == before


def test_disabled_tracing_emits_nothing(tmp_path):
    tracing.configure(enabled=False)
    ctx = tracing.new_trace(trace_dir=tmp_path)
    with tracing.attach(ctx), tracing.span("s"):
        tracing.event("e")
    assert not Path(ctx.file).exists()
    assert not tracing.flight_recorder.recent()


def test_wire_round_trip():
    ctx = tracing.new_trace(job_id="jobX")
    back = tracing.TraceContext.from_wire(ctx.to_wire())
    assert (back.trace_id, back.span_id, back.job_id) == \
        (ctx.trace_id, ctx.span_id, "jobX")
    assert back.file == ""            # sinks never cross the wire
    assert tracing.TraceContext.from_wire(None) is None
    assert tracing.TraceContext.from_wire({}) is None


def test_traceless_event_reaches_ring_only():
    tracing.event("admission.shed", reason="queue_full")
    (rec,) = tracing.flight_recorder.recent()
    assert rec["name"] == "admission.shed" and rec["trace_id"] == ""


# ------------------------------------------------------------ chrome export
def test_chrome_trace_schema(tmp_path):
    ctx = tracing.new_trace(job_id="j2", trace_dir=tmp_path)
    with tracing.attach(ctx):
        with tracing.span("work", ions=5):
            tracing.event("jax_profile", dir="/tmp/prof")
    out = tracing.to_chrome_trace(tracing.read_trace(ctx.file))
    evts = out["traceEvents"]
    assert evts and isinstance(evts, list)
    for e in evts:
        assert e["ph"] in ("X", "i", "M")
        assert "name" in e and "pid" in e
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and "dur" in e
        if e["ph"] == "i":
            assert e["s"] == "t"
    assert out["otherData"]["trace_id"] == ctx.trace_id
    assert out["otherData"]["jax_profile_dir"] == "/tmp/prof"
    json.dumps(out)                    # must be plain-JSON serializable


def test_torn_trailing_line_is_tolerated(tmp_path):
    ctx = tracing.new_trace(trace_dir=tmp_path)
    with tracing.attach(ctx), tracing.span("kept"):
        pass
    with open(ctx.file, "a") as f:
        f.write('{"kind": "span", "name": "torn-mid-wr')  # crash mid-write
    recs = tracing.read_trace(ctx.file)
    assert [r["name"] for r in recs] == ["kept"]


# ----------------------------------------------------------- ring bounds
def test_ring_bounds_under_concurrent_writers():
    tracing.configure(ring_size=100)
    n_threads, per_thread = 8, 200

    def writer(i: int) -> None:
        for k in range(per_thread):
            tracing.event(f"w{i}", k=k)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recent = tracing.flight_recorder.recent()
    assert len(recent) == 100          # bounded, and full
    assert all(r["kind"] == "event" for r in recent)
    assert tracing.flight_recorder.recent(7)[-1] == recent[-1]
    assert len(tracing.flight_recorder.recent(7)) == 7


# ------------------------------------- cross-process worker re-parenting
def test_worker_capture_and_emit_records(tmp_path):
    """The capture/emit halves of the process hop, in-process."""
    ctx = tracing.new_trace(job_id="j3", trace_dir=tmp_path)
    with tracing.capture() as buf:
        with tracing.span("isocalc_chunk", ctx=ctx, ci=0):
            tracing.event("failpoint", name="isocalc.worker")
    assert len(buf) == 2
    assert not Path(ctx.file).exists()          # capture bypassed the sinks
    assert not tracing.flight_recorder.recent()
    tracing.emit_records(buf, ctx)
    recs = tracing.read_trace(ctx.file)
    assert {r["name"] for r in recs} == {"isocalc_chunk", "failpoint"}
    chunk = next(r for r in recs if r["name"] == "isocalc_chunk")
    assert chunk["parent_id"] == ctx.span_id    # re-parented under the job
    assert chunk["trace_id"] == ctx.trace_id


@pytest.mark.slow
def test_worker_spans_cross_spawn_boundary(tmp_path):
    """A REAL spawned worker computes a chunk and returns its spans."""
    from concurrent.futures import ProcessPoolExecutor
    from multiprocessing import get_context

    from sm_distributed_tpu.ops.isocalc import _compute_chunk

    ctx = tracing.new_trace(job_id="spawned", trace_dir=tmp_path)
    args = (3, [("H2O", "+H"), ("C6H12O6", "+Na")],
            (1, 0.01, 10000, 4), False, ctx.to_wire())
    with ProcessPoolExecutor(max_workers=1,
                             mp_context=get_context("spawn")) as ex:
        ci, outputs, records = ex.submit(_compute_chunk, args).result()
    assert ci == 3 and len(outputs) == 2
    assert records, "worker returned no trace records"
    (chunk,) = [r for r in records if r["name"] == "isocalc_chunk"]
    assert chunk["trace_id"] == ctx.trace_id
    assert chunk["parent_id"] == ctx.span_id
    assert chunk["pid"] != __import__("os").getpid()
    tracing.emit_records(records, ctx)
    assert any(r["name"] == "isocalc_chunk"
               for r in tracing.read_trace(ctx.file))


def test_pattern_stream_traces_inline_chunks(tmp_path):
    """A traced (small, inline) generation emits gen + chunk spans into the
    job trace through the stream thread hop."""
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ctx = tracing.new_trace(job_id="iso", trace_dir=tmp_path)
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    with tracing.attach(ctx):
        table = calc.pattern_table([("H2O", "+H"), ("CO2", "+H")])
    assert table.n_ions == 2
    names = [r["name"] for r in tracing.read_trace(ctx.file)]
    assert "isocalc_gen" in names and "isocalc_chunk" in names


# ------------------------------------------------- continuation / restart
def test_trace_continues_across_attempts_and_restart(tmp_path):
    """Retry in scheduler A, then a NEW scheduler (simulating a restarted
    process) finishes the job — one trace file, one trace_id, two attempt
    spans, a retry event, and one root submit span."""
    from sm_distributed_tpu.service.scheduler import JobScheduler
    from sm_distributed_tpu.utils.config import ServiceConfig

    queue_dir = tmp_path / "q"
    trace_dir = tmp_path / "traces"
    from sm_distributed_tpu.engine.daemon import QueuePublisher

    pub = QueuePublisher(queue_dir)
    trace = {"trace_id": tracing.new_id(), "span": tracing.new_id(),
             "start": __import__("time").time()}
    pub.publish({"ds_id": "d1", "msg_id": "m1", "input_path": "x",
                 "service": {"trace": dict(trace)}})

    calls = {"n": 0}

    def flaky(msg, ctx=None):
        calls["n"] += 1
        with tracing.span("work"):
            if calls["n"] == 1:
                raise RuntimeError("first attempt fails")

    cfg = ServiceConfig(workers=1, poll_interval_s=0.02, max_attempts=3,
                        backoff_base_s=0.05, backoff_max_s=0.05,
                        backoff_jitter=0.0, http_port=0)
    s1 = JobScheduler(queue_dir, flaky, config=cfg, trace_dir=trace_dir)
    s1.start()
    # wait for the first (failing) attempt to be recorded, then "crash"
    deadline = __import__("time").time() + 20
    while calls["n"] < 1 and __import__("time").time() < deadline:
        __import__("time").sleep(0.01)
    # let the retry republish land before shutting down
    while __import__("time").time() < deadline:
        if list((queue_dir / "sm_annotate" / "pending").glob("*.json")):
            break
        __import__("time").sleep(0.01)
    s1.shutdown()

    s2 = JobScheduler(queue_dir, flaky, config=cfg, trace_dir=trace_dir)
    s2.start()
    assert s2.wait_for_terminal(1, timeout_s=30)
    s2.shutdown()

    path = tracing.trace_path(trace_dir, trace["trace_id"])
    recs = tracing.read_trace(path)
    assert not tracing.validate_records(recs)
    assert {r["trace_id"] for r in recs} == {trace["trace_id"]}
    names = [r["name"] for r in recs]
    attempts = [r for r in recs
                if r["kind"] == "span" and r["name"] == "attempt"]
    assert len(attempts) == 2, names
    assert names.count("retry") == 1
    roots = [r for r in recs
             if r["kind"] == "span" and r["name"] == "submit"]
    assert len(roots) == 1
    assert roots[0]["attrs"]["state"] == "done"
    # both claims (one per scheduler incarnation) are in the one file
    assert sum(1 for r in recs
               if r["kind"] == "event" and r["name"] == "claim") == 2


# ------------------------------------------------------------ JSON logging
def test_json_log_formatter_injects_trace_fields():
    from sm_distributed_tpu.utils.logger import JsonLogFormatter

    fmt = JsonLogFormatter()
    rec = logging.LogRecord("sm-tpu", logging.INFO, __file__, 1,
                            "phase %s done", ("score",), None)
    ctx = tracing.new_trace(job_id="jobZ")
    with tracing.attach(ctx):
        line = fmt.format(rec)
    out = json.loads(line)
    assert out["msg"] == "phase score done"
    assert out["trace_id"] == ctx.trace_id
    assert out["job_id"] == "jobZ"
    assert out["span"] == ctx.span_id
    assert out["level"] == "INFO" and out["logger"] == "sm-tpu"
    # untraced thread: fields present but empty
    out2 = json.loads(fmt.format(rec))
    assert out2["trace_id"] == "" and out2["job_id"] == ""


def test_init_logger_json_switch(tmp_path, capsys):
    from sm_distributed_tpu.utils import logger as logmod

    lg = logmod.init_logger(json_logs=True)
    try:
        assert all(isinstance(h.formatter, logmod.JsonLogFormatter)
                   for h in lg.handlers)
    finally:
        logmod.init_logger(json_logs=False)
        assert not any(isinstance(h.formatter, logmod.JsonLogFormatter)
                       for h in lg.handlers)


# ----------------------------------------------------- phase observers
def test_phase_observers_multi_and_exception_safe():
    from sm_distributed_tpu.utils import logger as logmod

    seen_a, seen_b = [], []

    def obs_a(phase, dt):
        seen_a.append(phase)
        raise RuntimeError("observer bug")     # must not break anything

    def obs_b(phase, dt):
        seen_b.append((phase, dt))

    logmod.add_phase_observer(obs_a)
    logmod.add_phase_observer(obs_b)
    logmod.add_phase_observer(obs_b)           # idempotent
    try:
        with logmod.phase_timer("p1"):
            pass
        assert seen_a == ["p1"]
        assert [p for p, _ in seen_b] == ["p1"]    # a's raise didn't starve b
        logmod.remove_phase_observer(obs_a)
        with logmod.phase_timer("p2"):
            pass
        assert seen_a == ["p1"] and len(seen_b) == 2
    finally:
        logmod.remove_phase_observer(obs_a)
        logmod.remove_phase_observer(obs_b)
    assert obs_a not in logmod._phase_observers
    assert obs_b not in logmod._phase_observers


def test_phase_timer_emits_span(tmp_path):
    from sm_distributed_tpu.utils.logger import phase_timer

    ctx = tracing.new_trace(trace_dir=tmp_path)
    timings = {}
    with tracing.attach(ctx):
        with phase_timer("stage_input", timings):
            pass
    (rec,) = tracing.read_trace(ctx.file)
    assert rec["name"] == "stage_input" and rec["attrs"]["phase"] is True
    assert "stage_input" in timings


# ------------------------------------------------------ /metrics satellite
def test_build_info_and_process_gauges():
    from sm_distributed_tpu.service.metrics import (
        MetricsRegistry,
        build_info_collector,
        process_collector,
    )

    reg = MetricsRegistry()
    build_info_collector(reg, backend="numpy_ref")
    process_collector(reg)
    text = reg.expose()
    assert 'sm_build_info{' in text and 'backend="numpy_ref"' in text
    assert "jax_version=" in text
    assert "sm_process_threads" in text
    assert "sm_process_resident_memory_bytes" in text
    assert "sm_process_open_fds" in text


# ----------------------------------------------- service integration shape
def _service_harness(tmp_path):
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts.load_sweep import Harness, build_fixtures

    fx = build_fixtures(tmp_path)
    return Harness(tmp_path, "svc"), fx


def test_service_end_to_end_trace(tmp_path):
    """Acceptance shape: spheroid fixture through the REAL in-process
    service → one root submit span covering claim → phases → ≥1 per-batch
    scoring span → ≥1 isocalc worker span → store_results, served as
    Perfetto-loadable Chrome JSON by GET /jobs/<id>/trace."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts.load_sweep import _msg

    h, fx = _service_harness(tmp_path)
    try:
        status, _hd, body = h.submit(_msg(fx, "fast", "traced"))
        assert status == 202 and body["trace_id"]
        rows = h.wait_terminal([body["msg_id"]])
        assert rows[body["msg_id"]]["state"] == "done", rows
        assert rows[body["msg_id"]]["trace_id"] == body["trace_id"]

        with urllib.request.urlopen(
                f"{h.base}/jobs/{body['msg_id']}/trace?raw=1",
                timeout=30.0) as r:
            records = json.loads(r.read())["records"]
        assert not tracing.validate_records(records)
        spans = {r["name"] for r in records if r["kind"] == "span"}
        for required in ("submit", "attempt", "stage_input", "read_dataset",
                         "score", "score_batch", "isocalc_chunk",
                         "store_results"):
            assert required in spans, (required, sorted(spans))
        (root,) = [r for r in records
                   if r["kind"] == "span" and r["name"] == "submit"]
        lo, hi = root["ts"] - 0.05, root["ts"] + root["dur"] + 0.05
        for r in records:
            if r["kind"] == "span":
                assert lo <= r["ts"] <= hi, (r["name"], r["ts"], lo, hi)

        with urllib.request.urlopen(
                f"{h.base}/jobs/{body['msg_id']}/trace", timeout=30.0) as r:
            chrome = json.loads(r.read())
        assert chrome["traceEvents"]
        assert chrome["otherData"]["trace_id"] == body["trace_id"]

        # flight recorder endpoint
        with urllib.request.urlopen(f"{h.base}/debug/events?n=10",
                                    timeout=30.0) as r:
            ring = json.loads(r.read())
        assert isinstance(ring, list) and len(ring) <= 10 and ring
    finally:
        h.shutdown()


def test_trace_report_renders_service_trace(tmp_path, capsys):
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts import trace_report
    from scripts.load_sweep import _msg

    h, fx = _service_harness(tmp_path)
    try:
        status, _hd, body = h.submit(_msg(fx, "fast", "rpt"))
        assert status == 202
        rows = h.wait_terminal([body["msg_id"]])
        assert rows[body["msg_id"]]["state"] == "done"
        path = tracing.trace_path(h.service.trace_dir, body["trace_id"])
        assert trace_report.main([str(path), "--validate"]) == 0
        text = capsys.readouterr().out
        assert "phase breakdown" in text and "store_results" in text
        assert trace_report.main([str(path), "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["state"] == "done"
        assert summary["phases"]["score"]["seconds"] > 0
        assert summary["n_batches"] >= 1
        assert summary["n_isocalc_worker_spans"] >= 1
        assert summary["accounting"]["queue_wait_s"] is not None
    finally:
        h.shutdown()


# ------------------------------------------ device captures (ISSUE 24)
def test_span_without_capture_enters_no_annotation_and_imports_no_jax():
    """The path with no capture: one ``is not None`` test, no jax."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from sm_distributed_tpu.utils import tracing\n"
        "assert tracing._capture is None\n"
        "ctx = tracing.new_trace(job_id='j')\n"
        "with tracing.span('a', ctx=ctx):\n"
        "    with tracing.span('b'):\n"
        "        tracing.annotate(n=1)\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        "assert [r['name'] for r in tracing.flight_recorder.recent()] "
        "== ['b', 'a']\n")
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_span_under_capture_enters_sm_annotation(tmp_path):
    """With the session's hook set, a span enters ``sm:<name>`` carrying
    its ids, is listed as open while it runs, and exits as it closes —
    also on a thread that attached the context."""
    from sm_distributed_tpu.analysis import profiling

    log = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            log.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    session = profiling.ProfileSession(tmp_path)
    session.annotation = FakeAnnotation
    ctx = tracing.new_trace(job_id="job-9", trace_dir=tmp_path)
    tracing.set_capture(lambda rec: profiling._OpenSpan(session, rec))
    try:
        with tracing.span("store_results", ctx=ctx) as outer:
            assert [r["name"] for r in session.open.values()] == \
                ["store_results"]

            def pool_thread():
                with tracing.attach(outer), tracing.span("store_tables"):
                    assert len(session.open) == 2

            t = threading.Thread(target=pool_thread)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        tracing.set_capture(None)
    assert session.open == {}
    assert [e[:2] for e in log] == [
        ("enter", "sm:store_results"), ("enter", "sm:store_tables"),
        ("exit", "sm:store_tables"), ("exit", "sm:store_results")]
    records = {r["name"]: r for r in tracing.read_trace(
        tracing.trace_path(tmp_path, ctx.trace_id))}
    for _what, name, kw in (e for e in log if e[0] == "enter"):
        rec = records[name.removeprefix("sm:")]
        assert kw == {"trace_id": ctx.trace_id, "span_id": rec["span_id"],
                      "job_id": "job-9"}
    # the hook is gone: the next span enters nothing
    with tracing.span("after", ctx=ctx):
        pass
    assert len(log) == 4


def test_annotate_adds_attrs_to_the_open_span(tmp_path):
    ctx = tracing.new_trace(trace_dir=tmp_path)
    tracing.annotate(ignored=True)               # untraced: a no-op
    with tracing.span("backend_build", ctx=ctx):
        with tracing.span("build_sort"):
            pass
        tracing.annotate(cache_hit=False, peaks_in=7)
    recs = {r["name"]: r for r in tracing.read_trace(
        tracing.trace_path(tmp_path, ctx.trace_id))}
    assert recs["backend_build"]["attrs"] == {"cache_hit": False,
                                              "peaks_in": 7}
    assert "attrs" not in recs["build_sort"]


def test_build_and_store_spans_of_a_real_job(tmp_path):
    """A real 8x8-px job on the jax backend (XLA-CPU): ``backend_build``
    with its four children and the four ``store_*`` spans appear once
    each, correctly parented; a resubmit hits the backend cache and builds
    nothing; a compile the job pays names its lease's chips."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts.load_sweep import Harness, _msg, build_fixtures
    from sm_distributed_tpu.models import msm_jax
    from sm_distributed_tpu.ops import buckets
    from sm_distributed_tpu.utils.config import SMConfig

    # the scoring jits are the process's (PR 34): a file that scored this
    # geometry earlier on the worker would leave the first job no compile
    with msm_jax._SHARED_JITS_LOCK:
        msm_jax._SHARED_JITS.clear()
    fx = build_fixtures(tmp_path)
    h = Harness(tmp_path, "svc", {
        "backend": "jax_tpu", "storage": {"store_images": True},
        "service": {"job_timeout_s": 300.0}})
    try:
        traces = []
        for msg_id in ("first", "again"):
            status, _hd, body = h.submit(_msg(fx, "fast", "ds", msg_id=msg_id))
            assert status == 202
            rows = h.wait_terminal([msg_id], timeout_s=300.0)
            assert rows[msg_id]["state"] == "done", rows[msg_id]
            traces.append(tracing.read_trace(tracing.trace_path(
                h.service.trace_dir, body["trace_id"])))
    finally:
        h.shutdown()
    first, again = traces
    assert not tracing.validate_records(first + again)

    def one(records, name):
        (span,) = [r for r in records if r["kind"] == "span"
                   and r["name"] == name]
        return span

    build = one(first, "backend_build")
    assert build["parent_id"] == one(first, "device_hold")["span_id"]
    assert build["attrs"]["cache_hit"] is False
    assert build["attrs"]["peaks_resident"] <= build["attrs"]["peaks_in"] \
        or build["attrs"]["peaks_resident"] % 1024 == 0
    assert build["attrs"]["resident_bytes"] > 0
    children = [one(first, n) for n in (
        "build_sort", "build_restrict", "build_pad_compact",
        "build_device_put")]
    assert all(c["parent_id"] == build["span_id"] for c in children)
    assert sum(c["dur"] for c in children) <= build["dur"]
    assert [c["ts"] for c in children] == sorted(c["ts"] for c in children)
    assert build["ts"] + build["dur"] <= one(first, "score")["ts"] + 1e-3
    for records in (first, again):
        store = one(records, "store_results")
        parts = [one(records, n) for n in (
            "store_select", "store_extract_images", "store_write_images",
            "store_tables")]
        assert all(p["parent_id"] == store["span_id"] for p in parts)
        assert sum(p["dur"] for p in parts) <= store["dur"]
        extract, write = parts[1], parts[2]
        assert extract["attrs"]["ions"] > 0
        assert extract["attrs"]["bytes"] == write["attrs"]["bytes"] > 0
        # the export's own counts (PR 27): one device call whose rows are
        # the lattice bucket of the kept ions, never above formula_batch;
        # what is fetched is rows x k windows of the 8x8 pixel bucket
        rows = extract["attrs"]["rows"]
        assert extract["attrs"]["calls"] == 1
        assert extract["attrs"]["ions"] <= rows == buckets.pow2ish(rows) \
            <= SMConfig.from_dict({}).parallel.formula_batch
        assert extract["attrs"]["fetched_bytes"] == rows * 4 * 64 * 4
        assert extract["attrs"]["bytes"] \
            == extract["attrs"]["ions"] * 4 * 64 * 4
        assert write["attrs"]["format"] == "npz"
        assert write["attrs"]["layout"] == "bitmask_v1"
        # the writer's own counts: 4 B a value + the bit mask, nothing more
        assert 0 < 4 * write["attrs"]["nnz"] <= write["attrs"]["bytes"]
        assert 4 * write["attrs"]["nnz"] < write["attrs"]["file_bytes"] \
            <= 4 * write["attrs"]["nnz"] + write["attrs"]["bytes"] // 32 + 65536
    hit = one(again, "backend_build")
    assert hit["attrs"]["cache_hit"] is True
    assert not [r for r in again if r["name"].startswith("build_")]
    compiles = [r for r in first if r["kind"] == "event"
                and r["name"] == "compile"]
    assert compiles and all(c["attrs"]["devices"] == [0] for c in compiles)
