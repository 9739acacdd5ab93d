"""analysis/profiling.py — the reduction of a ``jax.profiler`` capture
(ISSUE 24): busy union, self time by ``jax.named_scope``, the ``sm_clock``
mapping, idle gaps attributed to program spans, and the per-job spans built
for injection.  ``tests/data/scoped.xplane.pb`` is a capture a TPU v5e
really wrote (``tests/data/record_scoped_trace.py``); everything here reads
it on the CPU through ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from sm_distributed_tpu.analysis import profiling
from sm_distributed_tpu.service.fleetview import DeviceProfiler
from sm_distributed_tpu.service.metrics import MetricsRegistry
from sm_distributed_tpu.utils import tracing
from sm_distributed_tpu.utils.config import ProfileConfig, SMConfig

REPO = Path(__file__).resolve().parent.parent
SCOPED = REPO / "tests" / "data" / "scoped.xplane.pb"
SMALL = REPO / "benchmarks" / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def scoped():
    return profiling.load(SCOPED)


def _phase(annotations):
    (phase,) = [a for a in annotations if a[0] == "sm:phase"]
    return phase


def _job_trace(phase_stats, hold=(-0.02, 0.2), span=(0.0, 0.1)):
    """A hand-made job trace around the recording's ``sm:phase`` annotation:
    a lease hold from 20 ms before it, and one span below the hold."""
    t0 = phase_stats["wall_ns"] / 1e9
    base = {"trace_id": phase_stats["trace_id"], "job_id": "job-1",
            "pid": 1, "tid": 1}
    return [
        {**base, "kind": "event", "name": "device_token_acquired",
         "span_id": "hold", "ts": t0 + hold[0], "attrs": {"devices": [0]}},
        {**base, "kind": "span", "name": "phase", "span_id": "ssss",
         "parent_id": "hold", "ts": t0 + span[0], "dur": span[1] - span[0]},
        {**base, "kind": "span", "name": "device_hold", "span_id": "hold",
         "parent_id": "attempt", "ts": t0 + hold[0] - 0.01,
         "dur": hold[1] - hold[0] + 0.01},
    ]


def test_wire_reader_finds_the_scope_path():
    paths = profiling.op_paths(SCOPED)["/device:TPU:0"]
    by_scope = {profiling.scope_of(p) for p in paths.values()}
    assert {"sm_extract", "sm_chaos", profiling.UNSCOPED} <= by_scope
    assert any("sm_chaos" in p and "sort" in name
               for name, p in paths.items())


def test_union_and_self_times():
    merged, total = profiling.union([(0, 2), (1, 3), (5, 6)])
    assert merged == [[0, 3], [5, 6]] and total == 4
    # a loop [0, 10] with two children and a grandchild, then a lone op
    events = [(0, 10, "while"), (1, 4, "a"), (2, 3, "a.inner"), (5, 9, "b"),
              (12, 13, "c")]
    assert profiling.self_times(events) == [3, 2, 1, 4, 1]
    assert sum(profiling.self_times(events)) == \
        profiling.union([(a, b) for a, b, _ in events])[1]


def test_ops_without_metadata_inherit_a_scope():
    """The compiler's own ops (no ``tf_op``: scope None) take the scope of
    the op they nest under, else of the op before them in the same program
    run; an op WITH metadata but no ``sm_`` scope stays ``unscoped``."""
    ops = [(0, 1, "copy-start", None),            # before any scoped op
           (1, 2, "delta scatter", "sm_extract"),
           (2, 4, "sort", None), (4, 9, "scatter fusion", None),
           (9, 20, "while", "sm_chaos"), (10, 12, "dus", None),
           (20, 21, "add", "unscoped"), (21, 22, "copy", None),
           (30, 31, "sort", None),                # a program with no scope
           (31, 32, "dot", "unscoped")]
    runs = [(0, 25, "jit_score"), (30, 33, "jit_stale")]
    assert profiling.inherit_scopes(ops, runs) == [
        "unscoped", "sm_extract", "sm_extract", "sm_extract", "sm_chaos",
        "sm_chaos", "unscoped", "unscoped", "unscoped", "unscoped"]


def test_scoped_trace_busy_scopes_and_clock(scoped):
    chips, annotations, capture_ns = scoped
    red = profiling.reduce_planes(chips, annotations, capture_ns, [], [])
    (chip,) = red["chips"]
    assert 0 < chip["busy_s"] <= red["capture"]["seconds"]
    # self times sum to the union: nothing counted twice, nothing lost
    assert sum(chip["by_scope_s"].values()) == pytest.approx(chip["busy_s"])
    assert {"sm_extract", "sm_chaos", "unscoped"} == set(chip["by_scope_s"])
    assert chip["by_scope_s"]["sm_chaos"] > chip["by_scope_s"]["sm_extract"]
    # the iota the compiler made for the sort has no metadata of its own
    assert 0 < chip["inherited_s"] < chip["by_scope_s"]["sm_extract"]
    assert red["by_scope_s"] == chip["by_scope_s"]
    assert red["programs"][0]["name"].startswith("jit_step")
    assert red["programs"][0]["runs"] == 3
    # the clock: the sm:phase annotation lands on the wall time it carries
    clock = red["clock"]
    assert clock["pairs"] == 2 and abs(clock["drift_us"]) < 1000
    _name, start_ns, _end, stats = _phase(annotations)
    mapped = (start_ns + clock["wall_ns"] - clock["profiler_ns"]) / 1e9
    assert abs(mapped - stats["wall_ns"] / 1e9) < 1e-3
    # no job trace: every gap is between jobs, nothing to inject
    assert {g["host"] for g in red["idle_gaps"]} == {"between_jobs"}
    assert red["inject"] == [] and red["jobs"] == []
    assert sum(red["idle_by_host_s"].values()) + chip["busy_s"] == \
        pytest.approx(red["capture"]["seconds"])


def test_gaps_attribute_to_the_span_that_covers_them(scoped):
    chips, annotations, capture_ns = scoped
    stats = _phase(annotations)[3]
    red = profiling.reduce_planes(
        chips, annotations, capture_ns,
        [("job.jsonl", _job_trace(stats))], [])
    (job,) = red["jobs"]
    assert job["job"] == "job-1" and job["chips"] == [0] and job["whole"]
    hosts = red["idle_by_host_s"]
    # three programs 50 ms apart: the 100 ms span covers two of the sleeps,
    # the rest of the hold lies under no span of ours, and the capture's
    # two ends under no job at all
    assert hosts["phase"] == pytest.approx(0.1, abs=0.005)
    assert hosts["device_hold"] > 0.1 and hosts["between_jobs"] > 0.1
    assert red["idle_in_holds_s"] == pytest.approx(
        hosts["phase"] + hosts["device_hold"])
    longest = red["idle_gaps"][0]
    assert longest["host"] == "device_hold" and longest["job"] == "job-1"
    # the sm: annotation against its job-trace span, through the clock
    assert red["clock"]["annotations"]["n"] == 1
    (inj,) = red["inject"]
    assert inj["parent_id"] == "hold" and inj["file"] == "job.jsonl"
    scopes = {r["attrs"]["scope"]: r for r in inj["records"]
              if r["name"] == "device_scope"}
    assert set(scopes) == {"sm_extract", "sm_chaos", "unscoped"}
    assert all(r["attrs"]["whole"] and r["attrs"]["chip"] == 0
               for r in scopes.values())
    (busy,) = [r for r in inj["records"] if r["name"] == "device_busy"]
    assert busy["attrs"]["busy_s"] == pytest.approx(
        sum(r["attrs"]["device_s"] for r in scopes.values()))
    assert busy["attrs"]["hold_s"] == pytest.approx(0.22)
    idle = [r for r in inj["records"] if r["name"] == "device_idle"]
    assert 0 < len(idle) <= profiling.MAX_JOB_GAPS
    assert {r["attrs"]["host"] for r in idle} == {"phase", "device_hold"}
    assert {r["attrs"]["host_span_id"] for r in idle} == {"ssss", "hold"}
    # every device span lies inside the hold it is parented under
    for r in inj["records"]:
        assert busy["ts"] - 1e-6 <= r["ts"]
        assert r["ts"] + r["dur"] <= busy["ts"] + busy["dur"] + 1e-6


def test_span_still_open_at_the_end_of_the_capture(scoped):
    chips, annotations, capture_ns = scoped
    stats = _phase(annotations)[3]
    records = _job_trace(stats)[:1]          # lease granted, nothing closed
    t0 = stats["wall_ns"] / 1e9
    still_open = [{"trace_id": stats["trace_id"], "span_id": "ssss",
                   "parent_id": "hold", "name": "store_write_images",
                   "ts": t0 + 0.15}]
    red = profiling.reduce_planes(chips, annotations, capture_ns,
                                  [("job.jsonl", records)], still_open)
    (job,) = red["jobs"]
    assert not job["whole"]                  # the hold is cut at the end
    assert red["idle_by_host_s"]["store_write_images"] > 0.05
    (busy,) = [r for r in red["inject"][0]["records"]
               if r["name"] == "device_busy"]
    assert not busy["attrs"]["whole"]
    end = red["capture"]["start"] + red["capture"]["seconds"]
    assert busy["ts"] + busy["dur"] == pytest.approx(end)


def test_innermost_span_wins_the_timeline():
    hold = {"span_id": "h", "spans": [
        (0.0, 10.0, 1, "store_results", "a"),
        (2.0, 6.0, 2, "store_write_images", "b"),
        (8.0, None, 2, "store_tables", "c")]}       # still open
    assert profiling._timeline(hold, -1.0, 12.0) == [
        (-1.0, 0.0, "device_hold", "h"), (0.0, 2.0, "store_results", "a"),
        (2.0, 6.0, "store_write_images", "b"),
        (6.0, 8.0, "store_results", "a"), (8.0, 12.0, "store_tables", "c")]


def test_trace_without_scopes_or_host_plane_reduces():
    """PR 23's recording: host tracer off, no named scope, no sm_clock."""
    red = profiling.reduce_file(SMALL)
    assert red["clock"] is None and red["jobs"] == []
    (chip,) = red["chips"]
    assert set(chip["by_scope_s"]) == {"unscoped"}
    assert chip["by_scope_s"]["unscoped"] == pytest.approx(chip["busy_s"])
    assert 0 < chip["busy_s"] < red["capture"]["seconds"]
    assert red["capture"]["start"] == 0.0


def test_measured_roofline_is_not_clamped():
    assert profiling.measured_roofline(2.0, 1.0) == 2.0
    assert profiling.measured_roofline(0.0, 1.0) == 0.0


def test_cpu_capture_has_no_chips_and_injects_nothing(tmp_path):
    """A real capture on XLA-CPU: host planes only, no ``/device:TPU``."""

    class _Sched:
        def jobs(self):
            return []

    class _Svc:
        pass

    svc = _Svc()
    svc.metrics = MetricsRegistry()
    svc.scheduler = _Sched()
    svc.trace_dir = str(tmp_path / "traces")
    svc.sm_config = SMConfig.from_dict({"work_dir": str(tmp_path / "work")})
    ctx = tracing.new_trace(job_id="j", trace_dir=svc.trace_dir)
    prof = DeviceProfiler(svc, ProfileConfig())
    with tracing.span("before", ctx=ctx):
        pass
    import threading

    out = {}
    t = threading.Thread(target=lambda: out.update(r=prof.run(0.3)))
    t.start()
    deadline = 50
    while tracing._capture is None and deadline:
        threading.Event().wait(0.01)
        deadline -= 1
    with tracing.span("during", ctx=ctx):
        pass
    t.join(timeout=120)
    code, body = out["r"]
    assert code == 200, body
    assert body["chips"] == [] and body["by_scope_s"] == {}
    assert body["injected_spans"] == 0 and body["jobs"] == []
    assert body["trace_file"].endswith(".xplane.pb")
    assert body["clock"]["pairs"] == 2
    # the span opened under the capture was annotated, and the annotation
    # maps back onto its job-trace record through the clock
    ann = body["clock"]["annotations"]
    assert ann["n"] == ann["matched"] == 1 and ann["max_err_us"] < 1000
    assert tracing._capture is None
    assert "attribution" not in body


def test_trace_files_allow_for_the_coarse_mtime_clock(tmp_path):
    """A record written within a tick after the capture began can carry an
    mtime a few ms BEFORE ``t0_wall`` (the kernel stamps files from its
    coarse clock): such a file is still the capture's, a file last written
    seconds before it is not, a running job's always is."""
    import os

    class _Sched:
        def jobs(self):
            return [{"trace_id": "running", "state": "running"}]

    class _Svc:
        metrics = MetricsRegistry()
        scheduler = _Sched()
        trace_dir = str(tmp_path)
        sm_config = SMConfig.from_dict({"work_dir": str(tmp_path / "work")})

    t0 = 1_700_000_000.0
    for name, mtime in (("tick", t0 - 0.006), ("old", t0 - 5.0),
                        ("running", t0 - 500.0), ("new", t0 + 1.0)):
        path = tmp_path / f"{name}.jsonl"
        path.write_text("")
        os.utime(path, (mtime, mtime))
    got = DeviceProfiler(_Svc(), ProfileConfig())._trace_files({"t0_wall": t0})
    assert sorted(p.stem for p in got) == ["new", "running", "tick"]
