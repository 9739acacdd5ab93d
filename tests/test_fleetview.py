"""Fleet observability plane (ISSUE 20): exposition parse/merge semantics,
fleet SLO bit-equality with a single tracker observing the union, the
partial-view-with-evidence contract when a peer is unreachable, and the
device profiler's request-validation paths.

The 3-replica live-fleet behavior (mid-scrape SIGKILL, profile capture
during a sharded job) is gated end-to-end by scripts/fleet_smoke.py; these
tests pin the pure logic those gates are built on.
"""

from __future__ import annotations

import random
import socket
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from sm_distributed_tpu.service.fleetview import (  # noqa: E402
    DeviceProfiler,
    FleetView,
    merge_expositions,
    parse_exposition,
    slo_report_from_registry,
)
from sm_distributed_tpu.service.leases import ReplicaRegistry  # noqa: E402
from sm_distributed_tpu.service.metrics import (  # noqa: E402
    MetricsRegistry,
)
from sm_distributed_tpu.service.telemetry import SLOTracker  # noqa: E402
from sm_distributed_tpu.utils.config import (  # noqa: E402
    FleetViewConfig,
    ProfileConfig,
    TelemetryConfig,
)


def _dyadic(rng: random.Random) -> float:
    # multiples of 1/1024 add exactly in binary floating point, so summed
    # histogram `sum` fields are bit-equal however the adds are grouped
    return rng.randrange(0, 8192) / 1024.0


# ------------------------------------------------------------------ parsing
def test_parse_exposition_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("sm_x_jobs_total", "jobs", ("state",))
    c.labels(state="done").inc(3)
    c.labels(state="failed").inc(1)
    reg.gauge("sm_x_depth", "queue depth").set(7.5)
    h = reg.histogram("sm_x_wait_seconds", "waits")
    h.observe(0.3)
    h.observe(2.0)

    fams = parse_exposition(reg.expose())
    assert fams["sm_x_jobs_total"]["kind"] == "counter"
    assert fams["sm_x_depth"]["kind"] == "gauge"
    assert fams["sm_x_wait_seconds"]["kind"] == "histogram"
    counter_vals = {tuple(sorted(lab.items())): v
                    for suffix, lab, v in fams["sm_x_jobs_total"]["samples"]
                    if suffix == ""}
    assert counter_vals[(("state", "done"),)] == 3.0
    assert counter_vals[(("state", "failed"),)] == 1.0
    # histogram series resolve to their suffixes, +Inf bucket == count
    suffixes = {s for s, _, _ in fams["sm_x_wait_seconds"]["samples"]}
    assert suffixes == {"_bucket", "_sum", "_count"}
    inf = [v for s, lab, v in fams["sm_x_wait_seconds"]["samples"]
           if s == "_bucket" and lab.get("le") == "+Inf"]
    assert inf == [2.0]


def test_parse_exposition_skips_garbage_lines():
    text = ("# TYPE sm_ok_total counter\n"
            "sm_ok_total 4\n"
            "this line is not exposition at all {{{\n"
            "sm_no_value{label=\"x\"}\n")
    fams = parse_exposition(text)
    assert fams["sm_ok_total"]["samples"] == [("", {}, 4.0)]


# ------------------------------------------------------------------ merging
def test_merge_counters_summed_gauges_relabelled():
    scrapes = {}
    for rid, jobs, depth in (("r0", 5, 2.0), ("r1", 7, 9.0)):
        reg = MetricsRegistry()
        reg.counter("sm_y_jobs_total", "jobs").inc(jobs)
        reg.gauge("sm_y_depth", "depth").set(depth)
        scrapes[rid] = reg.expose()

    merged = merge_expositions(scrapes)
    text = merged.expose()
    # counters: one fleet total
    assert "sm_y_jobs_total 12" in text
    # gauges: one series per replica, re-labelled — a fleet-summed gauge
    # would be meaningless (occupancy, depth are per-replica states)
    assert 'sm_y_depth{replica="r0"} 2' in text
    assert 'sm_y_depth{replica="r1"} 9' in text


def test_merge_histograms_bit_equal_with_observing_union():
    rng = random.Random(20)
    per_replica = {f"r{i}": [_dyadic(rng) for _ in range(200)]
                   for i in range(3)}

    scrapes = {}
    for rid, samples in per_replica.items():
        reg = MetricsRegistry()
        h = reg.histogram("sm_z_lat_seconds", "lat")
        for s in samples:
            h.observe(s)
        scrapes[rid] = reg.expose()

    union = MetricsRegistry()
    hu = union.histogram("sm_z_lat_seconds", "lat")
    for samples in per_replica.values():
        for s in samples:
            hu.observe(s)

    merged = merge_expositions(scrapes)
    hm = merged._metrics["sm_z_lat_seconds"]
    for thr in (0.1, 1.0, 5.0, 1e9):
        assert hm.fraction_below(thr) == hu.fraction_below(thr)
    # the merged exposition's histogram series are identical too
    def series(reg):
        return sorted(line for line in reg.expose().splitlines()
                      if line.startswith("sm_z_lat_seconds"))
    assert series(merged) == series(union)


# ---------------------------------------------------------------- fleet SLO
def test_fleet_slo_bit_equal_with_single_tracker_on_union():
    """slo_report_from_registry over merged scrapes == SLOTracker.report of
    one tracker that observed every replica's samples — the /fleet/slo
    bit-equality contract the smoke gate re-checks live."""
    rng = random.Random(21)
    cfg = TelemetryConfig()

    union_reg = MetricsRegistry()
    union_tracker = SLOTracker(union_reg, cfg)

    scrapes = {}
    for rid in ("r0", "r1", "r2"):
        reg = MetricsRegistry()
        tracker = SLOTracker(reg, cfg)
        for _ in range(150):
            v = _dyadic(rng)
            tracker.h_queue_wait.observe(v)
            union_tracker.h_queue_wait.observe(v)
        for _ in range(80):
            v = _dyadic(rng)
            tracker.h_e2e.observe(v)
            union_tracker.h_e2e.observe(v)
        for _ in range(40):
            v = _dyadic(rng)
            tracker.h_read.observe(v)
            union_tracker.h_read.observe(v)
        scrapes[rid] = reg.expose()
    # first_annotation / stream_partial stay empty: count==0 SLIs must
    # report attainment None on both sides, not crash either

    merged = merge_expositions(scrapes)
    fleet = slo_report_from_registry(merged, cfg)
    single = union_tracker.report()
    assert fleet == single
    assert fleet["slos"]["first_annotation"]["attainment"] is None
    assert fleet["slos"]["queue_wait"]["count"] == 450


# ------------------------------------------- partial view, never an error
def _fake_service(tmp_path, rid="r0"):
    reg = MetricsRegistry()
    reg.counter("sm_fake_jobs_total", "jobs").inc(2)
    registry = ReplicaRegistry(tmp_path, rid, stale_after_s=8.0)
    registry.register()
    sched = types.SimpleNamespace(
        replica_id=rid, registry=registry, _evicted_hosts=set(),
        jobs=lambda: [])
    svc = types.SimpleNamespace(
        metrics=reg, scheduler=sched,
        sm_config=types.SimpleNamespace(
            telemetry=TelemetryConfig(), work_dir=str(tmp_path)),
        trace_dir=None)
    return svc


def _closed_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_fleetview_partial_view_with_dead_peer(tmp_path):
    """An alive-looking peer whose admin endpoint is gone (killed between
    heartbeats) yields a 200 partial view with per-replica evidence and a
    bumped sm_fleetview_scrape_errors_total — never a 500."""
    svc = _fake_service(tmp_path)
    # fake peer: fresh heartbeat (alive=True) but its admin port is closed
    peer = ReplicaRegistry(tmp_path, "r_dead", stale_after_s=8.0)
    peer.register()
    peer.beat({"admin": f"127.0.0.1:{_closed_port()}", "host": "host-b"})
    # and one peer that never gossiped an admin address at all
    legacy = ReplicaRegistry(tmp_path, "r_legacy", stale_after_s=8.0)
    legacy.register()

    # one shared round across the endpoint calls below (cache_ttl_s), so
    # the evidence counter's value stays the single failed scrape's
    fv = FleetView(svc, FleetViewConfig(scrape_timeout_s=0.5,
                                        cache_ttl_s=60.0))
    rnd = fv.collect(force=True)

    assert rnd.partial
    assert set(rnd.scrape_errors) == {"r_dead", "r_legacy"}
    assert "no admin address gossiped" in rnd.scrape_errors["r_legacy"]
    assert rnd.replicas["r_dead"]["alive"]
    assert rnd.replicas["r_dead"]["scraped"] is False
    assert rnd.replicas["r0"]["scraped"] is True

    code, slo = fv.slo()
    assert code == 200
    assert slo["fleet"]["partial"] is True
    assert slo["fleet"]["replicas_merged"] == 1
    assert slo["fleet"]["replicas_known"] == 3
    assert "r_dead" in slo["fleet"]["scrape_errors"]

    text = fv.metrics_text()
    assert "# fleetview: merged 3 replica(s), partial=true" in text
    assert "# fleetview: scrape of r_dead failed:" in text
    # local families still merged (self-scrape cannot fail)
    assert "sm_fake_jobs_total 2" in text
    # evidence counter carries the peer label
    assert 'sm_fleetview_scrape_errors_total{replica="r_dead"} 1' \
        in svc.metrics.expose()

    code, status = fv.status()
    assert code == 200
    assert status["partial"] is True
    assert status["alive"] == 3
    assert status["hosts"].get("host-b") == ["r_dead"]


def test_fleetview_cache_reuses_round(tmp_path):
    svc = _fake_service(tmp_path)
    fv = FleetView(svc, FleetViewConfig(cache_ttl_s=60.0))
    r1 = fv.collect()
    r2 = fv.collect()
    assert r2 is r1
    assert fv.collect(force=True) is not r1


# ------------------------------------------------------------ profiler API
def test_profiler_validation_paths(tmp_path):
    svc = _fake_service(tmp_path)

    disabled = DeviceProfiler(svc, ProfileConfig(enabled=False))
    code, body = disabled.run(1.0)
    assert code == 404 and body["reason"] == "not_found"

    prof = DeviceProfiler(svc, ProfileConfig(max_seconds=5.0))
    code, body = prof.run(-1.0)
    assert code == 400 and body["reason"] == "invalid_request"
    code, body = prof.run(0)
    assert code == 400

    # single-flight: a held capture lock means 409, never a queued stall
    assert prof._busy.acquire(blocking=False)
    try:
        code, body = prof.run(0.1)
        assert code == 409 and body["reason"] == "busy"
    finally:
        prof._busy.release()


@pytest.mark.slow
def test_profiler_capture_smoke(tmp_path):
    """A real (idle) capture returns 200 with the file the profiler wrote
    and its reduction — never an exception."""
    svc = _fake_service(tmp_path)
    prof = DeviceProfiler(svc, ProfileConfig(default_seconds=0.2))
    code, body = prof.run(0.2)
    assert code == 200
    assert body["seconds"] == 0.2
    assert body["trace_file"].endswith(".xplane.pb")
    assert body["clock"]["pairs"] == 2
    for key in ("chips", "by_scope_s", "programs", "idle_gaps", "jobs",
                "injected_spans"):
        assert key in body


def test_profiler_injects_device_spans_under_device_hold(tmp_path):
    """ISSUE 24: every job whose lease hold overlaps the capture gets
    ``device_scope`` / ``device_busy`` / ``device_idle`` spans parented
    under its ``device_hold`` — ``whole`` for a hold that began after the
    capture did and ended inside it, not for one the capture's edge cut."""
    from sm_distributed_tpu.analysis import profiling
    from sm_distributed_tpu.utils import tracing

    svc = _fake_service(tmp_path)
    svc.trace_dir = str(tmp_path / "traces")
    t0 = 1_790_000_000.0                       # wall time of profiler t=0
    ns = 1e9

    def clock(at_s):
        return ("sm_clock", at_s * ns, at_s * ns + 2000.0,
                {"wall_ns": int((t0 + at_s) * ns)})

    def op(a, b, name, scope):
        return (a * ns, b * ns, name, scope)

    # one chip, a 10 s capture; job "in" holds [2, 5], job "cut" from 8 on
    chips = {0: {"modules": [(2.5 * ns, 2.9 * ns, "jit_score(1)")], "ops": [
        op(2.5, 2.7, "%fusion.1 = f32[8] fusion()", "sm_extract"),
        op(2.7, 2.9, "%while.2 = f32[8] while()", "sm_chaos"),
        op(2.75, 2.85, "%custom-call.3 = f32[8] custom-call()", "sm_chaos"),
        op(4.0, 4.1, "%fusion.4 = f32[8] fusion()", "sm_store_extract"),
        op(8.5, 8.6, "%copy.5 = f32[8] copy()", "unscoped")]}}
    annotations = [clock(0.0), clock(10.0)]

    files = {}
    for job, lease_at, hold_end in (("in", 2.0, 5.0), ("cut", 8.0, None)):
        ctx = tracing.new_trace(job_id=job, trace_dir=svc.trace_dir)
        hold = ctx.child()
        rec = {"kind": "event", "trace_id": ctx.trace_id,
               "span_id": hold.span_id, "name": "device_token_acquired",
               "ts": t0 + lease_at, "pid": 1, "tid": 1, "job_id": job,
               "attrs": {"devices": [0]}}
        tracing.emit_records([rec], ctx)
        tracing.emit_span(hold, "store_results", ts=t0 + lease_at + 1.0,
                          dur=1.5, span_id="store" + job,
                          parent_id=hold.span_id)
        if hold_end is not None:
            tracing.emit_span(hold, "device_hold", ts=t0 + lease_at - 0.5,
                              dur=hold_end - lease_at + 0.5,
                              span_id=hold.span_id, parent_id=ctx.span_id)
        files[job] = (tracing.trace_path(svc.trace_dir, ctx.trace_id),
                      hold.span_id)
    traces = [(str(f), tracing.read_trace(f)) for f, _ in files.values()]
    reduced = profiling.reduce_planes(chips, annotations, 10.0 * ns,
                                      traces, [])
    assert [(j["job"], j["whole"]) for j in reduced["jobs"]] == \
        [("in", True), ("cut", False)]
    # store_results of "in" is [3, 4.5] less the 0.1 s op, of "cut" [9, 10.5]
    # cut at the capture's end; no job holds the chip in [0, 2] and [5, 8]
    assert reduced["idle_by_host_s"]["store_results"] == \
        pytest.approx(1.4 + 1.0, abs=1e-4)
    assert reduced["idle_by_host_s"]["between_jobs"] == \
        pytest.approx(5.0, abs=1e-4)

    prof = DeviceProfiler(svc, ProfileConfig())
    n = prof._inject_device_spans(reduced["inject"])
    tracing.close_files()
    got = {job: tracing.read_trace(f) for job, (f, _) in files.items()}
    assert not tracing.validate_records(got["in"] + got["cut"])
    assert n == sum(r["name"] in profiling.INJECTED
                    for recs in got.values() for r in recs)
    for job, whole in (("in", True), ("cut", False)):
        dev = [r for r in got[job] if r["name"] in profiling.INJECTED]
        assert dev and all(r["kind"] == "span" and r["job_id"] == job
                           and r["parent_id"] == files[job][1] for r in dev)
        assert all(r["attrs"]["whole"] is whole for r in dev
                   if r["name"] != "device_idle")
    scopes = {r["attrs"]["scope"]: r["attrs"] for r in got["in"]
              if r["name"] == "device_scope"}
    # self time: the custom call's 0.1 s comes out of the while loop's 0.2
    assert scopes["sm_chaos"]["device_s"] == pytest.approx(0.2)
    assert scopes["sm_chaos"]["n_ops"] == 2
    assert scopes["sm_extract"]["device_s"] == pytest.approx(0.2)
    assert scopes["sm_store_extract"]["device_s"] == pytest.approx(0.1)
    (busy,) = [r for r in got["in"] if r["name"] == "device_busy"]
    assert busy["attrs"]["busy_s"] == pytest.approx(0.5)
    assert busy["attrs"]["hold_s"] == pytest.approx(3.0)
    assert busy["ts"] == pytest.approx(t0 + 2.0)
    idle = [r for r in got["in"] if r["name"] == "device_idle"]
    assert max(idle, key=lambda r: r["dur"])["attrs"] == {
        "chip": 0, "host": "store_results", "host_span_id": "storein"}
    (cut_busy,) = [r for r in got["cut"] if r["name"] == "device_busy"]
    assert cut_busy["attrs"]["hold_s"] == pytest.approx(2.0, abs=1e-4)
    # a second capture over the same job does not read the first one's
    # device spans as program spans
    again = profiling.reduce_planes(
        chips, annotations, 10.0 * ns,
        [(str(f), tracing.read_trace(f)) for f, _ in files.values()], [])
    assert again["idle_by_host_s"] == reduced["idle_by_host_s"]


def test_each_chip_of_a_pool_gets_its_own_jobs_device_spans():
    """ISSUE 26: four one-chip jobs held on chips 0-3 at once (the pool of a
    2x2 host).  Each job's ``device_busy`` / ``device_scope`` spans carry its
    own chip and that chip's device time - chips 1-3 as chip 0."""
    from sm_distributed_tpu.analysis import profiling

    t0, ns = 1_790_000_000.0, 1e9
    chips, traces = {}, []
    for chip in range(4):
        # chip k is busy 0.1 x (k + 1) s inside its job's hold [2, 5]
        dur = 0.1 * (chip + 1)
        chips[chip] = {
            "modules": [(3.0 * ns, (3.0 + 2 * dur) * ns, "jit_score(1)")],
            "ops": [(3.0 * ns, (3.0 + dur) * ns, "%fusion.1 = f32[8] fusion()",
                     "sm_extract"),
                    ((3.0 + dur) * ns, (3.0 + 2 * dur) * ns,
                     "%while.2 = f32[8] while()", "sm_chaos")]}
        base = {"trace_id": f"t{chip}", "job_id": f"job{chip}", "pid": 1,
                "tid": chip}
        traces.append((f"job{chip}.jsonl", [
            {**base, "kind": "event", "name": "device_token_acquired",
             "span_id": f"hold{chip}", "ts": t0 + 2.0,
             "attrs": {"devices": [chip]}},
            {**base, "kind": "span", "name": "device_hold",
             "span_id": f"hold{chip}", "parent_id": "attempt",
             "ts": t0 + 1.0, "dur": 4.0}]))
    annotations = [("sm_clock", at * ns, at * ns + 2000.0,
                    {"wall_ns": int((t0 + at) * ns)}) for at in (0.0, 10.0)]
    red = profiling.reduce_planes(chips, annotations, 10.0 * ns, traces, [])
    assert sorted((j["job"], j["chips"], j["whole"]) for j in red["jobs"]) \
        == [(f"job{c}", [c], True) for c in range(4)]
    assert [(c["chip"], c["n_ops"]) for c in red["chips"]] == \
        [(c, 2) for c in range(4)]
    by_job = {h["job"]: h for h in red["inject"]}
    assert set(by_job) == {f"job{c}" for c in range(4)}   # one hold, one chip
    for chip in range(4):
        inj = by_job[f"job{chip}"]
        assert inj["parent_id"] == f"hold{chip}"
        assert inj["file"] == f"job{chip}.jsonl"
        (busy,) = [r for r in inj["records"] if r["name"] == "device_busy"]
        assert busy["attrs"]["chip"] == chip and busy["attrs"]["whole"]
        assert busy["attrs"]["busy_s"] == pytest.approx(0.2 * (chip + 1),
                                                        abs=1e-5)
        assert busy["attrs"]["hold_s"] == pytest.approx(3.0)
        scopes = {r["attrs"]["scope"]: r["attrs"] for r in inj["records"]
                  if r["name"] == "device_scope"}
        assert set(scopes) == {"sm_extract", "sm_chaos"}
        assert all(a["chip"] == chip and a["device_s"] ==
                   pytest.approx(0.1 * (chip + 1), abs=1e-5)
                   for a in scopes.values())
