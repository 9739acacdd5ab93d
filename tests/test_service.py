"""Service-layer tests: scheduler, failure policy, metrics, admin API.

Everything runs on CPU with FAKE job callbacks (no JAX, no search) — the
service contract (admission, concurrency, retry/backoff, dead-letter,
heartbeats, drain, exposition) is independent of what the jobs compute.
"""

import json
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from sm_distributed_tpu.engine.daemon import (
    QueuePublisher,
    heartbeat_path,
)
from sm_distributed_tpu.engine.residency import DatasetResidency
from sm_distributed_tpu.service import AnnotationService, JobScheduler, RetryPolicy
from sm_distributed_tpu.service.metrics import MetricsRegistry
from sm_distributed_tpu.utils.config import ServiceConfig, SMConfig
from sm_distributed_tpu.utils.logger import phase_timer


def _fast_cfg(**kw) -> ServiceConfig:
    base = dict(workers=3, poll_interval_s=0.02, job_timeout_s=5.0,
                max_attempts=3, backoff_base_s=0.05, backoff_max_s=0.5,
                backoff_jitter=0.0, heartbeat_interval_s=0.05,
                stale_after_s=0.5, drain_timeout_s=10.0, http_port=0)
    base.update(kw)
    return ServiceConfig(**base)


def _sm(tmp_path, **service_kw) -> SMConfig:
    import dataclasses

    return dataclasses.replace(
        SMConfig.from_dict({"work_dir": str(tmp_path / "work")}),
        service=_fast_cfg(**service_kw))


class FakeJobs:
    """Callback recording per-message attempt history; behavior is driven
    by message fields: ``fail_times`` (raise on the first N attempts),
    ``sleep_s`` (hold the worker), plus the shared residency exercised via
    ``phase_timer`` so the metric plumbing runs exactly as real jobs do."""

    def __init__(self, residency=None):
        self.residency = residency
        self.attempts: dict[str, list[float]] = {}
        self.device_tokens = []
        self._lock = threading.Lock()

    def __call__(self, msg, ctx=None):
        ds = msg["ds_id"]
        with self._lock:
            self.attempts.setdefault(ds, []).append(time.time())
            n_attempt = len(self.attempts[ds])
            if ctx is not None:
                self.device_tokens.append(ctx.device_token)
        with phase_timer("stage_input"):
            time.sleep(float(msg.get("sleep_s", 0.0)))
        if self.residency is not None:
            with phase_timer("read_dataset"):
                self.residency.dataset(("ds", ds), lambda: object())
        if n_attempt <= int(msg.get("fail_times", 0)):
            raise RuntimeError(f"boom on attempt {n_attempt} of {ds}")
        with phase_timer("search"):
            if ctx is not None and ctx.device_token is not None:
                with ctx.device_token:
                    pass


def test_service_integration_scheduler_retry_metrics_shutdown(tmp_path):
    """ISSUE acceptance: >= 8 jobs (one raising, one exceeding its timeout)
    through the scheduler — terminal states, retry-with-backoff then
    dead-letter, /metrics histograms + residency counters, and a
    SIGTERM-equivalent shutdown leaving nothing in running/."""
    residency = DatasetResidency(max_datasets=8, max_backends=8)
    jobs = FakeJobs(residency)
    service = AnnotationService(
        tmp_path / "q", jobs, sm_config=_sm(tmp_path),
        residency=residency, with_api=False)
    pub = service.publisher

    for i in range(6):                       # 6 plain jobs (2 repeat ds keys)
        pub.publish({"ds_id": f"ok{i % 4}", "input_path": "/in",
                     "msg_id": f"ok{i}"})
    # one job that raises on every attempt at bounded attempts=2
    pub.publish({"ds_id": "always_fails", "input_path": "/in",
                 "msg_id": "always_fails", "fail_times": 99,
                 "service": {"max_attempts": 2}})
    # one job that raises once, then succeeds (retry with backoff)
    pub.publish({"ds_id": "flaky", "input_path": "/in", "msg_id": "flaky",
                 "fail_times": 1})
    # one job that exceeds its per-job timeout (single attempt → dead-letter)
    pub.publish({"ds_id": "too_slow", "input_path": "/in", "msg_id": "slow",
                 "sleep_s": 3.0,
                 "service": {"timeout_s": 0.3, "max_attempts": 1}})

    service.start()
    assert service.scheduler.wait_for_terminal(9, timeout_s=30.0), \
        service.scheduler.stats()

    root = tmp_path / "q" / "sm_annotate"
    done = {p.stem for p in root.glob("done/*.json")}
    failed = {p.stem for p in root.glob("failed/*.json")}
    assert done == {f"ok{i}" for i in range(6)} | {"flaky"}
    assert failed == {"always_fails", "slow"}

    # retried with backoff: two attempts spaced >= base_s, then a third
    # never happened for the bounded job; flaky's retry also >= base_s
    assert len(jobs.attempts["always_fails"]) == 2
    assert len(jobs.attempts["flaky"]) == 2
    base = service.sm_config.service.backoff_base_s
    for ds in ("always_fails", "flaky"):
        t1, t2 = jobs.attempts[ds]
        assert t2 - t1 >= base, f"{ds} retried before its backoff elapsed"

    # dead-letter evidence: traceback + attempt count recorded
    dl = json.loads((root / "failed" / "always_fails.json").read_text())
    assert dl["attempts"] == 2
    assert "RuntimeError" in dl["traceback"] and "boom" in dl["error"]
    slow = json.loads((root / "failed" / "slow.json").read_text())
    assert "timeout" in slow["error"]

    # /metrics: per-phase histograms + residency hit/miss counters
    text = service.metrics.expose()
    assert 'sm_phase_seconds_bucket{le="+Inf",phase="stage_input"}' in text
    assert 'sm_phase_seconds_count{phase="search"}' in text
    assert 'sm_residency_hits_total{cache="dataset"}' in text
    # 6 distinct ds keys (ok0-3, flaky, always_fails; the timed-out job's
    # abandoned attempt may add a 7th later) → 4 hits from the ok0/ok1
    # repeats and the flaky/always_fails second attempts
    stats = residency.stats
    assert stats["dataset_hits"] == 4 and stats["dataset_misses"] >= 6
    assert 'sm_jobs_total{state="done"} 7' in text
    assert 'sm_jobs_total{state="failed"} 2' in text
    assert "sm_job_retries_total 2" in text
    assert "sm_job_timeouts_total 1" in text
    assert "sm_job_duration_seconds_count" in text

    # SIGTERM-equivalent: drain leaves nothing stranded in running/
    assert service.shutdown()
    assert list(root.glob("running/*")) == [], "message stranded in running/"
    # all 9 records reached terminal states
    states = {j["msg_id"]: j["state"] for j in service.scheduler.jobs()}
    assert len(states) == 9
    assert all(s in ("done", "failed") for s in states.values()), states


def test_scheduler_concurrency_and_device_token_serialization(tmp_path):
    """Workers overlap CPU phases; on a 1-chip pool (the old single-token
    configuration, pinned explicitly now that the pool auto-sizes to the
    visible devices) device holders still serialize."""
    active = []
    peak = [0]
    token_overlap = [0]
    lock = threading.Lock()

    def cb(msg, ctx):
        with lock:
            active.append(msg["ds_id"])
            peak[0] = max(peak[0], len(active))
        time.sleep(0.15)             # CPU phase — overlaps across workers
        with ctx.device_token:       # device phase — must serialize
            with lock:
                token_overlap[0] += 1
                assert token_overlap[0] == 1, "two jobs inside the TPU token"
            time.sleep(0.03)
            with lock:
                token_overlap[0] -= 1
        with lock:
            active.remove(msg["ds_id"])

    sched = JobScheduler(tmp_path / "q", cb,
                         config=_fast_cfg(workers=3, device_pool_size=1))
    pub = QueuePublisher(tmp_path / "q")
    for i in range(6):
        pub.publish({"ds_id": f"j{i}", "input_path": "/in", "msg_id": f"j{i}"})
    sched.start()
    assert sched.wait_for_terminal(6, timeout_s=20.0)
    assert sched.shutdown()
    assert peak[0] >= 2, "workers never overlapped"


def test_scheduler_priority_and_tenant_fairness(tmp_path):
    """Priority classes run first; within a class, the tenant with fewer
    in-flight jobs is preferred over a burst tenant."""
    order = []
    lock = threading.Lock()

    def cb(msg, ctx=None):
        with lock:
            order.append(msg["msg_id"])
        time.sleep(0.02)

    pub = QueuePublisher(tmp_path / "q")
    # burst tenant floods 4 normal jobs, then tenant B adds one normal and
    # one high; publish everything BEFORE the scheduler starts
    for i in range(4):
        pub.publish({"ds_id": f"a{i}", "input_path": "/in", "msg_id": f"a{i}",
                     "tenant": "burst"})
    pub.publish({"ds_id": "b0", "input_path": "/in", "msg_id": "b_norm",
                 "tenant": "B"})
    pub.publish({"ds_id": "b1", "input_path": "/in", "msg_id": "b_high",
                 "tenant": "B", "priority": "high"})
    pub.publish({"ds_id": "c", "input_path": "/in", "msg_id": "c_low",
                 "priority": "low"})

    sched = JobScheduler(tmp_path / "q", cb, config=_fast_cfg(workers=1))
    sched.start()
    assert sched.wait_for_terminal(7, timeout_s=20.0)
    assert sched.shutdown()
    assert order[0] == "b_high", f"high priority did not run first: {order}"
    assert order[-1] == "c_low", f"low priority did not run last: {order}"
    # fairness: tenant B's normal job is not stuck behind the whole burst —
    # it runs within the first three normal-class slots
    assert order.index("b_norm") <= 3, order


def test_scheduler_poison_message_dead_letters(tmp_path):
    def cb(msg, ctx=None):
        pass

    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "ok", "input_path": "/in", "msg_id": "ok"})
    (tmp_path / "q" / "sm_annotate" / "pending" / "poison.json").write_text("{nope")
    sched = JobScheduler(tmp_path / "q", cb, config=_fast_cfg(workers=1))
    sched.start()
    assert sched.wait_for_terminal(2, timeout_s=10.0)
    assert sched.shutdown()
    root = tmp_path / "q" / "sm_annotate"
    dl = json.loads((root / "failed" / "poison.json").read_text())
    assert "poison" in dl["error"] and "{nope" in dl["raw"]
    assert {p.stem for p in root.glob("done/*.json")} == {"ok"}


def test_scheduler_heartbeats_live_during_job(tmp_path):
    saw_hb = []

    def cb(msg, ctx=None):
        p = tmp_path / "q" / "sm_annotate" / "running" / f"{msg['msg_id']}.json"
        deadline = time.time() + 2.0
        while time.time() < deadline and not heartbeat_path(p).exists():
            time.sleep(0.01)
        saw_hb.append(heartbeat_path(p).exists())
        time.sleep(0.15)             # > heartbeat interval → refreshed

    sched = JobScheduler(tmp_path / "q", cb,
                         config=_fast_cfg(workers=1, heartbeat_interval_s=0.05))
    QueuePublisher(tmp_path / "q").publish(
        {"ds_id": "hb", "input_path": "/in", "msg_id": "hb"})
    sched.start()
    assert sched.wait_for_terminal(1, timeout_s=10.0)
    assert sched.shutdown()
    assert saw_hb == [True]
    # terminal move cleaned the heartbeat up
    root = tmp_path / "q" / "sm_annotate"
    assert not list(root.glob("running/*")), "running/ not empty"


def test_shutdown_requeues_claimed_but_unstarted(tmp_path):
    """With one slow worker and a full hand-off buffer, shutdown must move
    claimed-but-unstarted messages back to pending/ — nothing stranded."""
    release = threading.Event()

    def cb(msg, ctx=None):
        release.wait(5.0)

    sched = JobScheduler(tmp_path / "q", cb, config=_fast_cfg(workers=1))
    pub = QueuePublisher(tmp_path / "q")
    for i in range(4):
        pub.publish({"ds_id": f"d{i}", "input_path": "/in", "msg_id": f"d{i}"})
    sched.start()
    # wait until one job is running and at least one more is claimed
    deadline = time.time() + 5.0
    root = tmp_path / "q" / "sm_annotate"
    while time.time() < deadline:
        if sched.stats()["states"].get("running", 0) >= 1 and \
                len(list(root.glob("running/*.json"))) >= 2:
            break
        time.sleep(0.01)
    release.set()
    assert sched.shutdown()
    assert not list(root.glob("running/*")), "claimed message stranded"
    done = len(list(root.glob("done/*.json")))
    pending = len(list(root.glob("pending/*.json")))
    assert done + pending == 4 and done >= 1


# ---- the dispatcher's wake on POST /submit (PR 43): an in-process publish
# ends the idle wait at once; the timed scan at poll_interval_s stays the
# fallback for what no event announces

def _post_submit(base: str, msg: dict) -> str:
    req = urllib.request.Request(
        base + "/submit", method="POST", data=json.dumps(msg).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=5.0) as r:
        assert r.status == 202
        return json.loads(r.read())["msg_id"]


def _wait_claims(msg_ids, timeout_s: float = 5.0) -> dict[str, dict]:
    """msg_id -> its first ``claim`` event in the flight recorder, waiting
    until every one of ``msg_ids`` has been claimed (or the timeout)."""
    from sm_distributed_tpu.utils import tracing

    want = set(msg_ids)
    deadline = time.time() + timeout_s
    while True:
        found: dict[str, dict] = {}
        for r in tracing.flight_recorder.recent():
            if r.get("kind") == "event" and r["name"] == "claim" \
                    and r.get("job_id") in want:
                found.setdefault(r["job_id"], r)
        if len(found) == len(want) or time.time() >= deadline:
            return found
        time.sleep(0.005)


def _wakes(service, by: str) -> float:
    return service.scheduler.m_wakes.labels(by=by).value


def test_submit_wakes_the_idle_dispatcher(tmp_path):
    """(a) With a 30 s poll a POST /submit is claimed at once, and the
    claim says what woke the dispatcher for it."""
    service = AnnotationService(tmp_path / "q", FakeJobs(),
                                sm_config=_sm(tmp_path, poll_interval_s=30.0))
    service.start()
    try:
        host, port = service.api.address
        time.sleep(0.05)              # the dispatcher is in its idle wait
        msg_id = _post_submit(f"http://{host}:{port}",
                              {"ds_id": "wake_a", "input_path": "/in"})
        t_posted = time.time()
        claim = _wait_claims([msg_id]).get(msg_id)
        assert claim is not None, "never claimed: the wake-up was lost"
        assert claim["attrs"]["woken_by"] == "submit"
        assert claim["ts"] - t_posted < 0.5
        assert service.scheduler.wait_for_terminal(1, timeout_s=5.0)
        assert _wakes(service, "submit") == 1 and _wakes(service, "poll") == 0
        # the job's own trace says it too, and the report prints it
        from scripts import trace_report
        from sm_distributed_tpu.utils import tracing

        recs = tracing.read_trace(
            tracing.trace_path(service.trace_dir, claim["trace_id"]))
        lines = trace_report.render(trace_report.summarize(recs)).splitlines()
        said = [ln for ln in lines if ln.strip().startswith("claim ")]
        assert len(said) == 1 and said[0].endswith("woken_by=submit"), lines
    finally:
        assert service.shutdown()


def test_submit_burst_loses_no_wakeup_and_keeps_priority(tmp_path):
    """(b) 20 submits from 4 threads against one worker and a 30 s poll:
    every one is claimed without a poll elapsing, and those that were
    pending together still run by priority class."""
    gate = threading.Event()
    order = []
    lock = threading.Lock()

    def cb(msg, ctx=None):
        with lock:
            order.append(msg["ds_id"])
        if msg["ds_id"] == "blocker":
            gate.wait(10.0)

    service = AnnotationService(
        tmp_path / "q", cb,
        sm_config=_sm(tmp_path, poll_interval_s=30.0, workers=1))
    service.start()
    try:
        host, port = service.api.address
        base = f"http://{host}:{port}"
        t0 = time.time()
        ids = [_post_submit(base, {"ds_id": "blocker", "input_path": "/in"})]
        while not order and time.time() < t0 + 5.0:
            time.sleep(0.005)         # the one worker now sits in the gate
        prio = ("low", "normal", "high", "normal", "low")
        got = [[] for _ in range(4)]

        def client(k):
            for i, p in enumerate(prio):
                got[k].append(_post_submit(base, {
                    "ds_id": f"{p}-{k}-{i}", "input_path": "/in",
                    "priority": p}))

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ids += [m for g in got for m in g]
        gate.set()
        assert service.scheduler.wait_for_terminal(21, timeout_s=10.0), \
            service.scheduler.stats()
        assert time.time() - t0 < 15.0
        claims = _wait_claims(ids)
        assert len(claims) == 21
        assert {c["attrs"]["woken_by"] for c in claims.values()} == {"submit"}
        assert _wakes(service, "poll") == 0, "a job waited for the poll"
        # blocker + the one in the hand-off buffer + the one the dispatcher
        # held in its blocked put were claimed as they arrived; the other
        # 18 were pending together when the gate opened
        ranks = [{"high": 0, "normal": 1, "low": 2}[d.split("-")[0]]
                 for d in order[3:]]
        assert len(ranks) == 18 and ranks == sorted(ranks), order
    finally:
        gate.set()
        assert service.shutdown()


def test_foreign_publish_is_found_by_the_poll(tmp_path):
    """(c) A bare QueuePublisher (another process's publish: no event) is
    still claimed within one poll_interval_s, and reads ``poll``."""
    service = AnnotationService(tmp_path / "q", FakeJobs(),
                                sm_config=_sm(tmp_path, poll_interval_s=0.05),
                                with_api=False)
    service.start()
    try:
        time.sleep(0.1)               # idle: a couple of polls elapse
        t0 = time.time()
        QueuePublisher(tmp_path / "q").publish(
            {"ds_id": "wake_c", "input_path": "/in", "msg_id": "wake_c"})
        claim = _wait_claims(["wake_c"]).get("wake_c")
        assert claim is not None
        assert claim["attrs"]["woken_by"] == "poll"
        assert claim["ts"] - t0 < 0.05 + 0.5
        assert _wakes(service, "submit") == 0 and _wakes(service, "poll") >= 1
    finally:
        assert service.shutdown()


def test_shutdown_ends_the_idle_wait_and_wakes_are_counted(tmp_path):
    """(d) shutdown() during a 30 s idle wait returns at once, not a poll
    later; /metrics counts wakes by both causes."""
    service = AnnotationService(tmp_path / "q", FakeJobs(),
                                sm_config=_sm(tmp_path, poll_interval_s=0.05))
    service.start()
    host, port = service.api.address
    _post_submit(f"http://{host}:{port}",
                 {"ds_id": "wake_d", "input_path": "/in"})
    assert service.scheduler.wait_for_terminal(1, timeout_s=5.0)
    time.sleep(0.15)
    text = service.metrics.expose()
    assert 'sm_scheduler_dispatch_wakes_total{by="submit"} 1' in text
    assert _wakes(service, "poll") >= 1
    assert service.shutdown()

    idle = JobScheduler(tmp_path / "q2", FakeJobs(),
                        config=_fast_cfg(poll_interval_s=30.0),
                        metrics=MetricsRegistry())
    idle.start()
    time.sleep(0.05)                  # the dispatcher is in its 30 s wait
    t0 = time.time()
    assert idle.shutdown(timeout_s=5.0)
    assert time.time() - t0 < 2.0
    assert idle.m_wakes.labels(by="submit").value == 0, \
        "shutdown's wake was counted as a submit"


def test_retry_policy_backoff_shape():
    pol = RetryPolicy(max_attempts=5, base_s=1.0, max_s=8.0, jitter=0.0)
    assert [pol.backoff_s(n) for n in (1, 2, 3, 4, 5)] == \
        [1.0, 2.0, 4.0, 8.0, 8.0]
    jittered = RetryPolicy(base_s=1.0, max_s=60.0, jitter=0.5)
    for n in (1, 2, 3):
        d = jittered.backoff_s(n)
        assert 2.0 ** (n - 1) <= d <= 2.0 ** (n - 1) * 1.5


def test_metrics_registry_exposition_format():
    m = MetricsRegistry()
    c = m.counter("sm_test_total", "help text", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    g = m.gauge("sm_test_gauge", "a gauge")
    g.set(1.5)
    h = m.histogram("sm_test_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = m.expose()
    assert "# TYPE sm_test_total counter" in text
    assert 'sm_test_total{kind="a"} 3' in text
    assert "sm_test_gauge 1.5" in text
    assert 'sm_test_seconds_bucket{le="0.1"} 1' in text
    assert 'sm_test_seconds_bucket{le="1"} 2' in text
    assert 'sm_test_seconds_bucket{le="+Inf"} 3' in text
    assert "sm_test_seconds_count 3" in text
    assert "sm_test_seconds_sum 5.55" in text
    # re-registration returns the same family; type clashes are rejected
    assert m.counter("sm_test_total", labelnames=("kind",)) is c
    with pytest.raises(ValueError):
        m.gauge("sm_test_total")


def _get(url: str):
    with urllib.request.urlopen(url, timeout=5.0) as r:
        return r.status, r.read()


def test_admin_api_endpoints(tmp_path):
    jobs = FakeJobs()
    service = AnnotationService(tmp_path / "q", jobs, sm_config=_sm(tmp_path))
    service.start()
    try:
        host, port = service.api.address
        base = f"http://{host}:{port}"
        status, body = _get(base + "/healthz")
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert health["queue"] == {"pending": 0, "running": 0,
                                   "done": 0, "failed": 0, "quarantine": 0}
        assert health["admission"]["depth"] == 0

        # POST /submit → spooled + eventually done
        req = urllib.request.Request(
            base + "/submit", method="POST",
            data=json.dumps({"ds_id": "api1", "input_path": "/in"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5.0) as r:
            assert r.status == 202
            msg_id = json.loads(r.read())["msg_id"]
        assert service.scheduler.wait_for_terminal(1, timeout_s=10.0)

        status, body = _get(base + f"/jobs?state=done")
        rows = json.loads(body)
        assert [r["msg_id"] for r in rows] == [msg_id]
        assert rows[0]["ds_id"] == "api1" and rows[0]["attempts"] == 1

        status, body = _get(base + "/metrics")
        assert status == 200
        assert 'sm_jobs_total{state="done"} 1' in body.decode()
        assert 'sm_queue_depth{state="done"} 1' in body.decode()

        # bad submit → 400, unknown route → 404
        bad = urllib.request.Request(base + "/submit", method="POST",
                                     data=b"{not json")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(bad, timeout=5.0)
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nope", timeout=5.0)
        assert e.value.code == 404
    finally:
        service.shutdown()
    # after shutdown the API socket is closed
    with pytest.raises(OSError):
        _get(f"http://{host}:{port}/healthz")


def test_serve_cli_smoke(tmp_path, capsys):
    """`sm-tpu serve` end to end with a real (tiny) SearchJob through the
    service scheduler — the CPU-exercisable service-mode path."""
    from sm_distributed_tpu.engine.cli import main
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset

    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    sm_json = tmp_path / "sm.json"
    sm_json.write_text(json.dumps({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 2, "seed": 1},
        "storage": {"results_dir": str(tmp_path / "res")},
        "work_dir": str(tmp_path / "work"),
        "service": {"workers": 2, "poll_interval_s": 0.02,
                    "backoff_base_s": 0.05, "http_port": 0},
    }))
    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "srv1", "input_path": str(path),
                 "formulas": truth.formulas[:3],
                 "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
    pub.publish({"ds_id": "srv_bad", "input_path": "/nope.imzML",
                 "service": {"max_attempts": 2}})
    rc = main(["serve", str(tmp_path / "q"), "--sm-config", str(sm_json),
               "--max-jobs", "2"])
    assert rc == 0
    root = tmp_path / "q" / "sm_annotate"
    assert len(list(root.glob("done/*.json"))) == 1
    assert len(list(root.glob("failed/*.json"))) == 1
    assert not list(root.glob("running/*"))
    dl = json.loads(next(iter(root.glob("failed/*.json"))).read_text())
    assert dl["attempts"] == 2      # the retry policy ran a real SearchJob
    from sm_distributed_tpu.engine.storage import JobLedger

    assert (JobLedger(tmp_path / "res").jobs("srv1").status == "FINISHED").all()
