#!/usr/bin/env python
"""Overload-protection load sweep (ISSUE 4 proof).

Drives a REAL in-process ``AnnotationService`` — spool, scheduler, admin
API, real ``SearchJob``s on synthetic fixtures — with the traffic mixes the
admission/cancellation/degradation layer exists for, and asserts the
serving invariants after each mix:

- **burst**: 4x-capacity submit burst → queue depth stays below the
  configured bound, every shed submit gets a structured 429/503 with a
  ``Retry-After`` header and a JSON ``reason``, every accepted job reaches
  a terminal state;
- **sustained**: paced tenant-rotating traffic → bounded depth, everything
  terminal;
- **deadline**: an expired-in-queue job and a trips-mid-run job → both
  terminal with a deadline error, no partial results, no debris;
- **cancel**: ``DELETE /jobs/<id>`` on a running job → terminal
  ``cancelled``, the attempt thread unwinds (zero live ``attempt-*``
  threads), the device token is released;
- **poison**: a job that fails every attempt dead-letters with its
  traceback; a message whose persisted ``service.claims`` says it
  crash-looped its claims moves to ``quarantine/`` (the real process-crash
  loop is proven by ``scripts/chaos_sweep.py`` — here the claim counter is
  pre-stamped so the sweep stays in-process);
- **breaker** (full matrix only): with ``backend=jax_tpu`` and injected
  device errors (``backend.device_error`` failpoint), the circuit breaker
  demonstrably opens, jobs degrade to numpy scoring, and after the faults
  are healed a half-open probe closes it again;
- **device_fault** (full matrix only, ISSUE 14): a 24-job surge over an
  8-chip pool with one chip going sticky mid-sweep — the chip is
  quarantined (``service/health.py``), no later grant includes it, every
  job lands in ``done/`` exactly once, and p99 queue-wait stays bounded
  despite the 7/8 pool;
- **disk** (full matrix only, ISSUE 10): sustained traffic under a 64 MB
  disk budget already past the trace floor — jobs complete with trace
  writes dropped, deepening pressure sheds submits with a structured 507
  + ``Retry-After``, and freeing the space recovers admissions in place;
- **replicas** (full matrix only, ISSUE 8): a 10k-tenant-id traffic model
  over THREE real scheduler replica processes sharing one partitioned
  spool (``scripts/replica_chaos.py --replica-serve --bare`` — null jobs,
  the mix measures the SCHEDULING plane).  One replica is SIGKILLed
  mid-sweep; the survivors fence + take over its shards and the asserts
  are: every job terminal in ``done/`` exactly once, p99 queue-wait
  bounded, and tenant-hash-bucket fairness (no bucket's mean wait runs
  away from the global median);
- **pod** (full matrix only, ISSUE 17): a simulated 2-host pod — four
  replicas, two per named host (``SM_HOST_NAME``/``SM_PROCESS_ID``) —
  loses host h1 WHOLE mid-sweep (both its replicas SIGKILLed at once).
  All jobs terminal exactly once, p99 bounded, and the survivors' host
  watchdogs demonstrably evicted the dead host
  (``sm_pod_host_evictions_total``);
- **stream** (full matrix only, ISSUE 19): two live acquisitions chunked
  over HTTP (``mode=stream`` + ``POST /datasets/<id>/pixels``) into TWO
  replicas sharing one spool, while a batch burst contends for the
  worker pool and readers poll a published dataset; one replica is
  DRAINED mid-acquisition and its live stream hands off to the peer
  without burning an attempt — provisional re-rank coverage must keep
  pace with the instrument, every read answers 200 across the drain,
  and both streams must converge bit-identically (``check_exact``) to
  the batch report of the same spectra.

Usage::

    python scripts/load_sweep.py              # full matrix
    python scripts/load_sweep.py --smoke      # burst + poison + deadline (CI)
    python scripts/load_sweep.py --keep --work DIR

``SM_FAILPOINTS`` may be exported to combine any mix with fault injection
(raise/sleep/torn actions only — a ``crash`` action would kill the driver
itself; use the chaos sweep for process-death faults).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from scripts.chaos_sweep import _debris  # noqa: E402 — shared invariant
from sm_distributed_tpu.engine.daemon import annotate_callback  # noqa: E402
from sm_distributed_tpu.engine.residency import DatasetResidency  # noqa: E402
from sm_distributed_tpu.engine.storage import (  # noqa: E402
    RESULT_TABLES,
    read_result_tables,
)
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset  # noqa: E402
from sm_distributed_tpu.models import breaker as breaker_mod  # noqa: E402
from sm_distributed_tpu.service import AnnotationService  # noqa: E402
from sm_distributed_tpu.utils import failpoints  # noqa: E402
from sm_distributed_tpu.utils.config import SMConfig  # noqa: E402

TERMINAL = ("done", "failed", "cancelled", "quarantined")


class SweepError(AssertionError):
    pass


def _check(cond, msg: str) -> None:
    if not cond:
        raise SweepError(msg)


# ---------------------------------------------------------------- HTTP glue
def _http(base: str, method: str, path: str, body: dict | None = None):
    """(status, headers, parsed-json) — 4xx/5xx returned, not raised."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, method=method, data=data,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30.0) as r:
            return r.status, dict(r.headers), json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            parsed = json.loads(raw or b"{}")
        except json.JSONDecodeError:
            parsed = {"raw": raw.decode(errors="replace")}
        return e.code, dict(e.headers), parsed


# ------------------------------------------------------------------ harness
class Harness:
    """One service instance + the assertion helpers every mix shares."""

    def __init__(self, base: Path, name: str, sm_overrides: dict | None = None):
        self.dir = base / name
        self.queue_dir = self.dir / "queue"
        self.root = self.queue_dir / "sm_annotate"
        sm = {
            "backend": "numpy_ref",
            "fdr": {"decoy_sample_size": 2, "seed": 1},
            "parallel": {"formula_batch": 8, "checkpoint_every": 1,
                         "resident_datasets": 2, "order_ions": "table"},
            "storage": {"results_dir": str(self.dir / "results"),
                        "store_images": False},
            "work_dir": str(self.dir / "work"),
            "service": {
                "workers": 2, "poll_interval_s": 0.02, "job_timeout_s": 30.0,
                "max_attempts": 2, "backoff_base_s": 0.05,
                "backoff_max_s": 0.2, "backoff_jitter": 0.0,
                "heartbeat_interval_s": 0.1, "stale_after_s": 2.0,
                "drain_timeout_s": 20.0, "cancel_grace_s": 10.0,
                "quarantine_after": 3, "http_port": 0,
                "admission": {"max_queue_depth": 6, "max_tenant_inflight": 4,
                              "retry_after_s": 1.0},
            },
        }
        if sm_overrides:
            sm = _merge(sm, sm_overrides)
        self.sm_config = SMConfig.from_dict(sm)
        # the residency cache handed to the service too, as ``cli serve``
        # does: /metrics then has sm_residency_{hits,misses}_total
        residency = DatasetResidency.from_config(
            self.sm_config.parallel.resident_datasets)
        self.service = AnnotationService(
            self.queue_dir,
            annotate_callback(self.sm_config, residency=residency),
            sm_config=self.sm_config, residency=residency)
        self.service.start()
        host, port = self.service.api.address
        self.base = f"http://{host}:{port}"

    # ------------------------------------------------------------- actions
    def submit(self, msg: dict):
        return _http(self.base, "POST", "/submit", msg)

    def delete(self, msg_id: str):
        return _http(self.base, "DELETE", f"/jobs/{msg_id}")

    def jobs(self) -> dict:
        _s, _h, rows = _http(self.base, "GET", "/jobs")
        return {r["msg_id"]: r for r in rows}

    def metrics_text(self) -> str:
        req = urllib.request.Request(self.base + "/metrics")
        with urllib.request.urlopen(req, timeout=30.0) as r:
            return r.read().decode()

    def wait_terminal(self, msg_ids, timeout_s: float = 120.0) -> dict:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            rows = self.jobs()
            if all(m in rows and rows[m]["state"] in TERMINAL
                   for m in msg_ids):
                return rows
            time.sleep(0.05)
        rows = self.jobs()
        missing = {m: rows.get(m, {}).get("state", "absent") for m in msg_ids
                   if rows.get(m, {}).get("state") not in TERMINAL}
        raise SweepError(f"jobs never reached a terminal state: {missing}")

    # ---------------------------------------------------------- invariants
    def sample_depth(self) -> int:
        """Admitted-but-not-terminal occupancy as seen on disk.  running/
        is listed first: a message claimed between the two listings is then
        missed, not counted in both (an upper-bound check must not see a
        depth that never was)."""
        running = len(list(self.root.glob("running/*.json")))
        return running + len(list(self.root.glob("pending/*.json")))

    def assert_clean(self, label: str) -> None:
        zombies = [t.name for t in threading.enumerate()
                   if t.name.startswith("attempt-") and t.is_alive()]
        _check(not zombies, f"{label}: live attempt threads leaked: {zombies}")
        token = self.service.scheduler.device_token
        got = token.acquire(timeout=1.0)
        _check(got, f"{label}: device token still held")
        if got:
            token.release()
        leftovers = _debris([self.root, self.dir / "results",
                             self.dir / "work"])
        # checkpoint shards under work/ are legitimate mid-crash resume
        # state for FAILED jobs; everything else must be gone
        leftovers = [p for p in leftovers if ".ckpt." not in p]
        _check(not leftovers, f"{label}: tmp/heartbeat debris: {leftovers}")
        _check(not list(self.root.glob("running/*")),
               f"{label}: running/ not empty after drain")

    def shutdown(self):
        self.service.shutdown()


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


# ----------------------------------------------------------------- fixtures
def build_fixtures(base: Path) -> dict:
    """One tiny dataset every job shares (the isocalc cache + resident
    backend warm after job 1, so burst jobs are fast).  Mixes that need a
    deterministically LONG job arm a ``device.score_batch=sleep:...``
    failpoint instead of guessing at a bigger fixture's duration."""
    fast_path, fast_truth = generate_synthetic_dataset(
        base / "fx_fast", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    return {
        "fast": {"input_path": str(fast_path),
                 "formulas": fast_truth.formulas[:3],
                 "ds_config": {"isotope_generation": {"adducts": ["+H"]}}},
    }


def _msg(fx: dict, kind: str, ds_id: str, **extra) -> dict:
    m = {"ds_id": ds_id, "msg_id": ds_id, **fx[kind], **extra}
    return m


# -------------------------------------------------------------------- mixes
def mix_burst(h: Harness, fx: dict, n_submit: int) -> None:
    """4x-capacity burst: bounded depth, structured sheds, all accepted
    jobs terminal."""
    cap = h.sm_config.service.admission.max_queue_depth
    accepted, shed = [], []
    max_depth = 0
    for i in range(n_submit):
        status, headers, body = h.submit(
            _msg(fx, "fast", f"burst{i}", tenant=f"t{i % 3}"))
        if status == 202:
            accepted.append(body["msg_id"])
        else:
            shed.append((status, headers, body))
        max_depth = max(max_depth, h.sample_depth())
    _check(accepted, "burst: nothing was accepted")
    _check(shed, f"burst: {n_submit} submits at capacity {cap} shed nothing")
    for status, headers, body in shed:
        _check(status in (429, 503), f"burst: shed status {status}")
        _check("Retry-After" in headers,
               f"burst: shed response missing Retry-After: {headers}")
        _check(body.get("reason") in ("queue_full", "tenant_quota",
                                      "latency_overload"),
               f"burst: unstructured shed body {body}")
        _check("retry_after_s" in body and "error" in body,
               f"burst: shed body missing fields {body}")
    rows = h.wait_terminal(accepted)
    bad = [m for m in accepted if rows[m]["state"] != "done"]
    _check(not bad, f"burst: accepted jobs not done: "
                    f"{[(m, rows[m]['state'], rows[m]['error']) for m in bad]}")
    # the depth bound: pending+running on disk never exceeded the admission
    # cap (direct spool publishes would bypass it; everything here is HTTP)
    _check(max_depth <= cap,
           f"burst: observed depth {max_depth} > configured bound {cap}")
    while h.sample_depth():
        time.sleep(0.05)
    h.assert_clean("burst")
    print(f"  burst: {len(accepted)} accepted, {len(shed)} shed "
          f"(max depth {max_depth}/{cap})")


def mix_sustained(h: Harness, fx: dict, n_submit: int, gap_s: float) -> None:
    cap = h.sm_config.service.admission.max_queue_depth
    accepted, shed = [], []
    max_depth = 0
    for i in range(n_submit):
        status, _hd, body = h.submit(
            _msg(fx, "fast", f"sus{i}", tenant=f"t{i % 4}"))
        (accepted if status == 202 else shed).append(
            body.get("msg_id", f"sus{i}"))
        max_depth = max(max_depth, h.sample_depth())
        time.sleep(gap_s)
    rows = h.wait_terminal(accepted)
    bad = [m for m in accepted if rows[m]["state"] != "done"]
    _check(not bad, f"sustained: not done: {bad}")
    _check(max_depth <= cap, f"sustained: depth {max_depth} > {cap}")
    text = h.metrics_text()
    _check("sm_admission_latency_ewma_s" in text,
           "sustained: EWMA gauge missing from /metrics")
    h.assert_clean("sustained")
    print(f"  sustained: {len(accepted)} accepted, {len(shed)} shed "
          f"(max depth {max_depth}/{cap})")


def mix_deadline(h: Harness, fx: dict) -> None:
    prev = failpoints.active_spec()
    # every checkpoint group sleeps: jobs become deterministically long, so
    # the mid-run job's deadline reliably trips BETWEEN group boundaries
    failpoints.configure("device.score_batch=sleep:0.35")
    try:
        # starts immediately on an idle worker; ~1s of scoring against a
        # 0.6s deadline → the cancel lands mid-attempt
        status, _hd, body = h.submit(
            _msg(fx, "fast", "dl_midrun", deadline_s=0.6))
        _check(status == 202, f"deadline: submit failed ({status})")
        midrun_id = body["msg_id"]
        # occupy the remaining workers so the tight-deadline job below
        # expires while still QUEUED
        occupiers = []
        for i in range(2):
            status, _hd, body = h.submit(_msg(fx, "fast", f"occupy{i}"))
            _check(status == 202, f"deadline: occupier shed ({status})")
            occupiers.append(body["msg_id"])
        status, _hd, body = h.submit(
            _msg(fx, "fast", "dl_queued", deadline_s=0.05))
        _check(status == 202, f"deadline: submit failed ({status})")
        queued_id = body["msg_id"]
        rows = h.wait_terminal(occupiers + [queued_id, midrun_id])
    finally:
        failpoints.configure(prev)
    for mid, marker in ((queued_id, "before start"),
                        (midrun_id, "deadline")):
        _check(rows[mid]["state"] == "failed",
               f"deadline: {mid} state {rows[mid]['state']} "
               f"({rows[mid]['error']!r})")
        _check("deadline" in rows[mid]["error"] and marker in rows[mid]["error"],
               f"deadline: {mid} error {rows[mid]['error']!r}")
        _check(rows[mid]["attempts"] <= 1,
               f"deadline: {mid} was retried ({rows[mid]['attempts']} attempts)")
        dl = json.loads((h.root / "failed" / f"{mid}.json").read_text())
        _check("deadline" in dl["error"], f"deadline: spool file {dl}")
    # no partial results for the mid-run expiry
    _check(not (h.dir / "results" / "dl_midrun" / "annotations.parquet").exists(),
           "deadline: cancelled job stored partial results")
    h.assert_clean("deadline")
    print(f"  deadline: queued-expiry + mid-run expiry both terminal, "
          f"occupiers {[rows[m]['state'] for m in occupiers]}")


def mix_cancel(h: Harness, fx: dict) -> None:
    prev = failpoints.active_spec()
    failpoints.configure("device.score_batch=sleep:0.35")
    try:
        status, _hd, body = h.submit(_msg(fx, "fast", "cancel_me"))
        _check(status == 202, f"cancel: submit failed ({status})")
        mid = body["msg_id"]
        deadline = time.time() + 30.0
        while time.time() < deadline:
            rows = h.jobs()
            if rows.get(mid, {}).get("state") == "running":
                break
            time.sleep(0.02)
        else:
            raise SweepError("cancel: job never started running")
        status, _hd, body = h.delete(mid)
    finally:
        failpoints.configure(prev)
    _check(status in (200, 202), f"cancel: DELETE status {status} {body}")
    rows = h.wait_terminal([mid])
    _check(rows[mid]["state"] == "cancelled",
           f"cancel: state {rows[mid]['state']} ({rows[mid]['error']!r})")
    dl = json.loads((h.root / "failed" / f"{mid}.json").read_text())
    _check(dl.get("cancelled") is True, f"cancel: spool file {dl}")
    _check(not (h.dir / "results" / "cancel_me" / "annotations.parquet").exists(),
           "cancel: cancelled job stored results")
    # second DELETE reports terminal, unknown id is a structured 404
    status, _hd, _b = h.delete(mid)
    _check(status == 409, f"cancel: re-DELETE status {status}")
    status, _hd, _b = h.delete("no_such_job")
    _check(status == 404, f"cancel: unknown-id status {status}")
    h.assert_clean("cancel")
    print("  cancel: running job cancelled cleanly, token released")


def mix_poison(h: Harness, fx: dict) -> None:
    # (a) fails every attempt → dead-letter with the traceback
    status, _hd, body = h.submit(
        {"ds_id": "poison_dl", "msg_id": "poison_dl",
         "input_path": str(h.dir / "nope.imzML")})
    _check(status == 202, f"poison: submit failed ({status})")
    dl_id = body["msg_id"]
    # (b) a crash-looper: its persisted claim counter says it has been
    # claimed quarantine_after times without a terminal outcome (the chaos
    # sweep proves the counter moves under real process crashes)
    q_after = h.sm_config.service.quarantine_after
    status, _hd, body = h.submit(
        _msg(fx, "fast", "poison_q",
             service={"claims": q_after, "last_error": "simulated crash loop"}))
    _check(status == 202, f"poison: submit failed ({status})")
    q_id = body["msg_id"]
    rows = h.wait_terminal([dl_id, q_id])
    _check(rows[dl_id]["state"] == "failed",
           f"poison: dead-letter state {rows[dl_id]['state']}")
    dl = json.loads((h.root / "failed" / f"{dl_id}.json").read_text())
    _check(dl["attempts"] == h.sm_config.service.max_attempts
           and "traceback" in dl, f"poison: dead-letter evidence {list(dl)}")
    _check(rows[q_id]["state"] == "quarantined",
           f"poison: quarantine state {rows[q_id]['state']}")
    qf = json.loads((h.root / "quarantine" / f"{q_id}.json").read_text())
    _check("quarantine_reason" in qf
           and qf["service"]["claims"] == q_after + 1,
           f"poison: quarantine evidence {qf}")
    _check("sm_jobs_quarantined_total 1" in h.metrics_text(),
           "poison: quarantine counter missing from /metrics")
    h.assert_clean("poison")
    print("  poison: dead-letter w/ traceback + quarantine/ both reached")


def mix_breaker(base: Path, fx: dict) -> None:
    """Device errors open the breaker; jobs degrade to numpy; healing +
    cooldown recovers through a half-open probe (backend=jax_tpu on
    whatever platform jax has — CPU in CI)."""
    breaker_mod.reset_device_breaker()
    h = Harness(base, "breaker", sm_overrides={
        "backend": "jax_tpu",
        "service": {"max_attempts": 3, "breaker_threshold": 2,
                    "breaker_cooldown_s": 0.5},
    })
    try:
        failpoints.configure("backend.device_error=raise:RuntimeError?1")
        ids = []
        for name in ("brk1", "brk2"):
            status, _hd, body = h.submit(_msg(fx, "fast", name))
            _check(status == 202, f"breaker: submit failed ({status})")
            ids.append(body["msg_id"])
            h.wait_terminal([body["msg_id"]])
        rows = h.jobs()
        _check(all(rows[m]["state"] == "done" for m in ids),
               f"breaker: jobs under device faults not done: "
               f"{[(m, rows[m]['state']) for m in ids]}")
        # per-chip breakers (ISSUE 14): the leased jobs answer to their
        # CHIP's breaker, not the un-leased "*" singleton
        brk = breaker_mod.breaker_for("0")
        _check(brk is not None and brk.state == "open",
               f"breaker: expected chip-0 breaker open after injected "
               f"faults, got {brk.state if brk else 'absent'}")
        # heal the device, wait out the cooldown, probe
        failpoints.configure(None)
        time.sleep(h.sm_config.service.breaker_cooldown_s + 0.1)
        status, _hd, body = h.submit(_msg(fx, "fast", "brk_probe"))
        _check(status == 202, f"breaker: probe submit failed ({status})")
        h.wait_terminal([body["msg_id"]])
        rows = h.jobs()
        _check(rows[body["msg_id"]]["state"] == "done",
               f"breaker: probe job {rows[body['msg_id']]['state']}")
        _check(brk.state == "closed",
               f"breaker: expected closed after probe, got {brk.state}")
        hops = [(f, t) for _ts, f, t in brk.transitions]
        for hop in (("closed", "open"), ("open", "half_open"),
                    ("half_open", "closed")):
            _check(hop in hops, f"breaker: transition {hop} missing: {hops}")
        text = h.metrics_text()
        _check("sm_breaker_degraded_total" in text
               and 'sm_breaker_transitions_total{device="0",to="open"}'
               in text,
               "breaker: /metrics missing breaker families (per-chip "
               "device label, ISSUE 14)")
        h.assert_clean("breaker")
        print(f"  breaker: opened, degraded to numpy, recovered "
              f"(transitions {hops})")
    finally:
        failpoints.configure(None)
        h.shutdown()
        breaker_mod.reset_device_breaker()


def mix_disk(base: Path, fx: dict) -> None:
    """Disk-pressure mix (ISSUE 10): sustained traffic under a 64 MB disk
    budget already past the trace floor — every job completes with its
    trace writes dropped, deepening the pressure to the submit floor sheds
    with a structured 507 + Retry-After, and freeing the space recovers
    admissions without a restart."""
    mb = 1 << 20
    h = Harness(base, "disk", sm_overrides={
        "resources": {"disk_budget_bytes": 64 * mb,
                      "trace_floor_bytes": 48 * mb,
                      "cache_floor_bytes": 24 * mb,
                      "submit_floor_bytes": 8 * mb,
                      "gc_interval_s": 0.2},
    })
    filler = Path(h.sm_config.work_dir) / "filler.bin"
    filler.parent.mkdir(parents=True, exist_ok=True)
    try:
        governor = h.service.resources
        filler.write_bytes(b"\0" * (20 * mb))   # past the trace floor
        deadline = time.time() + 10.0
        while governor.level() < 1 and time.time() < deadline:
            time.sleep(0.05)
        _check(governor.level() == 1, "disk: never reached trace-drop level")
        accepted = []
        for i in range(6):
            status, _hd, body = h.submit(
                _msg(fx, "fast", f"disk{i}", tenant=f"t{i % 2}"))
            _check(status == 202, f"disk: level-1 submit shed ({status})")
            accepted.append(body["msg_id"])
        rows = h.wait_terminal(accepted)
        bad = [m for m in accepted if rows[m]["state"] != "done"]
        _check(not bad, f"disk: jobs under trace-drop not done: {bad}")
        from sm_distributed_tpu.utils import tracing

        for m in accepted:
            tid = rows[m]["trace_id"]
            _check(not tracing.trace_path(h.service.trace_dir, tid).exists(),
                   f"disk: {m} wrote a trace file under pressure")
        text = h.metrics_text()
        _check('sm_disk_degraded_writes_total{kind="trace"}' in text,
               "disk: trace-drop counter missing from /metrics")
        # deepen to the submit floor: structured 507 shed
        from sm_distributed_tpu.service.resources import LEVEL_SHED_SUBMITS

        filler.write_bytes(b"\0" * (60 * mb))
        deadline = time.time() + 10.0
        while governor.level() < LEVEL_SHED_SUBMITS and \
                time.time() < deadline:
            time.sleep(0.05)
        status, headers, body = h.submit(_msg(fx, "fast", "disk_shed"))
        _check(status == 507 and body.get("reason") == "disk_exhausted",
               f"disk: expected structured 507, got {status} {body}")
        _check("Retry-After" in headers, f"disk: no Retry-After: {headers}")
        # free the space: admissions recover in place
        filler.unlink()
        deadline = time.time() + 10.0
        while governor.level() > 0 and time.time() < deadline:
            time.sleep(0.05)
        status, _hd, body = h.submit(_msg(fx, "fast", "disk_recovered"))
        _check(status == 202, f"disk: post-recovery submit shed ({status})")
        h.wait_terminal([body["msg_id"]])
        h.assert_clean("disk")
        print(f"  disk: 6 jobs golden under trace-drop, 507 at the submit "
              f"floor, recovery after free-up")
    finally:
        if filler.exists():
            filler.unlink()
        h.shutdown()


def mix_device_fault(base: Path, fx: dict, n_jobs: int = 24,
                     p99_bound_s: float = 20.0) -> None:
    """Surge mix where one chip goes sticky mid-sweep (ISSUE 14): 24 jobs
    across 3 tenants over an 8-chip pool; once the surge is in flight,
    chip 3 takes an attributed sticky fault and is quarantined.  Asserts:
    every job terminal in ``done/`` exactly once, zero lost/dup spool
    messages, NO lease granted on the quarantined chip afterwards, p99
    queue-wait bounded despite the 7/8 pool, and the quarantine visible on
    /metrics.  Jobs score on numpy_ref — the pool is a scheduling-plane
    resource here, so the mix measures placement, not kernels."""
    from sm_distributed_tpu.models import faults as faults_mod

    h = Harness(base, "device_fault", sm_overrides={
        "service": {"workers": 6, "device_pool_size": 8,
                    "devices_per_job": 1, "max_attempts": 2,
                    "admission": {"max_queue_depth": 64,
                                  "max_tenant_inflight": 32}},
    })
    pool = h.service.device_pool
    granted_on_dead: list[dict] = []
    stop = threading.Event()
    quarantined_at = [0.0]
    holder_at_quarantine = [None]

    def _watch():
        # no NEW grant may include chip 3 after its quarantine (a lease
        # already holding it when the verdict lands finishes on its own —
        # quarantine fences placement, it does not revoke)
        while not stop.wait(0.01):
            if not quarantined_at[0]:
                continue
            snap = pool.snapshot()
            holder = snap["holders"].get("3")
            if holder is not None and holder != holder_at_quarantine[0]:
                granted_on_dead.append(snap["holders"])

    watcher = threading.Thread(target=_watch, daemon=True)
    watcher.start()
    try:
        # every batch-group score sleeps, so the surge keeps the pool busy
        # long enough for the mid-sweep fault to land under load
        failpoints.configure("device.score_batch=sleep:0.1")
        ids = []
        for i in range(n_jobs):
            status, _hd, body = h.submit(
                _msg(fx, "fast", f"df{i}", tenant=f"t{i % 3}"))
            _check(status == 202,
                   f"device_fault: submit {i} shed ({status})")
            ids.append(body["msg_id"])
            if i == n_jobs // 3:
                # mid-sweep: chip 3 goes sticky (1-chip attribution —
                # quarantined outright, models/faults.py taxonomy)
                holder_at_quarantine[0] = (
                    pool.snapshot()["holders"].get("3"))
                faults_mod.report_device_fault(
                    (3,), faults_mod.FAULT_STICKY, "sweep-injected sticky")
                quarantined_at[0] = time.time()
                _check(pool.health.state_of(3) == "quarantined",
                       "device_fault: chip 3 not quarantined")
        rows = h.wait_terminal(ids, timeout_s=180.0)
        bad = [m for m in ids if rows[m]["state"] != "done"]
        _check(not bad, f"device_fault: jobs not done: "
                        f"{[(m, rows[m]['state']) for m in bad]}")
        # exactly-once: every job in done/ once, nowhere else
        done = sorted(p.stem for p in (h.root / "done").glob("df*.json"))
        _check(done == sorted(ids),
               f"device_fault: done/ census mismatch ({len(done)} vs "
               f"{len(ids)})")
        for state in ("pending", "running", "failed", "quarantine"):
            leftover = list((h.root / state).glob("df*.json"))
            _check(not leftover,
                   f"device_fault: {state}/ not empty: {leftover}")
        _check(not granted_on_dead,
               f"device_fault: quarantined chip 3 appeared in grants: "
               f"{granted_on_dead[:3]}")
        # p99 queue wait bounded despite the 7/8 pool
        waits = sorted(max(0.0, rows[m]["started_at"]
                           - rows[m]["published_at"]) for m in ids)
        p99 = waits[min(len(waits) - 1, int(0.99 * len(waits)))]
        _check(p99 <= p99_bound_s,
               f"device_fault: p99 queue wait {p99:.2f}s > {p99_bound_s}s")
        text = h.metrics_text()
        _check("sm_device_quarantines_total 1" in text
               or "sm_device_quarantines_total" in text
               and pool.health.snapshot()["quarantines_total"] >= 1,
               "device_fault: quarantine not on /metrics")
        _check('sm_device_health{device="3"} 2' in text,
               "device_fault: sm_device_health gauge missing/incorrect")
        h.assert_clean("device_fault")
        print(f"  device_fault: {n_jobs} jobs done exactly-once on the "
              f"7/8 pool (p99 queue wait {p99:.2f}s), chip 3 quarantined "
              f"and never re-leased")
    finally:
        stop.set()
        watcher.join(timeout=2.0)
        failpoints.configure(None)
        h.shutdown()


def mix_replicas(base: Path, n_jobs: int = 600, tenant_space: int = 10_000,
                 n_replicas: int = 3, p99_bound_s: float = 30.0) -> None:
    """Multi-replica, 10k-tenant scheduling-plane mix with a mid-sweep
    replica kill (ISSUE 8 satellite; ROADMAP open item 2).

    Jobs are null callbacks (``--bare``): the mix measures claim latency,
    shard partitioning, takeover, and fairness — not scoring.  Queue wait
    per message is read back from the drained spool (the scheduler stamps
    ``service.claimed_at`` at every claim)."""
    import signal as _signal
    import subprocess

    rng = __import__("random").Random(8)
    mix_dir = base / "replicas"
    queue_dir = mix_dir / "queue"
    root = queue_dir / "sm_annotate"
    sm = {
        "backend": "numpy_ref",
        "work_dir": str(mix_dir / "work"),
        "storage": {"results_dir": str(mix_dir / "results")},
        "service": {
            "workers": 4, "poll_interval_s": 0.02, "job_timeout_s": 30.0,
            "max_attempts": 2, "backoff_base_s": 0.05, "backoff_max_s": 0.2,
            "backoff_jitter": 0.0, "heartbeat_interval_s": 0.2,
            "stale_after_s": 1.0, "drain_timeout_s": 20.0, "http_port": 0,
            "replicas": n_replicas, "spool_shards": 16,
            "replica_heartbeat_interval_s": 0.25,
            "replica_stale_after_s": 1.0, "takeover_interval_s": 0.3,
        },
    }
    mix_dir.mkdir(parents=True, exist_ok=True)
    sm_conf = mix_dir / "sm.json"
    sm_conf.write_text(json.dumps(sm, indent=2))
    from sm_distributed_tpu.engine.daemon import QueuePublisher

    pub = QueuePublisher(queue_dir)
    t_publish = time.time()
    for i in range(n_jobs):
        pub.publish({
            "ds_id": f"lj{i}", "msg_id": f"lj{i:05d}",
            "input_path": "null://", "tenant": f"t{rng.randrange(tenant_space)}",
        })
    script = str(REPO_ROOT / "scripts" / "replica_chaos.py")
    env = dict(__import__("os").environ)
    env.pop("SM_FAILPOINTS", None)
    procs = {}
    logs = {}
    for i in range(n_replicas):
        rid = f"r{i}"
        log = open(mix_dir / f"{rid}.log", "w")
        logs[rid] = log
        procs[rid] = subprocess.Popen(
            [sys.executable, script, "--replica-serve", str(queue_dir),
             str(sm_conf), "--replica-id", rid, "--bare",
             "--null-sleep", "0.002", "--idle-exit", "2.0"],
            env=env, stdout=log, stderr=log, cwd=str(REPO_ROOT))
    victim = procs["r0"]
    killed = False
    deadline = time.time() + 300.0
    try:
        while time.time() < deadline:
            done = len(list((root / "done").glob("*.json")))
            if not killed and done >= n_jobs // 3:
                # mid-sweep kill: no drain, no cleanup — claims die in
                # running/ and the survivors must fence + take them over
                victim.send_signal(_signal.SIGKILL)
                killed = True
                print(f"  replicas: killed r0 at {done}/{n_jobs} done")
            if done >= n_jobs:
                break
            if all(p.poll() is not None for p in procs.values()):
                raise SweepError(
                    f"replicas: all exited at {done}/{n_jobs} done")
            time.sleep(0.1)
        else:
            raise SweepError(
                f"replicas: did not drain in time "
                f"({len(list((root / 'done').glob('*.json')))}/{n_jobs})")
        _check(killed, "replicas: kill point never reached")
        drain_s = time.time() - t_publish
        for rid, p in procs.items():
            if rid == "r0":
                continue
            p.wait(timeout=30)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for log in logs.values():
            log.close()
    # ---- invariants from the drained spool -----------------------------
    done_msgs = list((root / "done").glob("*.json"))
    _check(len(done_msgs) == n_jobs,
           f"replicas: {len(done_msgs)}/{n_jobs} done")
    for state in ("pending", "running", "failed", "quarantine"):
        left = list((root / state).glob("*.json"))
        _check(not left, f"replicas: {len(left)} messages left in {state}/")
    waits_by_bucket: dict[int, list[float]] = {}
    waits = []
    import zlib

    for p in done_msgs:
        msg = json.loads(p.read_text())
        svc = msg.get("service", {})
        w = float(svc.get("claimed_at", 0.0)) - float(msg["published_at"])
        _check(w >= 0, f"replicas: negative queue wait on {p.name}")
        waits.append(w)
        bucket = zlib.crc32(str(msg.get("tenant")).encode()) % 10
        waits_by_bucket.setdefault(bucket, []).append(w)
    waits.sort()
    p50 = waits[len(waits) // 2]
    p99 = waits[min(len(waits) - 1, int(len(waits) * 0.99))]
    _check(p99 <= p99_bound_s,
           f"replicas: p99 queue wait {p99:.2f}s > bound {p99_bound_s}s")
    # fairness across the 10k-tenant space: hash tenants into 10 buckets;
    # no bucket's MEAN wait may run away from the global median (a starved
    # tenant class would show up as a hot bucket)
    means = {b: sum(v) / len(v) for b, v in waits_by_bucket.items()}
    worst = max(means.values())
    _check(worst <= max(4.0 * p50, p99, 2.0),
           f"replicas: unfair bucket mean {worst:.2f}s vs p50 {p50:.2f}s "
           f"(means {means})")
    print(f"  replicas: {n_jobs} jobs / {len({json.loads(p.read_text()).get('tenant') for p in done_msgs})} "
          f"tenants over {n_replicas} replicas, r0 killed mid-sweep; "
          f"drain {drain_s:.1f}s, queue-wait p50 {p50:.2f}s p99 {p99:.2f}s, "
          f"worst bucket mean {worst:.2f}s")


def mix_pod(base: Path, n_jobs: int = 240, p99_bound_s: float = 30.0) -> None:
    """Pod host-loss wave (ISSUE 17; ROADMAP item 2).

    A simulated 2-host pod: four bare scheduler replicas over one
    partitioned spool, two per named host (``SM_HOST_NAME`` /
    ``SM_PROCESS_ID`` — the launcher env contract), every replica running
    the host watchdog over the shared registry's per-process beat groups.
    Mid-sweep BOTH of host h1's replicas are SIGKILLed at once — a whole
    host dying, not a lone replica crash.  Asserts: every job terminal in
    ``done/`` exactly once (the survivors fence + take over the dead
    host's shards), p99 queue-wait bounded despite half the pod gone, and
    the survivors' exit metrics show the watchdog saw it
    (``sm_pod_host_evictions_total`` >= 1,
    ``sm_pod_process_up{process="1"}`` == 0)."""
    import signal as _signal
    import subprocess

    rng = __import__("random").Random(17)
    mix_dir = base / "pod"
    queue_dir = mix_dir / "queue"
    root = queue_dir / "sm_annotate"
    sm = {
        "backend": "numpy_ref",
        "work_dir": str(mix_dir / "work"),
        "storage": {"results_dir": str(mix_dir / "results")},
        "service": {
            "workers": 4, "poll_interval_s": 0.02, "job_timeout_s": 30.0,
            "max_attempts": 2, "backoff_base_s": 0.05, "backoff_max_s": 0.2,
            "backoff_jitter": 0.0, "heartbeat_interval_s": 0.2,
            "stale_after_s": 1.0, "drain_timeout_s": 20.0, "http_port": 0,
            "quarantine_after": 20,
            "replicas": 4, "spool_shards": 16,
            "replica_heartbeat_interval_s": 0.25,
            "replica_stale_after_s": 1.0, "takeover_interval_s": 0.3,
            # each replica's own 2-domain pool + host watchdog: process i
            # ↔ domain i, so the survivors' watchdogs fence domain 1 when
            # h1's beat group goes stale
            "device_pool_size": 4, "device_pool_hosts": 2,
            "host_watchdog_interval_s": 0.25, "host_stale_after_s": 1.0,
        },
    }
    mix_dir.mkdir(parents=True, exist_ok=True)
    sm_conf = mix_dir / "sm.json"
    sm_conf.write_text(json.dumps(sm, indent=2))
    from sm_distributed_tpu.engine.daemon import QueuePublisher

    pub = QueuePublisher(queue_dir)
    t_publish = time.time()
    for i in range(n_jobs):
        pub.publish({
            "ds_id": f"pj{i}", "msg_id": f"pj{i:05d}",
            "input_path": "null://", "tenant": f"t{rng.randrange(500)}",
        })
    script = str(REPO_ROOT / "scripts" / "replica_chaos.py")
    placement = {"r0": ("h0", 0), "r1": ("h0", 0),
                 "r2": ("h1", 1), "r3": ("h1", 1)}
    procs = {}
    logs = {}
    for rid, (host, pid) in placement.items():
        env = dict(__import__("os").environ)
        env.pop("SM_FAILPOINTS", None)
        env["SM_HOST_NAME"] = host
        env["SM_PROCESS_ID"] = str(pid)
        log = open(mix_dir / f"{rid}.log", "w")
        logs[rid] = log
        procs[rid] = subprocess.Popen(
            [sys.executable, script, "--replica-serve", str(queue_dir),
             str(sm_conf), "--replica-id", rid, "--bare",
             "--null-sleep", "0.01", "--idle-exit", "2.0",
             "--metrics-dump", str(mix_dir / "metrics" / f"{rid}.prom")],
            env=env, stdout=log, stderr=log, cwd=str(REPO_ROOT))
    victims = [rid for rid, (host, _p) in placement.items() if host == "h1"]
    killed = False
    deadline = time.time() + 300.0
    try:
        while time.time() < deadline:
            done = len(list((root / "done").glob("*.json")))
            if not killed and done >= n_jobs // 3:
                # host h1 dies whole: every one of its replicas at once
                for rid in victims:
                    procs[rid].send_signal(_signal.SIGKILL)
                killed = True
                print(f"  pod: killed host h1 ({', '.join(victims)}) at "
                      f"{done}/{n_jobs} done")
            if done >= n_jobs:
                break
            if all(p.poll() is not None for p in procs.values()):
                raise SweepError(f"pod: all exited at {done}/{n_jobs} done")
            time.sleep(0.1)
        else:
            raise SweepError(
                f"pod: did not drain in time "
                f"({len(list((root / 'done').glob('*.json')))}/{n_jobs})")
        _check(killed, "pod: kill point never reached")
        drain_s = time.time() - t_publish
        for rid, p in procs.items():
            if rid not in victims:
                p.wait(timeout=30)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for log in logs.values():
            log.close()
    # ---- invariants from the drained spool -----------------------------
    done_msgs = list((root / "done").glob("*.json"))
    _check(len(done_msgs) == n_jobs, f"pod: {len(done_msgs)}/{n_jobs} done")
    for state in ("pending", "running", "failed", "quarantine"):
        left = list((root / state).glob("*.json"))
        _check(not left, f"pod: {len(left)} messages left in {state}/")
    waits = []
    for p in done_msgs:
        msg = json.loads(p.read_text())
        w = (float(msg.get("service", {}).get("claimed_at", 0.0))
             - float(msg["published_at"]))
        _check(w >= 0, f"pod: negative queue wait on {p.name}")
        waits.append(w)
    waits.sort()
    p50 = waits[len(waits) // 2]
    p99 = waits[min(len(waits) - 1, int(len(waits) * 0.99))]
    _check(p99 <= p99_bound_s,
           f"pod: p99 queue wait {p99:.2f}s > bound {p99_bound_s}s")
    # the survivors' watchdogs must have seen the host die
    evictions = 0.0
    saw_down = False
    for rid, (host, _p) in placement.items():
        if host != "h0":
            continue
        dump = mix_dir / "metrics" / f"{rid}.prom"
        _check(dump.exists(), f"pod: survivor {rid} left no metrics dump")
        text = dump.read_text()
        for line in text.splitlines():
            if line.startswith("sm_pod_host_evictions_total"):
                evictions += float(line.rsplit(" ", 1)[1])
            if line.startswith('sm_pod_process_up{process="1"} 0'):
                saw_down = True
    _check(evictions >= 1,
           "pod: no survivor recorded sm_pod_host_evictions_total")
    _check(saw_down,
           'pod: no survivor exported sm_pod_process_up{process="1"} == 0')
    print(f"  pod: {n_jobs} jobs over 2 hosts x 2 replicas, host h1 "
          f"SIGKILLed whole mid-sweep; drain {drain_s:.1f}s, queue-wait "
          f"p50 {p50:.2f}s p99 {p99:.2f}s, survivor host evictions "
          f"{evictions:.0f}")


def mix_elastic(base: Path, n_jobs: int = 420, p99_bound_s: float = 30.0) -> None:
    """Elastic-fleet wave (ISSUE 11 proof; ROADMAP item 2).

    A FleetController (in-process, lock-order-instrumented) supervises bare
    scheduler replicas (``replica_chaos.py --replica-serve --bare`` — null
    jobs; this mix measures the SCALING plane) over one partitioned spool.
    A pre-published traffic surge drives the fleet 1→4 autonomously; as the
    queue drains, cooldown-damped scale-downs *drain* replicas back — the
    mix observes the fleet at 2 before stopping.  Asserts: every job
    reaches ``done/`` exactly once (replica_chaos's exactly-once census),
    p99 queue-wait bounded, drained replicas leave zero orphaned
    leases/heartbeat/registry files, and the ``sm_fleet_*`` families are
    exposed."""
    import subprocess

    from scripts.replica_chaos import _spool_census
    from sm_distributed_tpu.engine.daemon import QUEUE_ANNOTATE, QueuePublisher
    from sm_distributed_tpu.service.fleet import FleetController
    from sm_distributed_tpu.service.metrics import MetricsRegistry
    from sm_distributed_tpu.utils.config import FleetConfig

    mix_dir = base / "elastic"
    queue_dir = mix_dir / "queue"
    root = queue_dir / QUEUE_ANNOTATE
    sm = {
        "backend": "numpy_ref",
        "work_dir": str(mix_dir / "work"),
        "storage": {"results_dir": str(mix_dir / "results")},
        "service": {
            "workers": 2, "poll_interval_s": 0.02, "job_timeout_s": 30.0,
            "max_attempts": 2, "backoff_base_s": 0.05, "backoff_max_s": 0.2,
            "backoff_jitter": 0.0, "heartbeat_interval_s": 0.2,
            "stale_after_s": 1.0, "drain_timeout_s": 20.0, "http_port": 0,
            "replicas": 4, "spool_shards": 16,
            # claim churn during membership changes bumps claim counters;
            # keep quarantine out of the way (same rationale as
            # replica_chaos's template — the mix is elasticity, not poison)
            "quarantine_after": 50,
            "replica_heartbeat_interval_s": 0.1,
            "replica_stale_after_s": 1.0, "takeover_interval_s": 0.2,
        },
    }
    mix_dir.mkdir(parents=True, exist_ok=True)
    sm_conf = mix_dir / "sm.json"
    sm_conf.write_text(json.dumps(sm, indent=2))
    pub = QueuePublisher(queue_dir)
    t_publish = time.time()
    for i in range(n_jobs):
        pub.publish({"ds_id": f"ej{i}", "msg_id": f"ej{i:05d}",
                     "input_path": "null://", "tenant": f"t{i % 97}"})
    script = str(REPO_ROOT / "scripts" / "replica_chaos.py")
    env = dict(__import__("os").environ)
    env.pop("SM_FAILPOINTS", None)
    logs = []

    def _spawn(rid: str) -> subprocess.Popen:
        log = open(mix_dir / f"{rid}.log", "w")
        logs.append(log)
        # long idle-exit: replicas retire by DRAIN, not by queue idleness
        return subprocess.Popen(
            [sys.executable, script, "--replica-serve", str(queue_dir),
             str(sm_conf), "--replica-id", rid, "--bare",
             "--null-sleep", "0.05", "--idle-exit", "120"],
            env=env, stdout=log, stderr=log, cwd=str(REPO_ROOT))

    registry = MetricsRegistry()
    from sm_distributed_tpu.utils.config import SMConfig as _SM

    fc = FleetController(
        queue_dir,
        FleetConfig(min_replicas=1, max_replicas=4, decide_interval_s=0.15,
                    cooldown_s=1.0, hysteresis_ticks=2, scale_up_burn=1.0,
                    scale_down_burn=0.5, queue_high_per_replica=20.0,
                    queue_low_per_replica=0.5, spawn_timeout_s=30.0,
                    drain_timeout_s=30.0),
        _SM.from_dict(json.loads(sm_conf.read_text())).service,
        spawn=_spawn, metrics=registry)
    max_alive = 0
    saw_two_after_peak = False
    try:
        fc.start()
        deadline = time.time() + 240.0
        while time.time() < deadline:
            alive = len(fc.alive_replicas())
            max_alive = max(max_alive, alive)
            done = len(list((root / "done").glob("*.json")))
            if done >= n_jobs and max_alive >= 4 and alive <= 2:
                saw_two_after_peak = True
                break
            time.sleep(0.05)
        _check(saw_two_after_peak,
               f"elastic: never observed surge→4→2 "
               f"(max_alive={max_alive}, "
               f"done={len(list((root / 'done').glob('*.json')))}/{n_jobs}, "
               f"status={fc.status()})")
    finally:
        fc.shutdown()
        for log in logs:
            log.close()
    st = fc.status()
    _check(st["scale_events"]["up"] >= 3,
           f"elastic: expected >=3 scale-ups, got {st['scale_events']}")
    _check(st["drains_total"] >= 2,
           f"elastic: expected >=2 completed drains, got {st}")
    _check(st["crashes_total"] == 0,
           f"elastic: controller counted crashes: {st}")
    # exactly-once: every job in done/ once, nothing anywhere else
    # (replica_chaos's census invariant)
    census = _spool_census(root)
    want = sorted(f"ej{i:05d}" for i in range(n_jobs))
    _check(census["done"] == want,
           f"elastic: done/ census mismatch "
           f"({len(census['done'])}/{n_jobs} done)")
    others = {s: v for s, v in census.items() if s != "done" and v}
    _check(not others, f"elastic: messages left outside done/: "
                       f"{ {s: len(v) for s, v in others.items()} }")
    # drained replicas must leave no orphaned leases / heartbeats /
    # registry debris — the zero-loss drain's cleanliness contract
    leases_left = sorted(p.name for p in (root / "leases").glob("*.json"))
    _check(not leases_left, f"elastic: leftover lease files: {leases_left}")
    beats_left = sorted(p.name for p in (root / "replicas").glob("*.json"))
    _check(not beats_left,
           f"elastic: drained replicas left heartbeat files: {beats_left}")
    drains_left = sorted(p.name for p in (root / "replicas").glob("*.drain"))
    _check(not drains_left,
           f"elastic: drain sentinels not cleaned: {drains_left}")
    hb_left = [str(p) for p in root.rglob("*.hb")]
    _check(not hb_left, f"elastic: claim heartbeat debris: {hb_left}")
    # queue-wait bound under the surge (scheduler stamps claimed_at)
    waits = []
    for p in (root / "done").glob("*.json"):
        msg = json.loads(p.read_text())
        w = (float(msg.get("service", {}).get("claimed_at", 0.0))
             - float(msg["published_at"]))
        _check(w >= 0, f"elastic: negative queue wait on {p.name}")
        waits.append(w)
    waits.sort()
    p50 = waits[len(waits) // 2]
    p99 = waits[min(len(waits) - 1, int(len(waits) * 0.99))]
    _check(p99 <= p99_bound_s,
           f"elastic: p99 queue wait {p99:.2f}s > bound {p99_bound_s}s")
    # the acceptance metrics are exposed by the controller's registry (on
    # the hosting replica's /metrics under serve --fleet)
    text = registry.expose()
    for fam in ("sm_fleet_replicas", "sm_fleet_scale_events_total",
                "sm_fleet_drains_total"):
        _check(fam in text, f"elastic: {fam} missing from metrics")
    drain_s = time.time() - t_publish
    print(f"  elastic: {n_jobs} jobs; fleet 1→{max_alive}→2 "
          f"({st['scale_events']['up']} ups, {st['drains_total']} drains, "
          f"0 crashes); drain {drain_s:.1f}s, queue-wait p50 {p50:.2f}s "
          f"p99 {p99:.2f}s")


def _http_raw(base: str, path: str):
    """(status, headers, raw bytes) — for read-path GETs (tiles are PNG)."""
    req = urllib.request.Request(base + path)
    try:
        with urllib.request.urlopen(req, timeout=30.0) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def mix_read(base: Path, fx: dict, n_readers: int = 6, reads_each: int = 30,
             n_writes: int = 4, p99_bound_s: float = 1.0) -> None:
    """Read-plane mix (ISSUE 16): ~90/10 read/write over TWO in-process
    replicas sharing one spool + results tree.  Readers storm /datasets,
    annotation queries, cohorts, and tiles while a writer re-annotates one
    of the datasets (segment republish under read load); read admission is
    squeezed (``read.max_concurrent=2`` + a slowed cache-fill seam) so
    structured 429s demonstrably occur; one replica is taken out of
    rotation and shut down mid-storm.  Asserts: every read answered 200 or
    cleanly shed 429 (reason ``read_overload`` + Retry-After), p99 read
    latency bounded, cache hits visible on /metrics, every write terminal,
    and the final response exactly matches the on-disk segment — never a
    torn or stale one."""
    import random as _random

    overrides = {
        "storage": {"store_images": True},
        "service": {
            "replicas": 2, "spool_shards": 8,
            "replica_heartbeat_interval_s": 0.2,
            "replica_stale_after_s": 1.5, "takeover_interval_s": 0.3,
            "admission": {"max_queue_depth": 32},
            "read": {"max_concurrent": 2, "retry_after_s": 1.0},
        },
    }
    h1 = Harness(base, "read", sm_overrides=_merge(
        dict(overrides), {"service": {"replica_id": "r1"}}))
    h2 = Harness(base, "read", sm_overrides=_merge(
        dict(overrides), {"service": {"replica_id": "r2"}}))
    prev = failpoints.active_spec()
    try:
        # seed two datasets so cohorts span segments and tiles exist
        seeds = []
        for ds in ("read_a", "read_b"):
            status, _hd, body = h1.submit(_msg(fx, "fast", ds))
            _check(status == 202, f"read: seed submit shed ({status})")
            seeds.append(body["msg_id"])
        _wait_done(h1.root, seeds)
        seg_a = h1.dir / "results" / "read_a" / "segment.npz"
        _check(seg_a.exists(), "read: seed run published no segment")
        npz = h1.dir / "results" / "read_a" / "ion_images.npz"
        _check(npz.exists(), "read: seed run stored no ion images")
        from sm_distributed_tpu.engine.storage import SearchResultsStore

        _imgs, ions = SearchResultsStore.load_ion_images(npz)
        _check(ions, "read: empty ion-image npz")
        sf = ions[0][0]
        ion = urllib.parse.quote(f"{ions[0][0]}|{ions[0][1]}", safe="")
        paths = [
            "/datasets",
            "/datasets/read_a/annotations?order=msm&limit=2",
            "/datasets/read_a/annotations?fdr=0.5",
            "/datasets/read_b/annotations",
            f"/annotations?sf={sf}",
            f"/datasets/read_a/images/{ion}",
        ]
        # slow every cache fill so the 2-slot read admission demonstrably
        # sheds under 6 concurrent readers (sleeps only on MISSES — hits
        # stay fast, which is also what makes the p99 bound meaningful)
        failpoints.configure("read.cache_fill=sleep:0.05")
        targets = [h1.base, h2.base]
        results: list[tuple[int, dict, bytes, float]] = []
        res_lock = threading.Lock()
        writes: list[str] = []

        def _reader(seed: int) -> None:
            rng = _random.Random(seed)
            for _ in range(reads_each):
                t = rng.choice(list(targets))
                t0 = time.monotonic()
                status, headers, raw = _http_raw(t, rng.choice(paths))
                dt = time.monotonic() - t0
                with res_lock:
                    results.append((status, headers, raw, dt))
                time.sleep(0.02)      # pace the storm across the replica kill

        threads = [threading.Thread(target=_reader, args=(i,))
                   for i in range(n_readers)]
        for t in threads:
            t.start()
        # ~10% write plane: re-annotate read_a under the read storm — each
        # store atomically republishes the segment beneath the readers
        for i in range(n_writes):
            status, _hd, body = h1.submit(
                _msg(fx, "fast", "read_a", msg_id=f"rw{i}"))
            _check(status == 202, f"read: write {i} shed ({status})")
            writes.append(body["msg_id"])
            time.sleep(0.15)
            if i == n_writes // 2:
                # replica loss mid-storm: out of rotation first (what a
                # load balancer's health check does), a beat for issued
                # requests to land, then drain — every in-flight read
                # finishes, later reads route to r1
                targets[:] = [h1.base]
                time.sleep(0.3)
                h2.shutdown()
        for t in threads:
            t.join(timeout=120.0)
        failpoints.configure(None)
        _wait_done(h1.root, writes)
        # ---- asserts ----------------------------------------------------
        statuses = sorted({s for s, _h, _r, _d in results})
        _check(set(statuses) <= {200, 429},
               f"read: non-clean read outcomes {statuses}")
        sheds = [(h, r) for s, h, r, _d in results if s == 429]
        _check(sheds, "read: admission squeeze produced no 429s")
        for headers, raw in sheds:
            body = json.loads(raw)
            _check(body.get("reason") == "read_overload"
                   and "retry_after_s" in body,
                   f"read: unstructured shed body {body}")
            _check("Retry-After" in headers,
                   f"read: shed missing Retry-After: {headers}")
        lats = sorted(d for s, _h, _r, d in results if s == 200)
        _check(lats, "read: no successful reads")
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))]
        _check(p99 <= p99_bound_s,
               f"read: p99 read latency {p99:.3f}s > {p99_bound_s}s")
        text = h1.metrics_text()
        hits = sum(
            float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("sm_read_cache_hits_total{"))
        _check(hits > 0, "read: no cache hits on /metrics")
        _check("sm_read_requests_total" in text
               and "sm_read_latency_seconds" in text,
               "read: sm_read_* families missing from /metrics")
        # freshness + integrity: the final response must be exactly the
        # on-disk segment the last write published — never torn, never a
        # stale pre-republish cache entry
        from sm_distributed_tpu.engine.index import _load_file

        seg = _load_file(seg_a)
        status, _hd, raw = _http_raw(h1.base, "/datasets/read_a/annotations")
        _check(status == 200, f"read: final read failed ({status})")
        final = json.loads(raw)
        _check(final["published_at"] == seg.published_at
               and final["total"] == seg.n_rows,
               f"read: served view (job {final['job_id']} at "
               f"{final['published_at']}) != on-disk segment "
               f"(job {seg.job_id} at {seg.published_at})")
        n_reads = len(results)
        print(f"  read: {n_reads} reads ({len(sheds)} shed 429, "
              f"p99 {p99 * 1000:.0f}ms, {int(hits)} cache hits) + "
              f"{n_writes + 2} writes over 2 replicas, r2 retired "
              f"mid-storm; final view matches the on-disk segment")
    finally:
        failpoints.configure(prev)
        h1.shutdown()
        h2.shutdown()


def _wait_done(root: Path, msg_ids: list[str],
               timeout_s: float = 120.0, label: str = "read") -> None:
    """Spool-census wait (works across replicas, unlike one /jobs view)."""
    deadline = time.time() + timeout_s
    want = set(msg_ids)
    while time.time() < deadline:
        done = {p.stem for p in (root / "done").glob("*.json")}
        if want <= done:
            return
        bad = {p.stem for p in (root / "failed").glob("*.json")} & want
        if bad:
            raise SweepError(f"{label}: jobs dead-lettered: {sorted(bad)}")
        time.sleep(0.05)
    raise SweepError(f"{label}: jobs never drained: "
                     f"{sorted(want - done)}")


def mix_stream(base: Path, fx: dict, n_batch: int = 6,
               n_chunks: int = 3, n_readers: int = 2) -> None:
    """Mixed live/batch/read plane (ISSUE 19): two live acquisitions
    streamed chunk-by-chunk over HTTP into TWO in-process replicas sharing
    one spool, while a batch burst contends for the worker pool and
    readers poll the published golden dataset.  One replica is DRAINED
    mid-acquisition: its live stream hands off to the peer without
    burning an attempt (``stream.drain_handoff``) and resumes from the
    committed chunk log.  Asserts: batch traffic never starves the
    provisional re-ranks (coverage advances after every chunk group),
    every read answers 200 across the drain, both streams converge
    BIT-IDENTICALLY (``check_exact``) to the batch report of the same
    spectra, every job lands terminal in ``done/`` exactly once, and the
    sm_stream_* families + the stream-partial SLO are live."""
    import pandas as pd

    from sm_distributed_tpu.io.imzml import ImzMLReader
    from sm_distributed_tpu.service.leases import owned_shards, shard_of

    shards = 8
    overrides = {"service": {
        # 3 workers per replica: a live acquisition pins a worker for its
        # whole lifetime, the rest keep the batch burst moving
        "workers": 3,
        "replicas": 2, "spool_shards": shards,
        "replica_heartbeat_interval_s": 0.2,
        "replica_stale_after_s": 1.5, "takeover_interval_s": 0.3,
        "admission": {"max_queue_depth": 16, "max_tenant_inflight": 16},
        "stream": {"idle_timeout_s": 30.0, "poll_interval_s": 0.02,
                   "rescore_min_chunks": 1},
    }}
    h1 = Harness(base, "stream", sm_overrides=_merge(
        dict(overrides), {"service": {"replica_id": "r1"}}))
    h2 = Harness(base, "stream", sm_overrides=_merge(
        dict(overrides), {"service": {"replica_id": "r2"}}))
    try:
        with ImzMLReader(fx["fast"]["input_path"]) as rd:
            coords = rd.coordinates.tolist()
            spectra = [tuple(a.tolist() for a in rd.read_spectrum(i))
                       for i in range(rd.n_spectra)]
        n = len(coords)
        edges = [round(i * n / n_chunks) for i in range(n_chunks + 1)]
        # batch golden of the SAME spectra — the convergence target AND
        # the published dataset the read plane polls during acquisition
        status, _hd, body = h1.submit(_msg(fx, "fast", "stream_gold"))
        _check(status == 202, f"stream: golden submit shed ({status})")
        gold_id = body["msg_id"]
        _wait_done(h1.root, [gold_id], label="stream")
        batch_ids = [gold_id]
        # one acquisition shard-owned by EACH replica, so the drain below
        # demonstrably hands a live stream across the replica boundary
        r2_shards = owned_shards("r2", {"r1", "r2"}, shards)
        cands = [f"stream_{c}" for c in "abcdefghijklmnop"]
        ds_r1 = next(c for c in cands if shard_of(c, shards) not in r2_shards)
        ds_r2 = next(c for c in cands if shard_of(c, shards) in r2_shards)
        streams = (ds_r1, ds_r2)
        owner = {ds_r1: h1, ds_r2: h2}
        stream_ids = {}
        for ds in streams:
            msg = {"ds_id": ds, "msg_id": ds, "mode": "stream",
                   "formulas": fx["fast"]["formulas"],
                   "ds_config": fx["fast"]["ds_config"]}
            status, _hd, body = h1.submit(msg)
            _check(status == 202, f"stream: {ds} submit shed ({status})")
            stream_ids[ds] = body["msg_id"]
        # read plane: readers poll the golden's published annotations on
        # both replicas for the whole acquisition — every read must
        # answer 200, including across the drain
        paths = ["/datasets", "/datasets/stream_gold/annotations?limit=3",
                 "/datasets/stream_gold/annotations?order=msm"]
        targets = [h1.base, h2.base]
        stop_reads = threading.Event()
        reads: list[int] = []
        reads_lock = threading.Lock()

        def _reader(seed: int) -> None:
            i = seed
            while not stop_reads.is_set():
                ts = list(targets)
                try:
                    status, _hd, _b = _http(ts[i % len(ts)], "GET",
                                            paths[i % len(paths)])
                except OSError:
                    status = -1       # connection-level failure: fail loud
                with reads_lock:
                    reads.append(status)
                i += 1
                time.sleep(0.02)

        readers = [threading.Thread(target=_reader, args=(i,))
                   for i in range(n_readers)]
        for t in readers:
            t.start()
        drained = False
        for seq in range(n_chunks):
            lo, hi = edges[seq], edges[seq + 1]
            chunk = {"seq": seq, "coords": coords[lo:hi],
                     "mzs": [s[0] for s in spectra[lo:hi]],
                     "ints": [s[1] for s in spectra[lo:hi]]}
            for ds in streams:
                # every chunk lands on r1's ingest API — the shared work
                # dir means ingest is not pinned to the claim owner
                status, _hd, body = _http(
                    h1.base, "POST", f"/datasets/{ds}/pixels", chunk)
                _check(status == 200,
                       f"stream: {ds} chunk {seq} rejected ({status} {body})")
            # batch load lands BETWEEN chunk groups, contending for the
            # spare workers while both streams re-rank
            for _ in range(n_batch // n_chunks):
                i = len(batch_ids)
                status, _hd, body = h1.submit(
                    _msg(fx, "fast", f"smix{i}", tenant=f"t{i % 3}"))
                _check(status == 202, f"stream: batch {i} shed ({status})")
                batch_ids.append(body["msg_id"])
            # liveness under load: provisional coverage must reach this
            # chunk group on both streams before the next one is acquired
            # (polled on each stream's CLAIM OWNER — job records are
            # per-replica in-memory; the spool is what's shared)
            deadline = time.time() + 60.0
            lagging = dict(stream_ids)
            while lagging and time.time() < deadline:
                for ds, mid in list(lagging.items()):
                    _s, _hd, job = _http(owner[ds].base, "GET",
                                         f"/jobs/{mid}")
                    part = (job.get("partial") or {}).get("stream") or {}
                    if part.get("chunks", 0) >= seq + 1:
                        del lagging[ds]
                time.sleep(0.05)
            _check(not lagging,
                   f"stream: re-rank starved under batch load at chunk "
                   f"{seq}: {sorted(lagging)}")
            if not drained:
                # replica retired MID-ACQUISITION: r2 drains while its
                # live stream still has chunks to come — the stream job
                # must republish without burning an attempt and resume on
                # r1 from the committed chunk log.  Out of read rotation
                # first, a beat for issued reads to land, then drain.
                drained = True
                before = failpoints.recovery_counts().get(
                    "stream.drain_handoff", 0)
                targets[:] = [h1.base]
                time.sleep(0.3)
                h2.shutdown()
                got = failpoints.recovery_counts().get(
                    "stream.drain_handoff", 0)
                _check(got > before,
                       "stream: drain recorded no stream.drain_handoff")
                owner[ds_r2] = h1
        for ds in streams:
            status, _hd, body = _http(h1.base, "POST",
                                      f"/datasets/{ds}/finish", {})
            _check(status == 200, f"stream: {ds} finish failed "
                                  f"({status} {body})")
        _wait_done(h1.root, batch_ids + list(stream_ids.values()),
                   label="stream")
        stop_reads.set()
        for t in readers:
            t.join(timeout=30.0)
        _check(reads, "stream: read plane issued no reads")
        bad_reads = sorted({s for s in reads if s != 200})
        _check(not bad_reads,
               f"stream: read plane saw non-200 outcomes {bad_reads}")
        # bit-identity: each streamed report == the batch report of the
        # same spectra, down to the last bit (the ISSUE 19 tentpole) —
        # including the stream that crossed the replica boundary
        def _report(ds):
            return read_result_tables(h1.dir / "results" / ds)
        gold = _report("stream_gold")
        for ds in streams:
            got = _report(ds)
            for label, g, w in zip(RESULT_TABLES, got, gold):
                try:
                    pd.testing.assert_frame_equal(g, w, check_exact=True)
                except AssertionError as e:
                    raise SweepError(
                        f"stream: {ds} {label} not bit-identical to "
                        f"batch: {str(e).splitlines()[-1]}") from e
        text = h1.metrics_text()
        chunks_total = sum(
            float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("sm_stream_chunks_total"))
        _check(chunks_total == len(streams) * n_chunks,
               f"stream: sm_stream_chunks_total {chunks_total} != "
               f"{len(streams) * n_chunks}")
        _check("sm_stream_reranks_total" in text
               and "sm_stream_pixels_total" in text,
               "stream: sm_stream_* families missing from /metrics")
        _s, _hd, slo = _http(h1.base, "GET", "/slo")
        _check("stream_partial" in slo.get("slos", {}),
               "stream: stream_partial SLO missing from /slo")
        h1.assert_clean("stream")
        print(f"  stream: {len(streams)} live acquisitions x {n_chunks} "
              f"chunks + {len(batch_ids)} batch jobs + {len(reads)} reads "
              f"over 2 replicas, r2 drained mid-acquisition; provisional "
              f"coverage kept pace, reports bit-identical to batch")
    finally:
        h1.shutdown()
        h2.shutdown()


# ------------------------------------------------------------------- driver
def run_sweep(work: Path, smoke: bool = False, elastic_only: bool = False,
              read_only: bool = False, pod_only: bool = False,
              stream_only: bool = False) -> int:
    # lock-order detection (ISSUE 9): instrument every lock the service
    # stack creates below and fail the sweep on an acquisition-order cycle
    # — the load mixes drive scheduler workers, dispatcher, watchdog,
    # admission, device pool, telemetry, AND the fleet controller
    # concurrently, which is exactly the thread population a lurking
    # inversion needs
    from sm_distributed_tpu.analysis import lockorder

    lockorder.enable()
    work.mkdir(parents=True, exist_ok=True)
    t0 = time.time()
    try:
        if elastic_only:
            print("load sweep (elastic-fleet stage)")
            mix_elastic(work)
        elif pod_only:
            print("load sweep (pod host-loss stage)")
            mix_pod(work)
        elif read_only:
            print("load sweep (read-plane stage)")
            mix_read(work, build_fixtures(work))
        elif stream_only:
            print("load sweep (live-acquisition stage)")
            mix_stream(work, build_fixtures(work))
        else:
            fx = build_fixtures(work)
            h = Harness(work, "main")
            try:
                print(f"load sweep ({'smoke' if smoke else 'full'}) "
                      f"at {h.base}")
                mix_burst(h, fx, n_submit=(12 if smoke else 24))
                if not smoke:
                    mix_sustained(h, fx, n_submit=10, gap_s=0.1)
                    mix_cancel(h, fx)
                mix_deadline(h, fx)
                mix_poison(h, fx)
            finally:
                h.shutdown()
            if not smoke:
                mix_breaker(work, fx)
                mix_device_fault(work, fx)
                mix_disk(work, fx)
                mix_replicas(work)
                mix_pod(work)
                mix_read(work, fx)
                mix_stream(work, fx)
                mix_elastic(work)
        rep = lockorder.assert_no_cycles("load sweep")
        print(f"lock-order: no cycles ({rep['locks_instrumented']} locks, "
              f"{rep['edges']} order edges observed)")
    finally:
        lockorder.disable()
    print(f"load sweep OK ({time.time() - t0:.1f}s)")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: burst + deadline + poison")
    ap.add_argument("--elastic", action="store_true",
                    help="run only the elastic-fleet mix (1→4→2 wave with "
                         "exactly-once + clean-drain asserts)")
    ap.add_argument("--read", action="store_true",
                    help="run only the read-plane mix (~90/10 read/write "
                         "over two replicas, structured 429 sheds, p99 "
                         "bound, cache-hit ratio, replica kill mid-storm)")
    ap.add_argument("--pod", action="store_true",
                    help="run only the pod host-loss mix (2 hosts x 2 "
                         "replicas, host h1 SIGKILLed whole mid-sweep, "
                         "exactly-once + p99 + watchdog-eviction asserts)")
    ap.add_argument("--stream", action="store_true",
                    help="run only the live-acquisition mix (two streams "
                         "chunked over HTTP under a batch burst, provisional "
                         "re-rank liveness, check_exact batch convergence)")
    ap.add_argument("--work", default=None)
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args(argv)
    import shutil
    import tempfile

    work = Path(args.work) if args.work else Path(
        tempfile.mkdtemp(prefix="sm_load_"))
    try:
        return run_sweep(work, smoke=args.smoke, elastic_only=args.elastic,
                         read_only=args.read, pod_only=args.pod,
                         stream_only=args.stream)
    except SweepError as exc:
        print(f"load sweep FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        if not args.keep and args.work is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
