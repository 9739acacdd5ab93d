"""Crash-recovery chaos tests (ISSUE 2): the chaos sweep's crash-and-restart
convergence invariants, checkpoint resume under torn/corrupt trailing shards,
the orphaned-tmp startup sweep, and concurrent sqlite ledger access."""

import json
import os
import threading
import time
import zlib

import numpy as np
import pytest

import scripts.chaos_sweep as chaos
from sm_distributed_tpu.engine.daemon import QueueConsumer, sweep_orphan_tmp
from sm_distributed_tpu.engine.storage import JobLedger
from sm_distributed_tpu.models.msm_basic import SearchCheckpoint
from sm_distributed_tpu.utils import failpoints as fp


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.reset()
    yield
    fp.reset()


# ----------------------------------------------------------- chaos sweep
def _assert_sweep_ok(results):
    bad = [r for r in results if not r["ok"]]
    assert not bad, "\n".join(
        f"{r['scenario']}: {r.get('error')}\n{r.get('output_tail', '')}"
        for r in bad)


def test_chaos_smoke_subset(tmp_path):
    """The CI subset (3 failpoints): crash-at-failpoint + restart converges
    to the fault-free golden report with no lost messages or tmp debris."""
    _assert_sweep_ok(chaos.run_sweep(tmp_path, only=list(chaos.SMOKE)))


@pytest.mark.slow
def test_chaos_full_sweep(tmp_path):
    """Every registered failpoint, crashed and recovered in turn."""
    _assert_sweep_ok(chaos.run_sweep(tmp_path))


def test_every_failpoint_has_a_scenario():
    registered = set(fp.registered_failpoints())
    primaries = {sc.primary for sc in chaos.SCENARIOS}
    assert registered == primaries, (
        f"uncovered: {sorted(registered - primaries)}, "
        f"phantom: {sorted(primaries - registered)}")


# ------------------------------------------------- checkpoint corruption
def _make_checkpoint(tmp_path, n_groups=3, rows_per=10):
    ck = SearchCheckpoint(tmp_path, "fp-test")
    rng = np.random.default_rng(0)
    metrics = rng.random((n_groups * rows_per, 4))
    row_ranges = [(i * rows_per, (i + 1) * rows_per) for i in range(n_groups)]
    for gi in range(n_groups):
        ck.save(metrics, gi, n_groups, row_ranges)
    return ck, metrics, row_ranges


def test_checkpoint_truncated_trailing_shard_degrades_to_prefix(tmp_path):
    """ISSUE 2 satellite: a torn (truncated) trailing .npz shard is treated
    as missing — resume trusts the prefix before it and recomputes the rest,
    instead of crashing in np.load."""
    ck, metrics, row_ranges = _make_checkpoint(tmp_path)
    shard = ck._shard(2)
    blob = shard.read_bytes()
    shard.write_bytes(blob[: len(blob) // 2])

    out = np.zeros_like(metrics)
    assert ck.load(out, 3, row_ranges) == 2
    assert np.array_equal(out[:20], metrics[:20])
    assert (out[20:] == 0).all()
    assert fp.recovery_counts().get("ckpt.corrupt_shard") == 1

    # a truncated FIRST shard invalidates everything after it too
    blob0 = ck._shard(0).read_bytes()
    ck._shard(0).write_bytes(blob0[: len(blob0) // 3])
    assert ck.load(np.zeros_like(metrics), 3, row_ranges) == 0


def test_checkpoint_checksum_catches_silent_row_corruption(tmp_path):
    """np.load accepts a structurally-valid npz whose rows were swapped or
    rewritten; the CRC32 in the shard does not."""
    ck, metrics, row_ranges = _make_checkpoint(tmp_path)
    rows = np.random.default_rng(1).random((10, 4))    # plausible but wrong
    np.savez(ck._shard(1), fingerprint=np.str_("fp-test"), rows=rows,
             n_groups=3, checksum=zlib.crc32(metrics[10:20].tobytes()))
    out = np.zeros_like(metrics)
    assert ck.load(out, 3, row_ranges) == 1
    assert np.array_equal(out[:10], metrics[:10])
    assert (out[10:] == 0).all()


def test_checkpoint_zero_byte_shard(tmp_path):
    ck, metrics, row_ranges = _make_checkpoint(tmp_path)
    ck._shard(0).write_bytes(b"")
    assert ck.load(np.zeros_like(metrics), 3, row_ranges) == 0


# ------------------------------------------------------ orphan tmp sweep
def test_orphan_tmp_sweep_age_gated(tmp_path):
    """ISSUE 2 satellite: a crash between a publish's tmp write and its
    os.replace leaks `.{msg_id}.tmp` in pending/ forever; the startup sweep
    removes old orphans but never an in-flight publish."""
    consumer = QueueConsumer(tmp_path / "q", callback=None)
    pending = consumer.root / "pending"

    old_pub = pending / ".deadbeef.tmp"            # publisher-style orphan
    old_retry = pending / ".m01.json.tmp"          # scheduler-retry orphan
    fresh = pending / ".inflight.tmp"              # being written right now
    real = pending / "m02.json"                    # a live message
    for p in (old_pub, old_retry, fresh):
        p.write_text("{}")
    real.write_text(json.dumps({"ds_id": "d", "input_path": "/in"}))
    old = time.time() - 600
    os.utime(old_pub, (old, old))
    os.utime(old_retry, (old, old))

    assert consumer.sweep_orphans(max_age_s=30.0) == 2
    assert not old_pub.exists() and not old_retry.exists()
    assert fresh.exists(), "an in-flight publish tmp must survive"
    assert real.exists(), "real messages are untouchable"
    assert fp.recovery_counts().get("spool.orphan_tmp") == 2
    # crash-recovery callers that know the writers are dead sweep everything
    assert sweep_orphan_tmp(consumer.root, max_age_s=0.0) == 1
    assert not fresh.exists()


def test_scheduler_start_sweeps_orphans(tmp_path):
    from sm_distributed_tpu.service import JobScheduler
    from sm_distributed_tpu.utils.config import ServiceConfig

    sched = JobScheduler(
        tmp_path / "q", lambda msg: None,
        config=ServiceConfig(workers=1, poll_interval_s=0.05,
                             stale_after_s=30.0, http_port=0))
    orphan = sched.root / "pending" / ".crashed.tmp"
    orphan.write_text("{}")
    old = time.time() - 600
    os.utime(orphan, (old, old))
    sched.start()
    try:
        assert not orphan.exists()
    finally:
        sched.shutdown()


# ------------------------------------------------------ sqlite robustness
def test_ledger_concurrent_writers_no_database_locked(tmp_path):
    """ISSUE 2 satellite: concurrent scheduler workers each hold their own
    connection to the one ledger file; WAL + busy timeout must absorb the
    write collisions that killed them with 'database is locked' before."""
    errors: list[Exception] = []

    def worker(k: int):
        try:
            ledger = JobLedger(tmp_path)
            for i in range(8):
                ledger.upsert_dataset(f"ds{k}", f"ds{k}", "/in", {})
                job_id = ledger.start_job(f"ds{k}")
                if i % 2:
                    ledger.finish_job(job_id)
                else:
                    ledger.fail_job(job_id, "boom")
            ledger.close()
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    ledger = JobLedger(tmp_path)
    try:
        jobs = ledger.jobs()
        assert len(jobs) == 6 * 8
        assert not (jobs.status == "STARTED").any()
        mode = ledger._conn.execute("PRAGMA journal_mode").fetchone()[0]
        assert str(mode).lower() == "wal"
    finally:
        ledger.close()


def test_ledger_waits_for_the_wal_switch_of_a_fresh_database(
        tmp_path, monkeypatch):
    """Two jobs that start together on a new results directory: sqlite
    answers the second connection's ``PRAGMA journal_mode=WAL`` with
    "database is locked" at once while the first makes the switch (the busy
    timeout is not consulted there).  The ledger waits and asks again."""
    import sqlite3

    from sm_distributed_tpu.engine import storage

    refused = []

    class _BusyAtTheSwitch(sqlite3.Connection):
        def execute(self, sql, *args):
            if sql.startswith("PRAGMA journal_mode=WAL") and len(refused) < 3:
                refused.append(sql)
                raise sqlite3.OperationalError("database is locked")
            return super().execute(sql, *args)

    real = sqlite3.connect
    monkeypatch.setattr(
        storage.sqlite3, "connect",
        lambda *a, **kw: real(*a, factory=_BusyAtTheSwitch, **kw))
    ledger = JobLedger(tmp_path)
    try:
        assert len(refused) == 3
        mode = ledger._conn.execute("PRAGMA journal_mode").fetchone()[0]
        assert str(mode).lower() == "wal"
        ledger.upsert_dataset("a", "a", "/in", {})
        assert ledger.job_status(ledger.start_job("a")) == "STARTED"
    finally:
        ledger.close()
    # any other operational error is not waited for
    monkeypatch.setattr(_BusyAtTheSwitch, "execute", lambda self, sql, *a: (
        _ for _ in ()).throw(sqlite3.OperationalError("disk I/O error")))
    with pytest.raises(sqlite3.OperationalError, match="disk I/O"):
        JobLedger(tmp_path / "other")


def test_ledger_fail_stale_started_scoped(tmp_path):
    ledger = JobLedger(tmp_path)
    try:
        ledger.upsert_dataset("a", "a", "/in", {})
        ledger.upsert_dataset("b", "b", "/in", {})
        ja = ledger.start_job("a")
        jb = ledger.start_job("b")
        assert ledger.fail_stale_started("a") == 1
        assert ledger.job_status(ja) == "FAILED"
        assert ledger.job_status(jb) == "STARTED"
        assert ledger.fail_stale_started() == 1     # unscoped sweeps the rest
        assert ledger.fail_stale_started() == 0
    finally:
        ledger.close()
