"""Multi-replica scheduler protocol (ISSUE 8): spool shards, rendezvous
ownership, fenced lease claims, fence-rejection races, replica takeover,
peer-aware admission, and the /peers endpoint."""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import pytest

from sm_distributed_tpu.engine.daemon import (
    QueuePublisher,
    sweep_orphan_tmp,
)
from sm_distributed_tpu.engine.storage import JobLedger
from sm_distributed_tpu.service.admission import AdmissionController
from sm_distributed_tpu.service.leases import (
    FenceRejectedError,
    LeaseStore,
    ReplicaRegistry,
    owned_shards,
    shard_of,
)
from sm_distributed_tpu.service.metrics import MetricsRegistry
from sm_distributed_tpu.service.scheduler import JobScheduler
from sm_distributed_tpu.utils.config import AdmissionConfig, ServiceConfig

QUEUE = "sm_annotate"


def _cfg(**kw) -> ServiceConfig:
    base = dict(workers=1, poll_interval_s=0.02, job_timeout_s=10.0,
                max_attempts=2, backoff_base_s=0.02, backoff_max_s=0.05,
                backoff_jitter=0.0, heartbeat_interval_s=0.1,
                stale_after_s=0.5, drain_timeout_s=5.0,
                spool_shards=8, replica_heartbeat_interval_s=0.1,
                replica_stale_after_s=0.6, takeover_interval_s=0.1)
    base.update(kw)
    return ServiceConfig(**base)


# ------------------------------------------------------------------ shards
def test_shard_of_stable_and_bounded():
    for p in (1, 2, 8, 64):
        for mid in ("a", "m0", "x" * 40):
            s = shard_of(mid, p)
            assert 0 <= s < max(1, p)
            assert s == shard_of(mid, p)          # deterministic
    assert shard_of("anything", 1) == 0


def test_rendezvous_ownership_partitions_and_rebalances():
    replicas = {"r0", "r1", "r2"}
    owned = {r: owned_shards(r, replicas, 16) for r in replicas}
    # a partition: disjoint and complete
    all_shards = set()
    for r, s in owned.items():
        assert not all_shards & s
        all_shards |= s
    assert all_shards == set(range(16))
    # every replica computes the same assignment from the same alive set
    assert owned_shards("r1", {"r0", "r1", "r2"}, 16) == owned["r1"]
    # killing r0 moves ONLY r0's shards; survivors keep theirs (minimal
    # movement is the point of rendezvous hashing)
    owned_after = {r: owned_shards(r, {"r1", "r2"}, 16) for r in ("r1", "r2")}
    for r in ("r1", "r2"):
        assert owned[r] <= owned_after[r]
    assert owned_after["r1"] | owned_after["r2"] == set(range(16))
    # single replica owns everything
    assert owned_shards("solo", {"solo"}, 8) == set(range(8))


# ------------------------------------------------------------------ leases
def test_lease_claim_renew_check_roundtrip(tmp_path):
    store = LeaseStore(tmp_path, "r0", epoch=1)
    lease = store.claim("m1")
    assert lease.fence == 1
    store.check(lease)                             # holder passes
    assert store.renew(lease) is True
    store.check(lease)
    # release keeps the fence; the next claim bumps past it
    store.release(lease)
    lease2 = store.claim("m1")
    assert lease2.fence == 2
    with pytest.raises(FenceRejectedError):
        store.check(lease)                         # ghost holder rejected


def test_fence_bump_rejects_stale_holder(tmp_path):
    a = LeaseStore(tmp_path, "rA", epoch=1)
    b = LeaseStore(tmp_path, "rB", epoch=1)
    la = a.claim("m1")
    # takeover: B fences A out, then re-claims
    b.bump("m1")
    assert a.renew(la) is False                    # renewal discovers the loss
    with pytest.raises(FenceRejectedError):
        a.check(la)
    lb = b.claim("m1")
    b.check(lb)                                    # the new holder passes
    # terminal clear: EVERY outstanding token is now rejected
    b.clear("m1")
    with pytest.raises(FenceRejectedError):
        b.check(lb)


def test_lease_epoch_distinguishes_restarted_holder(tmp_path):
    old = LeaseStore(tmp_path, "r0", epoch=1)
    lease_old = old.claim("m1")
    new = LeaseStore(tmp_path, "r0", epoch=2)      # same id, restarted
    new.claim("m1")
    with pytest.raises(FenceRejectedError):
        old.check(lease_old)


def test_lease_orphan_sweep(tmp_path):
    root = tmp_path / "q"
    (root / "pending").mkdir(parents=True)
    (root / "running").mkdir(parents=True)
    store = LeaseStore(root, "r0")
    store.claim("gone")                            # message never spooled
    store.claim("kept")
    (root / "pending" / "kept.json").write_text("{}")
    assert store.sweep_orphans(root, max_age_s=0.0) == 1
    assert (store.dir / "kept.json").exists()
    assert not (store.dir / "gone.json").exists()


# ---------------------------------------------------------------- registry
def test_registry_register_beat_alive_retire(tmp_path):
    a = ReplicaRegistry(tmp_path, "r0", stale_after_s=5.0)
    assert a.register() == 1
    b = ReplicaRegistry(tmp_path, "r1", stale_after_s=5.0)
    b.register()
    assert a.alive() == {"r0", "r1"}
    peers = {p["replica_id"]: p for p in a.peers()}
    assert peers["r1"]["alive"] is True
    b.retire()
    assert a.alive() == {"r0"}
    # a restart bumps the epoch
    assert ReplicaRegistry(tmp_path, "r0").register() == 2


def test_registry_staleness(tmp_path):
    a = ReplicaRegistry(tmp_path, "r0", stale_after_s=0.2)
    a.register()
    b = ReplicaRegistry(tmp_path, "r1", stale_after_s=0.2)
    b.register()
    time.sleep(0.3)
    a.beat()
    assert a.alive() == {"r0"}                     # r1's beat lapsed


# ------------------------------------------------- ledger/daemon satellites
def test_fail_stale_started_scoped_to_ds_ids_and_before(tmp_path):
    ledger = JobLedger(tmp_path)
    try:
        for ds in ("a", "b"):
            ledger.upsert_dataset(ds, ds, "x", {})
        ledger.start_job("a")
        cutoff = time.time() + 0.01
        time.sleep(0.02)
        live = ledger.start_job("b")               # a live peer's fresh row
        # scoped: only dataset "a", only rows before the takeover instant
        assert ledger.fail_stale_started(ds_ids=["a", "b"],
                                         before=cutoff) == 1
        assert ledger.job_status(live) == "STARTED"
        assert ledger.fail_stale_started(ds_ids=[]) == 0
        # ds_ids excludes datasets not listed
        assert ledger.fail_stale_started(ds_ids=["zz"]) == 0
    finally:
        ledger.close()


def test_sweep_orphan_tmp_scoped_to_shards(tmp_path):
    root = tmp_path / QUEUE
    (root / "pending").mkdir(parents=True)
    ids = [f"m{i}" for i in range(8)]
    for mid in ids:
        (root / "pending" / f".{mid}.tmp").write_text("x")
    total = 4
    mine = {s for s in range(total) if s % 2 == 0}
    swept = sweep_orphan_tmp(root, max_age_s=0.0, shards=mine,
                             total_shards=total)
    expect = sum(1 for mid in ids if shard_of(mid, total) in mine)
    assert swept == expect
    left = list((root / "pending").glob(".*.tmp"))
    assert len(left) == len(ids) - expect
    # unscoped sweeps the rest
    assert sweep_orphan_tmp(root, max_age_s=0.0) == len(left)


# ------------------------------------------------------- scheduler protocol
def _publish(queue_dir: Path, msg_id: str, **extra) -> None:
    QueuePublisher(queue_dir).publish(
        {"ds_id": msg_id, "msg_id": msg_id, "input_path": "null://", **extra})


def test_single_replica_owns_all_shards_and_drains(tmp_path):
    done = []
    sched = JobScheduler(tmp_path, lambda msg: done.append(msg["msg_id"]),
                         config=_cfg())
    assert sched._owned == set(range(8))
    for i in range(4):
        _publish(tmp_path, f"m{i}")
    sched.start()
    assert sched.wait_for_terminal(4, timeout_s=20.0)
    sched.shutdown()
    assert sorted(done) == [f"m{i}" for i in range(4)]
    root = tmp_path / QUEUE
    # terminal outcomes cleared their leases
    assert not list((root / "leases").glob("*.json"))
    assert len(list((root / "done").glob("*.json"))) == 4


def test_two_replicas_partition_claims(tmp_path):
    """Each replica only claims its own shards; together they drain all."""
    claimed: dict[str, list[str]] = {"r1": [], "r2": []}

    def make_cb(rid):
        def cb(msg):
            claimed[rid].append(msg["msg_id"])
        return cb

    scheds = [JobScheduler(tmp_path, make_cb(rid),
                           config=_cfg(replica_id=rid, replicas=2))
              for rid in ("r1", "r2")]
    ids = [f"m{i}" for i in range(10)]
    for mid in ids:
        _publish(tmp_path, mid)
    for s in scheds:
        s.start()
    deadline = time.time() + 30.0
    root = tmp_path / QUEUE
    while time.time() < deadline and \
            len(list((root / "done").glob("*.json"))) < len(ids):
        time.sleep(0.05)
    for s in scheds:
        s.shutdown()
    assert sorted(claimed["r1"] + claimed["r2"]) == ids
    assert not set(claimed["r1"]) & set(claimed["r2"])   # exactly-once
    # the split follows the rendezvous shard map
    alive = {"r1", "r2"}
    for rid in ("r1", "r2"):
        owned = owned_shards(rid, alive, 8)
        for mid in claimed[rid]:
            assert shard_of(mid, 8) in owned


def test_takeover_requeues_dead_replica_claims(tmp_path):
    """A dead replica's stale claim is fenced + requeued by the survivor,
    whose rerun completes exactly once."""
    root = tmp_path / QUEUE
    # simulate the dead replica: a claim sitting in running/ with a stale
    # lease and no heartbeat (its process is gone)
    _publish(tmp_path, "dead1")
    dead_store = LeaseStore(root, "rdead", epoch=1)
    (root / "running").mkdir(parents=True, exist_ok=True)
    src = root / "pending" / "dead1.json"
    dst = root / "running" / "dead1.json"
    src.rename(dst)
    dead_lease = dead_store.claim("dead1")
    time.sleep(0.6)                               # age past stale_after_s
    done = []
    sched = JobScheduler(tmp_path, lambda m: done.append(m["msg_id"]),
                         config=_cfg(replica_id="r1"))
    sched.start()
    assert sched.wait_for_terminal(1, timeout_s=20.0)
    sched.shutdown()
    assert done == ["dead1"]
    assert (root / "done" / "dead1.json").exists()
    # the dead holder's token is now rejected at every write seam
    with pytest.raises(FenceRejectedError):
        dead_store.check(dead_lease)
    assert sched._fenced_count == 0               # the SURVIVOR was clean


def test_takeover_scan_spares_a_claim_in_progress(tmp_path):
    """A message claimed by rename keeps its publish-time mtime and has no
    lease until the dispatcher writes one: a takeover scan of the SAME
    replica that lands in between (here: exactly there) leaves the claim
    alone, and the job completes once, unfenced."""
    import os

    root = tmp_path / QUEUE
    _publish(tmp_path, "old1")
    aged = time.time() - 10.0                     # long past stale_after_s
    os.utime(root / "pending" / "old1.json", (aged, aged))
    done = []
    sched = JobScheduler(tmp_path, lambda m: done.append(m["msg_id"]),
                         config=_cfg(replica_id="r1"))
    lease_claim, scanned = sched.leases.claim, []

    def claim_after_a_scan(msg_id):
        assert (root / "running" / f"{msg_id}.json").exists()
        sched._takeover_scan()
        scanned.append(msg_id)
        return lease_claim(msg_id)

    sched.leases.claim = claim_after_a_scan
    sched.start()
    assert sched.wait_for_terminal(1, timeout_s=20.0)
    sched.shutdown()
    assert scanned == ["old1"] and done == ["old1"]
    assert (root / "done" / "old1.json").exists()
    assert not (root / "pending" / "old1.json").exists()
    assert sched._fenced_count == 0


def test_fence_race_two_replicas_one_completion(tmp_path):
    """The satellite race: two replicas end up claiming the same message
    around a lease expiry — exactly one completes; the loser's spool and
    ledger writes are all rejected."""
    root = tmp_path / QUEUE
    release = threading.Event()
    ran = []

    def slow_cb(msg, ctx):
        ran.append(msg["msg_id"])
        assert release.wait(20.0)
        # the loser reaches its commit only after being fenced: the
        # ctx.fence gate (what SearchJob calls pre-store/pre-ledger-commit)
        # must reject it HERE, before any durable write
        if ctx.fence is not None:
            ctx.fence()

    cfg_a = _cfg(replica_id="rA", heartbeat_interval_s=30.0,
                 stale_after_s=0.3)
    a = JobScheduler(tmp_path, slow_cb, config=cfg_a)
    _publish(tmp_path, "race1")
    a.start()
    deadline = time.time() + 10.0
    while time.time() < deadline and not ran:
        time.sleep(0.02)
    assert ran == ["race1"]
    # rA's claim heartbeat interval is 30 s: its claim looks dead within
    # 0.3 s.  rB takes over, fences rA, and completes the job itself.
    done_b = []

    def fast_cb(msg):
        done_b.append(msg["msg_id"])

    b = JobScheduler(tmp_path, fast_cb, config=_cfg(replica_id="rB",
                                                    stale_after_s=0.3))
    time.sleep(0.4)
    b.start()
    assert b.wait_for_terminal(1, timeout_s=20.0)
    assert done_b == ["race1"]
    # wake the loser: its fence gate rejects, the scheduler abandons all
    # writes, and the message is NOT moved/duplicated
    release.set()
    deadline = time.time() + 10.0
    while time.time() < deadline and a._fenced_count == 0:
        time.sleep(0.02)
    assert a._fenced_count == 1
    a.shutdown()
    b.shutdown()
    census = {s: [p.stem for p in (root / s).glob("*.json")]
              for s in ("pending", "running", "done", "failed")}
    assert census["done"] == ["race1"]
    assert not census["pending"] and not census["running"] \
        and not census["failed"]


def test_fenced_claim_frees_admission_slot(tmp_path):
    adm = AdmissionController(AdmissionConfig(max_queue_depth=4))
    d = adm.try_admit("t1")
    assert d.accepted
    adm.confirm("mfence", "t1")
    sched = JobScheduler(tmp_path, lambda m: None, config=_cfg(),
                         admission=adm)
    rec = sched._record("mfence")
    rec.tenant = "t1"
    lease = sched.leases.claim("mfence")
    with sched._records_lock:
        sched._lease_by_msg["mfence"] = lease
    sched.leases.bump("mfence")                   # a peer fences it out
    assert sched._fence_ok(rec, "complete") is False
    assert adm.stats()["depth"] == 0              # slot released
    assert sched._fenced_count == 1


# ------------------------------------------------------ peer-aware admission
def test_admission_peer_view_global_quota_and_shed():
    cfg = AdmissionConfig(max_queue_depth=10, max_tenant_inflight=4,
                          latency_shed_s=5.0)
    adm = AdmissionController(cfg)
    peers: list[dict] = []
    adm.set_peer_view(lambda: peers)
    assert adm.try_admit("t1").accepted
    # peers report the tenant near quota: 3 remote + 1 local = 4 → shed
    peers = [{"depth": 3, "tenants": {"t1": 3}, "latency_ewma_s": 0.1,
              "shedding": False}]
    d = adm.try_admit("t1")
    assert not d.accepted and d.reason == "tenant_quota"
    # another tenant still fits (global depth 1 local + 3 peer = 4 < 10)
    assert adm.try_admit("t2").accepted
    # peers at global depth bound → queue_full
    peers = [{"depth": 8, "tenants": {}, "latency_ewma_s": 0.1,
              "shedding": False}]
    d = adm.try_admit("t3")
    assert not d.accepted and d.reason == "queue_full"
    # a peer in latency shed drags this replica into shedding too
    peers = [{"depth": 0, "tenants": {}, "latency_ewma_s": 9.0,
              "shedding": True}]
    d = adm.try_admit("t4")
    assert not d.accepted and d.reason == "latency_overload"
    # peer view failure degrades to local-only, never an exception
    def boom():
        raise RuntimeError("registry unreadable")
    adm.set_peer_view(boom)
    assert adm.try_admit("t5").accepted


def test_admission_sync_from_spool_scoped(tmp_path):
    for i in range(6):
        _publish(tmp_path, f"m{i}")
    adm = AdmissionController(AdmissionConfig())
    mine = {s for s in range(8) if s % 2}
    n = adm.sync_from_spool(
        tmp_path / QUEUE,
        owns_msg=lambda mid: shard_of(mid, 8) in mine)
    expect = sum(1 for i in range(6) if shard_of(f"m{i}", 8) in mine)
    assert n == expect == adm.stats()["depth"]


# --------------------------------------------------------- peers + metrics
def test_peers_view_and_replica_metrics(tmp_path):
    m = MetricsRegistry()
    sched = JobScheduler(tmp_path, lambda msg: None,
                         config=_cfg(replica_id="rX", replicas=2), metrics=m)
    other = ReplicaRegistry(tmp_path / QUEUE, "rY")
    other.register()
    other.beat(summary={"admission": {"depth": 2, "tenants": {"t": 2},
                                      "latency_ewma_s": 0.5,
                                      "shedding": False}})
    sched._recompute_owned()
    view = sched.peers()
    assert view["replica_id"] == "rX"
    ids = {p["replica_id"] for p in view["replicas"]}
    assert ids == {"rX", "rY"}
    assert sorted(view["owned"]) == view["owned"]
    peer_adm = sched.peer_admission_summaries()
    assert peer_adm and peer_adm[0]["depth"] == 2 \
        and peer_adm[0]["replica_id"] == "rY"
    text = m.expose()
    assert 'sm_replica_up{replica="rX"} 1' in text
    assert 'sm_replica_shards_owned{replica="rX"}' in text
    assert "sm_replica_peers_alive 2" in text
    # ownership excludes the live peer's share
    assert sched._owned == owned_shards("rX", {"rX", "rY"}, 8)


def test_orphan_rescue_claims_unowned_aged_messages(tmp_path):
    """Liveness failsafe: a message in a shard nobody owns is still claimed
    once it ages past the rescue horizon."""
    import os

    done = []
    cfg = _cfg(replica_id="r1", stale_after_s=0.5)
    sched = JobScheduler(tmp_path, lambda m: done.append(m["msg_id"]),
                         config=cfg)
    # a live "peer" that will never actually claim (wedged): it owns some
    # shards from r1's point of view
    wedged = ReplicaRegistry(tmp_path / QUEUE, "rwedged",
                             stale_after_s=60.0)
    wedged.register()
    ids = [f"m{i}" for i in range(6)]
    for mid in ids:
        _publish(tmp_path, mid)
    # age every pending message past the rescue horizon (10x stale = 5 s)
    old = time.time() - 10.0
    for p in (tmp_path / QUEUE / "pending").glob("*.json"):
        os.utime(p, (old, old))
    sched.start()
    assert sched.wait_for_terminal(len(ids), timeout_s=30.0)
    sched.shutdown()
    assert sorted(done) == ids                    # rescued the peer's share
