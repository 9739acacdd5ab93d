"""Device-pool allocator invariants + mesh-geometry edge cases (ISSUE 7).

The pool invariants (no double-grant, contiguity, FIFO-ish fairness,
release-on-cancel/crash) run against the real DevicePool with fake holders;
the scheduler-level tests prove the tentpole's acceptance shape — two
1-chip jobs holding DISTINCT chips concurrently instead of queueing on the
old single token — through the real JobScheduler.
"""

import threading
import time

import pytest

from sm_distributed_tpu.engine.daemon import QueuePublisher
from sm_distributed_tpu.service.device_pool import (
    DeviceLease,
    DevicePool,
    resolve_pool_size,
)
from sm_distributed_tpu.service.scheduler import JobScheduler
from sm_distributed_tpu.utils.config import ParallelConfig, ServiceConfig


# --------------------------------------------------------- mesh edge cases
def test_resolve_axis_sizes_edge_cases():
    from sm_distributed_tpu.parallel.mesh import resolve_axis_sizes

    # 1-device degenerate mesh: everything collapses to 1x1
    assert resolve_axis_sizes(1, ParallelConfig()) == (1, 1)
    assert resolve_axis_sizes(
        1, ParallelConfig(pixels_axis=1, formulas_axis=1)) == (1, 1)
    # product < n_devices is PACKING, not an error (a 2x2 sub-mesh on an
    # 8-chip pool leaves 4 chips for other jobs)
    assert resolve_axis_sizes(
        8, ParallelConfig(pixels_axis=2, formulas_axis=2)) == (2, 2)
    assert resolve_axis_sizes(
        5, ParallelConfig(pixels_axis=2, formulas_axis=2)) == (2, 2)
    # non-dividing -1 axes refuse loudly instead of silently dropping chips
    with pytest.raises(ValueError, match="does not divide"):
        resolve_axis_sizes(8, ParallelConfig(pixels_axis=-1, formulas_axis=3))
    with pytest.raises(ValueError, match="does not divide"):
        resolve_axis_sizes(7, ParallelConfig(pixels_axis=2, formulas_axis=-1))
    # over-subscription refuses
    with pytest.raises(ValueError, match="needs"):
        resolve_axis_sizes(8, ParallelConfig(pixels_axis=3, formulas_axis=3))
    # zero / below -1 are config errors, not meshes
    for pix, form in ((0, 1), (1, 0), (-2, 1), (1, -3)):
        with pytest.raises(ValueError, match="must be -1 or positive"):
            resolve_axis_sizes(8, ParallelConfig(pixels_axis=pix,
                                                 formulas_axis=form))
    # odd device counts still resolve when the explicit axis divides
    assert resolve_axis_sizes(
        6, ParallelConfig(pixels_axis=-1, formulas_axis=2)) == (3, 2)
    assert resolve_axis_sizes(
        6, ParallelConfig(pixels_axis=3, formulas_axis=-1)) == (3, 2)


def test_make_mesh_over_lease_subset():
    """A sub-mesh over an explicit device subset keeps exactly those
    devices, in order (the contiguous-lease -> mesh contract)."""
    import jax

    from sm_distributed_tpu.parallel.mesh import make_mesh

    devs = jax.devices()[2:6]
    mesh = make_mesh(ParallelConfig(pixels_axis=2, formulas_axis=2),
                     devices=devs)
    assert dict(mesh.shape) == {"pixels": 2, "formulas": 2}
    assert [d.id for d in mesh.devices.flat] == [d.id for d in devs]


def test_lease_devices_out_of_range_fallback():
    from sm_distributed_tpu.parallel.mesh import lease_devices

    assert lease_devices(None) is None
    got = lease_devices((2, 3))
    assert [d.id for d in got] == [2, 3]
    # indices beyond the visible devices are dropped; nothing usable left
    # falls back to None (config mesh) instead of failing the job
    assert lease_devices((10_000, 10_001)) is None
    partial = lease_devices((1, 10_000))
    assert [d.id for d in partial] == [1]


# ------------------------------------------------------------ pool invariants
def test_pool_no_double_grant_and_contiguity_under_stress():
    """64 threads x random-size leases: at no instant is a chip owned by
    two leases, and every grant is a contiguous run."""
    pool = DevicePool(8)
    owners = [None] * 8
    lock = threading.Lock()
    errors = []

    def worker(seed):
        import random

        rng = random.Random(seed)
        for _ in range(25):
            lease = pool.lease(rng.randint(1, 4), msg_id=f"w{seed}")
            with lease:
                devs = lease.devices
                with lock:
                    if list(devs) != list(range(devs[0], devs[0] + len(devs))):
                        errors.append(f"non-contiguous grant {devs}")
                    for i in devs:
                        if owners[i] is not None:
                            errors.append(f"double grant of chip {i}")
                        owners[i] = lease
                time.sleep(0.001)
                with lock:
                    for i in devs:
                        owners[i] = None

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(64)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:5]
    assert pool.in_use_count() == 0
    assert pool.grants_total == 64 * 25


def test_pool_packs_small_jobs_onto_distinct_chips():
    pool = DevicePool(4)
    a, b = pool.lease(1, "a"), pool.lease(1, "b")
    big = pool.lease(2, "big")
    assert a.acquire(timeout=1) and b.acquire(timeout=1)
    assert big.acquire(timeout=1)
    held = set(a.devices) | set(b.devices) | set(big.devices)
    assert len(held) == 4, "grants overlapped"
    assert pool.locked()                     # every chip busy = legacy locked
    a.release(), b.release(), big.release()
    assert not pool.locked() and pool.in_use_count() == 0


def test_pool_fifo_ish_fairness_same_size():
    """Equal-size waiters are granted strictly in arrival order."""
    pool = DevicePool(1)
    holder = pool.lease(1, "holder")
    assert holder.acquire(timeout=1)
    grant_order = []
    lock = threading.Lock()

    def wait(name, lease):
        assert lease.acquire(timeout=10)
        with lock:
            grant_order.append(name)
        time.sleep(0.02)
        lease.release()

    threads = []
    for name in ("first", "second", "third"):
        lease = pool.lease(1, name)
        # register the queue position deterministically before spawning the
        # next waiter (a timed-out poll RETAINS the position)
        assert not lease.acquire(timeout=0.01)
        threads.append(threading.Thread(target=wait, args=(name, lease)))
    for t in threads:
        t.start()
    time.sleep(0.05)
    holder.release()
    for t in threads:
        t.join(timeout=10)
    assert grant_order == ["first", "second", "third"]


def test_pool_small_jobs_bypass_waiting_submesh_job():
    """A waiting sub-mesh lease does not block 1-chip jobs from packing
    around it (FIFO-ish, not strict FIFO)..."""
    pool = DevicePool(4, max_bypass=64)
    hold = pool.lease(2, "hold")
    assert hold.acquire(timeout=1)           # chips 0-1 busy
    big = pool.lease(4, "big")
    assert not big.acquire(timeout=0.02)     # waits for the full pool
    small = pool.lease(1, "small")
    assert small.acquire(timeout=1), "small job blocked behind sub-mesh waiter"
    small.release()
    hold.release()
    assert big.acquire(timeout=5)            # ...and the big job gets there
    big.release()


def test_pool_starved_waiter_seals_queue():
    """With the bypass budget exhausted, later grants stop until the
    starved larger lease is served (anti-starvation)."""
    pool = DevicePool(2, max_bypass=0)
    hold = pool.lease(1, "hold")
    assert hold.acquire(timeout=1)
    big = pool.lease(2, "big")
    assert not big.acquire(timeout=0.02)     # queued, cannot be satisfied
    late = pool.lease(1, "late")
    # a free chip exists, but max_bypass=0 seals the queue behind `big`
    assert not late.acquire(timeout=0.05)
    hold.release()
    assert big.acquire(timeout=5)
    big.release()
    assert late.acquire(timeout=5)
    late.release()


def test_pool_release_while_waiting_deregisters():
    """The cancel path: a lease released while still queued leaves the
    wait queue (and is harmless to release twice)."""
    pool = DevicePool(1)
    holder = pool.lease(1, "holder")
    assert holder.acquire(timeout=1)
    waiter = pool.lease(1, "waiter")
    assert not waiter.acquire(timeout=0.02)
    assert pool.waiters() == 1
    waiter.release()                         # cancelled while waiting
    waiter.release()                         # idempotent
    assert pool.waiters() == 0
    holder.release()
    assert pool.in_use_count() == 0


def test_pool_lease_clamps_and_legacy_token_protocol():
    pool = DevicePool(4)
    assert pool.lease(99).n == 4             # clamp to pool size
    assert pool.lease(0).n == 1
    # legacy single-token protocol on the pool object itself
    assert pool.acquire(timeout=1)
    assert pool.in_use_count() == 1
    pool.release()
    assert pool.in_use_count() == 0
    with pytest.raises(RuntimeError):
        pool.release()
    with pool:
        assert pool.in_use_count() == 1
    assert pool.in_use_count() == 0


def test_pool_double_acquire_raises():
    pool = DevicePool(2)
    lease = pool.lease(1)
    assert lease.acquire(timeout=1)
    with pytest.raises(RuntimeError, match="already holds"):
        lease.acquire(timeout=1)
    lease.release()


def test_resolve_pool_size():
    assert resolve_pool_size(ServiceConfig(device_pool_size=3)) == 3
    # jax is imported in the test session → auto sees the virtual 8-chip mesh
    assert resolve_pool_size(ServiceConfig(), backend="jax_tpu") >= 8
    assert resolve_pool_size(None) >= 1


# ------------------------------------------------- scheduler integration
def _cfg(**kw) -> ServiceConfig:
    base = dict(workers=3, poll_interval_s=0.02, job_timeout_s=10.0,
                max_attempts=1, backoff_base_s=0.05, heartbeat_interval_s=0.05,
                stale_after_s=0.5, drain_timeout_s=10.0)
    base.update(kw)
    return ServiceConfig(**base)


def test_two_one_chip_jobs_overlap_on_distinct_chips(tmp_path):
    """THE tentpole acceptance shape: two 1-chip jobs hold device leases
    with DISTINCT chips at the same time — no single-token serialization."""
    holds = {}                               # msg_id -> (devices, t0, t1)
    lock = threading.Lock()
    barrier = threading.Barrier(2, timeout=10)

    def cb(msg, ctx):
        with ctx.device_token as lease:
            barrier.wait()                   # both INSIDE their holds at once
            t0 = time.time()
            time.sleep(0.05)
            with lock:
                holds[msg["msg_id"]] = (lease.devices, t0, time.time())

    sched = JobScheduler(tmp_path / "q", cb,
                         config=_cfg(device_pool_size=8, devices_per_job=1))
    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "a", "input_path": "/in", "msg_id": "a"})
    pub.publish({"ds_id": "b", "input_path": "/in", "msg_id": "b"})
    sched.start()
    assert sched.wait_for_terminal(2, timeout_s=20.0), sched.stats()
    assert sched.shutdown()
    assert set(holds) == {"a", "b"}
    (devs_a, a0, a1), (devs_b, b0, b1) = holds["a"], holds["b"]
    assert len(devs_a) == 1 and len(devs_b) == 1
    assert set(devs_a).isdisjoint(devs_b), "two jobs granted the same chip"
    assert a0 < b1 and b0 < a1, "holds did not overlap"
    assert sched.device_pool.in_use_count() == 0


def test_submit_devices_override_claims_submesh(tmp_path):
    """A per-submit ``devices`` field claims a contiguous sub-mesh of that
    size; the config default applies otherwise; oversize clamps."""
    seen = {}

    def cb(msg, ctx):
        with ctx.device_token as lease:
            seen[msg["msg_id"]] = lease.devices

    sched = JobScheduler(tmp_path / "q", cb,
                         config=_cfg(workers=1, device_pool_size=8,
                                     devices_per_job=2))
    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "d", "input_path": "/in", "msg_id": "default"})
    pub.publish({"ds_id": "d", "input_path": "/in", "msg_id": "four",
                 "devices": 4})
    pub.publish({"ds_id": "d", "input_path": "/in", "msg_id": "oversize",
                 "devices": 64})
    sched.start()
    assert sched.wait_for_terminal(3, timeout_s=20.0), sched.stats()
    assert sched.shutdown()
    assert len(seen["default"]) == 2
    assert len(seen["four"]) == 4
    assert list(seen["four"]) == list(range(seen["four"][0],
                                            seen["four"][0] + 4))
    assert len(seen["oversize"]) == 8        # clamped to the pool


def test_lease_released_on_callback_crash(tmp_path):
    """A job that raises INSIDE its device hold (the with-exit releases)
    and one that raises while the lease is still waiting both leave the
    pool clean — the scheduler's finally is the crash backstop."""
    def cb(msg, ctx):
        if msg["msg_id"] == "crash_held":
            with ctx.device_token:
                raise RuntimeError("boom inside hold")
        # crash BEFORE ever acquiring: lease must be deregistered, and a
        # half-acquired (queued) lease must not leak either
        ctx.device_token.acquire(timeout=0.01)
        raise RuntimeError("boom before/while waiting")

    sched = JobScheduler(tmp_path / "q", cb,
                         config=_cfg(workers=2, device_pool_size=2))
    pub = QueuePublisher(tmp_path / "q")
    pub.publish({"ds_id": "x", "input_path": "/in", "msg_id": "crash_held"})
    pub.publish({"ds_id": "x", "input_path": "/in", "msg_id": "crash_wait"})
    sched.start()
    assert sched.wait_for_terminal(2, timeout_s=20.0), sched.stats()
    assert sched.shutdown()
    assert sched.device_pool.in_use_count() == 0
    assert sched.device_pool.waiters() == 0


def test_pool_metrics_exposition(tmp_path):
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    m = MetricsRegistry()
    pool = DevicePool(2)
    pool.attach_metrics(m)
    pool.attach_metrics(m)                   # idempotent
    with pool.lease(1, "j1"):
        text = m.expose()
        assert 'sm_device_pool_in_use{device="0"} 1' in text
        assert 'sm_device_pool_in_use{device="1"} 0' in text
        assert "sm_device_pool_grants_total 1" in text
        assert "sm_device_pool_devices 2" in text
    text = m.expose()
    assert 'sm_device_pool_in_use{device="0"} 0' in text
    assert "sm_device_pool_wait_seconds_count 1" in text


# --------------------------- occupancy counters (ISSUE 26): what two scrapes
# of the in_use gauge cannot integrate
def _sample(text, name, **labels):
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    line = name + ("{" + inner + "}" if inner else "") + " "
    (hit,) = [row for row in text.splitlines() if row.startswith(line)]
    return float(hit[len(line):])


# each step: (seconds on the clock, what happens then); a scrape names what
# held_seconds per chip, the pool's clock and the wait histogram must read
_OCCUPANCY = {
    # held seconds are the sum of the hold durations, chip by chip
    "released": [
        (0.0, ("acquire", "a", 1)), (0.5, ("acquire", "b", 1)),
        (2.0, ("release", "a")), (4.0, ("release", "b")),
        (5.0, ("acquire", "a", 1)), (6.0, ("release", "a")),
        (9.0, ("scrape", [3.0, 3.5], 9.0, (0.0, 3)))],
    # a lease still open counts up to the scrape, and only once
    "open_at_scrape": [
        (1.0, ("acquire", "a", 1)),
        (5.0, ("scrape", [4.0, 0.0], 5.0, (0.0, 1))),
        (7.0, ("scrape", [6.0, 0.0], 7.0, (0.0, 1))),
        (8.0, ("release", "a")),
        (8.5, ("scrape", [7.0, 0.0], 8.5, (0.0, 1)))],
    # a two-chip lease holds both of its chips
    "two_chip_lease": [
        (0.0, ("acquire", "a", 2)), (1.5, ("release", "a")),
        (2.0, ("scrape", [1.5, 1.5], 2.0, (0.0, 1)))],
    # the wait histogram's _sum / _count grow by one observation per grant
    "wait_histogram": [
        (0.0, ("acquire", "a", 2)), (1.0, ("queue", "b", 1)),
        (1.5, ("queue", "c", 2)),
        (2.0, ("scrape", [2.0, 2.0], 2.0, (0.0, 1))),
        (3.0, ("release", "a")), (3.0, ("acquire", "b", 1)),
        (3.5, ("scrape", [3.5, 3.0], 3.5, (2.0, 2))),
        (4.0, ("release", "b")), (4.0, ("acquire", "c", 2)),
        (6.0, ("scrape", [6.0, 5.0], 6.0, (4.5, 3)))],
}


@pytest.mark.parametrize("case", sorted(_OCCUPANCY))
def test_pool_occupancy_counters(case, monkeypatch):
    from types import SimpleNamespace

    from sm_distributed_tpu.service import device_pool
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    now = [100.0]
    monkeypatch.setattr(device_pool, "time",
                        SimpleNamespace(monotonic=lambda: now[0]))
    m = MetricsRegistry()
    pool = DevicePool(2)
    pool.attach_metrics(m)
    leases = {}
    for at, (op, *args) in _OCCUPANCY[case]:
        now[0] = 100.0 + at
        if op == "acquire":
            name, n = args
            lease = leases.setdefault(name, pool.lease(n, name))
            assert lease.acquire(timeout=0)
        elif op == "queue":                  # asks, and has to wait
            name, n = args
            leases[name] = pool.lease(n, name)
            assert not leases[name].acquire(blocking=False)
        elif op == "release":
            leases.pop(args[0]).release()
        else:
            held, clock, (wait_sum, wait_count) = args
            text = m.expose()
            for i, want in enumerate(held):
                assert _sample(text, "sm_device_pool_held_seconds_total",
                               device=i) == pytest.approx(want)
            assert _sample(text, "sm_device_pool_clock_seconds_total") == \
                pytest.approx(clock)
            assert _sample(text, "sm_device_pool_wait_seconds_sum") == \
                pytest.approx(wait_sum)
            assert _sample(text, "sm_device_pool_wait_seconds_count") == \
                wait_count
            assert _sample(text, "sm_device_pool_grants_total") == wait_count


# ------------------------------------------ quarantine fragmentation (ISSUE 14)
def _quarantine(pool, *chips):
    for c in chips:
        assert pool.health._quarantine(c, "test quarantine")


def test_pool_fragmented_by_quarantine_grants_non_contiguous():
    """Quarantine chips 2 and 5 of 8: the longest healthy contiguous run
    is 2 chips, yet a 4-chip lease must still grant — non-contiguous,
    from the free healthy chips, warned rather than waiting forever."""
    pool = DevicePool(8)
    _quarantine(pool, 2, 5)
    lease = pool.lease(4, "frag")
    assert lease.acquire(timeout=2)
    assert list(lease.devices) == [0, 1, 3, 4]      # host-major free picks
    assert 2 not in lease.devices and 5 not in lease.devices
    # a second 4-chip lease WAITS (6 healthy chips exist — busy is not
    # quarantined; only quarantine shrinks a request), then grants
    # non-contiguous once the first releases
    other = pool.lease(4, "frag2")
    assert not other.acquire(timeout=0.05)
    lease.release()
    assert other.acquire(timeout=2)
    assert list(other.devices) == [0, 1, 3, 4]
    other.release()
    assert pool.in_use_count() == 0


def test_pool_healthy_but_busy_still_waits_contiguous():
    """Without quarantine the legacy semantics are untouched: a pool
    fragmented only by BUSY leases waits for a contiguous run instead of
    granting a scattered one."""
    pool = DevicePool(4)
    mid = pool.lease(1, "mid")
    assert mid.acquire(timeout=1)
    # occupy chip 1 specifically: grab 0-1 then free 0
    a = pool.lease(1, "a")
    assert a.acquire(timeout=1)
    assert set(mid.devices) | set(a.devices) == {0, 1}
    big = pool.lease(3, "big")
    assert not big.acquire(timeout=0.05), \
        "3-chip lease must wait for a contiguous run, not scatter"
    big.release()
    mid.release(), a.release()


def test_pool_fairness_and_bypass_hold_under_quarantine():
    """FIFO-ish fairness and the bypass budget still hold on the shrunken
    pool: a starved larger waiter seals the queue exactly as before."""
    pool = DevicePool(4, max_bypass=0)
    _quarantine(pool, 3)
    hold = pool.lease(1, "hold")
    assert hold.acquire(timeout=1)
    big = pool.lease(3, "big")                       # needs all 3 healthy
    assert not big.acquire(timeout=0.02)
    late = pool.lease(1, "late")
    assert not late.acquire(timeout=0.05), "queue not sealed behind big"
    hold.release()
    assert big.acquire(timeout=5)
    assert len(big.devices) == 3 and 3 not in big.devices
    big.release()
    assert late.acquire(timeout=5)
    late.release()


def test_pool_release_and_reap_idempotent_with_quarantine():
    """Release/reap stay idempotent when quarantine shrank the pool, and
    a quarantined chip never re-enters circulation through release."""
    pool = DevicePool(4)
    lease = pool.lease(4, "all")
    assert lease.acquire(timeout=1)
    assert len(lease.devices) == 4
    pool.health._quarantine(2, "went sticky while held")
    lease.release()
    lease.release()                                  # idempotent
    pool.reap(lease)                                 # no-op after release
    nxt = pool.lease(4, "next")
    assert nxt.acquire(timeout=2)
    assert 2 not in nxt.devices and len(nxt.devices) == 3
    nxt.release()
    assert pool.in_use_count() == 0 and pool.waiters() == 0


def test_pool_never_quarantines_last_healthy_chip():
    pool = DevicePool(2)
    assert pool.health._quarantine(0, "bad")
    assert not pool.health._quarantine(1, "bad"), \
        "the last healthy chip must never be fenced"
    lease = pool.lease(2, "survivor")
    assert lease.acquire(timeout=1)
    assert list(lease.devices) == [1]
    lease.release()
