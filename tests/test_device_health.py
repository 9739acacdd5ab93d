"""HealthTracker unit + integration tests (ISSUE 14, service/health.py):
state transitions, probe attribution, half-open readmission, host
eviction, metrics exposition, the lease-time probe through the pool, and
the sharded-spec primer support."""

from __future__ import annotations

import time

import pytest

from sm_distributed_tpu.models import faults
from sm_distributed_tpu.service.device_pool import DevicePool
from sm_distributed_tpu.service.health import HealthTracker
from sm_distributed_tpu.utils import failpoints


@pytest.fixture(autouse=True)
def _reset_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def _tracker(size=4, **kw):
    kw.setdefault("probe_on_lease", True)
    kw.setdefault("reprobe_after_s", 0.05)
    return HealthTracker(size, **kw)


# ---------------------------------------------------------- state machine
def test_sticky_single_chip_quarantines_immediately():
    ht = _tracker()
    ht.report_fault((2,), faults.FAULT_STICKY, "launch failed")
    assert ht.state_of(2) == "quarantined"
    assert ht.quarantined() == frozenset({2})
    assert ht.healthy_count() == 3
    snap = ht.snapshot()
    assert snap["quarantines_total"] == 1
    chip = next(c for c in snap["chips"] if c["device"] == 2)
    assert chip["reason"].startswith("sticky fault")


def test_transient_faults_strike_then_quarantine():
    ht = _tracker(fault_quarantine=3)
    for n in range(2):
        ht.report_fault((1,), faults.FAULT_TRANSIENT, "timeout")
        assert ht.state_of(1) == "suspect", f"strike {n}"
    # a clean group resets the counter
    ht.report_ok((1,))
    assert ht.state_of(1) == "ok"
    for _ in range(3):
        ht.report_fault((1,), faults.FAULT_TRANSIENT, "timeout")
    assert ht.state_of(1) == "quarantined"


def test_sharded_sticky_fault_probe_attributes_culprit():
    """An N-chip lease fault cannot name its chip: every leased chip goes
    suspect and the probe fingers the dead one."""
    ht = _tracker()
    ht.simulate_bad({3})
    ht.report_fault((0, 1, 2, 3), faults.FAULT_STICKY, "mesh died")
    assert ht.state_of(3) == "quarantined"
    assert [ht.state_of(c) for c in (0, 1, 2)] == ["suspect"] * 3
    # probes pass on the survivors -> no quarantine, but the strike stays
    ht.report_ok((0, 1, 2))
    assert [ht.state_of(c) for c in (0, 1, 2)] == ["ok"] * 3


def test_unattributable_sticky_faults_quarantine_by_strikes():
    """Probes that keep passing while sharded jobs keep dying: every
    leased chip accumulates strikes and quarantines at the threshold
    (minus the last-healthy-chip guard)."""
    ht = _tracker(size=2, fault_quarantine=2)
    ht.report_fault((0, 1), faults.FAULT_STICKY, "mystery")
    assert [ht.state_of(c) for c in (0, 1)] == ["suspect"] * 2
    ht.report_fault((0, 1), faults.FAULT_STICKY, "mystery")
    states = sorted(ht.state_of(c) for c in (0, 1))
    # chip 0 quarantines at strike 2; chip 1 is then the LAST healthy chip
    assert states == ["quarantined", "suspect"]


def test_reprobe_readmits_recovered_chip():
    ht = _tracker()
    ht.simulate_bad({1})
    ht.report_fault((1,), faults.FAULT_STICKY, "dead")
    assert ht.state_of(1) == "quarantined"
    time.sleep(0.06)
    # still bad: the re-probe fails and re-arms the cooldown
    assert ht.reprobe_due() == []
    assert ht.state_of(1) == "quarantined"
    ht.simulate_bad(())
    time.sleep(0.06)
    assert ht.reprobe_due() == [1]
    assert ht.state_of(1) == "ok"
    assert ht.snapshot()["readmits_total"] == 1


def test_host_eviction_fences_whole_domain():
    ht = HealthTracker(8, hosts=2, host_evict_fraction=0.5,
                       probe_on_lease=False, reprobe_after_s=0.0)
    ht.report_fault((0,), faults.FAULT_STICKY, "dead")
    assert ht.state_of(1) == "ok", "one chip out of four is below 50%"
    ht.report_fault((1,), faults.FAULT_STICKY, "dead")
    # 2/4 of host 0 out -> the remaining two are evicted with it
    assert [ht.state_of(c) for c in (0, 1, 2, 3)] == ["quarantined"] * 4
    assert [ht.state_of(c) for c in (4, 5, 6, 7)] == ["ok"] * 4
    assert ht.snapshot()["host_evictions_total"] == 1


def test_probe_failpoint_counts_as_probe_failure():
    ht = _tracker(size=2)
    failpoints.configure("device.probe=raise:OSError@1")
    assert ht.probe_chips([0, 1]) == [0]
    snap = ht.snapshot()
    assert snap["probes_total"] == {"pass": 1, "fail": 1}


def test_health_metrics_exposition():
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    m = MetricsRegistry()
    ht = _tracker()
    ht.attach_metrics(m)
    ht.report_fault((2,), faults.FAULT_STICKY, "dead")
    text = m.expose()
    assert 'sm_device_health{device="2"} 2' in text
    assert 'sm_device_health{device="0"} 0' in text
    assert "sm_device_quarantines_total 1" in text
    assert "sm_device_readmits_total 0" in text
    assert "sm_device_host_evictions_total 0" in text


# ------------------------------------------------------- pool integration
def test_lease_time_probe_quarantines_and_regrants():
    """A grant whose probe fails is returned and re-evaluated over the
    survivors — the job never touches the dead chip."""
    pool = DevicePool(3, health=_tracker(size=3))
    pool.health.simulate_bad({0})
    lease = pool.lease(2, "probe_me")
    assert lease.acquire(timeout=2)
    assert list(lease.devices) == [1, 2]
    assert pool.health.state_of(0) == "quarantined"
    lease.release()
    snap = pool.snapshot()
    assert snap["health"]["quarantined"] == 1


def test_scheduler_retry_releases_excluding_quarantined(tmp_path):
    """Scheduler-level mesh-shrink shape: attempt 1 reports a sticky
    fault on its chip mid-callback; the retry's lease must exclude it."""
    from sm_distributed_tpu.engine.daemon import QueuePublisher
    from sm_distributed_tpu.service.scheduler import JobScheduler
    from sm_distributed_tpu.utils.config import ServiceConfig

    seen = []

    def cb(msg, ctx):
        with ctx.device_token:
            seen.append(tuple(ctx.device_token.devices))
            if len(seen) == 1:
                faults.report_device_fault(
                    ctx.device_token.devices, faults.FAULT_STICKY,
                    "injected sticky")
                raise RuntimeError("attempt 1 dies with its chip")

    cfg = ServiceConfig(workers=1, poll_interval_s=0.02, max_attempts=2,
                        backoff_base_s=0.02, backoff_max_s=0.05,
                        backoff_jitter=0.0, device_pool_size=2,
                        health_reprobe_after_s=0.0, http_port=0)
    sched = JobScheduler(tmp_path / "q", cb, config=cfg)
    QueuePublisher(tmp_path / "q").publish(
        {"ds_id": "x", "input_path": "/in", "msg_id": "m1"})
    sched.start()
    assert sched.wait_for_terminal(1, timeout_s=20.0), sched.stats()
    assert sched.shutdown()
    assert len(seen) == 2, seen
    first, second = seen
    assert first != second and not (set(first) & set(second)), \
        f"retry re-leased the quarantined chip: {seen}"
    assert sched.device_pool.health.state_of(first[0]) == "quarantined"


# ------------------------------------------------- primer sharded support
def test_primer_compiles_recorded_sharded_spec(tmp_path,
                                               isolated_compile_cache):
    """ISSUE 14 satellite (the PR 13 follow-up): a recorded mesh-shaped
    spec AOT-compiles through prime_spec — including a shrunken-mesh
    topology — and hosts without enough devices skip gracefully."""
    import numpy as np

    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.ops import buckets
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.parallel.sharded import make_jax_backend
    from sm_distributed_tpu.service.primer import prime_spec
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    buckets.reset()
    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    ds = SpectralDataset.from_imzml(path)
    dsc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 2, "seed": 1},
         "parallel": {"formula_batch": 8, "overlap_isocalc": "off"},
         "work_dir": str(tmp_path / "work")})
    iso = IsocalcWrapper(dsc.isotope_generation, cache_dir=None)
    pairs = [(f, "+H") for f in truth.formulas[:4]]
    table = iso.stream_table(pairs, [True] * 4).result_table()
    out4 = make_jax_backend(ds, dsc, sm, restrict_table=table,
                            device_indices=(0, 1, 2, 3)).score_batch(table)
    out3 = make_jax_backend(ds, dsc, sm, restrict_table=table,
                            device_indices=(0, 1, 2)).score_batch(table)
    # the mesh-shrink contract the recovery path rides on
    assert np.array_equal(out4, out3), "mesh shapes disagree bitwise"
    specs = [s for s in buckets.recorded_specs() if s["kind"] == "sharded"]
    assert sorted(s["devices"] for s in specs) == [3, 4]
    for s in specs:
        assert s["mesh_pix"] * s["mesh_form"] == s["devices"]
        assert prime_spec(s, sm_config=sm) == "compiled"
    # a mesh wider than the host skips instead of failing the cycle
    too_big = dict(specs[0], devices=4096, mesh_pix=4096)
    assert prime_spec(too_big, sm_config=sm) == "skipped:devices"
    # pre-topology (legacy) manifest entries skip gracefully too
    legacy = dict(specs[0])
    legacy.update(k=0, g=0, c=0, wc=0)
    legacy.pop("mesh_pix")
    assert prime_spec(legacy, sm_config=sm) == "skipped:legacy_spec"
    buckets.reset()
