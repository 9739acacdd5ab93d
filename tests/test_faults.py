"""Device-fault taxonomy (ISSUE 14, models/faults.py): classification
matrix, the breaker/oom overlap regression, per-chip breaker semantics,
and the listener seam."""

from __future__ import annotations

import pytest

from sm_distributed_tpu.models import breaker as breaker_mod
from sm_distributed_tpu.models import faults
from sm_distributed_tpu.utils import failpoints


@pytest.fixture(autouse=True)
def _reset_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


# ------------------------------------------------------------ classification
def test_classification_matrix():
    # OOM stays the sizing signal (models/oom.py is the authority)
    assert faults.classify(MemoryError("boom")) == faults.FAULT_OOM
    assert faults.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "2147483648 bytes")) == faults.FAULT_OOM
    # known-transient runtime hiccups: class-based and status-text-based
    assert faults.classify(TimeoutError("rpc")) == faults.FAULT_TRANSIENT
    assert faults.classify(ConnectionError("peer")) == faults.FAULT_TRANSIENT
    assert faults.classify(RuntimeError(
        "DEADLINE_EXCEEDED: collective all-reduce timed out after "
        "120s")) == faults.FAULT_TRANSIENT
    assert faults.classify(RuntimeError(
        "UNAVAILABLE: socket closed")) == faults.FAULT_TRANSIENT
    assert faults.classify(OSError(
        "device link died: connection reset")) == faults.FAULT_TRANSIENT
    # everything else at the device seam is sticky
    assert faults.classify(RuntimeError(
        "INTERNAL: failed to enqueue program")) == faults.FAULT_STICKY
    assert faults.classify(RuntimeError(
        "injected failpoint backend.chip_fault (hit 1)")) == \
        faults.FAULT_STICKY


# the compiler's own words for a Pallas kernel past its scoped VMEM
# (libtpu 0.0.34, AOT-compiled for a v5e during bring-up)
VMEM_COMPILE_ERROR = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %_lambda_.1 = f32[4096,4096]{1,0:T(8,128)} "
    "custom-call(%a_0_.1), custom_call_target=\"tpu_custom_call\". Scoped "
    "allocation with size 128.00M and limit 16.00M exceeded scoped vmem "
    "limit by 112.00M. It should not be possible to run out of scoped vmem")


def test_program_faults_are_not_device_faults():
    """Raised while tracing, lowering or compiling: the program is wrong,
    the chip is fine — never oom, transient or sticky."""

    class LoweringException(Exception):     # Pallas' class, matched by name
        pass

    for exc in (
            AttributeError("module 'jax.experimental.pallas' has no "
                           "attribute 'unblocked'"),
            TypeError("BlockSpec.__init__() got an unexpected keyword "
                      "argument"),
            ValueError("bad shape"),
            NotImplementedError("All block dimensions must be Elements or "
                                "none of them can be Elements."),
            LoweringException("Block shape (1, 512) must be divisible by "
                              "(8, 128)"),
            RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                         "unsupported operand layout"),
            RuntimeError(VMEM_COMPILE_ERROR),
            # a compiler refusal may quote any status text
            RuntimeError("Mosaic failed to compile: all-reduce ABORTED")):
        assert faults.classify(exc) == faults.FAULT_PROGRAM, exc


def test_program_fault_fails_job_without_breaker_or_degrade(
        tmp_path, monkeypatch):
    """A lowering/compile exception on the scoring path fails the job: no
    breaker count (threshold 1 would have opened), no numpy degrade, no
    health report, no batch backoff."""
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.models import msm_jax, oom
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.service.metrics import MetricsRegistry
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 2, "seed": 1},
         "parallel": {"formula_batch": 8, "overlap_isocalc": "off",
                      "pixels_axis": 1, "formulas_axis": 1},
         "service": {"breaker_threshold": 1},
         "work_dir": str(tmp_path / "work")})
    metrics = MetricsRegistry()
    breaker_mod.attach_metrics(metrics)
    oom.attach_metrics(metrics)

    class Sink:
        faults = []

        def report_fault(self, devices, kind, error):
            self.faults.append(kind)

        def report_ok(self, devices):
            pass

    faults.set_fault_listener(Sink())

    def score(self, tables, cancel=None):
        raise AttributeError("module 'jax.experimental.pallas' has no "
                             "attribute 'unblocked'")

    for exc_text, raiser in (("unblocked", score),
                             ("scoped vmem", None)):
        if raiser is None:
            def raiser(self, tables, cancel=None):
                raise RuntimeError(VMEM_COMPILE_ERROR)
        monkeypatch.setattr(msm_jax.JaxBackend, "score_batches", raiser)
        search = MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm,
                                device_indices=(0,))
        with pytest.raises((AttributeError, RuntimeError), match=exc_text):
            search.search()
        assert search.last_backend.name == "jax_tpu"
        assert breaker_mod.get_device_breaker(devices=(0,)).state == "closed"
    text = metrics.expose()
    assert "sm_breaker_degraded_total 1" not in text
    assert metrics.value("sm_breaker_degraded_total") in (None, 0.0)
    assert metrics.value("sm_oom_events_total") == 0.0
    assert oom.snapshot()["events"] == 0 and not oom.snapshot()["safe_batches"]
    assert Sink.faults == []


def test_transient_xla_error_does_not_feed_breaker(tmp_path):
    """THE overlap regression (ISSUE 14 satellite): an XlaRuntimeError
    that is NOT RESOURCE_EXHAUSTED but IS a known-transient collective
    timeout used to count toward the breaker.  Routed through
    models/faults.py it must fail the attempt for the retry policy with
    the breaker untouched (threshold 1 would have opened on one count)."""
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 2, "seed": 1},
         "parallel": {"formula_batch": 8, "overlap_isocalc": "off"},
         "service": {"breaker_threshold": 1},
         "work_dir": str(tmp_path / "work")})
    # ConnectionError at the chip-fault seam = the collective-timeout class
    failpoints.configure("backend.chip_fault=raise:ConnectionError")
    with pytest.raises(ConnectionError, match="backend.chip_fault"):
        MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    assert breaker_mod.get_device_breaker().state == "closed", \
        "a transient fault must never count toward the breaker"
    failpoints.configure(None)
    # the same seam with a sticky class still opens the threshold-1 breaker
    failpoints.configure("backend.chip_fault=raise:RuntimeError@1")
    MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    assert breaker_mod.get_device_breaker().state == "open"


# --------------------------------------------------------- per-chip breakers
def test_per_chip_breakers_are_independent():
    cfg = type("C", (), {"breaker_threshold": 1, "breaker_cooldown_s": 60.0})
    lease_a = breaker_mod.get_device_breaker(cfg, devices=(0, 1))
    assert lease_a.allow_device() and lease_a.state == "closed"
    assert lease_a.record_failure()          # threshold 1: both chips open
    assert lease_a.state == "open" and not lease_a.allow_device()
    # a DIFFERENT lease over healthy chips is unaffected
    lease_b = breaker_mod.get_device_breaker(cfg, devices=(2, 3))
    assert lease_b.allow_device() and lease_b.state == "closed"
    # ...and so is the un-leased "*" singleton
    assert breaker_mod.get_device_breaker().state == "closed"
    # any lease sharing a tripped chip sees the open state
    lease_c = breaker_mod.get_device_breaker(cfg, devices=(1, 2))
    assert lease_c.state == "open"
    snap = breaker_mod.breakers_snapshot()
    assert snap["0"]["state"] == "open" and snap["2"]["state"] == "closed"


def test_breaker_metrics_carry_device_label():
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    m = MetricsRegistry()
    breaker_mod.attach_metrics(m)
    cfg = type("C", (), {"breaker_threshold": 1, "breaker_cooldown_s": 60.0})
    breaker_mod.get_device_breaker(cfg, devices=(5,)).record_failure()
    text = m.expose()
    assert 'sm_breaker_state{device="5"} 2' in text
    assert 'sm_breaker_transitions_total{device="5",to="open"} 1' in text


# ------------------------------------------------------------- listener seam
def test_fault_listener_dispatch_and_clear():
    class Sink:
        def __init__(self):
            self.faults = []
            self.oks = []

        def report_fault(self, devices, kind, error):
            self.faults.append((devices, kind))

        def report_ok(self, devices):
            self.oks.append(devices)

    sink = Sink()
    faults.set_fault_listener(sink)
    faults.report_device_fault((0, 1), faults.FAULT_STICKY, "boom")
    faults.report_device_ok((0, 1))
    # un-leased reports have nothing to attribute
    faults.report_device_fault(None, faults.FAULT_STICKY, "boom")
    assert sink.faults == [((0, 1), faults.FAULT_STICKY)]
    assert sink.oks == [(0, 1)]
    # clear-if-ours: someone else's registration survives a stale clear
    other = Sink()
    faults.set_fault_listener(other)
    faults.clear_fault_listener(sink)
    faults.report_device_fault((2,), faults.FAULT_TRANSIENT, "t")
    assert other.faults == [((2,), faults.FAULT_TRANSIENT)]
    faults.clear_fault_listener(other)
    faults.report_device_fault((3,), faults.FAULT_STICKY, "x")
    assert len(other.faults) == 1
