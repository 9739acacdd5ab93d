"""HBM-OOM adaptive scoring (ISSUE 10): classification, halved-batch
retry with bit-identical results, no breaker involvement, and the
proven-safe batch memory later jobs start from."""

from __future__ import annotations

import pandas as pd
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models import breaker as breaker_mod
from sm_distributed_tpu.models import oom
from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
from sm_distributed_tpu.utils import failpoints
from sm_distributed_tpu.utils.config import DSConfig, SMConfig


@pytest.fixture(autouse=True)
def _reset_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


# ------------------------------------------------------------ classification
def test_is_oom_classification():
    assert oom.is_oom_error(MemoryError("boom"))
    assert oom.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "2147483648 bytes"))
    assert oom.is_oom_error(Exception("XlaRuntimeError: Resource exhausted"))
    assert not oom.is_oom_error(RuntimeError("device link died"))
    assert not oom.is_oom_error(ValueError("bad shape"))
    # the TPU compiler's static HBM plan refusing a program is still OOM...
    assert oom.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 20.10G of 15.75G hbm."))


def test_vmem_exhaustion_is_not_an_oom_backoff():
    """...but a Pallas kernel past its scoped VMEM comes back under the
    same status and is NOT a sizing signal: halving the batch until the
    kernel happens to compile would hide a kernel that does not fit."""
    from test_faults import VMEM_COMPILE_ERROR

    assert not oom.is_oom_error(RuntimeError(VMEM_COMPILE_ERROR))
    assert not oom.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space smem"))


def test_safe_batch_registry_roundtrip():
    key = oom.shape_key(4096, "jax_tpu", (0, 1))
    assert oom.safe_batch_for(key) is None
    oom.record_safe_batch(key, 512)
    assert oom.safe_batch_for(key) == 512
    # distinct shapes are distinct entries
    assert oom.safe_batch_for(oom.shape_key(4096, "jax_tpu", None)) is None
    snap = oom.snapshot()
    assert snap["recoveries"] == 1 and snap["safe_batches"] == {key: 512}
    oom.reset()
    assert oom.safe_batch_for(key) is None


# ------------------------------------------------------------- real searches
def _fixture(tmp_path):
    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=11)
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    common = {"backend": "jax_tpu",
              "fdr": {"decoy_sample_size": 2, "seed": 1},
              "parallel": {"formula_batch": 8, "overlap_isocalc": "off"},
              "service": {"breaker_threshold": 1},
              "work_dir": str(tmp_path / "work")}
    return ds, truth, ds_config, SMConfig.from_dict(common)


def test_oom_backoff_bit_identical_and_breaker_closed(tmp_path):
    """An injected RESOURCE_EXHAUSTED (MemoryError) halves the batch and
    rescores in place: stored annotations are bit-identical to the
    untouched device run, the breaker (threshold 1!) never opens, and the
    converged size lands in the safe-batch registry."""
    ds, truth, ds_config, sm = _fixture(tmp_path)
    clean = MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    assert breaker_mod.get_device_breaker().state == "closed"
    oom.reset()

    failpoints.configure("backend.device_error=raise:MemoryError@1")
    backed_off = MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    # bit-identical: batch size only sets padding/scratch shapes
    pd.testing.assert_frame_equal(backed_off.annotations, clean.annotations,
                                  check_exact=True)
    pd.testing.assert_frame_equal(backed_off.all_metrics, clean.all_metrics,
                                  check_exact=True)
    # OOM must NEVER count as a device fault — threshold is 1, so a single
    # record_failure would have opened the breaker
    assert breaker_mod.get_device_breaker().state == "closed"
    snap = oom.snapshot()
    assert snap["events"] >= 1 and snap["recoveries"] >= 1
    key = oom.shape_key(ds.n_pixels, "jax_tpu", None)
    assert oom.safe_batch_for(key) == 4   # 8-ion slices halved once


def test_learned_safe_batch_reused_by_next_job(tmp_path):
    """The next search on the same (dataset shape, backend, lease) starts
    at the learned batch — no second OOM discovery."""
    ds, truth, ds_config, sm = _fixture(tmp_path)
    failpoints.configure("backend.device_error=raise:MemoryError@1")
    MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    failpoints.configure(None)
    events_before = oom.snapshot()["events"]

    again = MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm)
    again.search()
    assert again._batch_eff == 4          # started at the learned size
    # device padding capped too — down to the mesh's batch granule (the
    # 8-device CPU test mesh cannot pad below formula×pixel shards)
    granule = getattr(again.last_backend, "_batch_granule", 1)
    assert again.last_backend.batch <= max(4, granule)
    assert oom.snapshot()["events"] == events_before


def test_oom_at_single_ion_batch_fails_without_breaker(tmp_path):
    """An OOM that persists all the way down to a 1-ion batch is a real
    failure for the retry policy — but still never a breaker count."""
    ds, truth, ds_config, sm = _fixture(tmp_path)
    # every hit fires: the backoff ladder 8 -> 4 -> 2 -> 1 exhausts
    failpoints.configure("backend.device_error=raise:MemoryError")
    with pytest.raises(MemoryError, match="backend.device_error"):
        MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    assert breaker_mod.get_device_breaker().state == "closed"
    # nothing proven safe — the registry must not poison later jobs
    assert oom.safe_batch_for(
        oom.shape_key(ds.n_pixels, "jax_tpu", None)) is None


def test_non_oom_device_error_still_feeds_breaker(tmp_path):
    """The sizing classification must not swallow real device faults: a
    RuntimeError at the same seam opens the (threshold-1) breaker and the
    job degrades to numpy as before."""
    ds, truth, ds_config, sm = _fixture(tmp_path)
    failpoints.configure("backend.device_error=raise:RuntimeError@1")
    MSMBasicSearch(ds, truth.formulas[:4], ds_config, sm).search()
    assert breaker_mod.get_device_breaker().state == "open"
