"""ISSUE 3 coverage: parallel-vs-serial bit-exactness, worker-crash
recovery through the new isocalc failpoints, CRC shard degradation, the
device blur->centroid stage, and incremental-shard (overlapped) scoring
equivalence."""

from __future__ import annotations

import os

import numpy as np
import pytest

import sm_distributed_tpu.ops.isocalc as iso_mod
from sm_distributed_tpu.io.fixtures import expand_formula_list
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.utils import failpoints
from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

CFG = IsotopeGenerationConfig(adducts=("+H",))


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    os.environ.pop("SM_FAILPOINTS", None)
    os.environ.pop("SM_ISOCALC_CHUNK", None)
    failpoints.reset()


def _pairs(n=20, adducts=("+H", "+Na")):
    return [(sf, a) for sf in expand_formula_list(n) for a in adducts]


def test_serial_and_pool_write_identical_shard_bytes(tmp_path, monkeypatch):
    """The tentpole's core guarantee: per-chunk shards merge bit-exactly —
    same filenames, same bytes — no matter how many workers computed them."""
    monkeypatch.setattr(iso_mod, "_PARALLEL_THRESHOLD", 8)
    pairs = _pairs(12)
    d_ser, d_par = tmp_path / "ser", tmp_path / "par"
    ser = IsocalcWrapper(CFG, cache_dir=d_ser, n_procs=1, chunk_size=8)
    t_ser = ser.pattern_table(pairs)
    par = IsocalcWrapper(CFG, cache_dir=d_par, n_procs=2, chunk_size=8)
    t_par = par.pattern_table(pairs)
    assert par.last_stats["workers"] == 2
    assert t_ser.sfs == t_par.sfs
    np.testing.assert_array_equal(t_ser.mzs, t_par.mzs)
    np.testing.assert_array_equal(t_ser.ints, t_par.ints)
    s_names = sorted(p.name for p in d_ser.glob("theor_peaks_*"))
    p_names = sorted(p.name for p in d_par.glob("theor_peaks_*"))
    assert s_names == p_names and len(s_names) >= 2
    for name in s_names:
        assert (d_ser / name).read_bytes() == (d_par / name).read_bytes()


def test_worker_crash_recovers_via_inline_fallback(tmp_path, monkeypatch):
    """A pool worker hard-crashing (isocalc.worker=crash) breaks the pool;
    the driver rebuilds it, then falls back to inline compute — the job
    still completes with correct results and the recovery is counted."""
    monkeypatch.setattr(iso_mod, "_PARALLEL_THRESHOLD", 4)
    pairs = _pairs(6)
    clean = IsocalcWrapper(CFG, n_procs=1).pattern_table(pairs)
    # spawned children read SM_FAILPOINTS at import; the parent process
    # imported failpoints long ago with no spec, so the inline fallback
    # in the parent is NOT armed — exactly a "poisoned worker" scenario
    os.environ["SM_FAILPOINTS"] = "isocalc.worker=crash@1"
    calc = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=2, chunk_size=8)
    table = calc.pattern_table(pairs)
    assert table.sfs == clean.sfs
    np.testing.assert_array_equal(table.mzs, clean.mzs)
    rec = failpoints.recovery_counts()
    assert rec.get("isocalc.pool_broken", 0) >= 1
    assert rec.get("isocalc.chunk_inline", 0) >= 1


def test_worker_raise_is_retried(tmp_path, monkeypatch):
    """A chunk raising in a worker (typed fault, not a crash) is retried
    without poisoning the other chunks."""
    monkeypatch.setattr(iso_mod, "_PARALLEL_THRESHOLD", 4)
    pairs = _pairs(6)
    clean = IsocalcWrapper(CFG, n_procs=1).pattern_table(pairs)
    os.environ["SM_FAILPOINTS"] = "isocalc.worker=raise:RuntimeError@1"
    calc = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=2, chunk_size=8)
    table = calc.pattern_table(pairs)
    np.testing.assert_array_equal(table.mzs, clean.mzs)
    assert failpoints.recovery_counts().get("isocalc.worker_retry", 0) >= 1


def test_crash_leaves_resumable_shard_prefix(tmp_path):
    """Serial-path crash mid-generation (the chaos scenario's in-process
    twin): the committed chunk prefix survives, and the rerun loads it
    instead of recomputing those patterns."""
    pairs = _pairs(8)
    failpoints.configure("isocalc.worker=raise:RuntimeError@3")
    calc = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1, chunk_size=4)
    with pytest.raises(RuntimeError, match="injected failpoint"):
        calc.pattern_table(pairs)
    failpoints.configure(None)
    prefix = sorted(tmp_path.glob("theor_peaks_*"))
    assert len(prefix) == 2          # chunks 0 and 1 committed before the hit
    calc2 = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1, chunk_size=4)
    assert len(calc2._cache) == 8    # 2 chunks x 4 pairs served from disk
    t2 = calc2.pattern_table(pairs)
    clean = IsocalcWrapper(CFG, n_procs=1).pattern_table(pairs)
    np.testing.assert_array_equal(t2.mzs, clean.mzs)


def test_silent_shard_corruption_caught_by_crc(tmp_path):
    """Payload bytes corrupted INSIDE a valid zip (what np.load cannot see)
    must fail the shard CRC: the shard is dropped + unlinked and its
    entries recompute (PR 2's checkpoint hardening, extended to isocalc)."""
    calc = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1)
    t1 = calc.pattern_table([("C6H12O6", "+H"), ("H2O", "+H")])
    shard = next(tmp_path.glob("theor_peaks_*_c00000.npz"))
    with np.load(shard, allow_pickle=False) as z:
        data = {k: z[k].copy() for k in z.files}
    data["ints"][0, 0] += 1.0        # silent corruption; zip stays valid
    np.savez(shard, **data)          # crc member left stale on purpose
    failpoints.reset()
    calc2 = IsocalcWrapper(CFG, cache_dir=tmp_path)   # must not raise
    assert calc2._cache == {}
    assert not shard.exists()        # poison file removed, not just skipped
    assert failpoints.recovery_counts().get("isocalc.corrupt_shard", 0) == 1
    t2 = calc2.pattern_table([("C6H12O6", "+H"), ("H2O", "+H")])
    np.testing.assert_array_equal(t2.mzs, t1.mzs)


def test_stream_publishes_incremental_prefix(tmp_path):
    """wait_rows() returns as soon as the leading rows' chunks land, before
    the whole generation finishes."""
    os.environ["SM_ISOCALC_CHUNK"] = "4"
    pairs = _pairs(10, adducts=("+H",))
    calc = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1)
    stream = calc.stream_table(pairs)
    ready = stream.wait_rows(4)
    assert 4 <= ready <= stream.n_ions
    table = stream.result_table()
    assert stream.ready_rows() == table.n_ions == len(pairs)
    clean = IsocalcWrapper(CFG, n_procs=1).pattern_table(pairs)
    np.testing.assert_array_equal(table.mzs, clean.mzs)


def test_device_blur_centroid_matches_oracle(tmp_path):
    """The batched XLA blur->centroid stage (ops/isocalc_jax.py) matches the
    NumPy oracle within its documented tolerance, finds the same peak
    counts, and caches under a SEPARATE parameter key."""
    pairs = _pairs(10)
    oracle = IsocalcWrapper(CFG, n_procs=1).pattern_table(pairs)
    dev = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1,
                         device_blur=True)
    t_dev = dev.pattern_table(pairs)
    assert t_dev.sfs == oracle.sfs
    np.testing.assert_array_equal(t_dev.n_valid, oracle.n_valid)
    assert np.abs(t_dev.mzs - oracle.mzs).max() < 5e-6
    assert np.abs(t_dev.ints - oracle.ints).max() < 1e-3
    # separate cache namespace: an oracle-mode wrapper sees none of it
    host = IsocalcWrapper(CFG, cache_dir=tmp_path, n_procs=1)
    assert host._cache == {}
    # and a device-mode wrapper warm-loads all of it
    dev2 = IsocalcWrapper(CFG, cache_dir=tmp_path, device_blur=True)
    assert len(dev2._cache) == t_dev.n_ions


@pytest.fixture(scope="module")
def small_search_setup(tmp_path_factory):
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import (
        FIXTURE_FORMULAS,
        generate_synthetic_dataset,
    )

    td = tmp_path_factory.mktemp("overlap_ds")
    path, truth = generate_synthetic_dataset(
        td, nrows=12, ncols=12, formulas=FIXTURE_FORMULAS[:8],
        present_fraction=0.6, noise_peaks=40, mz_jitter_ppm=0.5, seed=7)
    return SpectralDataset.from_imzml(path), truth


def _run_search(ds, truth, tmp_path, overlap: str, prefetch=False,
                checkpoint=True):
    from sm_distributed_tpu.models.msm_basic import (
        IsotopePrefetch,
        MSMBasicSearch,
    )
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    ds_cfg = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                                 "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict({
        "backend": "numpy_ref",
        "fdr": {"decoy_sample_size": 8, "seed": 42},
        "parallel": {"formula_batch": 16, "order_ions": "table",
                     "checkpoint_every": 2 if checkpoint else 0,
                     "overlap_isocalc": overlap},
    })
    pf = IsotopePrefetch(truth.formulas, ds_cfg, sm,
                         str(tmp_path / "iso")) if prefetch else None
    search = MSMBasicSearch(
        ds, truth.formulas, ds_cfg, sm,
        isocalc_cache_dir=str(tmp_path / "iso"),
        checkpoint_dir=str(tmp_path / "ckpt") if checkpoint else None,
        prefetch=pf)
    return search.search()


def test_overlapped_scoring_equals_serial_phases(small_search_setup, tmp_path):
    """Incremental-shard scoring equivalence: scoring the leading checkpoint
    groups while generation streams must produce the identical report."""
    import pandas as pd

    ds, truth = small_search_setup
    os.environ["SM_ISOCALC_CHUNK"] = "16"   # several chunks -> real overlap
    b_off = _run_search(ds, truth, tmp_path / "off", overlap="off")
    b_auto = _run_search(ds, truth, tmp_path / "auto", overlap="auto")
    for key in ("annotations", "all_metrics"):
        lhs = getattr(b_off, key).sort_values(["sf", "adduct"]).reset_index(drop=True)
        rhs = getattr(b_auto, key).sort_values(["sf", "adduct"]).reset_index(drop=True)
        pd.testing.assert_frame_equal(lhs, rhs)


def test_prefetch_path_equals_inline_path(small_search_setup, tmp_path):
    """SearchJob's staging-overlap entry point (IsotopePrefetch) must be
    result-identical to search() doing its own decoys + generation."""
    import pandas as pd

    ds, truth = small_search_setup
    b_inline = _run_search(ds, truth, tmp_path / "a", overlap="auto",
                           checkpoint=False)
    b_prefetch = _run_search(ds, truth, tmp_path / "b", overlap="auto",
                             prefetch=True, checkpoint=False)
    pd.testing.assert_frame_equal(
        b_inline.all_metrics.sort_values(["sf", "adduct"]).reset_index(drop=True),
        b_prefetch.all_metrics.sort_values(["sf", "adduct"]).reset_index(drop=True))


def test_overlap_resumes_from_checkpoint(small_search_setup, tmp_path):
    """The pairs-based fingerprint must let an overlapped search resume from
    a mid-search checkpoint written by an earlier overlapped run."""
    ds, truth = small_search_setup
    from sm_distributed_tpu.utils.failpoints import failpoint  # noqa: F401

    failpoints.configure("device.score_batch=raise:RuntimeError@3")
    with pytest.raises(RuntimeError, match="injected failpoint"):
        _run_search(ds, truth, tmp_path, overlap="auto")
    failpoints.configure(None)
    shards = list((tmp_path / "ckpt").glob("*.ckpt.npz"))
    assert len(shards) == 2          # two groups durable before the fault
    b = _run_search(ds, truth, tmp_path, overlap="auto")
    b_clean = _run_search(ds, truth, tmp_path / "clean", overlap="off")
    import pandas as pd

    pd.testing.assert_frame_equal(
        b.all_metrics.sort_values(["sf", "adduct"]).reset_index(drop=True),
        b_clean.all_metrics.sort_values(["sf", "adduct"]).reset_index(drop=True))


def test_rate_collector_derives_scrape_rate():
    from sm_distributed_tpu.service.metrics import MetricsRegistry, rate_collector

    reg = MetricsRegistry()
    count = {"v": 0}
    rate_collector(reg, "test_rate_per_s", "t", lambda: count["v"])
    assert "test_rate_per_s 0" in reg.expose()
    count["v"] = 500
    import time

    time.sleep(0.05)
    text = reg.expose()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("test_rate_per_s"))
    assert float(line.split()[-1]) > 0


def test_warmup_manifest_skips_second_process(small_search_setup,
                                              isolated_compile_cache):
    """Warm-start trim: a second backend over the same stream + persistent
    cache skips the representative-batch executions, and still scores
    identically."""
    from sm_distributed_tpu.models.msm_basic import _slice_table, make_backend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    ds, truth = small_search_setup
    ds_cfg = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                                 "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu",
         # 1x1 mesh: the test targets JaxBackend.warmup; conftest forces 8
         # virtual host devices, which would route to the sharded backend
         "parallel": {"formula_batch": 16, "pixels_axis": 1,
                      "formulas_axis": 1}})
    table = IsocalcWrapper(ds_cfg.isotope_generation).pattern_table(
        [(sf, "+H") for sf in truth.formulas])
    batches = [_slice_table(table, s, min(s + 16, table.n_ions))
               for s in range(0, table.n_ions, 16)]
    b1 = make_backend("jax_tpu", ds, ds_cfg, sm, table=table)
    b1.warmup(batches)
    assert b1.last_warmup_skipped is False
    r1 = b1.score_batch(batches[0])
    b2 = make_backend("jax_tpu", ds, ds_cfg, sm, table=table)
    b2.warmup(batches)
    assert b2.last_warmup_skipped is True
    np.testing.assert_array_equal(r1, b2.score_batch(batches[0]))
