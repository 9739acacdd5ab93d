"""The third residency (ISSUE 40): a finished ion table - decoy draw plus
every isotope pattern of the job's list - kept across jobs of one parameter
set (``engine/residency.DatasetResidency``, ``models/msm_basic.py::
IsotopePrefetch``).  A hit hands back the table a fresh prefetch would
build, bit for bit; every part of the key misses on its own; a failed,
cancelled or partial table is never kept; the arrays are read-only; two
jobs of one process store the same report with and without it."""

from __future__ import annotations

import copy
import os
import sys
import threading

import numpy as np
import pandas as pd
import pytest

import sm_distributed_tpu.ops.isocalc as iso_mod
from sm_distributed_tpu.engine.residency import DatasetResidency
from sm_distributed_tpu.engine.search_job import SearchJob
from sm_distributed_tpu.io.fixtures import (
    expand_formula_list,
    generate_synthetic_dataset,
)
from sm_distributed_tpu.models.msm_basic import (
    IsotopePrefetch,
    ResidentIonTable,
    ion_table_key,
)
from sm_distributed_tpu.utils import failpoints, tracing
from sm_distributed_tpu.utils.config import DSConfig, SMConfig

FORMULAS = expand_formula_list(10)
DS = {"isotope_generation": {"adducts": ["+H", "+Na"], "charge": 1,
                             "isocalc_sigma": 0.01,
                             "isocalc_pts_per_mz": 5000, "n_peaks": 4},
      "image_generation": {"ppm": 3.0}}
SM = {"backend": "numpy_ref", "fdr": {"decoy_sample_size": 3, "seed": 7},
      "parallel": {"isocalc_device": "off"}}


@pytest.fixture(autouse=True)
def _clean():
    yield
    os.environ.pop("SM_ISOCALC_CHUNK", None)
    failpoints.reset()


def _configs(ds=None, sm=None):
    return DSConfig.from_dict(ds or DS), SMConfig.from_dict(sm or SM)


def _prefetch(cache_dir, residency=None, formulas=FORMULAS, ds=None, sm=None):
    return IsotopePrefetch(formulas, *_configs(ds, sm), str(cache_dir),
                           residency=residency)


def _finish(prefetch):
    """What ``MSMBasicSearch`` does with a prefetch: join, wait for the
    stream, offer the table."""
    fdr, assignment, stream = prefetch.result()
    table = stream.result_table()
    prefetch.keep()
    return fdr, assignment, table


def _same_table(got, want):
    for name in ("mzs", "ints", "n_valid", "targets"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert got.sfs == want.sfs and got.adducts == want.adducts


# ------------------------------------------------------------ (a) a hit
def test_a_hit_is_the_table_a_fresh_prefetch_builds(tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    first = _prefetch(tmp_path / "a", residency)
    _finish(first)
    assert first.isocalc is not None
    assert residency.stats["ion_table_misses"] == 1

    hit = _prefetch(tmp_path / "a", residency)
    # no thread, no wrapper, no stream that generates: result() is there
    assert hit._thread is None and hit.isocalc is None
    fdr, assignment, table = _finish(hit)
    assert hit.stream.cold_patterns == 0 and hit.stream.gen_seconds == 0.0
    assert residency.stats["ion_table_hits"] == 1
    assert residency.stats["ion_table_misses"] == 1

    # a fresh build: its own cache directory, no residency
    f_fdr, f_assignment, fresh = _finish(_prefetch(tmp_path / "b"))
    _same_table(table, fresh)
    assert fresh.n_ions > 2 * len(FORMULAS) and not fresh.targets.all()
    assert assignment.sample == f_assignment.sample
    assert assignment.decoy_sample_size == f_assignment.decoy_sample_size
    assert (fdr.decoy_sample_size, fdr.target_adducts, fdr.seed) == (
        f_fdr.decoy_sample_size, f_fdr.target_adducts, f_fdr.seed)


# ------------------------------------------------- (b) every part of the key
def _vary(path, value):
    def change(formulas, ds, sm):
        node = {"ds": ds, "sm": sm}
        for step in path[:-1]:
            node = node[step]
        node[path[-1]] = value
        return formulas
    return change


KEY_PARTS = {
    "one_formula": lambda f, ds, sm: f[:-1] + ["C7H15NO3"],
    "reordered": lambda f, ds, sm: f[::-1],
    "adducts": _vary(("ds", "isotope_generation", "adducts"), ["+H", "+K"]),
    "charge": _vary(("ds", "isotope_generation", "charge"), 2),
    "isocalc_sigma": _vary(("ds", "isotope_generation", "isocalc_sigma"),
                           0.02),
    "isocalc_pts_per_mz": _vary(
        ("ds", "isotope_generation", "isocalc_pts_per_mz"), 4000),
    "n_peaks": _vary(("ds", "isotope_generation", "n_peaks"), 3),
    "decoy_sample_size": _vary(("sm", "fdr", "decoy_sample_size"), 4),
    "fdr_seed": _vary(("sm", "fdr", "seed"), 8),
    "device_mode": _vary(("sm", "parallel", "isocalc_device"), "on"),
}


@pytest.fixture(scope="module")
def base_entry(tmp_path_factory):
    cache = tmp_path_factory.mktemp("base_iso")
    residency = DatasetResidency(max_datasets=1, max_backends=1)
    _finish(_prefetch(cache, residency))
    key = ion_table_key(FORMULAS, *_configs())
    return cache, key, residency.ion_table(key)


@pytest.mark.parametrize("part", sorted(KEY_PARTS))
def test_each_key_part_misses_and_builds_its_own_table(part, base_entry,
                                                       tmp_path):
    cache, base_key, entry = base_entry
    ds, sm = copy.deepcopy(DS), copy.deepcopy(SM)
    formulas = KEY_PARTS[part](list(FORMULAS), ds, sm)
    assert "C7H15NO3" not in FORMULAS
    key = ion_table_key(formulas, *_configs(ds, sm))
    assert key != base_key and hash(key) != hash(base_key)

    residency = DatasetResidency(max_datasets=2, max_backends=2)
    residency.keep_ion_table(base_key, entry)
    varied = _prefetch(cache, residency, formulas, ds, sm)
    assert varied._thread is not None            # a miss: today's path
    _fdr, assignment, table = _finish(varied)
    assert residency.stats["ion_table_hits"] == 0
    assert residency.stats["ion_table_misses"] == 1
    assert varied.device_blur is (part == "device_mode")
    # both live side by side, each under its own key
    assert residency.ion_table(base_key) is entry
    own = residency.ion_table(key)
    assert own is not entry and own.table.mzs is table.mzs
    assert own.device_blur is (part == "device_mode")
    # and the miss built what a prefetch without any residency builds
    _f, f_assignment, fresh = _finish(
        _prefetch(tmp_path / "fresh", None, formulas, ds, sm))
    _same_table(table, fresh)
    assert assignment.sample == f_assignment.sample
    differs = (table.sfs != entry.table.sfs
               or table.adducts != entry.table.adducts
               or table.mzs.shape != entry.table.mzs.shape
               or not np.array_equal(table.mzs, entry.table.mzs)
               or not np.array_equal(table.ints, entry.table.ints))
    assert differs, part


def test_env_device_mode_is_in_the_key(monkeypatch):
    """``isocalc_device: off`` leaves the mode to SM_ISOCALC_DEVICE: the key
    follows what the wrapper would resolve, not the knob."""
    monkeypatch.delenv("SM_ISOCALC_DEVICE", raising=False)
    oracle = ion_table_key(FORMULAS, *_configs())
    monkeypatch.setenv("SM_ISOCALC_DEVICE", "1")
    device = ion_table_key(FORMULAS, *_configs())
    assert oracle != device
    sm = copy.deepcopy(SM)
    sm["parallel"]["isocalc_device"] = "on"
    assert ion_table_key(FORMULAS, *_configs(sm=sm)) == device


def test_the_key_holds_nothing_of_a_dataset_and_dedups_like_the_prefetch(
        tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    _finish(_prefetch(tmp_path, residency))
    # duplicates collapse before the digest, as they do before the draw
    twice = _prefetch(tmp_path, residency, FORMULAS + FORMULAS[:3])
    assert twice._thread is None
    assert residency.stats["ion_table_hits"] == 1


# ------------------------------------------------- (c) two jobs, one process
@pytest.fixture(scope="module")
def section(tmp_path_factory):
    td = tmp_path_factory.mktemp("itr_ds")
    return generate_synthetic_dataset(
        td, nrows=8, ncols=8, present_fraction=0.5, noise_peaks=40, seed=11)


def _job(tmp, name, section, residency, overlap="auto"):
    """One SearchJob under its own trace; its stored tables and records."""
    path, truth = section
    sm = SMConfig.from_dict({
        **SM,
        "parallel": {"isocalc_device": "off", "formula_batch": 16,
                     "overlap_isocalc": overlap},
        "storage": {"results_dir": str(tmp / name / "res")},
        "work_dir": str(tmp / "work")})       # one isocalc cache for all
    ds_cfg = DSConfig.from_dict(DS)
    ctx = tracing.new_trace(job_id=name, trace_dir=tmp / "traces")
    with tracing.attach(ctx):
        SearchJob("itr", name, path, ds_cfg, sm, formulas=truth.formulas,
                  residency=residency).run()
    tracing.close_file(ctx.file)
    stored = {t: pd.read_parquet(tmp / name / "res" / "itr" / f"{t}.parquet")
              for t in ("annotations", "all_metrics")}
    return stored, tracing.read_trace(ctx.file)


def _spans(records, name):
    return [r for r in records if r["kind"] == "span" and r["name"] == name]


def _same_report(got, want):
    for t in ("annotations", "all_metrics"):
        drop = [c for c in ("ds_id", "job_id") if c in got[t].columns]
        pd.testing.assert_frame_equal(
            got[t].drop(columns=drop), want[t].drop(columns=drop),
            check_exact=True, obj=t)


@pytest.mark.parametrize("overlap", ["auto", "off"])
def test_second_job_scores_the_resident_table_and_stores_the_same_report(
        section, tmp_path, overlap):
    """``overlap: off`` has no SearchJob prefetch: ``search()`` makes its own
    and goes through the same lookup."""
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    first, rec1 = _job(tmp_path, "first", section, residency, overlap)
    second, rec2 = _job(tmp_path, "second", section, residency, overlap)
    plain, rec0 = _job(tmp_path, "plain", section, None, overlap)
    _same_report(second, first)
    _same_report(second, plain)
    assert len(first["annotations"]) > 0
    assert residency.stats["ion_table_hits"] == 1
    assert residency.stats["ion_table_misses"] == 1

    (setup,) = _spans(rec2, "isotope_prefetch_setup")
    table = residency.ion_table(ion_table_key(
        list(dict.fromkeys(section[1].formulas)), *_configs()))
    assert setup["attrs"] == {
        "formulas": len(set(section[1].formulas)),
        "ions": table.table.n_ions, "cache": "resident",
        "table_bytes": table.table.n_ions * (16 * 4 + 5)}
    # this thread ran it, and says so like every other span of the job
    assert 0.0 <= setup["cpu"] <= setup["dur"] + 0.002 and setup["dur"] < 0.05
    for gone in ("pattern_cache_load", "decoy_selection", "isocalc_gen"):
        assert not _spans(rec2, gone), gone
    (join,) = _spans(rec2, "prefetch_join")
    assert join["dur"] < 0.05
    (patterns,) = _spans(rec2, "isotope_patterns")
    if overlap == "off":      # the overlapped host path does not annotate
        assert patterns["attrs"]["computed"] == 0
        assert patterns["attrs"]["cached"] == table.table.n_ions
    assert not tracing.validate_records(rec2)

    # the miss before it, and a job without a residency (the isocalc cache
    # warm by then: the disk tier, a new process's first job), open the
    # same span once, around the draw and the shard read-back
    for rec, cache in ((rec1, "cold"), (rec0, "warm")):
        (setup,) = _spans(rec, "isotope_prefetch_setup")
        assert setup["attrs"]["cache"] == cache
        assert setup["attrs"]["table_bytes"] == table.table.n_ions * 69
        (load,) = _spans(rec, "pattern_cache_load")
        (draw,) = _spans(rec, "decoy_selection")
        assert load["parent_id"] == draw["parent_id"] == setup["span_id"]
        assert (load["attrs"]["entries"] > 0) is (cache == "warm")


# ------------------------------------------------------- (d) read-only arrays
def test_a_write_into_a_resident_array_raises(tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    _f, _a, built = _finish(_prefetch(tmp_path, residency))
    _f, _a, table = _finish(_prefetch(tmp_path, residency))
    assert table.mzs is built.mzs      # the arrays the miss filled
    for name in ("mzs", "ints", "n_valid", "targets"):
        arr = getattr(table, name)
        assert not arr.flags.writeable, name
        with pytest.raises(ValueError, match="read-only"):
            arr[0] = 0
    entry = residency.ion_table(ion_table_key(FORMULAS, *_configs()))
    with pytest.raises(AttributeError):       # frozen
        entry.table = None


# --------------------------------------------- (e) only a whole table is kept
def test_a_failed_stream_leaves_no_entry(tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    os.environ["SM_ISOCALC_CHUNK"] = "8"
    failpoints.configure("isocalc.worker=raise:RuntimeError@2")
    failed = _prefetch(tmp_path, residency)
    _fdr, _assignment, stream = failed.result()
    with pytest.raises(RuntimeError, match="injected failpoint"):
        stream.result_table()
    assert not stream.complete()
    failed.keep()
    key = ion_table_key(FORMULAS, *_configs())
    assert residency.ion_table(key) is None
    failpoints.reset()
    # the rerun resumes from the shard prefix and ITS table is kept
    _f, _a, table = _finish(_prefetch(tmp_path, residency))
    assert residency.ion_table(key).table.mzs is table.mzs


def test_a_cancelled_stream_leaves_no_entry(tmp_path, monkeypatch):
    """``cancel()`` ends the stream's thread cleanly with rows missing:
    ``result_table()`` does not raise then, so ``complete()`` is the gate."""
    os.environ["SM_ISOCALC_CHUNK"] = "8"
    reached, gate = threading.Event(), threading.Event()
    real = iso_mod._compute_chunk

    def held(args):
        if args[0] == 1:
            reached.set()
            assert gate.wait(30)
        return real(args)

    monkeypatch.setattr(iso_mod, "_compute_chunk", held)
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    prefetch = _prefetch(tmp_path, residency)
    _fdr, _assignment, stream = prefetch.result()
    assert reached.wait(30)
    stream._cancel.set()
    gate.set()
    prefetch.cancel()
    assert not stream._thread.is_alive()
    assert 0 < stream.ready_rows() < stream.n_ions
    stream.result_table()                       # does not raise
    assert not stream.complete()
    prefetch.keep()
    assert residency.ion_table(ion_table_key(FORMULAS, *_configs())) is None


def test_a_failed_setup_leaves_no_entry_and_reraises(tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    sm = copy.deepcopy(SM)
    sm["fdr"]["decoy_sample_size"] = 500        # more than the adducts there are
    bad = _prefetch(tmp_path, residency, sm=sm)
    with pytest.raises(ValueError, match="decoy_sample_size"):
        bad.result()
    bad.cancel()
    assert residency._ion_tables.data == {}


def test_cancel_on_a_hit_returns(tmp_path):
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    _finish(_prefetch(tmp_path, residency))
    hit = _prefetch(tmp_path, residency)
    hit.cancel()
    hit.keep()                                  # nothing to offer either
    _f, _a, table = _finish(hit)                # still whole
    assert table.n_ions > 0 and len(residency._ion_tables.data) == 1


# ------------------------------------------------------------ (f) the bound
def _entry(tag):
    return ResidentIonTable(fdr=None, assignment=None, table=tag,
                            device_blur=False)


def test_the_lru_evicts_at_the_datasets_bound():
    residency = DatasetResidency(max_datasets=2, max_backends=7)
    keys = [("ion_table", str(i)) for i in range(3)]
    for k in keys[:2]:
        residency.keep_ion_table(k, _entry(k))
    assert residency.ion_table(keys[0]).table == keys[0]   # 0 is the newest
    residency.keep_ion_table(keys[2], _entry(keys[2]))
    assert residency.ion_table(keys[1]) is None            # 1 was the oldest
    assert residency.ion_table(keys[0]) is not None
    assert residency.ion_table(keys[2]) is not None
    # the first insert wins: a concurrent builder's duplicate is dropped
    assert residency.keep_ion_table(keys[0], _entry("late")).table == keys[0]
    assert residency.stats["ion_table_hits"] == 3
    assert residency.stats["ion_table_misses"] == 1
    assert residency.stats["dataset_misses"] == 0


def test_resident_datasets_0_keeps_nothing(tmp_path):
    residency = DatasetResidency(max_datasets=0, max_backends=0)
    for _ in range(2):
        prefetch = _prefetch(tmp_path, residency)
        assert prefetch._thread is not None
        _finish(prefetch)
    assert residency._ion_tables.data == {}
    assert residency.stats["ion_table_hits"] == 0
    assert residency.stats["ion_table_misses"] == 2


# -------------------------------------------------- workers on one table
def test_workers_racing_for_one_table_all_score_the_same_one(tmp_path):
    """More workers than cores, the interpreter switching every few
    microseconds: every prefetch ends with an identical table, the residency
    with ONE entry, and no lookup is lost from the counters."""
    residency = DatasetResidency(max_datasets=2, max_backends=2)
    _finish(_prefetch(tmp_path, None))          # warm the disk tier
    n = 2 * (os.cpu_count() or 4)
    tables, errors = [None] * n, []
    start = threading.Barrier(n)

    def work(i):
        try:
            start.wait(30)
            tables[i] = _finish(_prefetch(tmp_path, residency))[2]
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    for table in tables[1:]:
        _same_table(table, tables[0])
    stats = residency.stats
    assert stats["ion_table_hits"] + stats["ion_table_misses"] == n
    assert stats["ion_table_misses"] >= 1
    assert len(residency._ion_tables.data) == 1
    kept = residency.ion_table(ion_table_key(FORMULAS, *_configs())).table
    _same_table(kept, tables[0])
    assert not kept.mzs.flags.writeable
