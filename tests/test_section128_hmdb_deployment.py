"""A typical section against an HMDB-scale table as a deployment (ISSUE 41),
at 16x16 px: ONE in-process service with ``benchmarks/configs/
maldi-section-128-hmdb.json``'s own ``sm_config`` and ``ds_config`` and a
table of 400 formulas x 21 = 8,400 ions in 33 batches of 256.  One job makes
the section resident; two resubmits under the same ``ds_id`` (the cell's
traffic) score it again.  Every stored report is compared with the
benchmark's plain reference (``benchmarks/oracle.py``), the same table scored
with each extraction variant forced is bit-identical to the plain path, and
the jobs' traces and ``/metrics`` hold what the deployment added to the
tracing (``sm_extract_slots_total`` / ``sm_extract_peaks_total``, ``slots`` /
``peaks`` on ``presize`` and ``score_plan``), which its two readers read.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))

import datasets  # noqa: E402  (benchmarks/)
import jobtrace  # noqa: E402
import oracle  # noqa: E402
from serve import metric_sum  # noqa: E402  (benchmarks/serve.py)
from scripts.load_sweep import Harness  # noqa: E402

CONFIGS = REPO / "benchmarks" / "configs"
HMDB = json.loads((CONFIGS / "maldi-section-128-hmdb.json").read_text())
SECTION = json.loads((CONFIGS / "maldi-section-128.json").read_text())
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "hmdb-section128-reannotate"
SMALL_TABLE_CELL = "hmdb-section64-reannotate"
# only the dataset parameters, oracle_sample_ions and the batch differ from
# the file: 20 of 400 formulas with signal, 33 batches
SMALL = json.loads(json.dumps(HMDB))
SMALL["dataset"].update(nrows=16, ncols=16, n_formulas=400,
                        present_fraction=0.05, noise_peaks=60)
SMALL["guarantees"]["oracle_sample_ions"] = 300
SMALL["sm_config"]["parallel"]["formula_batch"] = 256
N_IONS = 400 * (1 + HMDB["guarantees"]["decoys_per_target"])
BATCHES = -(-N_IONS // 256)
IDS = ["hmdb128-0", "hmdb128-1", "hmdb128-2"]
SLOTS, PEAKS = "sm_extract_slots_total", "sm_extract_peaks_total"


def test_the_file_is_the_128_section_but_for_its_table():
    """Every key outside the ones ISSUE 41 names equals
    ``maldi-section-128.json``'s: the two configurations differ in the table
    alone, and the spectra keep their 300 formulas with signal."""
    texts = {"name", "deployment", "source", "assumed"}
    assert set(HMDB) == set(SECTION)
    for key in set(HMDB) - texts - {"dataset"}:
        assert HMDB[key] == SECTION[key], key    # reduced: the same two cuts
    for key in texts:
        assert HMDB[key] != SECTION[key], key
    assert HMDB["reduced"] == ["formulas", "target_adducts"]
    resized = {"n_formulas", "present_fraction"}
    assert set(HMDB["dataset"]) == set(SECTION["dataset"])
    for key in set(HMDB["dataset"]) - resized:
        assert HMDB["dataset"][key] == SECTION["dataset"][key], key
    n = HMDB["dataset"]["n_formulas"]
    assert n in (4000, 3000, 2000, 1500)         # the sizing rule's rungs
    assert HMDB["dataset"]["present_fraction"] * n == pytest.approx(300)
    # every rung's readings, the seeds and the censuses are on the record
    for word in ("report_s", "whole run", "seed", "census", "4000"):
        assert word in HMDB["assumed"]["formulas"], word
    assert HMDB["chips"] == 1 and len(HMDB["source"]) <= 200
    assert len(set(datasets.formula_list(n))) == n


def test_the_manifest_names_the_deployment_its_cell_and_two_metrics():
    # later PRs append their entries after this one's (ISSUE 47 did)
    entry, = [c for c in MANIFEST["configs"] if c["name"] == HMDB["name"]]
    assert entry["source"] == HMDB["source"]
    assert entry["reduced"] == HMDB["reduced"]
    assert entry["file"] == "benchmarks/configs/maldi-section-128-hmdb.json"
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, HMDB["name"], "reannotate", 1)
    both = {"workloads": [SMALL_TABLE_CELL, CELL]}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [dict(by_name[n], workloads=by_name[n]["workloads"][:2])
            for n in ("extract_slot_fill_pct", "plan_executables")] == [
        {"name": "extract_slot_fill_pct", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "scoring",
         "moves": "ions_per_s", **both},
        {"name": "plan_executables", "unit": "count", "better": "lower",
         "source": "program_span", "layer": "compile cache",
         "moves": "report_p95_s", **both}]
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "store_images_s", "lease_device_busy_pct", "extract_device_s",
        "chaos_device_s", "moments_device_s", "chaos_roofline_pct",
        "hold_stall_s", "hold_unnamed_s", "host_cpu_per_job_s",
        "interp_late_ms", "pattern_load_s", "patterns_computed_in_window",
        "batch_host_ms", "extract_slot_fill_pct", "plan_executables",
        # which path the chaos kernel's programs took (ISSUE 48)
        "chaos_sparse_pct",
        # whether the image export reached the writer in chunks (ISSUE 49)
        "export_streamed_pct"}
    # the cell reports what the other resident cells report
    e2e = {m["name"] for m in MANIFEST["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}


@pytest.fixture(scope="module")
def section(tmp_path_factory):
    return datasets.generate(tmp_path_factory.mktemp("hmdb128_ds"),
                             SMALL["dataset"], 4100)


@pytest.fixture(scope="module")
def served(tmp_path_factory, section):
    """The three jobs through one service: kept answers, raw traces and the
    ``/metrics`` text before the first job and after each."""
    tmp = tmp_path_factory.mktemp("hmdb128")
    sm = json.loads(json.dumps(SMALL["sm_config"]))
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1})
    h = Harness(tmp, "hmdb128", sm_overrides=sm)
    results, kept = tmp / "hmdb128" / "results", tmp / "answers"
    scrapes, traces = [h.metrics_text()], {}
    try:
        for msg_id in IDS:
            status, _hd, body = h.submit({
                "ds_id": "hmdb128-ds", "msg_id": msg_id,
                "input_path": section["path"],
                "formulas": section["formulas"],
                "ds_config": SMALL["ds_config"]})
            assert status == 202, body
            row = h.wait_terminal([msg_id], timeout_s=300.0)[msg_id]
            assert (row["state"], row["attempts"]) == ("done", 1), row
            # a reprocess overwrites results/<ds_id>: keep each answer
            shutil.copytree(results / "hmdb128-ds", kept / msg_id)
            scrapes.append(h.metrics_text())
            with urllib.request.urlopen(
                    f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
                traces[msg_id] = json.loads(r.read())["records"]
    finally:
        h.shutdown()
    return {"kept": kept, "scrapes": scrapes, "traces": traces}


@pytest.mark.parametrize("msg_id", IDS)
def test_every_report_is_the_reference_answer(served, section, msg_id):
    """By the cell's own limits, and the resubmits bit-identical to the
    first job's."""
    said: list[str] = []
    nums = oracle.compare_job(served["kept"], msg_id, section, SMALL, 41, {})
    assert oracle.decide(nums, oracle.limits(SMALL["guarantees"]),
                         said.append), said
    for table in ("all_metrics.parquet", "annotations.parquet"):
        got = pd.read_parquet(served["kept"] / msg_id / table)
        want = pd.read_parquet(served["kept"] / IDS[0] / table)
        drop = [c for c in ("ds_id", "job_id") if c in got.columns]
        pd.testing.assert_frame_equal(
            got.drop(columns=drop), want.drop(columns=drop),
            check_exact=True, obj=f"{msg_id} vs {IDS[0]}: {table}")
    assert len(pd.read_parquet(
        served["kept"] / msg_id / "all_metrics.parquet")) == N_IONS


def _one(records, name):
    span, = jobtrace.spans(records, name)
    return span


def _delta(served, name, i, label=""):
    """What family ``name`` grew by over job ``i``."""
    before, after = (metric_sum(served["scrapes"][j], name, label)
                     for j in (i, i + 1))
    assert after is not None, name
    return after - (before or 0)


def test_the_plans_say_what_extraction_is_handed(served):
    """``presize`` and the two ``score_plan`` spans carry ``slots`` and
    ``peaks`` beside the census, the dispatches follow the plans, and the
    counters grow by exactly what a job's plans said."""
    for i, msg_id in enumerate(IDS):
        rec = served["traces"][msg_id]
        presize = _one(rec, "presize")["attrs"]
        assert presize["batches"] == BATCHES == 33
        assert {"executables", "band_buckets", "variants"} <= set(presize)
        assert presize["slots"] >= presize["peaks"] > 0
        plans = [p["attrs"] for p in jobtrace.spans(rec, "score_plan")]
        assert sorted(p["batches"] for p in plans) == [1, BATCHES - 1]
        for p in plans:
            assert p["slots"] >= p["peaks"] >= 0
        # the capacities are at their fixpoint after presize: the groups'
        # plans add up to the stream's
        assert sum(p["slots"] for p in plans) == presize["slots"]
        assert sum(p["peaks"] for p in plans) == presize["peaks"]
        assert _delta(served, SLOTS, i) == presize["slots"]
        assert _delta(served, PEAKS, i) == presize["peaks"]
        for variant, n in presize["variants"].items():
            assert n > 0
            label = f'variant="{variant}"'
            assert (_delta(served, SLOTS, i, label)
                    >= _delta(served, PEAKS, i, label) > 0)
    # every job of the resident section plans the same stream
    first = _one(served["traces"][IDS[0]], "presize")["attrs"]
    for msg_id in IDS[1:]:
        assert _one(served["traces"][msg_id], "presize")["attrs"] == first


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", REPO / "benchmarks" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_two_readers_read_those_jobs(served):
    """The window is the two resubmits: ``/metrics`` after the first job and
    after the last."""
    jobs = [{"trace": served["traces"][m]} for m in IDS[1:]]
    run = {"jobs": jobs, "metrics_before": served["scrapes"][1],
           "metrics_after": served["scrapes"][-1]}
    said = [_one(j["trace"], "presize")["attrs"] for j in jobs]
    fill = _reader("extract_slot_fill_pct")(run)
    assert fill == pytest.approx(100.0 * sum(a["peaks"] for a in said)
                                 / sum(a["slots"] for a in said))
    assert 0 < fill <= 100
    assert _reader("plan_executables")(run) == said[0]["executables"] >= 1
    # nothing to read: no job, an untraced job, a window in which nothing
    # was dispatched, a program without the counters or the attr (the
    # parent commit's), a job of one group (no presize span)
    empty = {"jobs": [{"trace": None}], "metrics_before": "",
             "metrics_after": ""}
    for name in ("extract_slot_fill_pct", "plan_executables"):
        assert _reader(name)(empty) is None, name
        assert _reader(name)({**empty, "jobs": []}) is None, name
    still = {**run, "metrics_before": run["metrics_after"]}
    assert _reader("extract_slot_fill_pct")(still) is None
    old = [dict(r, attrs={k: v for k, v in r.get("attrs", {}).items()
                          if k != "executables"})
           if r["name"] == "presize" else r for r in jobs[0]["trace"]]
    assert _reader("plan_executables")({**run, "jobs": [{"trace": old}]}) \
        is None
    one_group = [r for r in jobs[0]["trace"] if r["name"] != "presize"]
    assert _reader("plan_executables")(
        {**run, "jobs": [{"trace": one_group}]}) is None


def test_trace_report_prints_slots_and_peaks(served):
    from scripts import trace_report

    text = trace_report.render(
        trace_report.summarize(served["traces"][IDS[1]]))
    said = _one(served["traces"][IDS[1]], "presize")["attrs"]
    for want in (f"slots={said['slots']}", f"peaks={said['peaks']}",
                 "executables=", "variants="):
        assert want in text, (want, text)


@pytest.fixture(scope="module")
def scored(section, tmp_path_factory):
    """The table scored by ``MSMBasicSearch`` under a pair of
    (``band_slice``, ``peak_compaction``) on one chip, as under the cell's
    lease: sorted frames, the census the backend settles on and what the
    counters grew by."""
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models import msm_jax
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch, _slice_table
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    ds = SpectralDataset.from_imzml(section["path"])
    ds_cfg = DSConfig.from_dict(SMALL["ds_config"])
    iso = str(tmp_path_factory.mktemp("hmdb128_iso"))
    made: dict = {}

    def score(band_slice, peak_compaction):
        key = (band_slice, peak_compaction)
        if key not in made:
            sm = json.loads(json.dumps(SMALL["sm_config"]))
            sm["parallel"].update(band_slice=band_slice,
                                  peak_compaction=peak_compaction)
            search = MSMBasicSearch(ds, section["formulas"], ds_cfg,
                                    SMConfig.from_dict(sm),
                                    isocalc_cache_dir=iso,
                                    device_indices=(0,))    # as a lease of one
            before = msm_jax.extract_load_events()
            bundle = search.search()
            after = msm_jax.extract_load_events()
            grew = {v: tuple(a - b for a, b in zip(
                load, before.get(v, (0, 0)))) for v, load in after.items()}
            table, backend = search.last_table, search.last_backend
            census = backend._plan_census([
                backend._flat_plan(_slice_table(table, s, s + 256))
                for s in range(0, table.n_ions, 256)])
            by = ["sf", "adduct"]
            made[key] = {
                "all": bundle.all_metrics.sort_values(by).reset_index(
                    drop=True),
                "ann": bundle.annotations.sort_values(by).reset_index(
                    drop=True),
                "census": census,
                "grew": {v: g for v, g in grew.items() if g != (0, 0)}}
        return made[key]

    return score


@pytest.mark.parametrize("band_slice, peak_compaction, only", [
    ("on", "auto", "band"), ("auto", "on", "compact"), ("auto", "auto", None)])
def test_every_extraction_variant_scores_the_table_alike(
        scored, section, band_slice, peak_compaction, only):
    """``band_slice`` on, ``peak_compaction`` on and both ``auto`` against
    the plain path (both off): per-ion metrics and FDR levels bit for bit."""
    plain, got = scored("off", "off"), scored(band_slice, peak_compaction)
    assert set(plain["census"]["variants"]) == {"plain"}
    # with neither plan made, every resident slot counts as a peak inside
    assert plain["census"]["slots"] == plain["census"]["peaks"] > 0
    assert len(got["all"]) == N_IONS
    pd.testing.assert_frame_equal(got["all"], plain["all"], check_exact=True)
    pd.testing.assert_frame_equal(got["ann"], plain["ann"], check_exact=True)
    np.testing.assert_array_equal(got["ann"].fdr_level.to_numpy(),
                                  plain["ann"].fdr_level.to_numpy())
    census = got["census"]
    assert sum(census["variants"].values()) == BATCHES
    assert census["slots"] >= census["peaks"] > 0
    if only:
        assert census["variants"] == {only: BATCHES}
        assert set(got["grew"]) == {only}
    # one search dispatches every batch once: the counters grew by the census
    assert tuple(map(sum, zip(*got["grew"].values()))) == (
        census["slots"], census["peaks"])
    # a forced variant never hands extraction more than the plain path does
    assert census["slots"] <= plain["census"]["slots"]
    assert set(got["ann"][got["ann"].fdr_level <= 0.1].sf) >= set(
        section["present"])


def test_extract_load_counter_loses_no_update_between_workers():
    """Scheduler workers enqueue at once: 16 threads, each counting 2,000
    dispatches of (7 slots, 3 peaks)."""
    from sm_distributed_tpu.models import msm_jax

    before = msm_jax.extract_load_events().get("stress", (0, 0))
    workers = [threading.Thread(target=lambda: [
        msm_jax._count_extract_load("stress", 7, 3) for _ in range(2000)])
        for _ in range(16)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    after = msm_jax.extract_load_events()["stress"]
    assert (after[0] - before[0], after[1] - before[1]) == (
        16 * 2000 * 7, 16 * 2000 * 3)
    with msm_jax._EXTRACT_LOAD_LOCK:        # leave /metrics as it was
        if before == (0, 0):
            del msm_jax._EXTRACT_LOAD["stress"]
        else:
            msm_jax._EXTRACT_LOAD["stress"] = list(before)
