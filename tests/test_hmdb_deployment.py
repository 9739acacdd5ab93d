"""The database at its own size as a deployment (ISSUE 39), at 8x8 px: ONE
in-process service with ``benchmarks/configs/maldi-section-64-hmdb.json``'s
own ``sm_config`` and ``ds_config`` and a table of 400 formulas x 21 = 8,400
ions in 33 batches of 256.  One job makes the section resident and computes
the table's patterns cold; two resubmits under the same ``ds_id`` (the
cell's traffic) score the table it left resident (ISSUE 40; the read-back
from the isocalc cache, a new process's first job, is held by
``test_ion_table_residency.py``).  Every stored report
is compared with the benchmark's plain reference (``benchmarks/oracle.py``,
numpy/scipy, nothing of the program) and the resubmits are bit-identical to
the first.  The same jobs' traces and ``/metrics`` hold what the deployment
added to the tracing, and the three per-layer readers it brought read them.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
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
from sm_distributed_tpu.utils.config import DSConfig  # noqa: E402

CONFIGS = REPO / "benchmarks" / "configs"
HMDB = json.loads((CONFIGS / "maldi-section-64-hmdb.json").read_text())
SECTION = json.loads((CONFIGS / "maldi-section-64.json").read_text())
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "hmdb-section64-reannotate"
# only the dataset parameters, oracle_sample_ions and the batch differ from
# the file: 20 of 400 formulas with signal, 33 batches
SMALL = json.loads(json.dumps(HMDB))
SMALL["dataset"].update(nrows=8, ncols=8, n_formulas=400,
                        present_fraction=0.05, noise_peaks=60)
SMALL["guarantees"]["oracle_sample_ions"] = 300
SMALL["sm_config"]["parallel"]["formula_batch"] = 256
N_IONS = 400 * (1 + HMDB["guarantees"]["decoys_per_target"])
BATCHES = -(-N_IONS // 256)
IDS = ["hmdb-0", "hmdb-1", "hmdb-2"]


def test_the_file_is_the_64_section_but_for_its_table():
    """Every key outside the ones ISSUE 39 names equals
    ``maldi-section-64.json``'s: the two configurations differ in the table
    alone, and the spectra keep their 300 formulas with signal."""
    texts = {"name", "deployment", "source", "assumed", "reduced"}
    assert set(HMDB) == set(SECTION)
    for key in set(HMDB) - texts - {"dataset"}:
        assert HMDB[key] == SECTION[key], key
    for key in texts - {"reduced"}:       # under 8000, the same three cuts
        assert HMDB[key] != SECTION[key], key
    resized = {"n_formulas", "present_fraction"}
    assert set(HMDB["dataset"]) == set(SECTION["dataset"])
    for key in set(HMDB["dataset"]) - resized:
        assert HMDB["dataset"][key] == SECTION["dataset"][key], key
    n = HMDB["dataset"]["n_formulas"]
    assert n in (8000, 6000, 4000)
    assert round(HMDB["dataset"]["present_fraction"] * n) == 300
    assert HMDB["dataset"]["present_fraction"] * n == pytest.approx(300)
    assert ("formulas" in HMDB["reduced"]) is (n < 8000)
    assert set(HMDB["reduced"]) - {"formulas"} == {"target_adducts", "pixels"}
    # the rung is the sizing rule's: its readings are on the record
    assert "report_s" in HMDB["assumed"]["formulas"]
    assert "whole run" in HMDB["assumed"]["formulas"]
    assert HMDB["chips"] == 1 and len(HMDB["source"]) <= 200
    # the generator holds the table: distinct formulas, none past the data
    assert len(set(datasets.formula_list(n))) == n


def test_the_manifest_names_the_deployment_and_its_three_metrics():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == HMDB["name"]]
    assert entry["source"] == HMDB["source"]
    assert entry["reduced"] == HMDB["reduced"]
    cell, = [w for w in MANIFEST["workloads"] if w["config"] == HMDB["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "reannotate", 1)
    # later cells append their names after this one's (ISSUE 41 did)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    new = {"workloads": [CELL], "better": "lower"}
    assert [dict(by_name[n], workloads=by_name[n]["workloads"][:1])
            for n in ("pattern_load_s", "patterns_computed_in_window",
                      "batch_host_ms")] == [
        {"name": "pattern_load_s", "unit": "s", "source": "program_span",
         "layer": "isotope patterns", "moves": "report_s", **new},
        {"name": "patterns_computed_in_window", "unit": "count",
         "source": "program_counter", "layer": "isotope patterns",
         "moves": "report_p95_s", **new},
        {"name": "batch_host_ms", "unit": "ms", "source": "program_span",
         "layer": "scoring", "moves": "ions_per_s", **new}]
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "store_images_s", "lease_device_busy_pct", "extract_device_s",
        "chaos_device_s", "moments_device_s", "chaos_roofline_pct",
        "hold_stall_s", "hold_unnamed_s", "host_cpu_per_job_s",
        "interp_late_ms", "pattern_load_s", "patterns_computed_in_window",
        "batch_host_ms", "extract_slot_fill_pct", "plan_executables",
        # one ranking and one stored assignment a job, against the three of
        # hmdb-section64-3adducts-reannotate (ISSUE 47)
        "fdr_rank_s", "assignment_store_s",
        # which path the chaos kernel's programs took (ISSUE 48)
        "chaos_sparse_pct"}


@pytest.fixture(scope="module")
def section(tmp_path_factory):
    return datasets.generate(tmp_path_factory.mktemp("hmdb_ds"),
                             SMALL["dataset"], 3900)


@pytest.fixture(scope="module")
def served(tmp_path_factory, section):
    """The three jobs through one service: kept answers, raw traces and the
    ``/metrics`` text before the first job and after each."""
    tmp = tmp_path_factory.mktemp("hmdb")
    sm = json.loads(json.dumps(SMALL["sm_config"]))
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1})
    h = Harness(tmp, "hmdb", sm_overrides=sm)
    results, kept = tmp / "hmdb" / "results", tmp / "answers"
    scrapes, traces = [h.metrics_text()], {}
    try:
        for msg_id in IDS:
            status, _hd, body = h.submit({
                "ds_id": "hmdb-ds", "msg_id": msg_id,
                "input_path": section["path"],
                "formulas": section["formulas"],
                "ds_config": SMALL["ds_config"]})
            assert status == 202, body
            row = h.wait_terminal([msg_id], timeout_s=300.0)[msg_id]
            assert (row["state"], row["attempts"]) == ("done", 1), row
            # a reprocess overwrites results/<ds_id>: keep each answer
            shutil.copytree(results / "hmdb-ds", kept / msg_id)
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
    nums = oracle.compare_job(served["kept"], msg_id, section, SMALL, 39, {})
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


def test_the_traces_say_what_the_table_cost(served):
    for i, msg_id in enumerate(IDS):
        rec = served["traces"][msg_id]
        setup = _one(rec, "isotope_prefetch_setup")
        n_peaks = DSConfig.from_dict(
            SMALL["ds_config"]).isotope_generation.n_peaks
        assert setup["attrs"] == {
            "formulas": 400, "ions": N_IONS,
            "cache": "cold" if i == 0 else "resident",
            # two f64 blocks, an i32 and a bool an ion
            "table_bytes": N_IONS * (16 * n_peaks + 5)}
        if i == 0:
            decoys = _one(rec, "decoy_selection")
            assert decoys["attrs"] == {
                "formulas": 400, "decoys": 20, "target_adducts": 1,
                "triples": 8000, "distinct_decoys": 8000}
            load = _one(rec, "pattern_cache_load")
            assert (decoys["parent_id"] == load["parent_id"]
                    == setup["span_id"])
            assert load["attrs"] == {"shards": 0, "entries": 0, "bytes": 0}
        else:
            # a resubmit scores the table the first job left resident: no
            # decoy draw, no wrapper, no shard read back
            for gone in ("decoy_selection", "pattern_cache_load"):
                assert not jobtrace.spans(rec, gone), gone
        patterns = _one(rec, "isotope_patterns")["attrs"]
        assert patterns["phase"] is True and patterns["ions"] == N_IONS
        assert (patterns["computed"], patterns["cached"]) == (
            (N_IONS, 0) if i == 0 else (0, N_IONS))
        assert patterns["gen_s"] >= 0
        presize = _one(rec, "presize")["attrs"]
        assert presize["batches"] == BATCHES == 33
        assert sum(presize["variants"].values()) == BATCHES
        assert 1 <= presize["executables"] <= BATCHES
        assert presize["band_buckets"] <= presize["executables"]
        plans = jobtrace.spans(rec, "score_plan")
        assert sorted(p["attrs"]["batches"] for p in plans) == [1, BATCHES - 1]
        merged: dict[str, int] = {}
        for p in plans:
            assert {"executables", "band_buckets"} <= set(p["attrs"])
            for v, n in p["attrs"]["variants"].items():
                merged[v] = merged.get(v, 0) + n
        assert merged == presize["variants"]    # dispatch follows presize
        enq = [s for s in jobtrace.spans(rec, "score_batch")
               if s["attrs"].get("enqueue")]
        assert len(enq) == BATCHES
        assert {s["attrs"]["backend"] for s in enq} == {"jax_tpu"}
        fdr = _one(rec, "fdr")["attrs"]
        assert (fdr["ions"], fdr["targets"], fdr["decoys"]) == (
            N_IONS, 400, N_IONS - 400)
        tables = _one(rec, "store_tables")["attrs"]
        assert tables["rows"] == N_IONS + 400 and tables["bytes"] > 0


def test_metrics_count_the_cold_table_once_and_a_hit_every_resubmit(served):
    def deltas(name, label=""):
        vals = [metric_sum(s, name, label) for s in served["scrapes"]]
        assert None not in vals, name    # exposed before the first job
        return [b - a for a, b in zip(vals, vals[1:])]

    assert deltas("sm_isocalc_patterns_total") == [N_IONS, 0, 0]
    assert deltas("sm_isocalc_cache_entries_loaded_total") == [0, 0, 0]
    ion_table = 'cache="ion_table"'
    assert deltas("sm_residency_misses_total", ion_table) == [1, 0, 0]
    assert deltas("sm_residency_hits_total", ion_table) == [0, 1, 1]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", REPO / "benchmarks" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_three_readers_read_those_jobs(served):
    """The window is the two resubmits: ``/metrics`` after the first job and
    after the last."""
    jobs = [{"trace": served["traces"][m]} for m in IDS[1:]]
    run = {"jobs": jobs, "metrics_before": served["scrapes"][1],
           "metrics_after": served["scrapes"][-1]}
    setups = [_one(j["trace"], "isotope_prefetch_setup")["dur"] for j in jobs]
    assert _reader("pattern_load_s")(run) == pytest.approx(np.median(setups))
    assert _reader("patterns_computed_in_window")(run) == 0
    cold = {**run, "metrics_before": served["scrapes"][0]}
    assert _reader("patterns_computed_in_window")(cold) == N_IONS
    per_batch = []
    for j in jobs:
        host = sum(s["dur"] for name in ("presize", "score_plan")
                   for s in jobtrace.spans(j["trace"], name))
        host += sum(s["dur"] for s in jobtrace.spans(j["trace"], "score_batch"))
        per_batch.append(1000.0 * host / BATCHES)
    got = _reader("batch_host_ms")(run)
    assert got == pytest.approx(np.median(per_batch)) and got > 0
    # nothing to read: no job, an untraced job, a program without the
    # counter, a job of one group (no presize span)
    empty = {"jobs": [{"trace": None}], "metrics_before": "",
             "metrics_after": ""}
    for name in ("pattern_load_s", "patterns_computed_in_window",
                 "batch_host_ms"):
        assert _reader(name)(empty) is None, name
        assert _reader(name)({**empty, "jobs": []}) is None, name
    one_group = [r for r in jobs[0]["trace"] if r["name"] != "presize"]
    assert _reader("batch_host_ms")(
        {**run, "jobs": [{"trace": one_group}]}) is None


def test_trace_report_prints_the_new_attrs(served):
    from scripts import trace_report

    wants = {IDS[0]: ("cache=cold", "entries=0", "decoys=20", "cached=0",
                      f"computed={N_IONS}"),
             IDS[1]: ("cache=resident", "table_bytes=", f"cached={N_IONS}",
                      "computed=0")}
    for msg_id, said in wants.items():
        text = trace_report.render(
            trace_report.summarize(served["traces"][msg_id]))
        for want in said + ("executables=", "band_buckets=", "variants=",
                            "targets=400", f"rows={N_IONS + 400}"):
            assert want in text, (msg_id, want, text)


def test_one_batch_and_33_batches_score_the_table_alike(section, tmp_path):
    """The same table as ONE batch and as 33: chaos and FDR levels equal,
    spatial / spectral / msm inside the program's COMPONENT_CONTRACTS."""
    from sm_distributed_tpu.analysis.numerics import component_report
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    ds = SpectralDataset.from_imzml(section["path"])
    ds_cfg = DSConfig.from_dict(SMALL["ds_config"])
    cols = ["chaos", "spatial", "spectral", "msm"]
    got = {}
    for batch in (256, 16384):
        sm = json.loads(json.dumps(SMALL["sm_config"]))
        sm["parallel"]["formula_batch"] = batch
        bundle = MSMBasicSearch(
            ds, section["formulas"], ds_cfg, SMConfig.from_dict(sm),
            isocalc_cache_dir=str(tmp_path / "iso")).search()
        allm = bundle.all_metrics.sort_values(
            ["sf", "adduct"]).reset_index(drop=True)
        ann = bundle.annotations.sort_values(
            ["sf", "adduct"]).reset_index(drop=True)
        assert len(allm) == N_IONS
        got[batch] = (allm, ann)
    (many, ann_many), (one, ann_one) = got[256], got[16384]
    assert list(many.sf) == list(one.sf)
    assert list(many.adduct) == list(one.adduct)
    np.testing.assert_array_equal(many.chaos.to_numpy(), one.chaos.to_numpy())
    report = component_report(many[cols].to_numpy(), one[cols].to_numpy())
    assert {c: r["outside"] for c, r in report.items()} == dict.fromkeys(
        cols, 0), report
    np.testing.assert_array_equal(ann_many.fdr_level.to_numpy(),
                                  ann_one.fdr_level.to_numpy())
    assert set(ann_one[ann_one.fdr_level <= 0.1].sf) >= set(section["present"])


def test_entries_loaded_counter_loses_no_update_between_workers():
    """Scheduler workers build wrappers at once: 16 threads on 8 cores, the
    interpreter switching every microsecond, each counting 2,000 loads of 7
    entries into the module's total and an attached registry."""
    import threading

    from sm_distributed_tpu.ops import isocalc
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    name = "sm_isocalc_cache_entries_loaded_total"
    had = isocalc._metrics_registry
    reg = MetricsRegistry()
    isocalc.attach_metrics(reg)
    before = metric_sum(reg.expose(), name)
    workers = [threading.Thread(target=lambda: [
        isocalc._count_entries_loaded(7) for _ in range(2000)])
        for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
    finally:
        sys.setswitchinterval(interval)
        isocalc._metrics_registry = had
    assert not any(w.is_alive() for w in workers)
    assert metric_sum(reg.expose(), name) - before == 16 * 2000 * 7
