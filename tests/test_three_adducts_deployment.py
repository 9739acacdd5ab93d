"""The source's own three target adducts as a deployment (ISSUE 47), at 8x8
px: ONE in-process service with
``benchmarks/configs/maldi-section-64-hmdb-3adducts.json``'s own ``sm_config``
and ``ds_config`` and a table of 60 formulas x {+H,+Na,+K}.  One job makes
the section and the ion table resident and draws the decoys; two resubmits
under the same ``ds_id`` (the cell's traffic) rank by the draw it left
resident; a fourth dies between the tmp writes and the renames; a fifth runs
under {+H} alone.  Every stored report is compared with the benchmark's plain
reference (``benchmarks/oracle.py``) USING THE ASSIGNMENT THE PROGRAM STORED
(``target_decoy_add.parquet``), which is the seeded draw row for row and the
same bytes after every resubmit.  The same jobs' traces and ``/metrics`` hold
what the deployment added to the tracing, and the three per-layer readers it
brought read them.
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
from sm_distributed_tpu.engine.storage import (  # noqa: E402
    FP_RESULTS_RENAME,
    RESULT_TABLES,
    read_result_tables,
)
from sm_distributed_tpu.ops.fdr import ASSIGNMENT_COLUMNS, FDR  # noqa: E402
from sm_distributed_tpu.utils import failpoints  # noqa: E402
from sm_distributed_tpu.utils.config import DSConfig  # noqa: E402

CONFIGS = REPO / "benchmarks" / "configs"
THREE = json.loads(
    (CONFIGS / "maldi-section-64-hmdb-3adducts.json").read_text())
HMDB = json.loads((CONFIGS / "maldi-section-64-hmdb.json").read_text())
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, SIBLING = "hmdb-section64-3adducts-reannotate", "hmdb-section64-reannotate"
ADDUCTS = ["+H", "+Na", "+K"]
N_FORMULAS, K = 60, THREE["guarantees"]["decoys_per_target"]
TRIPLES = N_FORMULAS * len(ADDUCTS) * K
# only the dataset's size, oracle_sample_ions and the batch differ from the
# file: 18 of 60 formulas with signal, six an adduct
SMALL = json.loads(json.dumps(THREE))
SMALL["dataset"].update(nrows=8, ncols=8, n_formulas=N_FORMULAS,
                        noise_peaks=60)
SMALL["guarantees"]["oracle_sample_ions"] = 300
SMALL["sm_config"]["parallel"]["formula_batch"] = 256
ONE = json.loads(json.dumps(SMALL))
ONE["ds_config"]["isotope_generation"]["adducts"] = ["+H"]
del ONE["dataset"]["adducts"]
IDS = ["three-0", "three-1", "three-2"]
LIMITS = oracle.limits(THREE["guarantees"])


def test_the_file_is_the_hmdb_section_under_the_sources_three_adducts():
    """Every key outside the ones ISSUE 47 names equals
    ``maldi-section-64-hmdb.json``'s, and ``target_adducts`` is no longer a
    cut: the first configuration without it under ``reduced``."""
    texts = {"name", "deployment", "source", "assumed", "reduced"}
    assert set(THREE) == set(HMDB)
    for key in set(THREE) - texts - {"dataset", "ds_config"}:
        assert THREE[key] == HMDB[key], key
    for key in texts:
        assert THREE[key] != HMDB[key], key
    assert THREE["reduced"] == ["formulas", "pixels"]
    assert "target_adducts" in HMDB["reduced"]
    assert set(THREE["assumed"]) == {"spectra", "formulas", "formula_list",
                                     "present_fraction", "device_memory"}
    assert {**THREE["dataset"], "adducts": None} == {
        **HMDB["dataset"], "n_formulas": 1000, "present_fraction": 0.3,
        "adducts": None}
    assert THREE["dataset"]["adducts"] == ADDUCTS
    assert THREE["ds_config"]["image_generation"] == \
        HMDB["ds_config"]["image_generation"]
    assert THREE["ds_config"]["isotope_generation"] == {"adducts": ADDUCTS}
    assert DSConfig.from_dict(
        THREE["ds_config"]).isotope_generation.adducts == tuple(ADDUCTS)
    assert round(1000 * THREE["dataset"]["present_fraction"]) == 300
    assert THREE["chips"] == 1 and len(THREE["source"]) <= 200
    # sized by its decoys: the survey's seeds and its result are on record
    for said in ("positives_survey", "64", "0 of"):
        assert said in THREE["assumed"]["formulas"], said
    assert "the stored decoy assignment is the one the job ranked by" in \
        THREE["deployment"]
    assert len(set(datasets.formula_list(1000))) == 1000


def test_the_manifest_names_the_deployment_and_its_three_metrics():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == THREE["name"]]
    assert entry["source"] == THREE["source"]
    assert entry["reduced"] == THREE["reduced"] == ["formulas", "pixels"]
    # found by name: a later configuration may stand anywhere (C20)
    assert entry["file"] == \
        "benchmarks/configs/maldi-section-64-hmdb-3adducts.json"
    others = [c for c in MANIFEST["configs"] if c is not entry]
    assert all("target_adducts" in c["reduced"] for c in others)
    assert entry["source"] not in {c["source"] for c in others}
    cell, = [w for w in MANIFEST["workloads"] if w["config"] == THREE["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "reannotate", 1)
    both = {"better": "lower", "unit": "s", "source": "program_span",
            "layer": "fdr store", "moves": "report_s",
            "workloads": [SIBLING, CELL]}
    # later metrics append after these three (ISSUE 48 did)
    by_name = {m["name"]: i for i, m in enumerate(MANIFEST["per_layer"])}
    first = by_name["fdr_rank_s"]
    assert MANIFEST["per_layer"][first:first + 3] == [
        {"name": "fdr_rank_s", **both},
        {"name": "assignment_store_s", **both},
        {"name": "decoy_shared_pct", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "isotope patterns",
         "moves": "ions_per_s", "workloads": [CELL]}]

    def listed(name):
        return {m["name"] for m in MANIFEST["per_layer"]
                if name in m.get("workloads", [])}

    # everything the sibling cell reports, and the share of shared decoys
    assert listed(CELL) == listed(SIBLING) | {"decoy_shared_pct"}
    # wherever both are listed the cell stands behind its sibling; where
    # in a list that is, is for the lists' other cells to say (C20)
    assert all(m["workloads"].index(CELL) > m["workloads"].index(SIBLING)
               for m in MANIFEST["per_layer"]
               if CELL in m.get("workloads", [])
               and SIBLING in m["workloads"])
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}


@pytest.fixture(scope="module")
def section(tmp_path_factory):
    return datasets.generate(tmp_path_factory.mktemp("three_ds"),
                             SMALL["dataset"], 4700)


@pytest.fixture(scope="module")
def section_h(tmp_path_factory):
    """The same section with all its signal under {+H}."""
    return datasets.generate(tmp_path_factory.mktemp("one_ds"),
                             ONE["dataset"], 4700)


def _keep(results: Path, ds_id: str, kept: Path) -> None:
    """As ``benchmarks/traffic.py::Driver.wait`` keeps an answer."""
    kept.mkdir(parents=True)
    for table in RESULT_TABLES:
        shutil.copy(results / ds_id / table, kept)


@pytest.fixture(scope="module")
def served(tmp_path_factory, section, section_h):
    """The five jobs through one service: kept answers, raw traces, the
    ``/metrics`` text before the first job and after each, and the stored
    tables' bytes around the job that died before its renames."""
    tmp = tmp_path_factory.mktemp("three")
    sm = json.loads(json.dumps(SMALL["sm_config"]))
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1})
    h = Harness(tmp, "three", sm_overrides=sm)
    results, kept = tmp / "three" / "results", tmp / "answers"
    out = {"kept": kept, "scrapes": [h.metrics_text()], "traces": {}}

    def job(msg_id, ds_id, cfg, want="done", ds=section):
        status, _hd, body = h.submit({
            "ds_id": ds_id, "msg_id": msg_id, "input_path": ds["path"],
            "formulas": ds["formulas"], "ds_config": cfg["ds_config"]})
        assert status == 202, body
        row = h.wait_terminal([msg_id], timeout_s=300.0)[msg_id]
        assert (row["state"], row["attempts"]) == (want, 1), row
        out["scrapes"].append(h.metrics_text())
        with urllib.request.urlopen(
                f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
            out["traces"][msg_id] = json.loads(r.read())["records"]

    def stored():
        return {t: (results / "three-ds" / t).read_bytes()
                for t in RESULT_TABLES}

    try:
        for msg_id in IDS:
            job(msg_id, "three-ds", SMALL)
            # a reprocess overwrites results/<ds_id>: keep each answer
            _keep(results, "three-ds", kept / msg_id)
        out["before_crash"] = stored()
        failpoints.configure(f"{FP_RESULTS_RENAME}=raise:OSError@1")
        try:
            job("three-crash", "three-ds", SMALL, want="failed")
        finally:
            failpoints.reset()
        out["after_crash"] = stored()
        out["debris"] = sorted(
            p.name for p in (results / "three-ds").glob("*.tmp"))
        job("one", "one-ds", ONE, ds=section_h)
        _keep(results, "one-ds", kept / "one")
    finally:
        h.shutdown()
    return out


def _numbers(served, section, msg_id, cfg=SMALL):
    return oracle.compare_job(served["kept"], msg_id, section, cfg, 47, {})


@pytest.mark.parametrize("msg_id", IDS)
def test_every_report_is_the_reference_answer_by_the_stored_assignment(
        served, section, msg_id):
    """All eight numbers of ``correct`` inside the cell's own limits, with
    the assignment the PROGRAM stored; the resubmits bit-identical to the
    first job's."""
    assert (served["kept"] / msg_id / oracle.ASSIGNMENT).exists()
    said: list[str] = []
    nums = _numbers(served, section, msg_id)
    assert set(nums) == set(LIMITS) and len(nums) == 8
    assert oracle.decide(nums, LIMITS, said.append), said
    assert nums["ion_table_faults"] == nums["fdr_level_mismatches"] == \
        nums["positives_above_fdr"] == 0
    for got, want in zip(read_result_tables(served["kept"] / msg_id),
                         read_result_tables(served["kept"] / IDS[0])):
        pd.testing.assert_frame_equal(got, want, check_exact=True)
    ann = pd.read_parquet(served["kept"] / msg_id / "annotations.parquet")
    assert sorted(ann.adduct.unique()) == sorted(ADDUCTS)
    assert (ann.groupby("adduct").size() == N_FORMULAS).all()
    found = ann[ann.fdr_level <= 0.1]
    assert set(zip(found.sf, found.adduct)) >= {
        tuple(i) for i in section["present_ions"]}


def test_without_the_stored_table_the_same_answer_is_not_correct(
        served, section, tmp_path):
    """The control: what the parent's job reads (one fault a formula)."""
    shutil.copytree(served["kept"] / IDS[0], tmp_path / IDS[0])
    (tmp_path / IDS[0] / oracle.ASSIGNMENT).unlink()
    nums = oracle.compare_job(tmp_path, IDS[0], section, SMALL, 47, {})
    assert nums["ion_table_faults"] == N_FORMULAS
    assert not oracle.decide(nums, LIMITS, lambda _line: None)


def test_the_stored_table_is_the_seeded_draw_row_for_row(served, section):
    draw = FDR(K, tuple(ADDUCTS), SMALL["sm_config"]["fdr"]["seed"]
               ).decoy_adduct_selection(section["formulas"])
    stored = pd.read_parquet(served["kept"] / IDS[0] / oracle.ASSIGNMENT)
    assert list(stored.columns) == list(ASSIGNMENT_COLUMNS) == \
        oracle.ASSIGNMENT_COLUMNS
    pd.testing.assert_frame_equal(stored, draw.frame, check_exact=True)
    # the columns say what the mapping says, in the mapping's order
    assert [tuple(r) for r in stored.itertuples(index=False)] == [
        (sf, ta, da) for (sf, ta), das in draw.sample.items() for da in das]
    assert len(stored) == draw.n_triples == TRIPLES
    # a decoy two target adducts sampled is scored once
    allm = pd.read_parquet(served["kept"] / IDS[0] / "all_metrics.parquet")
    distinct = len(stored[["sf", "decoy_adduct"]].drop_duplicates())
    assert distinct == draw.n_distinct_decoys < TRIPLES
    assert len(allm) == N_FORMULAS * 3 + distinct == oracle.distinct_ions(
        served["kept"] / IDS[0], N_FORMULAS, ADDUCTS, K)
    assert int((~allm.is_target).sum()) == distinct


def _one(records, name):
    span, = jobtrace.spans(records, name)
    return span


def _distinct(served):
    stored = pd.read_parquet(served["kept"] / IDS[0] / oracle.ASSIGNMENT)
    return len(stored[["sf", "decoy_adduct"]].drop_duplicates())


def test_a_resident_reannotation_stores_the_same_bytes_and_draws_nothing(
        served):
    first = (served["kept"] / IDS[0] / oracle.ASSIGNMENT).read_bytes()
    for i, msg_id in enumerate(IDS):
        rec = served["traces"][msg_id]
        assert (served["kept"] / msg_id / oracle.ASSIGNMENT
                ).read_bytes() == first
        setup = _one(rec, "isotope_prefetch_setup")
        assert setup["attrs"]["cache"] == ("cold" if i == 0 else "resident")
        if i == 0:
            draw = _one(rec, "decoy_selection")
            assert draw["parent_id"] == setup["span_id"]
            assert draw["attrs"] == {
                "formulas": N_FORMULAS, "decoys": K, "target_adducts": 3,
                "triples": TRIPLES, "distinct_decoys": _distinct(served)}
        else:
            assert not jobtrace.spans(rec, "decoy_selection")


def test_the_traces_say_what_was_ranked_and_stored(served):
    n_ions = N_FORMULAS * 3 + _distinct(served)
    for msg_id in IDS:
        rec = served["traces"][msg_id]
        fdr = _one(rec, "fdr")
        assert fdr["attrs"]["rankings"] == 3
        assert (fdr["attrs"]["ions"], fdr["attrs"]["targets"]) == (
            n_ions, N_FORMULAS * 3)
        ranks = jobtrace.spans(rec, "fdr_rank")
        final = [s for s in ranks if s["parent_id"] == fdr["span_id"]]
        assert [s["attrs"] for s in final] == [
            {"adduct": a, "targets": N_FORMULAS,
             "decoy_entries": N_FORMULAS * K} for a in ADDUCTS]
        assert sum(s["dur"] for s in final) <= fdr["dur"]
        # the provisional ranking of the first batch takes the same route
        partial = _one(rec, "partial_fdr")
        early = [s for s in ranks if s["parent_id"] == partial["span_id"]]
        assert len(early) + len(final) == len(ranks) and early
        assert {s["attrs"]["adduct"] for s in early} <= set(ADDUCTS)
        assert all(s["attrs"]["decoy_entries"] == K * s["attrs"]["targets"]
                   for s in early)
        tables = _one(rec, "store_tables")
        assert tables["attrs"]["rows"] == n_ions + N_FORMULAS * 3
        wrote = _one(rec, "store_assignment")
        assert wrote["parent_id"] == tables["span_id"]
        assert wrote["attrs"] == {"rows": TRIPLES, "bytes": (
            served["kept"] / msg_id / oracle.ASSIGNMENT).stat().st_size}


def test_metrics_count_the_triples_the_distinct_decoys_and_the_rankings(
        served):
    def deltas(name, label=""):
        vals = [metric_sum(s, name, label) for s in served["scrapes"]]
        assert None not in vals, name    # exposed before the first job
        return [b - a for a, b in zip(vals, vals[1:])]

    # three done jobs, the one that died AFTER its fdr, the {+H} job
    distinct = _distinct(served)
    assert deltas("sm_fdr_decoy_triples_total") == \
        [TRIPLES] * 4 + [N_FORMULAS * K]
    assert deltas("sm_fdr_decoy_ions_total") == \
        [distinct] * 4 + [N_FORMULAS * K]
    assert deltas("sm_fdr_rankings_total", 'adduct="+H"') == [1] * 5
    for adduct in ("+Na", "+K"):
        assert deltas("sm_fdr_rankings_total",
                      f'adduct="{adduct}"') == [1] * 4 + [0]


def test_a_crash_before_the_renames_leaves_all_three_old_tables(served):
    assert set(served["before_crash"]) == set(RESULT_TABLES)
    assert served["after_crash"] == served["before_crash"]
    # the dead job had written all three under their tmp names
    assert {t + ".tmp" for t in RESULT_TABLES} <= set(served["debris"])
    rec = served["traces"]["three-crash"]
    assert jobtrace.spans(rec, "store_assignment")


def test_one_target_adduct_stores_its_assignment_too(served, section_h,
                                                     tmp_path):
    """One path: the table is there under {+H} alone, and says what the
    oracle had to imply so far."""
    stored = pd.read_parquet(served["kept"] / "one" / oracle.ASSIGNMENT)
    assert len(stored) == N_FORMULAS * K
    assert set(stored.target_adduct) == {"+H"}
    assert len(stored.drop_duplicates()) == len(stored)
    with_file = _numbers(served, section_h, "one", ONE)
    assert oracle.decide(with_file, LIMITS, lambda _line: None)
    shutil.copytree(served["kept"] / "one", tmp_path / "one")
    (tmp_path / "one" / oracle.ASSIGNMENT).unlink()
    assert oracle.compare_job(tmp_path, "one", section_h, ONE, 47,
                              {}) == with_file
    rec = served["traces"]["one"]
    assert _one(rec, "fdr")["attrs"]["rankings"] == 1
    assert _one(rec, "decoy_selection")["attrs"] == {
        "formulas": N_FORMULAS, "decoys": K, "target_adducts": 1,
        "triples": N_FORMULAS * K, "distinct_decoys": N_FORMULAS * K}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", REPO / "benchmarks" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_three_readers_read_those_jobs(served):
    """The window is the two resubmits: ``/metrics`` after the first job and
    after the last of the three."""
    jobs = [{"trace": served["traces"][m]} for m in IDS[1:]]
    run = {"jobs": jobs, "metrics_before": served["scrapes"][1],
           "metrics_after": served["scrapes"][3]}
    per_job = []
    for j in jobs:
        fdr = _one(j["trace"], "fdr")
        per_job.append(sum(s["dur"] for s in jobtrace.spans(
            j["trace"], "fdr_rank") if s["parent_id"] == fdr["span_id"]))
    got = _reader("fdr_rank_s")(run)
    assert got == pytest.approx(np.median(per_job)) and got > 0
    assert got < np.median([sum(s["dur"] for s in jobtrace.spans(
        j["trace"], "fdr_rank")) for j in jobs])      # partial_fdr's left out
    assert _reader("assignment_store_s")(run) == pytest.approx(np.median(
        [_one(j["trace"], "store_assignment")["dur"] for j in jobs]))
    shared = _reader("decoy_shared_pct")(run)
    assert shared == pytest.approx(100 * (1 - _distinct(served) / TRIPLES))
    assert 15 < shared < 30
    # one target adduct: no two samples of a formula to share a decoy
    alone = {**run, "metrics_before": served["scrapes"][4],
             "metrics_after": served["scrapes"][5]}
    assert _reader("decoy_shared_pct")(alone) == 0
    # nothing to read: no job, an untraced job, a program without the spans
    # or the counters (the parent), a window in which no job ranked
    empty = {"jobs": [{"trace": None}], "metrics_before": "",
             "metrics_after": ""}
    for name in ("fdr_rank_s", "assignment_store_s", "decoy_shared_pct"):
        assert _reader(name)(empty) is None, name
        assert _reader(name)({**empty, "jobs": []}) is None, name
    parent = [r for r in jobs[0]["trace"]
              if r["name"] not in ("fdr_rank", "store_assignment")]
    for name in ("fdr_rank_s", "assignment_store_s"):
        assert _reader(name)({**run, "jobs": [{"trace": parent}]}) is None
    idle = {**run, "metrics_before": run["metrics_after"]}
    assert _reader("decoy_shared_pct")(idle) is None


def test_trace_report_prints_the_new_spans_under_their_parents(served):
    from scripts import trace_report

    text = trace_report.render(
        trace_report.summarize(served["traces"][IDS[0]]))
    for want in ("target_adducts=3", f"triples={TRIPLES}",
                 f"distinct_decoys={_distinct(served)}", "rankings=3",
                 "adducts=+H,+Na,+K", f"targets={N_FORMULAS * 3}",
                 f"decoy_entries={TRIPLES}", f"rows={TRIPLES}"):
        assert want in text, (want, text)
    lines = text.splitlines()
    at = {name: next(i for i, ln in enumerate(lines)
                     if ln.strip().startswith(name + " "))
          for name in ("fdr", "fdr_rank", "store_results", "store_tables",
                       "store_assignment")}
    assert at["fdr"] < at["fdr_rank"] < at["store_results"] \
        < at["store_tables"] < at["store_assignment"]
    assert "x3" in lines[at["fdr_rank"]]        # the final rankings alone
