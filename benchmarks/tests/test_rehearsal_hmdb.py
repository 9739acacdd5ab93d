"""CPU rehearsal of ``hmdb-section64-reannotate``, by hand like its neighbours
(``test_rehearsal.py``, whose helpers this uses): the cell at 8x8 px and 400
formulas (8,400 ions, 33 batches of 256) through ``run.run_cell``, untraced
and traced.  A CPU capture has no ``/device:TPU`` plane, so the traced run
wants every per-layer metric the cell lists but the ``device_trace`` ones.
The three readers this cell brought are read here from
``data/hmdb_job.trace.jsonl``: the raw trace of one in-window job of the
cell's traced run on the chip (PR 39, the final tree's ``git archive``), as
``GET /jobs/<id>/trace?raw=1`` served it.  Later cells and metrics append
their names after this one's, so nothing here asks for a place in a list."""

from __future__ import annotations

import json

import pytest

from test_device_span_layers import reader
from test_rehearsal import BENCH, LINE_KEYS, MANIFEST, rehearse, run

CELL = "hmdb-section64-reannotate"
RECORDED = BENCH / "tests" / "data" / "hmdb_job.trace.jsonl"
TABLE = {"dataset": {"n_formulas": 400, "present_fraction": 0.05}}
NEW = ["pattern_load_s", "patterns_computed_in_window", "batch_host_ms"]


def test_the_cell_is_the_deployment_the_issue_names():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "reannotate"
    assert run.traffic_gen.sizes(cell["traffic"], 1) == (1, 1)
    cfg = cell["config"]
    assert (cfg["dataset"]["nrows"], cfg["dataset"]["ncols"]) == (64, 64)
    assert cfg["dataset"]["n_formulas"] in (8000, 6000, 4000)
    assert round(cfg["dataset"]["n_formulas"]
                 * cfg["dataset"]["present_fraction"]) == 300
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", [])}
    assert listed >= {"store_images_s", "lease_device_busy_pct",
                      "extract_device_s", "chaos_device_s",
                      "moments_device_s", "chaos_roofline_pct",
                      "hold_stall_s", "hold_unnamed_s",
                      "host_cpu_per_job_s", "interp_late_ms", *NEW}
    want = {"pattern_load_s": {
                "unit": "s", "source": "program_span",
                "layer": "isotope patterns", "moves": "report_s"},
            "patterns_computed_in_window": {
                "unit": "count", "source": "program_counter",
                "layer": "isotope patterns", "moves": "report_p95_s"},
            "batch_host_ms": {
                "unit": "ms", "source": "program_span", "layer": "scoring",
                "moves": "ions_per_s"}}
    for name in NEW:
        entry = dict(by_name[name])
        assert entry.pop("workloads")[0] == CELL    # later cells append
        assert entry == {"name": name, "better": "lower", **want[name]}
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if run.reports(m, CELL)}
    assert reported == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [False, True])
def test_hmdb_on_cpu(trace, monkeypatch):
    out = rehearse(CELL, 1, trace, monkeypatch, seed=2147484039,
                   overrides=TABLE)
    assert set(out) >= LINE_KEYS and out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"report_s", "report_p95_s",
                                       "ions_per_s", "setup_s"}
        return
    want = {m["name"] for m in MANIFEST["per_layer"]
            if run.reports(m, CELL) and m["source"] != "device_trace"}
    assert {"store_images_s", "residency_hit_pct", *NEW} <= want \
        <= set(out["metrics"])
    assert out["metrics"]["residency_hit_pct"]["value"] == 100.0
    assert out["metrics"]["patterns_computed_in_window"]["value"] == 0.0
    assert out["metrics"]["pattern_load_s"]["value"] > 0
    assert out["metrics"]["batch_host_ms"]["value"] > 0
    assert "stage_parse_s" not in out["metrics"]     # moves a metric the
    assert "backend_build_s" not in out["metrics"]   # cell does not report


def test_the_three_readers_on_the_recorded_job_trace():
    records = [json.loads(line) for line in RECORDED.read_text().splitlines()]
    job = {"trace": records}

    def one(name):
        span, = [r for r in records if r.get("kind") == "span"
                 and r["name"] == name]
        return span

    setup, presize = one("isotope_prefetch_setup"), one("presize")
    ions, batches = setup["attrs"]["ions"], presize["attrs"]["batches"]
    assert ions in (168000, 126000, 84000) and batches == -(-ions // 2048)
    # what the chip's run said of the table (spans of PR 39)
    assert setup["attrs"] == {"formulas": ions // 21, "ions": ions,
                              "cache": "warm"}
    assert one("pattern_cache_load")["attrs"]["entries"] == ions
    assert one("isotope_patterns")["attrs"]["computed"] == 0
    assert sum(presize["attrs"]["variants"].values()) == batches
    assert 1 <= presize["attrs"]["executables"] <= batches
    assert one("fdr")["attrs"] == {"phase": True, "ions": ions,
                                   "targets": ions // 21,
                                   "decoys": ions - ions // 21}
    assert one("store_tables")["attrs"]["rows"] == ions + ions // 21

    run_ = {"jobs": [job, {"trace": None}]}
    assert reader("pattern_load_s")(run_) == pytest.approx(setup["dur"])
    plans = [r for r in records if r.get("kind") == "span"
             and r["name"] in ("presize", "score_plan")]
    enq = [r for r in records if r.get("kind") == "span"
           and r["name"] == "score_batch" and r["attrs"].get("enqueue")]
    assert len(enq) == batches and len(plans) == 3
    want = 1000.0 * sum(r["dur"] for r in plans + enq) / batches
    assert reader("batch_host_ms")(run_) == pytest.approx(want)
    assert 0.5 < want < 200.0
    counter = "sm_isocalc_patterns_total"
    text = f"# TYPE {counter} counter\n{counter} {ions}\n"
    assert reader("patterns_computed_in_window")(
        {"metrics_before": text, "metrics_after": text}) == 0
    assert reader("patterns_computed_in_window")(
        {"metrics_before": "", "metrics_after": text}) == ions
    # nothing to read: no job, an untraced job, a program without the
    # counter, a job scored as one group (no presize span)
    for name in ("pattern_load_s", "batch_host_ms"):
        assert reader(name)({"jobs": []}) is None
        assert reader(name)({"jobs": [{"trace": None}]}) is None
    assert reader("patterns_computed_in_window")(
        {"metrics_before": "", "metrics_after": ""}) is None
    no_presize = [r for r in records if r["name"] != "presize"]
    assert reader("batch_host_ms")({"jobs": [{"trace": no_presize}]}) is None
