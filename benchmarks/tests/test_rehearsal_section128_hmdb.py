"""CPU rehearsal of ``hmdb-section128-reannotate``, by hand like its
neighbours (``test_rehearsal.py``, whose helpers this uses): the cell at 8x8
px and 400 formulas (8,400 ions, 33 batches of 256) through
``run.run_cell``, untraced and traced.  A CPU capture has no ``/device:TPU``
plane, so the traced run wants every per-layer metric the cell lists but the
``device_trace`` ones.  The two readers this cell brought are read here from
``data/hmdb128_job.trace.jsonl`` as well: the raw trace of one in-window job
of the cell's traced run on the chip (PR 41), as
``GET /jobs/<id>/trace?raw=1`` served it.  Later cells append their names
after this one's, so nothing here asks for a last place in a list."""

from __future__ import annotations

import json

import pytest

from test_device_span_layers import reader
from test_rehearsal import BENCH, LINE_KEYS, MANIFEST, rehearse, run

CELL = "hmdb-section128-reannotate"
RECORDED = BENCH / "tests" / "data" / "hmdb128_job.trace.jsonl"
TABLE = {"dataset": {"n_formulas": 400, "present_fraction": 0.05}}
NEW = ["extract_slot_fill_pct", "plan_executables"]


def test_the_cell_is_the_deployment_the_issue_names():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "reannotate"
    assert run.traffic_gen.sizes(cell["traffic"], 1) == (1, 1)
    cfg = cell["config"]
    assert cfg["name"] == "maldi-section-128-hmdb"
    assert (cfg["dataset"]["nrows"], cfg["dataset"]["ncols"]) == (128, 128)
    assert cfg["dataset"]["n_formulas"] in (4000, 3000, 2000, 1500)
    assert round(cfg["dataset"]["n_formulas"]
                 * cfg["dataset"]["present_fraction"]) == 300
    assert cfg["reduced"] == ["formulas", "target_adducts"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", [])}
    assert listed >= {"store_images_s", "lease_device_busy_pct",
                      "extract_device_s", "chaos_device_s",
                      "moments_device_s", "chaos_roofline_pct",
                      "hold_stall_s", "hold_unnamed_s",
                      "host_cpu_per_job_s", "interp_late_ms",
                      "pattern_load_s", "patterns_computed_in_window",
                      "batch_host_ms", *NEW}
    want = {"extract_slot_fill_pct": {
                "unit": "%", "better": "higher", "source": "program_counter",
                "layer": "scoring", "moves": "ions_per_s"},
            "plan_executables": {
                "unit": "count", "better": "lower", "source": "program_span",
                "layer": "compile cache", "moves": "report_p95_s"}}
    for name in NEW:
        entry = dict(by_name[name])
        assert entry.pop("workloads")[:2] == ["hmdb-section64-reannotate", CELL]
        assert entry == {"name": name, **want[name]}
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if run.reports(m, CELL)}
    assert reported == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [False, True])
def test_hmdb128_on_cpu(trace, monkeypatch):
    out = rehearse(CELL, 1, trace, monkeypatch, seed=2147484041,
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
    assert 0 < out["metrics"]["extract_slot_fill_pct"]["value"] <= 100.0
    assert out["metrics"]["plan_executables"]["value"] >= 1
    assert "stage_parse_s" not in out["metrics"]     # moves a metric the
    assert "backend_build_s" not in out["metrics"]   # cell does not report


def test_the_two_readers_on_the_recorded_job_trace():
    records = [json.loads(line) for line in RECORDED.read_text().splitlines()]
    job = {"trace": records}

    def spans(name):
        return [r for r in records if r.get("kind") == "span"
                and r["name"] == name]

    presize, = spans("presize")
    said = presize["attrs"]
    ions = spans("isotope_prefetch_setup")[0]["attrs"]["ions"]
    assert ions in (84000, 63000, 42000, 31500)
    assert said["batches"] == -(-ions // 2048)
    assert sum(said["variants"].values()) == said["batches"]
    # the band floor and the sticky compact capacity pad: the chip's job
    # handed extraction more slots than its batches hold peaks
    assert said["slots"] > said["peaks"] > 0
    plans = spans("score_plan")
    assert sum(p["attrs"]["slots"] for p in plans) == said["slots"]
    assert sum(p["attrs"]["peaks"] for p in plans) == said["peaks"]
    run_ = {"jobs": [job, {"trace": None}]}
    assert reader("plan_executables")(run_) == said["executables"]
    # a job's dispatches count what its plans said
    names = ("sm_extract_slots_total", "sm_extract_peaks_total")

    def text(slots, peaks):
        return "".join(
            f"# TYPE {n} counter\n{n}{{variant=\"compact\"}} {v}\n"
            for n, v in zip(names, (slots, peaks)))

    window = {"metrics_before": text(said["slots"], said["peaks"]),
              "metrics_after": text(3 * said["slots"], 3 * said["peaks"])}
    assert reader("extract_slot_fill_pct")(window) == pytest.approx(
        100.0 * said["peaks"] / said["slots"])
    # nothing to read: the parent's program (no counters, no attr), no job,
    # an untraced job, a window without a dispatch
    assert reader("extract_slot_fill_pct")(
        {"metrics_before": "", "metrics_after": ""}) is None
    assert reader("extract_slot_fill_pct")(
        {**window, "metrics_before": window["metrics_after"]}) is None
    assert reader("plan_executables")({"jobs": []}) is None
    assert reader("plan_executables")({"jobs": [{"trace": None}]}) is None
    parent = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                             if k not in ("executables", "slots", "peaks")})
              if r["name"] == "presize" else r for r in records]
    assert reader("plan_executables")({"jobs": [{"trace": parent}]}) is None
