"""CPU rehearsal of ``slide256-reannotate``, by hand like its neighbours
(``test_rehearsal.py``, whose helpers this uses): the cell at 8x8 px through
``run.run_cell``, untraced and traced.  A CPU capture has no ``/device:TPU``
plane, so the traced run wants every per-layer metric the cell lists but the
``device_trace`` ones; ``chaos_roofline_pct``, which this cell brought, is
read here from ``data/slide256_job.trace.jsonl``: the raw trace of one
in-window job of the cell's traced run on the chip (PR 30, the final tree's
``git archive``, seed 3000004001, job 0003), its lease hold
wholly inside the capture, as ``GET /jobs/<id>/trace?raw=1`` served it.
Later cells and metrics append their names after this one's, so nothing here
asks for a place in a list."""

from __future__ import annotations

import json

import pytest

from test_device_span_layers import reader
from test_rehearsal import BENCH, LINE_KEYS, MANIFEST, rehearse, run

CELL = "slide256-reannotate"
RECORDED = BENCH / "tests" / "data" / "slide256_job.trace.jsonl"


def test_the_cell_is_the_deployment_the_issue_names():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "reannotate"
    assert run.traffic_gen.sizes(cell["traffic"], 1) == (1, 1)
    cfg = cell["config"]
    assert (cfg["dataset"]["nrows"], cfg["dataset"]["ncols"]) == (256, 256)
    assert "pixels" not in cfg["reduced"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", [])}
    assert listed >= {"store_images_s", "lease_device_busy_pct",
                      "extract_device_s", "chaos_device_s",
                      "moments_device_s", "chaos_roofline_pct"}
    new = dict(by_name["chaos_roofline_pct"])
    assert new.pop("workloads")[0] == CELL          # later cells append
    assert new == {"name": "chaos_roofline_pct", "unit": "%",
                   "better": "higher", "source": "device_trace",
                   "layer": "kernels", "moves": "ions_per_s"}
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if run.reports(m, CELL)}
    assert reported == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [False, True])
def test_slide256_on_cpu(trace, monkeypatch):
    out = rehearse(CELL, 1, trace, monkeypatch, seed=2147484030)
    assert set(out) >= LINE_KEYS and out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"report_s", "report_p95_s",
                                       "ions_per_s", "setup_s"}
        return
    want = {m["name"] for m in MANIFEST["per_layer"]
            if run.reports(m, CELL) and m["source"] != "device_trace"}
    assert {"store_images_s", "residency_hit_pct"} <= want \
        <= set(out["metrics"])
    assert out["metrics"]["residency_hit_pct"]["value"] == 100.0
    assert "stage_parse_s" not in out["metrics"]     # moves a metric the
    assert "backend_build_s" not in out["metrics"]   # cell does not report
    assert "chaos_roofline_pct" not in out["metrics"]   # no device spans


def test_chaos_roofline_from_the_recorded_job_trace():
    records = [json.loads(line) for line in RECORDED.read_text().splitlines()]
    cell = run.load_cell(run.ROOT, CELL)
    cell["n_ions"] = 10500
    read = reader("chaos_roofline_pct")
    job = {"trace": records}
    base = {"cell": cell, "device_kind": "TPU v5 lite"}
    chaos_s = sum(r["attrs"]["device_s"] for r in records
                  if r.get("name") == "device_scope"
                  and r["attrs"]["scope"] == "sm_chaos")
    assert 0.5 < chaos_s < 5.0
    # every principal image read once at 819 GB/s: 3.36 ms a job
    least = 10500 * 256 * 256 * 4 / 819e9
    got = read({**base, "jobs": [job, {"trace": None}]})
    assert got == pytest.approx(100.0 * least / chaos_s)
    assert 0.0 < got < 105.0
    # nothing to read: no job, an untraced job, a job cut by the capture
    assert read({**base, "jobs": []}) is None
    appended = ("device_scope", "device_busy", "device_idle")
    host_only = [r for r in records if r["name"] not in appended]
    assert read({**base, "jobs": [{"trace": host_only}]}) is None
    cut = [{**r, "attrs": {**r["attrs"], "whole": False}}
           if r["name"] in appended else r for r in records]
    assert read({**base, "jobs": [{"trace": cut}]}) is None
    # what the chip's run said of the backend (span backend_build, PR 30)
    build, = [r for r in records if r["name"] == "backend_build"]
    assert {k: build["attrs"][k] for k in (
        "cache_hit", "peaks_resident", "pixels", "rows_bucket", "chaos_route",
        "chaos_block", "chaos_lane_fill_pct", "hist_scratch_bytes")} == {
        "cache_hit": True, "peaks_resident": 58720256, "pixels": 65536,
        "rows_bucket": 256, "chaos_route": "packed",
        "chaos_block": [256, 384, 1], "chaos_lane_fill_pct": 66.7,
        "hist_scratch_bytes": 4 * 65537 * 16385}
