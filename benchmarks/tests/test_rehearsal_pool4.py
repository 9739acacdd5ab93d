"""CPU rehearsal of ``section64-uploads-pack4``, by hand like its neighbour
(``test_rehearsal.py``, whose helpers this uses): the cell at 8x8 px on four
virtual CPU devices, untraced and traced.  The traced run wants every
per-layer metric the cell lists but those a CPU capture cannot give (the
``device_trace`` ones: a CPU capture has no ``/device:TPU`` plane), the
three pool metrics among them and inside their ranges."""

from __future__ import annotations

import json

import pytest

from test_rehearsal import LINE_KEYS, MANIFEST, rehearse, run

CELL, CHIPS = "section64-uploads-pack4", 4
POOL_METRICS = {"pool_chips_held_mean", "pool_wait_s", "pre_lease_s"}


def test_the_cell_is_the_deployment_the_issue_names():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["chips"] == CHIPS and cell["traffic_name"] == "uploads"
    cfg = cell["config"]
    assert "profile_seconds" not in cfg        # the mix's 30 s applies
    assert cfg["sm_config"]["service"] == {
        "workers": 8, "device_pool_size": 4, "devices_per_job": 1}
    one = json.loads((run.BENCH / "configs/maldi-section-64.json").read_text())
    for key in ("dataset", "ds_config", "guarantees", "reduced"):
        assert cfg[key] == one[key], key
    for key in ("backend", "fdr", "parallel"):
        assert cfg["sm_config"][key] == one["sm_config"][key], key
    assert run.traffic_gen.sizes(cell["traffic"], CHIPS) == (8, 16)
    for m in MANIFEST["per_layer"]:
        if m["name"] in POOL_METRICS:
            assert m["workloads"] == [CELL]


@pytest.mark.parametrize("trace", [False, True])
def test_pack4_on_cpu(trace, monkeypatch):
    out = rehearse(CELL, CHIPS, trace, monkeypatch, seed=2147484026)
    assert set(out) >= LINE_KEYS and out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == CHIPS
    if not trace:
        assert set(out["metrics"]) == {"report_s", "report_p95_s",
                                       "ions_per_s", "setup_s"}
        return
    want = {m["name"] for m in MANIFEST["per_layer"]
            if run.reports(m, CELL) and m["source"] != "device_trace"}
    assert POOL_METRICS <= want <= set(out["metrics"])
    assert "stage_parse_s" not in out["metrics"]     # moves a metric the
    assert "backend_build_s" not in out["metrics"]   # cell does not report
    got = {k: out["metrics"][k]["value"] for k in POOL_METRICS}
    assert 0.0 < got["pool_chips_held_mean"] <= CHIPS
    assert got["pool_wait_s"] >= 0.0 and got["pre_lease_s"] > 0.0
    assert out["metrics"]["pool_chips_held_mean"]["unit"] == "chips"
