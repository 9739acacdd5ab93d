"""CPU rehearsal of ``section128-workingset-reannotate``, by hand like its
neighbours (``test_rehearsal.py``, whose helpers this uses): the cell at 8x8
px with its catalogue cut to six through ``run.run_cell``, untraced and
traced.  On the CPU the devices report no ``bytes_limit``, so ``"auto"``
keeps everything and evicts nothing, which is what the cell promises on the
chip too; the budget itself is tier-1's (``tests/test_residency_budget.py``)
and, on the chip, ``forced_budget_on_chip.py``'s.  ``resident_hbm_pct`` wants
the chip's limit gauge and has nothing to read here."""

from __future__ import annotations

import pytest

from test_rehearsal import LINE_KEYS, MANIFEST, cpu_readable, rehearse, run

CELL = "section128-workingset-reannotate"
SIX = {"traffic": {"catalogue": 6}}
NEW = ["resident_hbm_pct", "residency_evictions_in_window"]


def test_the_cell_is_the_deployment_the_issue_names():
    cell = run.load_cell(run.ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "workingset"
    clients, catalogue = run.traffic_gen.sizes(cell["traffic"], 1)
    assert clients == 2 and catalogue in (16, 12, 8)
    assert cell["traffic"]["ds_id"] == "same"
    cfg = cell["config"]
    assert cfg["name"] == "maldi-section-128-workingset"
    assert cfg["sm_config"]["parallel"]["resident_datasets"] == "auto"
    assert (cfg["dataset"]["nrows"], cfg["dataset"]["ncols"]) == (128, 128)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "residency"


@pytest.mark.parametrize("trace", [False, True])
def test_workingset_on_cpu(trace, monkeypatch):
    out = rehearse(CELL, 1, trace, monkeypatch, seed=2147484051,
                   overrides=SIX)
    assert set(out) >= LINE_KEYS and out["correct"] is True
    assert out["attempted"] >= 2 and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"report_s", "report_p95_s",
                                       "ions_per_s", "setup_s"}
        return
    want = cpu_readable(CELL) - {"resident_hbm_pct"}
    assert {"residency_hit_pct", "pre_lease_s",
            "residency_evictions_in_window"} <= want <= set(out["metrics"])
    assert out["metrics"]["residency_hit_pct"]["value"] == 100.0
    assert out["metrics"]["residency_evictions_in_window"]["value"] == 0.0
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
