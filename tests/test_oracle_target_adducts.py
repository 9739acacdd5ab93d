"""The benchmark's own cases of the oracle, the generator and ``n_ions`` under
three target adducts (``benchmarks/tests/test_oracle_target_adducts.py``,
ISSUE 46) in tier-1: imported from where they live, path-relative, 26 cases.

Two of them pinned what ISSUE 47 changes, and the file is the benchmark's
(not this suite's to edit), so they are restated here under their own names:
the manifest now has a seventh configuration (the first with
``dataset.adducts``), and the program now stores the decoy assignment it
ranked by, so a three-adduct job through ``run.run_cell`` is correct WITHOUT
``assignment.stored_with_every_answer`` and stops being so when the stored
table is taken away."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pandas as pd

_CASES = (Path(__file__).resolve().parent.parent / "benchmarks" / "tests"
          / "test_oracle_target_adducts.py")
_spec = importlib.util.spec_from_file_location(
    "bench_oracle_target_adducts", _CASES)
cases = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = cases
_spec.loader.exec_module(cases)

globals().update({name: obj for name, obj in vars(cases).items()
                  if name.startswith("test_") or name == "served"})

ADDED = "maldi-section-64-hmdb-3adducts"
# PR 51: ``maldi-section-128``'s ``dataset`` block word for word (the
# deployment differs in what stays resident, not in the section)
WORKINGSET = "maldi-section-128-workingset"


def test_every_configuration_is_pinned():  # noqa: F811
    """The configurations ISSUE 46 found (and PR 50's, of the same block)
    keep the bytes it pinned (the parametrised case beside this one); the
    one added by ISSUE 47 spreads its signal over the three adducts.  By
    NAME, so that a later configuration can stand anywhere in the manifest
    (ROADMAP C20): the pinned names are among the manifest's, and one whose
    ``dataset`` block is a pinned one's is that section."""
    names = {c["name"] for c in cases.MANIFEST["configs"]}
    assert set(cases.PARENT) | {ADDED} <= names
    assert cases._block(ADDED)["adducts"] == cases.THREE
    assert WORKINGSET in names
    assert cases._block(WORKINGSET) == cases._block("maldi-section-128")


def test_three_adducts_through_run_cell(monkeypatch, capsys):  # noqa: F811
    """``hmdb-section64-reannotate`` at 8x8 px through ``run.run_cell`` with
    the overrides ``three_adducts_on_chip.py`` hands it, and nothing written
    by the test: ``n_ions`` is set after the warm-up from the assignment the
    PROGRAM stored and is the rows a job scored; with the table taken out of
    the kept answers the same job is not correct."""
    from test_rehearsal import rehearse, run
    from three_adducts_on_chip import CELL, overrides

    n = cases.N_FORMULAS
    over = run.merge(overrides(n), {"dataset": {"present_fraction": 0.2}})
    kept: dict = {}
    out = rehearse(CELL, 1, False, monkeypatch, seed=2147484046,
                   overrides=over,
                   before_check=lambda work, sample: kept.update(
                       work=work, sample=sample))
    assert out["correct"] is True and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["compared"].values())
    assert set(name.split(":")[-1] for name in out["compared"]) == {
        "broken_guarantees", *cases.LIMITS}
    said = capsys.readouterr()
    answers = [kept["work"] / "answers" / j["msg_id"] for j in kept["sample"]]
    assert all((a / cases.oracle.ASSIGNMENT).exists() for a in answers)
    n_ions, = {len(pd.read_parquet(a / "all_metrics.parquet"))
               for a in answers}
    assert f"bench: {n_ions} ions a job (distinct; nominal {n * 63} = " \
        f"{n} formulas x 3 target adduct(s) x 21)" in said.out
    assert 3 + 20 < n_ions / n < 63

    def without_the_table(work, sample):
        for j in sample:
            (work / "answers" / j["msg_id"] / cases.oracle.ASSIGNMENT).unlink()

    out = rehearse(CELL, 1, False, monkeypatch, seed=2147484046,
                   overrides=over, before_check=without_the_table)
    assert out["correct"] is False
    faults = {name: c["value"] for name, c in out["compared"].items()
              if name.endswith("ion_table_faults")}
    assert faults and set(faults.values()) == {n}
