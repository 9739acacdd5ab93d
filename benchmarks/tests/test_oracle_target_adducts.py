"""The oracle, the generator and the ion count under the source's own three
target adducts ({+H,+Na,+K}), at 8x8 px, in process (ISSUE 46; by hand:
``JAX_PLATFORMS=cpu python3 -m pytest
benchmarks/tests/test_oracle_target_adducts.py -q -p no:cacheprovider``,
~1 min).

ONE in-process service with ``maldi-section-64-hmdb.json``'s own
``sm_config`` scores a 60-formula table twice: under the three adducts (the
section's formulas with signal spread over the same three) and under {+H}
alone.  The program does not store its decoy assignment yet, so the test
writes ``target_decoy_add.parquet`` from the program's seeded draw
(``assignment.py``).  (a) the three-adduct job reads every number inside its
limit and ``distinct_ions`` counts the rows it scored; (b) six controls each
read OUTSIDE; (c) with one target adduct the implied assignment, the file
written out and the PARENT's rule (kept below, as it stood on 087be24) give
the same numbers, on sound and on broken answers; (d) the generator without
``adducts`` makes the parent's bytes under the parent's cache key, and with
it spreads the signal over the list.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import datasets  # noqa: E402
import oracle  # noqa: E402
from assignment import old_rule, seeded_assignment  # noqa: E402
from reference import scoring  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
HMDB = json.loads(
    (BENCH / "configs" / "maldi-section-64-hmdb.json").read_text())
THREE = ["+H", "+Na", "+K"]
N_FORMULAS = 60
SEED = 4600000046


def sized(adducts: list[str]) -> dict:
    cfg = json.loads(json.dumps(HMDB))
    cfg["dataset"].update(nrows=8, ncols=8, n_formulas=N_FORMULAS,
                          present_fraction=0.2, noise_peaks=60)
    if len(adducts) > 1:
        cfg["dataset"]["adducts"] = adducts
    cfg["ds_config"]["isotope_generation"]["adducts"] = adducts
    cfg["guarantees"]["oracle_sample_ions"] = 300
    cfg["sm_config"]["parallel"]["formula_batch"] = 256
    return cfg


CFG3, CFG1 = sized(THREE), sized(["+H"])
LIMITS = oracle.limits(HMDB["guarantees"])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """{"three" | "one": (kept answers directory, dataset)}: one job each
    through one service, answers kept as ``traffic.Driver.wait`` keeps them."""
    from scripts.load_sweep import Harness

    tmp = tmp_path_factory.mktemp("adducts")
    sm = json.loads(json.dumps(HMDB["sm_config"]))
    sm["parallel"]["formula_batch"] = 256
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1})
    h = Harness(tmp, "svc", sm_overrides=sm)
    out = {}
    try:
        for name, cfg in (("three", CFG3), ("one", CFG1)):
            section = datasets.generate(tmp / "ds", cfg["dataset"], SEED)
            status, _hd, body = h.submit({
                "ds_id": name, "msg_id": name, "input_path": section["path"],
                "formulas": section["formulas"],
                "ds_config": cfg["ds_config"]})
            assert status == 202, body
            row = h.wait_terminal([name], timeout_s=300.0)[name]
            assert (row["state"], row["attempts"]) == ("done", 1), row
            kept = tmp / "answers" / name
            kept.mkdir(parents=True)
            for table in ("all_metrics.parquet", "annotations.parquet"):
                shutil.copy(tmp / "svc" / "results" / name / table, kept)
            out[name] = (tmp / "answers", section)
    finally:
        h.shutdown()
    return out


def numbers(answers: Path, msg_id: str, section: dict, cfg: dict) -> dict:
    return oracle.compare_job(answers, msg_id, section, cfg, 46, {})


def outside(nums: dict) -> set[str]:
    said: list[str] = []
    oracle.decide(nums, LIMITS, said.append)
    return {line.split()[1] for line in said if line.endswith("OUTSIDE")}


def variant(tmp_path, served, name, assign=None, allm=None, ann=None):
    """A copy of ``name``'s kept answer with any of its tables replaced
    (``assign`` False: no assignment file)."""
    answers, section = served[name]
    shutil.copytree(answers / name, tmp_path / name)
    for table, df in (("all_metrics.parquet", allm),
                      ("annotations.parquet", ann),
                      (oracle.ASSIGNMENT, assign)):
        if df is not None and df is not False:
            df.to_parquet(tmp_path / name / table)
    return tmp_path, section


# ------------------------------------------------------- (a) three adducts
def test_three_adduct_job_is_inside_every_limit(served, tmp_path):
    answers, section = served["three"]
    draw = seeded_assignment(section["formulas"], CFG3)
    assert len(draw) == N_FORMULAS * 3 * 20
    at, section = variant(tmp_path, served, "three", assign=draw)
    nums = numbers(at, "three", section, CFG3)
    assert outside(nums) == set(), nums
    assert set(nums) == set(LIMITS)
    allm = pd.read_parquet(at / "three" / "all_metrics.parquet")
    n_ions = oracle.distinct_ions(at / "three", N_FORMULAS, THREE, 20)
    assert n_ions == len(allm) == len(allm.drop_duplicates(["sf", "adduct"]))
    # three samples of 20 from 75 share decoys: fewer than the nominal 63
    assert 3 + 20 < n_ions / N_FORMULAS < 3 * 21
    assert int(allm.is_target.sum()) == 3 * N_FORMULAS
    # the signal sits under all three adducts and every one is found
    ions = {tuple(i) for i in section["present_ions"]}
    assert {a for _sf, a in ions} == set(THREE)
    ann = pd.read_parquet(at / "three" / "annotations.parquet")
    assert ions <= set(zip(ann.sf[ann.fdr_level <= 0.1],
                           ann.adduct[ann.fdr_level <= 0.1]))
    # by formula alone the check would pass a job that found each formula
    # under the WRONG adduct; by ion it does not
    wrong = ann.copy()
    wrong.loc[wrong.fdr_level <= 0.1, "adduct"] = np.roll(
        wrong.adduct[wrong.fdr_level <= 0.1].to_numpy(), 1)
    at2, _ = variant(tmp_path / "w", served, "three", assign=draw, ann=wrong)
    assert numbers(at2, "three", section, CFG3)["positives_above_fdr"] > 0


# ------------------------------------------------------------ (b) controls
def _unscored_decoy(draw, allm):
    sf = draw.sf[0]
    scored = set(allm.adduct[allm.sf == sf])
    spare = sorted(oracle.DECOY_ADDUCTS - set(THREE) - scored)[0]
    out = draw.copy()
    out.loc[0, "decoy_adduct"] = spare
    return out


CONTROLS = {
    "old_rule": ("assign", lambda draw, allm: old_rule(draw, THREE),
                 "ion_table_faults", N_FORMULAS),
    "unscored_decoy": ("assign", _unscored_decoy, "ion_table_faults", 1),
    "row_dropped": ("assign", lambda draw, allm: draw.iloc[1:],
                    "ion_table_faults", 1),
    "decoy_row_doubled": (
        "allm", lambda draw, allm: pd.concat(
            [allm, allm[~allm.is_target].iloc[:1]], ignore_index=True),
        "ion_table_faults", 1),
    "file_absent": ("assign", lambda draw, allm: False,
                    "ion_table_faults", N_FORMULAS),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_reads_outside(served, tmp_path, control):
    kind, alter, number, count = CONTROLS[control]
    answers, section = served["three"]
    draw = seeded_assignment(section["formulas"], CFG3)
    allm = pd.read_parquet(answers / "three" / "all_metrics.parquet")
    altered = alter(draw, allm)
    at, section = variant(
        tmp_path, served, "three",
        assign=altered if kind == "assign" else draw,
        allm=altered if kind == "allm" else None)
    nums = numbers(at, "three", section, CFG3)
    print(f"{control}: {nums}")
    assert number in outside(nums), nums
    assert nums[number] == count, nums
    # (the old rule ranks each adduct's targets against ~2.3 x the decoys; at
    # this size, where no decoy scores above a target with signal, the levels
    # come out the same all the same: 0 mismatches beside the 60 faults.
    # three_adducts_on_chip.py reads the rule again at the cell's own size)


def test_control_a_stored_level_moved(served, tmp_path):
    answers, section = served["three"]
    ann = pd.read_parquet(answers / "three" / "annotations.parquet")
    levels = sorted(scoring.FDR_LEVELS) + [1.0]
    at_level = levels.index(ann.fdr_level[0])
    ann.loc[0, "fdr_level"] = levels[(at_level + 1) % len(levels)]
    at, section = variant(
        tmp_path, served, "three", ann=ann,
        assign=seeded_assignment(section["formulas"], CFG3))
    nums = numbers(at, "three", section, CFG3)
    assert outside(nums) == {"fdr_level_mismatches"}
    assert nums["fdr_level_mismatches"] == 1


# --------------------------------------- (c) one target adduct: as before
def parent_numbers(answers, msg_id, dataset, config):
    """``ion_table_faults``, ``fdr_level_mismatches`` and
    ``positives_above_fdr`` as ``oracle.compare_job`` computed them on
    087be24 (its lines 59-73 and 99-113), kept here as the witness."""
    targets = set(config["ds_config"]["isotope_generation"]["adducts"])
    decoys_per = config["guarantees"]["decoys_per_target"]
    allm = pd.read_parquet(answers / msg_id / "all_metrics.parquet")
    ann = pd.read_parquet(answers / msg_id / "annotations.parquet")
    faults = 0
    by_sf = allm.groupby("sf", sort=False)
    if set(by_sf.groups) != set(dataset["formulas"]):
        faults += 1
    for _, g in by_sf:
        t = g[g.is_target]
        d = g[~g.is_target]
        if set(t.adduct) != targets or len(t) != len(targets) \
                or len(d) != decoys_per * len(targets) \
                or d.adduct.nunique() != len(d) \
                or not set(d.adduct) <= oracle.DECOY_ADDUCTS - targets:
            faults += 1
    mism = 0
    for ta in sorted(targets):
        t = allm[allm.is_target & (allm.adduct == ta)]
        d = allm[~allm.is_target]
        levels = scoring.fdr_levels(t.msm.to_numpy(), d.msm.to_numpy(),
                                    decoys_per)
        ref = pd.DataFrame({"sf": t.sf.to_numpy(), "adduct": ta,
                            "level_ref": levels})
        both = ann.merge(ref, on=["sf", "adduct"])
        mism += int(len(ref) - len(both)) \
            + int((both.fdr_level != both.level_ref).sum())
    mism += abs(len(ann) - int(allm.is_target.sum()))
    found = set(ann[(ann.fdr_level <= 0.1)
                    & ann.adduct.isin(list(targets))].sf)
    return {"ion_table_faults": faults, "fdr_level_mismatches": mism,
            "positives_above_fdr": len(set(dataset["present"]) - found)}


def _drop_target(allm, ann):
    return allm.drop(index=allm.index[allm.is_target][3]), ann


def _drop_decoys(allm, ann):
    return allm.drop(index=allm.index[~allm.is_target][[0, 25, 26]]), ann


def _double_decoy(allm, ann):
    return pd.concat([allm, allm[~allm.is_target].iloc[[5]]],
                     ignore_index=True), ann


def _target_as_decoy(allm, ann):
    out = allm.copy()
    out.loc[out.index[~out.is_target][7], "adduct"] = "+H"
    return out, ann


def _strange_decoy(allm, ann):
    out = allm.copy()
    out.loc[out.index[~out.is_target][9], "adduct"] = "+Qq"
    return out, ann


def _formula_gone(allm, ann):
    sf = allm.sf.iloc[0]
    return allm[allm.sf != sf], ann[ann.sf != sf]


def _msm_moved(allm, ann):
    out = allm.copy()
    out.loc[~out.is_target, "msm"] = out.msm[~out.is_target] + 0.2
    return out, ann


def _annotation_gone(allm, ann):
    return allm, ann.iloc[1:]


BREAKS = {"sound": lambda allm, ann: (allm, ann),
          "target_row_dropped": _drop_target,
          "decoy_rows_dropped": _drop_decoys,
          "decoy_row_doubled": _double_decoy,
          "target_adduct_as_decoy": _target_as_decoy,
          "adduct_outside_the_list": _strange_decoy,
          "formula_gone": _formula_gone, "decoy_msm_moved": _msm_moved,
          "annotation_gone": _annotation_gone}


@pytest.mark.parametrize("broken", sorted(BREAKS))
def test_one_adduct_numbers_are_the_parents(served, tmp_path, broken):
    answers, section = served["one"]
    assert "present_ions" not in section
    allm = pd.read_parquet(answers / "one" / "all_metrics.parquet")
    ann = pd.read_parquet(answers / "one" / "annotations.parquet")
    allm, ann = BREAKS[broken](allm, ann)
    at, _ = variant(tmp_path / "implied", served, "one", allm=allm, ann=ann)
    implied = numbers(at, "one", section, CFG1)
    want = parent_numbers(at, "one", section, CFG1)
    assert {k: implied[k] for k in want} == want
    if broken == "sound":
        assert outside(implied) == set() and len(allm) == N_FORMULAS * 21
        assert oracle.distinct_ions(at / "one", N_FORMULAS, ["+H"], 20) \
            == N_FORMULAS * 21                 # no file: today's product
        # the file written out: the same numbers, the same count
        at, _ = variant(tmp_path / "stored", served, "one",
                        assign=seeded_assignment(section["formulas"], CFG1))
        assert numbers(at, "one", section, CFG1) == implied
        assert oracle.distinct_ions(at / "one", N_FORMULAS, ["+H"], 20) \
            == N_FORMULAS * 21
    else:
        assert outside(implied) != set(), implied


def test_the_printed_lines_are_the_parents_text(served):
    """``decide`` prints what it printed, and collects the same numbers for
    the result line's last key."""
    answers, section = served["one"]
    nums = numbers(answers, "one", section, CFG1)
    said: list[str] = []
    compared: dict = {}
    assert oracle.decide({f"one:{k}": v for k, v in nums.items()}, LIMITS,
                         said.append, compared)
    assert [line.split()[1] for line in said] == [
        f"one:{k}" for k in (
            "ion_table_faults", "nonfinite_metrics", "chaos_max_abs_err",
            "spatial_max_abs_err", "spectral_max_abs_err", "msm_max_abs_err",
            "fdr_level_mismatches", "positives_above_fdr")]
    assert said[0] == "correct: one:ion_table_faults = 0 limit 0 ok"
    assert compared["one:msm_max_abs_err"] == {
        "value": nums["msm_max_abs_err"], "limit": 4e-06}


# ------------------------------------------------------- (d) the generator
# ``<cache key>``, sha256 of the ``.ibd`` and peaks of each configuration's
# ``dataset`` block at 8x8 px, seed 4600000046, computed on 087be24
PARENT = {
    "maldi-section-64": (
        "d7475e301afd8c8c",
        "23368f72c0ad803657266f556c893f1bce31bf5900de12a06c08791267e96206",
        68876),
    "maldi-section-128": (
        "d7475e301afd8c8c",
        "23368f72c0ad803657266f556c893f1bce31bf5900de12a06c08791267e96206",
        68876),
    "maldi-section-64-pool4": (
        "d7475e301afd8c8c",
        "23368f72c0ad803657266f556c893f1bce31bf5900de12a06c08791267e96206",
        68876),
    "maldi-slide-256": (
        "d7475e301afd8c8c",
        "23368f72c0ad803657266f556c893f1bce31bf5900de12a06c08791267e96206",
        68876),
    "maldi-section-64-hmdb": (
        "9477920ad245d869",
        "95ab74470d9e9b290bac70ccd5234363ac62d7b15afd9f15d68e178d7a6bf95a",
        68900),
    "maldi-section-128-hmdb": (
        "acdd855dbd9dadb4",
        "7e5f235c1adc32dbbb9bdee3dab1e974ff52f24904e0fb94baa9ff3473c64fbb",
        68908),
}


def _block(name: str) -> dict:
    entry, = [c for c in MANIFEST["configs"] if c["name"] == name]
    cfg = json.loads((REPO / entry["file"]).read_text())
    return {**cfg["dataset"], "nrows": 8, "ncols": 8}


def test_every_configuration_is_pinned():
    assert {c["name"] for c in MANIFEST["configs"]} == set(PARENT)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_without_adducts_the_bytes_and_the_key_are_the_parents(name, tmp_path):
    params = _block(name)
    assert "adducts" not in params
    meta = datasets.generate(tmp_path, params, SEED)
    path = Path(meta["path"])
    ibd = hashlib.sha256(path.with_suffix(".ibd").read_bytes()).hexdigest()
    assert (path.parent.name, ibd, meta["n_peaks"]) == PARENT[name]
    assert "present_ions" not in meta


def test_with_adducts_the_signal_spreads_over_the_list(tmp_path):
    params = _block("maldi-section-64-hmdb")
    one = datasets.generate(tmp_path, params, SEED)
    three = datasets.generate(tmp_path, {**params, "adducts": THREE}, SEED)
    again = datasets.generate(tmp_path / "again",
                              {**params, "adducts": THREE}, SEED)
    assert Path(three["path"]).parent.name != Path(one["path"]).parent.name
    assert Path(three["path"]).parent.name == Path(again["path"]).parent.name
    assert Path(three["path"]).with_suffix(".ibd").read_bytes() == \
        Path(again["path"]).with_suffix(".ibd").read_bytes()
    assert three["present"] == one["present"]       # the same formulas
    assert [sf for sf, _a in three["present_ions"]] == three["present"]
    per_adduct = pd.Series([a for _sf, a in three["present_ions"]]
                           ).value_counts()
    assert set(per_adduct.index) == set(THREE)
    assert per_adduct.max() - per_adduct.min() <= 1     # 300 = 3 x 100
    # one adduct a formula: the same isotope lines a pixel as with {+H}
    assert abs(three["n_peaks"] / one["n_peaks"] - 1.0) < 0.01
    other = datasets.generate(tmp_path, {**params, "adducts": THREE},
                              SEED + 1)
    assert other["present_ions"] != three["present_ions"]


# ------------------------------------- the whole command, three adducts
def test_three_adducts_through_run_cell(monkeypatch, capsys):
    """``hmdb-section64-reannotate`` at 8x8 px through ``run.run_cell`` with
    the overrides ``three_adducts_on_chip.py`` hands it: ``n_ions`` is set
    after the warm-up from the kept assignment and is the rows a job
    scored; without the assignment the same job is not correct."""
    from assignment import stored_with_every_answer
    from test_rehearsal import rehearse, run
    from three_adducts_on_chip import CELL, overrides

    over = run.merge(overrides(N_FORMULAS), {"dataset": {
        "present_fraction": 0.2}})
    config = run.merge(run.load_cell(run.ROOT, CELL)["config"], over)
    kept: dict = {}
    with stored_with_every_answer(config):
        out = rehearse(CELL, 1, False, monkeypatch, seed=2147484046,
                       overrides=over,
                       before_check=lambda work, sample: kept.update(
                           work=work, sample=sample))
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in out["compared"].values())
    assert set(n.split(":")[-1] for n in out["compared"]) == {
        "broken_guarantees", *LIMITS}
    said = capsys.readouterr()
    rows = {len(pd.read_parquet(kept["work"] / "answers" / j["msg_id"]
                                / "all_metrics.parquet"))
            for j in kept["sample"]}
    n_ions, = rows
    assert f"bench: {n_ions} ions a job (distinct; nominal " \
        f"{N_FORMULAS * 63} = {N_FORMULAS} formulas x 3 target adduct(s) " \
        "x 21)" in said.out
    assert 3 + 20 < n_ions / N_FORMULAS < 63
    # the numbers compared are the last lines of standard error
    last = said.err.strip().splitlines()[-len(out["compared"]):]
    assert all(line.startswith("correct: ") and line.endswith(" ok")
               for line in last), last
    # the same job with no assignment kept: one fault a formula
    out = rehearse(CELL, 1, False, monkeypatch, seed=2147484046,
                   overrides=over)
    assert out["correct"] is False
    faults = {c["value"] for n, c in out["compared"].items()
              if n.endswith(":ion_table_faults")}
    assert faults == {N_FORMULAS}
