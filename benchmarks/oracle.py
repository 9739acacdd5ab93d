"""The comparison that decides ``correct``: what the timed path stored for a
job, against the plain reference (``reference/``) and the guarantees the
configuration states.  numpy, scipy and pandas only; runs after the window,
once the child has exited.  Every number compared is printed beside its
limit, in every run."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from reference import scoring  # noqa: E402

# the decoy adducts a deployment may sample from (upstream sm-engine
# fdr.py::DECOY_ADDUCTS): implausible adducts
DECOY_ADDUCTS = {"+" + el for el in (
    "He Li Be B C N O F Ne Mg Al Si P S Cl Ar Ca Sc Ti V Cr Mn Fe Co Ni Cu "
    "Zn Ga Ge As Se Br Kr Rb Sr Y Zr Nb Mo Ru Rh Pd Ag Cd In Sn Sb Te I Xe "
    "Cs Ba La Ce Pr Nd Sm Eu Gd Tb Dy Ho Ir Th Pt Os Yb Lu Tm Er Pb Tl Hg Au "
    "W Ta Hf Re").split()}
COMPONENTS = ("chaos", "spatial", "spectral", "msm")
# upstream's table of that name, which a job MAY store beside its two
# parquets: one row per sampled (sf, target_adduct, decoy_adduct)
ASSIGNMENT = "target_decoy_add.parquet"
ASSIGNMENT_COLUMNS = ["sf", "target_adduct", "decoy_adduct"]


def limits(guarantees: dict) -> dict:
    """Each number's limit, from the configuration's stated guarantees: the
    components as absolute errors on values in [0, 1] (set from the sound
    runs' largest and the bf16 control's smallest readings on the chip,
    PERF.md section 2), everything else exact."""
    out = {f"{c}_max_abs_err": guarantees["component_abs_limit"][c]
           for c in COMPONENTS}
    out.update({"fdr_level_mismatches": 0, "positives_above_fdr": 0,
                "ion_table_faults": 0, "nonfinite_metrics": 0})
    return out


def read_assignment(kept: Path, allm, targets: list[str]):
    """(the job's decoy assignment as rows of ``ASSIGNMENT_COLUMNS``, whether
    the job stored it).  With ONE target adduct the file is optional and the
    assignment implied: every decoy row of a formula belongs to its one
    target.  With more and no file there is no assignment (every formula
    then faults)."""
    import pandas as pd

    if (kept / ASSIGNMENT).exists():
        return pd.read_parquet(kept / ASSIGNMENT)[ASSIGNMENT_COLUMNS], True
    if len(targets) == 1:
        d = allm[~allm.is_target]
        return pd.DataFrame({"sf": d.sf.to_numpy(),
                             "target_adduct": targets[0],
                             "decoy_adduct": d.adduct.to_numpy()}), False
    return pd.DataFrame(columns=ASSIGNMENT_COLUMNS), False


def distinct_ions(kept: Path, n_formulas: int, targets: list[str],
                  decoys_per: int) -> int:
    """The DISTINCT ions a job scores, from its kept answer: every formula
    under each target adduct plus the distinct ``(sf, decoy_adduct)`` of its
    stored assignment (a decoy two target adducts sampled is scored once).
    With one target adduct and no file the samples cannot overlap: the
    nominal product, which is also what stands in for a job of several
    adducts that stored none (``ion_table_faults`` fails that job)."""
    if not (kept / ASSIGNMENT).exists():
        return n_formulas * len(targets) * (1 + decoys_per)
    import pandas as pd

    assign = pd.read_parquet(kept / ASSIGNMENT)
    return n_formulas * len(targets) + len(
        assign[["sf", "decoy_adduct"]].drop_duplicates())


def ion_table_faults(allm, assign, formulas: list[str], targets: list[str],
                     decoys_per: int) -> int:
    """One for each formula whose rows are not: exactly one target row per
    target adduct; per ``(sf, target adduct)`` exactly ``decoys_per`` DISTINCT
    sampled decoy adducts, all of ``DECOY_ADDUCTS`` less the targets; its
    decoy rows the union of its samples, each once (no sampled decoy without
    its scored row and none the other way).  One more when the table's
    formulas are not the dataset's."""
    import pandas as pd

    decoy_pool = sorted(DECOY_ADDUCTS - set(targets))
    t, d = allm[allm.is_target], allm[~allm.is_target]
    sfs = pd.Index(pd.unique(pd.concat([allm.sf, assign.sf])))
    bad = pd.Series(False, index=sfs)

    def mark(per_sf_ok):
        bad[:] = bad.to_numpy() | ~per_sf_ok.reindex(
            sfs, fill_value=False).to_numpy(dtype=bool)

    by = t.assign(known=t.adduct.isin(targets)).groupby("sf", sort=False)
    mark((by.size() == len(targets))
         & (by.adduct.nunique() == len(targets)) & by.known.all())
    sample = assign.assign(
        known=assign.decoy_adduct.isin(decoy_pool)
        & assign.target_adduct.isin(targets)).groupby(
        ["sf", "target_adduct"], sort=False)
    whole = ((sample.size() == decoys_per)
             & (sample.decoy_adduct.nunique() == decoys_per)
             & sample.known.all())
    per_sf = whole.groupby(level="sf", sort=False)
    mark(per_sf.all() & (per_sf.size() == len(targets)))
    mark(~d.duplicated(["sf", "adduct"], keep=False).groupby(
        d.sf, sort=False).any())
    scored = d[["sf", "adduct"]].drop_duplicates()
    sampled = assign[["sf", "decoy_adduct"]].drop_duplicates().rename(
        columns={"decoy_adduct": "adduct"})
    sides = scored.merge(sampled, how="outer", indicator=True)
    bad[sides.sf[sides._merge != "both"].unique()] = True
    return int(bad.sum()) + int(set(allm.sf) != set(formulas))


def compare_job(answers: Path, msg_id: str, dataset: dict, config: dict,
                seed: int, cache: dict) -> dict:
    """The numbers of one job's kept answer (``limits`` has a limit for
    each)."""
    import pandas as pd

    ds_cfg = config["ds_config"]
    iso = {"charge": 1, "isocalc_sigma": 0.01, "isocalc_pts_per_mz": 10000,
           "n_peaks": config["guarantees"]["isotope_peaks"],
           **{k: v for k, v in ds_cfg["isotope_generation"].items()
              if k != "adducts"}}
    targets = sorted(set(ds_cfg["isotope_generation"]["adducts"]))
    decoys_per = config["guarantees"]["decoys_per_target"]
    allm = pd.read_parquet(answers / msg_id / "all_metrics.parquet")
    ann = pd.read_parquet(answers / msg_id / "annotations.parquet")
    assign, stored = read_assignment(answers / msg_id, allm, targets)
    out = {}

    # the ion table: every formula once per target adduct, plus the distinct
    # decoys its target adducts sampled
    out["ion_table_faults"] = ion_table_faults(
        allm, assign, dataset["formulas"], targets, decoys_per)

    # components of a seeded sample of ions against the reference
    key = dataset["path"]
    if key not in cache:
        cache[key] = scoring.Dataset(Path(key),
                                     ds_cfg["image_generation"]["ppm"])
    ref_ds = cache[key]
    n = len(allm)
    k = min(config["guarantees"]["oracle_sample_ions"], n)
    idx = np.sort(np.random.default_rng(int(seed)).choice(n, k, replace=False))
    sub = allm.iloc[idx]
    ions = list(zip(sub.sf, sub.adduct))
    if (key, tuple(ions)) not in cache:
        cache[key, tuple(ions)] = scoring.score_ions(
            ref_ds, ions, iso, ds_cfg["image_generation"].get("nlevels", 30))
    want = cache[key, tuple(ions)]
    got = sub[list(COMPONENTS)].to_numpy()
    out["nonfinite_metrics"] = int((~np.isfinite(
        allm[list(COMPONENTS)].to_numpy())).sum())
    err = np.abs(got.astype(np.float32).astype(np.float64)
                 - want.astype(np.float32).astype(np.float64))
    for i, c in enumerate(COMPONENTS):
        out[f"{c}_max_abs_err"] = float(np.nan_to_num(err[:, i],
                                                      nan=np.inf).max())

    # FDR levels re-derived from the served msm: each target adduct's
    # targets against THAT adduct's own samples, one entry per sampled
    # (sf, ta, da) (a decoy two adducts share counts in both rankings, as
    # upstream's merge on target_decoy_add does)
    mism = 0
    decoys = allm[~allm.is_target]
    if stored:
        by_ion = decoys.drop_duplicates(["sf", "adduct"]).rename(
            columns={"adduct": "decoy_adduct"})[["sf", "decoy_adduct", "msm"]]
    for ta in targets:
        t = allm[allm.is_target & (allm.adduct == ta)]
        d_msm = assign[assign.target_adduct == ta].merge(
            by_ion, how="left").msm.fillna(0.0).to_numpy() \
            if stored else decoys.msm.to_numpy()
        levels = scoring.fdr_levels(t.msm.to_numpy(), d_msm, decoys_per)
        ref = pd.DataFrame({"sf": t.sf.to_numpy(), "adduct": ta,
                            "level_ref": levels})
        both = ann.merge(ref, on=["sf", "adduct"])
        mism += int(len(ref) - len(both)) \
            + int((both.fdr_level != both.level_ref).sum())
    mism += abs(len(ann) - int(allm.is_target.sum()))
    out["fdr_level_mismatches"] = mism
    hits = ann[(ann.fdr_level <= 0.1) & ann.adduct.isin(targets)]
    if "present_ions" in dataset:
        out["positives_above_fdr"] = len(
            {tuple(ion) for ion in dataset["present_ions"]}
            - set(zip(hits.sf, hits.adduct)))
    else:
        out["positives_above_fdr"] = len(set(dataset["present"])
                                         - set(hits.sf))
    return out


def line(name: str, value, limit) -> str:
    """A number beside its limit, as every run prints it."""
    return (f"correct: {name} = {value!r} limit {limit!r} "
            f"{'ok' if value <= limit else 'OUTSIDE'}")


def decide(numbers: dict, lim: dict, say, compared: dict | None = None
           ) -> bool:
    """Print each number beside its limit; True when every one holds.
    ``compared`` collects ``{name: {"value", "limit"}}`` for the result
    line."""
    ok = True
    for name, value in numbers.items():
        limit = lim[name.split(":")[-1]]
        ok &= bool(value <= limit)
        say(line(name, value, limit))
        if compared is not None:
            compared[name] = {"value": value, "limit": limit}
    return ok


def check_jobs(answers: Path, jobs: list[dict], config: dict, seed: int,
               say, compared: dict | None = None) -> bool:
    t0 = time.time()
    cache: dict = {}
    lim = limits(config["guarantees"])
    ok = True
    for job in jobs:
        nums = compare_job(answers, job["msg_id"], job["dataset"], config,
                           seed, cache)
        ok &= decide({f"{job['msg_id']}:{k}": v for k, v in nums.items()},
                     lim, say, compared)
    say(f"oracle over {[j['msg_id'] for j in jobs]}: {time.time() - t0:.1f}s")
    return ok
