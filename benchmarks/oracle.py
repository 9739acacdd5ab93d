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
    targets = set(ds_cfg["isotope_generation"]["adducts"])
    decoys_per = config["guarantees"]["decoys_per_target"]
    allm = pd.read_parquet(answers / msg_id / "all_metrics.parquet")
    ann = pd.read_parquet(answers / msg_id / "annotations.parquet")
    out = {}

    # the ion table: every formula once per target adduct, plus its
    # distinct sampled decoys
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
                or not set(d.adduct) <= DECOY_ADDUCTS - targets:
            faults += 1
    out["ion_table_faults"] = faults

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

    # FDR levels re-derived from the served msm of ALL ions
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
    out["fdr_level_mismatches"] = mism
    found = set(ann[(ann.fdr_level <= 0.1)
                    & ann.adduct.isin(list(targets))].sf)
    out["positives_above_fdr"] = len(set(dataset["present"]) - found)
    return out


def decide(numbers: dict, lim: dict, say) -> bool:
    """Print each number beside its limit; True when every one holds."""
    ok = True
    for name, value in numbers.items():
        limit = lim[name.split(":")[-1]]
        good = value <= limit
        ok &= bool(good)
        say(f"correct: {name} = {value!r} limit {limit!r} "
            f"{'ok' if good else 'OUTSIDE'}")
    return ok


def check_jobs(answers: Path, jobs: list[dict], config: dict, seed: int,
               say) -> bool:
    t0 = time.time()
    cache: dict = {}
    lim = limits(config["guarantees"])
    ok = True
    for job in jobs:
        nums = compare_job(answers, job["msg_id"], job["dataset"], config,
                           seed, cache)
        ok &= decide({f"{job['msg_id']}:{k}": v for k, v in nums.items()},
                     lim, say)
    say(f"oracle over {[j['msg_id'] for j in jobs]}: {time.time() - t0:.1f}s")
    return ok
