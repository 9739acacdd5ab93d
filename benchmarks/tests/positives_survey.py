#!/usr/bin/env python3
"""Which ions with signal would a job lose at FDR 10%, by the plain reference
alone, without a chip and without the program's scores (by hand):

    JAX_PLATFORMS=cpu python3 benchmarks/tests/positives_survey.py \
        --config maldi-section-64-hmdb --adducts +H +Na +K \
        --formulas 6000 4000 3000 --seed 4600006001 --seeds 4 [--signal 900]

``positives_above_fdr`` asks for EVERY ion with signal at FDR <= 10%, so a
cell is sized by its decoys as much as by its seconds (PERF.md, PR 39): a
ranking of ``n`` ions with signal tolerates ``2 n`` decoy entries at or above
its weakest.  The section of (configuration, seed) is generated as a run
generates it, the decoy assignment drawn as the program draws it
(``assignment.py``), and the reference scores the ions that can score at all:
every target with signal and every ion whose principal isotope window holds a
line of the signal (an ion whose principal image is noise alone has a chaos,
and so an msm, of ~0: ~3 lit pixels of 4,096 at this noise density).  Then
each target adduct's ranking as ``oracle.py`` makes it.  One line a (table
size, seed): the ions lost, each with its msm and the decoy entries and
targets at or above it.  ~3 min a seed at 6,000 formulas x 3 adducts on 8
cores (the isotope patterns of ~294,000 ions are most of it).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from multiprocessing import get_context
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import datasets  # noqa: E402
from assignment import seeded_assignment  # noqa: E402
from reference import scoring  # noqa: E402
from reference.isocalc import isotope_peaks  # noqa: E402


def _principal(args):
    (sf, adduct), iso = args
    peaks = isotope_peaks(sf, adduct, iso["charge"], iso["isocalc_sigma"],
                          iso["isocalc_pts_per_mz"], iso["n_peaks"])
    if peaks is None:
        return np.nan
    mzs, ints = peaks
    return float(mzs[int(np.argmax(ints))])


def survey(config: dict, seed: int, cache: Path, procs: int) -> dict:
    ds_cfg = config["ds_config"]
    targets = list(ds_cfg["isotope_generation"]["adducts"])
    decoys_per = config["guarantees"]["decoys_per_target"]
    iso = {"charge": 1, "isocalc_sigma": 0.01, "isocalc_pts_per_mz": 10000,
           "n_peaks": config["guarantees"]["isotope_peaks"]}
    ppm = ds_cfg["image_generation"]["ppm"]
    section = datasets.generate(cache, config["dataset"], seed)
    present = [tuple(i) for i in section.get(
        "present_ions", [[sf, targets[0]] for sf in section["present"]])]
    draw = seeded_assignment(section["formulas"], config)
    ions = [(sf, ta) for sf in section["formulas"] for ta in targets]
    ions += list(dict.fromkeys(zip(draw.sf, draw.decoy_adduct)))
    with get_context("spawn").Pool(procs) as pool:
        principal = np.array(pool.map(
            _principal, [(ion, iso) for ion in ions], chunksize=2048))
    # the lines of the signal, widened by the window and the generator's
    # jitter (5 sigma)
    lines = np.sort(np.concatenate([
        isotope_peaks(sf, a, n_peaks=iso["n_peaks"])[0] for sf, a in present]))
    reach = (ppm + 5 * config["dataset"]["mz_jitter_ppm"]) * 1e-6
    at = np.clip(np.searchsorted(lines, principal), 1, len(lines) - 1)
    near = np.minimum(np.abs(lines[at] - principal),
                      np.abs(lines[at - 1] - principal)) <= reach * principal
    chosen = [ion for ion, hit in zip(ions, near) if hit]
    chosen += [ion for ion in present if ion not in set(chosen)]
    ref = scoring.Dataset(Path(section["path"]), ppm)
    got = scoring.score_ions(ref, chosen, iso,
                             ds_cfg["image_generation"].get("nlevels", 30))
    msm = dict(zip(chosen, got[:, 3]))
    lost = []
    for ta in targets:
        t_ions = [(sf, ta) for sf in section["formulas"]]
        t_msm = np.array([msm.get(ion, 0.0) for ion in t_ions])
        mine = draw[draw.target_adduct == ta]
        d_msm = np.array([msm.get(ion, 0.0)
                          for ion in zip(mine.sf, mine.decoy_adduct)])
        levels = dict(zip(t_ions, scoring.fdr_levels(t_msm, d_msm,
                                                     decoys_per)))
        for ion in present:
            if ion[1] == ta and levels[ion] > 0.1:
                lost.append({
                    "ion": list(ion), "msm": float(msm[ion]),
                    "decoy_entries_at_or_above": int(
                        (d_msm >= msm[ion]).sum()),
                    "targets_at_or_above": int((t_msm >= msm[ion]).sum()),
                    "level": float(levels[ion])})
    weakest = min(msm[ion] for ion in present)
    return {"formulas": len(section["formulas"]), "seed": seed,
            "ions": len(ions), "scored": len(chosen),
            "with_signal": len(present), "weakest_msm": float(weakest),
            "lost": lost}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="maldi-section-64-hmdb")
    ap.add_argument("--adducts", nargs="+", default=["+H", "+Na", "+K"])
    ap.add_argument("--formulas", type=int, nargs="+", default=[6000])
    ap.add_argument("--seed", type=int, default=4600006001)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--signal", type=int, default=0,
                    help="formulas with signal (default: the file's)")
    ap.add_argument("--procs", type=int, default=8)
    ap.add_argument("--cache", default=str(
        BENCH.parent / ".cache" / "bench" / "datasets"))
    args = ap.parse_args(argv)
    config = json.loads(
        (BENCH / "configs" / f"{args.config}.json").read_text())
    config["ds_config"]["isotope_generation"]["adducts"] = args.adducts
    if len(args.adducts) > 1:
        config["dataset"]["adducts"] = args.adducts
    n_signal = args.signal or round(config["dataset"]["n_formulas"]
                                    * config["dataset"]["present_fraction"])
    for n in args.formulas:
        config["dataset"].update(n_formulas=n, present_fraction=n_signal / n)
        for seed in range(args.seed, args.seed + args.seeds):
            t0 = time.time()
            out = survey(config, seed, Path(args.cache), args.procs)
            print(f"positives_survey: {json.dumps(out)} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
