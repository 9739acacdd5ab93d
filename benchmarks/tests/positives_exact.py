#!/usr/bin/env python3
"""``positives_survey.py`` without its shortcut (by hand, CPU, no chip):

    JAX_PLATFORMS=cpu python3 benchmarks/tests/positives_exact.py \
        --config maldi-section-64-hmdb-3adducts --seed 4700047001 --seeds 64 \
        [--formulas 1500 --signal 300]

Which ions with signal a job of (configuration, table size, seed) loses at FDR
10%, by the plain reference over EVERY ion of the table: the section generated
as a run generates it, the decoy assignment drawn as the program draws it
(``assignment.py``), every ion scored by ``reference/scoring.py``, each target
adduct's ranking as ``oracle.py`` makes it.  ``positives_survey.py`` scores
only the ions whose principal window holds a line of the signal and counts the
rest as msm 0; with a weakest positive under msm ~0.15 the rest decide: it
read ``lost: []`` where a chip run lost an ion (PERF.md section 6, PR 47).  An
ion whose principal image is empty costs one window lookup, so the whole table
is no slower: 73,416 ions in ~47 s on 8 cores.  One line a (table size,
seed): the ions lost, the three weakest ions with signal, and per target
adduct the decoy entries above msm 0 / 0.05 / at or above 0.3 (a ranking of
``n`` ions with signal tolerates ``2 n`` entries above its weakest).
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

_SECTION = None


def _open(path: str, ppm: float) -> None:
    global _SECTION
    _SECTION = scoring.Dataset(Path(path), ppm)


def _msm(args) -> np.ndarray:
    ions, iso, nlevels = args
    return scoring.score_ions(_SECTION, ions, iso, nlevels)[:, 3]


def survey(config: dict, seed: int, cache: Path, procs: int) -> dict:
    ds_cfg = config["ds_config"]
    targets = list(ds_cfg["isotope_generation"]["adducts"])
    decoys_per = config["guarantees"]["decoys_per_target"]
    iso = {"charge": 1, "isocalc_sigma": 0.01, "isocalc_pts_per_mz": 10000,
           "n_peaks": config["guarantees"]["isotope_peaks"]}
    image = ds_cfg["image_generation"]
    section = datasets.generate(cache, config["dataset"], seed)
    present = [tuple(i) for i in section.get(
        "present_ions", [[sf, targets[0]] for sf in section["present"]])]
    draw = seeded_assignment(section["formulas"], config)
    ions = [(sf, ta) for sf in section["formulas"] for ta in targets]
    ions += list(dict.fromkeys(zip(draw.sf, draw.decoy_adduct)))
    chunks = [(ions[i:i + 512], iso, image.get("nlevels", 30))
              for i in range(0, len(ions), 512)]
    with get_context("spawn").Pool(
            procs, initializer=_open,
            initargs=(section["path"], image["ppm"])) as pool:
        msm = dict(zip(ions, np.concatenate(pool.map(_msm, chunks))))
    lost, entries = [], {}
    for ta in targets:
        t_ions = [(sf, ta) for sf in section["formulas"]]
        t_msm = np.array([msm[ion] for ion in t_ions])
        mine = draw[draw.target_adduct == ta]
        d_msm = np.array([msm[ion]
                          for ion in zip(mine.sf, mine.decoy_adduct)])
        levels = dict(zip(t_ions, scoring.fdr_levels(t_msm, d_msm,
                                                     decoys_per)))
        entries[ta] = [int((d_msm > 0).sum()), int((d_msm > 0.05).sum()),
                       int((d_msm >= 0.3).sum())]
        for ion in present:
            if ion[1] == ta and levels[ion] > 0.1:
                lost.append({
                    "ion": list(ion), "msm": float(msm[ion]),
                    "decoy_entries_at_or_above": int(
                        (d_msm >= msm[ion]).sum()),
                    "targets_at_or_above": int((t_msm >= msm[ion]).sum()),
                    "level": float(levels[ion])})
    return {"formulas": len(section["formulas"]), "seed": seed,
            "ions": len(ions), "with_signal": len(present),
            "weakest3": [round(float(m), 4)
                         for m in sorted(msm[ion] for ion in present)[:3]],
            "decoy_entries_gt0_gt005_ge03": entries, "lost": lost}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="maldi-section-64-hmdb-3adducts")
    ap.add_argument("--formulas", type=int, nargs="+", default=[0],
                    help="table sizes (default: the file's)")
    ap.add_argument("--signal", type=int, default=0,
                    help="formulas with signal (default: the file's)")
    ap.add_argument("--seed", type=int, default=4700047001)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--procs", type=int, default=8)
    ap.add_argument("--cache", default=str(
        BENCH.parent / ".cache" / "bench" / "datasets"))
    args = ap.parse_args(argv)
    config = json.loads(
        (BENCH / "configs" / f"{args.config}.json").read_text())
    n_signal = args.signal or round(config["dataset"]["n_formulas"]
                                    * config["dataset"]["present_fraction"])
    for n in args.formulas:
        n = n or config["dataset"]["n_formulas"]
        config["dataset"].update(n_formulas=n, present_fraction=n_signal / n)
        for seed in range(args.seed, args.seed + args.seeds):
            t0 = time.time()
            out = survey(config, seed, Path(args.cache), args.procs)
            print(f"positives_exact: {json.dumps(out)} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
