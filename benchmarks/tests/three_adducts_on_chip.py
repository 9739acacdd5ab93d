#!/usr/bin/env python3
"""The sizing run of the NEXT issue's cell, by hand, on the chip (ISSUE 46):

    python3 benchmarks/tests/three_adducts_on_chip.py [--seed N] \
        [--rungs 6000 4000 3000] [--seconds 51]

``hmdb-section64-reannotate`` through ``run.run_cell`` with the source's own
three target adducts ({+H,+Na,+K}: ``ds_config`` for the job, ``dataset`` for
the section's signal) and the table at each rung, largest first, until one
meets ISSUE 39's rule: median ``report_s`` <= 10.0 s, ``whole run`` <= 270 s,
``correct`` true.  The program does not store its decoy assignment yet, so
``assignment.py`` puts the seeded draw's table beside every kept answer.  Not
a cell: nothing of it is in ``BENCHMARK.json``.  After the rung that passes,
the OLD rule of ``oracle.py`` (every decoy of the table against each target
adduct) is read once on the same answer, for the record.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import pandas as pd

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import oracle  # noqa: E402
import run  # noqa: E402
import assignment  # noqa: E402

CELL = "hmdb-section64-reannotate"
THREE = ["+H", "+Na", "+K"]
RULE = {"report_s": 10.0, "whole_run_s": 270.0}


def overrides(n_formulas: int) -> dict:
    return {"ds_config": {"isotope_generation": {"adducts": THREE}},
            "dataset": {"adducts": THREE, "n_formulas": n_formulas,
                        "present_fraction": 300 / n_formulas}}


def under_old_rule(work: Path, sample: list[dict], config: dict, seed: int) -> dict:
    """``sample[-1]``'s numbers with every decoy of the table assigned to
    each target adduct: what ``oracle.py`` ranked by before ISSUE 46."""
    msg_id = sample[-1]["msg_id"]
    kept = work / "answers" / msg_id
    draw = pd.read_parquet(kept / oracle.ASSIGNMENT)
    assignment.old_rule(draw, THREE).to_parquet(kept / oracle.ASSIGNMENT)
    try:
        return oracle.compare_job(work / "answers", msg_id,
                                  sample[-1]["dataset"], config, seed, {})
    finally:
        draw.to_parquet(kept / oracle.ASSIGNMENT)


def lost_positives(work: Path, job: dict) -> list[dict]:
    """The ions with signal that ``job`` did not report at FDR <= 10%, each
    with its msm and the decoy entries and targets of its adduct's ranking
    at or above it (FDR 10% tolerates 2 decoy entries a target there)."""
    kept = work / "answers" / job["msg_id"]
    allm = pd.read_parquet(kept / "all_metrics.parquet")
    ann = pd.read_parquet(kept / "annotations.parquet")
    draw = pd.read_parquet(kept / oracle.ASSIGNMENT)
    hits = ann[ann.fdr_level <= 0.1]
    found = set(zip(hits.sf, hits.adduct))
    msm = allm.set_index(["sf", "adduct"]).msm
    out = []
    for sf, ta in map(tuple, job["dataset"]["present_ions"]):
        if (sf, ta) in found:
            continue
        mine = draw[draw.target_adduct == ta]
        d = msm.reindex(list(zip(mine.sf, mine.decoy_adduct))).to_numpy()
        t = allm[allm.is_target & (allm.adduct == ta)].msm.to_numpy()
        out.append({"ion": [sf, ta], "msm": float(msm[sf, ta]),
                    "decoy_entries_at_or_above": int((d >= msm[sf, ta]).sum()),
                    "targets_at_or_above": int((t >= msm[sf, ta]).sum()),
                    "level": float(ann[(ann.sf == sf) & (ann.adduct == ta)]
                                   .fdr_level.iloc[0])})
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4600006001)
    ap.add_argument("--rungs", type=int, nargs="+",
                    default=[6000, 4000, 3000])
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    for i, rung in enumerate(args.rungs):
        over = overrides(rung)
        config = run.merge(run.load_cell(run.ROOT, CELL)["config"], over)
        kept: dict = {}
        lines: list[str] = []
        run.T_START = t0 = time.time()
        with assignment.stored_with_every_answer(config):
            run.run_cell(CELL, args.seed + i, args.seconds, False,
                         platform=args.platform, overrides=over,
                         before_check=lambda work, sample: kept.update(
                             work=work, sample=sample),
                         emit=lines.append)
        whole = time.time() - t0
        out = json.loads(lines[-1])
        report = out["metrics"]["report_s"]["value"]
        ok = (out["correct"] and report <= RULE["report_s"]
              and whole <= RULE["whole_run_s"])
        print(f"three_adducts_on_chip: rung {rung} seed {args.seed + i}: "
              f"report_s {report:.4f} (<= {RULE['report_s']}), whole run "
              f"{whole:.1f}s (<= {RULE['whole_run_s']}), correct "
              f"{out['correct']}: {'THE RUNG' if ok else 'fails the rule'}; "
              f"{json.dumps(out['metrics'])} {json.dumps(out['device'])}",
              flush=True)
        if not out["correct"]:
            job = kept["sample"][-1]
            print(f"three_adducts_on_chip: lost by {job['msg_id']}: "
                  f"{json.dumps(lost_positives(kept['work'], job))}",
                  flush=True)
        if ok:
            t1 = time.time()
            nums = under_old_rule(kept["work"], kept["sample"], config,
                            args.seed + i)
            print(f"three_adducts_on_chip: the old rule on "
                  f"{kept['sample'][-1]['msg_id']} ({time.time() - t1:.1f}s): "
                  f"{json.dumps(nums)}", flush=True)
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
