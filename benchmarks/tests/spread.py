#!/usr/bin/env python3
"""Measure a cell's run-to-run spread the way the bounds are set from it:

    python3 benchmarks/tests/spread.py --workload <cell> --seconds 51 \
        --seeds 101 102 103 104 105 106 --sets 2 --out chiprun_out/<cell>.jsonl

Each set runs the command once per seed (a new process each, as the driver
does); both sets use the same seeds.  Prints, per metric and set, the median
and the spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median), and the
wider of the sets' spreads - a bound is about five times the widest over the
cells.  ``setup_s`` leaves out the very first run, which compiles."""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, default=51)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    sets: list[list[dict]] = []
    with open(args.out, "a") as log:
        for s in range(args.sets):
            rows = []
            for seed in args.seeds:
                proc = subprocess.run(
                    [*manifest["command"], "--workload", args.workload,
                     "--seed", str(seed), "--seconds", str(args.seconds),
                     "--trace", "0"], cwd=ROOT, capture_output=True,
                    text=True)
                last = proc.stdout.strip().splitlines()[-1] \
                    if proc.stdout.strip() else ""
                print(f"spread: set {s} seed {seed} rc={proc.returncode} "
                      f"{last}", flush=True)
                if proc.returncode != 0:
                    print(proc.stdout[-3000:], proc.stderr[-3000:])
                    return 1
                row = json.loads(last)
                rows.append(row)
                log.write(json.dumps({"set": s, "seed": seed, **row}) + "\n")
                log.flush()
                for line in proc.stdout.splitlines():
                    if any(key in line for key in (
                            "OUTSIDE", "samples:", "window:", "whole run",
                            "_max_abs_err", "layers this untraced")):
                        print("   ", line[:400], flush=True)
            sets.append(rows)
    for name in sets[0][0]["metrics"]:
        per_set = []
        for rows in sets:
            vals = [r["metrics"][name]["value"] for r in rows]
            if name == "setup_s" and rows is sets[0]:
                vals = vals[1:]
            per_set.append((statistics.median(vals), spread(vals)))
        print(f"spread: {name}: " + "; ".join(
            f"set {i} median {m:.4f} spread {100 * sp:.2f}%"
            for i, (m, sp) in enumerate(per_set))
            + f"; widest {100 * max(sp for _, sp in per_set):.2f}%"
            + (f"; set1/set0 median {per_set[1][0] / per_set[0][0]:.4f}"
               if len(per_set) > 1 else ""), flush=True)
    bad = [r for rows in sets for r in rows if not r["correct"]]
    print(f"spread: {sum(len(r) for r in sets)} runs, "
          f"{len(bad)} with correct false")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
