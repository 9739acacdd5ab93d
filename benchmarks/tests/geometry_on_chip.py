#!/usr/bin/env python3
"""Which chaos kernel geometry a cell's backend runs, as the program itself
says it on the chip - by hand:

    python3 benchmarks/tests/geometry_on_chip.py --workload <cell> --seed <n> \
        [--jobs 3]

Starts the cell's ``serve`` child, submits the cell's first dataset
``--jobs`` times under one ds_id (one whole job, then resident ones), and
after each job prints the ``backend_build`` attrs of its trace (``pixels``,
``chaos_route``, ``chaos_block``, ``chaos_lane_fill_pct``,
``hist_scratch_bytes``, PR 30) and what ``sm_chaos_images_total`` grew by.
Exits 1 when a job's span lacks the attrs or the counter did not rise by the
job's ions under exactly one ``{route, images_per_program}``.  Not run by
the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import datasets  # noqa: E402
import jobtrace  # noqa: E402
import run  # noqa: E402
from serve import Serve, identity  # noqa: E402

ATTRS = ("pixels", "rows_bucket", "chaos_route", "chaos_block",
         "chaos_lane_fill_pct", "hist_scratch_bytes")
COUNTER = "sm_chaos_images_total"


def samples(text: str) -> dict[str, float]:
    return {line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in text.splitlines() if line.startswith(COUNTER + "{")}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    cfg = cell["config"]
    work = run.ROOT / ".cache" / "bench" / "work" / f"{args.workload}.geometry"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    bad = 0
    with Serve(run.ROOT, work, cfg["sm_config"]) as serve:
        ds = datasets.generate(run.ROOT / ".cache" / "bench" / "datasets",
                               cfg["dataset"], args.seed)
        n_ions = len(ds["formulas"]) * len(
            cfg["ds_config"]["isotope_generation"]["adducts"]) * (
            1 + cfg["guarantees"]["decoys_per_target"])
        serve.ready()
        identity(serve, "tpu", cell["chips"])
        driver = run.traffic_gen.Driver(
            serve, [ds], cfg["ds_config"], f"g{args.seed}",
            cell["traffic"]["poll_ms"], True, work / "answers")
        try:
            before = samples(serve.metrics())
            for _ in range(args.jobs):
                job = driver.submit()
                driver.wait(job, time.time() + 900.0)
                after = samples(serve.metrics())
                grew = {k: v - before.get(k, 0.0) for k, v in after.items()
                        if v != before.get(k, 0.0)}
                before = after
                build = jobtrace.spans(serve.trace(job["msg_id"]),
                                       "backend_build")
                attrs = build[0].get("attrs", {}) if build else {}
                said = {k: attrs.get(k) for k in ("cache_hit",) + ATTRS}
                ok = job["row"]["state"] == "done" \
                    and all(attrs.get(k) is not None for k in ATTRS) \
                    and list(grew.values()) == [float(n_ions)]
                bad += not ok
                print(f"geometry: job {job['msg_id']} "
                      f"{job['row']['state']}: backend_build {said}; "
                      f"{COUNTER} grew by {grew} (a job has {n_ions} ions) "
                      f"{'ok' if ok else 'FAULT'}", flush=True)
        finally:
            driver.close()
        serve.sigterm()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
