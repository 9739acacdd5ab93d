#!/usr/bin/env python3
"""The answer may not depend on the chip a job landed on - shown on the chip,
by hand, right after a run of a cell whose pool has several chips:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 51 --trace 0
    python3 benchmarks/tests/each_chip_on_chip.py --workload <cell> --seed <n> \
        --seconds 51 [--held-mean <pool_chips_held_mean of that run>]

Reads what the run left under ``.cache/bench/work/<cell>/``: the child's job
traces (which chip each job was leased) and the answers the client kept.  For
every chip of the pool, the first window job leased there is compared with
the plain reference through ``oracle.py``'s own functions and limits; exits 1
when a chip has no job or a number is outside its limit.  Also prints the
chip-seconds the traces' lease holds put inside the window, over the
window's seconds: what ``pool_chips_held_mean`` reads from the pool's
counters (the window here starts at the first window submit, within a poll
of the harness's scrape).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import datasets  # noqa: E402
import jobtrace  # noqa: E402
import oracle  # noqa: E402
import run  # noqa: E402


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--held-mean", type=float)
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    cfg, chips = cell["config"], cell["chips"]
    _clients, n_cat = run.traffic_gen.sizes(cell["traffic"], chips)
    work = run.ROOT / ".cache" / "bench" / "work" / args.workload
    cache = run.ROOT / ".cache" / "bench" / "datasets"

    jobs = {}
    for path in sorted((work / "work" / "traces").glob("*.jsonl")):
        records = [json.loads(line) for line in path.read_text().splitlines()
                   if line.strip()]
        msg_id = next((r["job_id"] for r in records if r.get("job_id")), "")
        if msg_id.startswith(f"s{args.seed}-"):
            jobs[msg_id] = records
    window = {m: r for m, r in jobs.items()
              if int(m.rsplit("-", 1)[1]) >= n_cat}
    t0 = min(s["ts"] for r in window.values()
             for s in jobtrace.spans(r, "submit"))
    t1 = t0 + args.seconds
    held = 0.0
    for records in jobs.values():
        granted = jobtrace.event_ts(records, "device_token_acquired")
        hold = jobtrace.spans(records, "device_hold")
        if granted is None:
            continue
        # a job cancelled at the window's end may not have closed its hold
        end = hold[0]["ts"] + hold[0]["dur"] if hold else t1
        held += max(0.0, min(end, t1) - max(granted, t0)) \
            * max(1, len(jobtrace.lease_devices(records)))
    print(f"each_chip: {len(window)} window jobs traced; lease holds put "
          f"{held:.3f} chip-s inside the {args.seconds:.0f}s window = "
          f"{held / args.seconds:.4f} chips held on average"
          + (f"; pool_chips_held_mean read {args.held_mean:.4f} "
             f"({100 * (args.held_mean * args.seconds / held - 1):+.2f}%)"
             if args.held_mean else ""), flush=True)

    lim, ref_cache, bad = oracle.limits(cfg["guarantees"]), {}, 0
    for chip in range(chips):
        on_chip = sorted(m for m, r in window.items()
                         if jobtrace.lease_devices(r) == [chip]
                         and (work / "answers" / m).is_dir())
        print(f"each_chip: chip {chip}: {len(on_chip)} finished window "
              f"job(s) {on_chip}", flush=True)
        if not on_chip:
            bad += 1
            continue
        msg_id = on_chip[0]
        k = int(msg_id.rsplit("-", 1)[1]) % n_cat
        dataset = datasets.generate(cache, cfg["dataset"], args.seed + k)
        nums = oracle.compare_job(work / "answers", msg_id, dataset, cfg,
                                  args.seed, ref_cache)
        ok = oracle.decide({f"chip{chip}:{msg_id}:{k_}": v
                            for k_, v in nums.items()}, lim,
                           lambda line: print(f"each_chip: {line}",
                                              flush=True))
        bad += not ok
    print("each_chip: " + (f"{bad} chip(s) FAILED" if bad else
                           "every chip matches the reference"), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
