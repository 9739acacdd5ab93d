#!/usr/bin/env python3
"""The working set PAST its budget, by hand, on the chip (ISSUE 51, step 7e):

    python3 benchmarks/tests/forced_budget_on_chip.py --seed <n> \
        [--fit <sections>] [--seconds 51]

``section128-workingset-reannotate`` as configured but for ONE thing no
config key can say: the serve child is started through a launcher that hands
``DatasetResidency`` a ``device_limit_bytes`` in place of the chip's
``bytes_limit``: the scoring reserve of a 128x128 backend plus room for
``--fit`` of the catalogue's sections (by default half of them).  The cycle
then misses every time (least recently used is cyclic order's worst case): every
in-window job builds its backend again and evicts the oldest, by
``cause="bytes"``.  It must still come out ``correct: true`` with no OOM
event (``broken_guarantees`` 0); the script prints the evictions by cause and
the residency's bytes beside its budget, and exits 1 unless there were
evictions by bytes, none by count, and a correct line.  Not run by the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import run  # noqa: E402
import serve  # noqa: E402

CELL = "section128-workingset-reannotate"
# one 128x128 backend, from shapes (models/msm_jax.py): the lattice's
# 14,680,064 resident slots at 8 B, and beside them the histogram scratch
# and three image blocks of one batch (scoring_reserve_bytes)
SLOT_BYTES = 14_680_064 * 8
RESERVE_BYTES = 4 * 16385 * 16385 + 3 * 4 * 2048 * 4 * 16384

LAUNCHER = """
import functools, sys
from sm_distributed_tpu.engine import residency
residency.DatasetResidency.__init__ = functools.partialmethod(
    residency.DatasetResidency.__init__, device_limit_bytes={limit})
from sm_distributed_tpu.engine.cli import main
sys.exit(main(sys.argv[1:]))
"""


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fit", type=float, default=None,
                    help="sections the device limit has room for "
                         "(default: half the traffic mix's catalogue)")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--slot-bytes", type=int, default=SLOT_BYTES)
    ap.add_argument("--reserve-bytes", type=int, default=RESERVE_BYTES)
    ap.add_argument("--overrides", type=json.loads, default=None,
                    help="run_cell's overrides, for a rehearsal at 8x8 px "
                         "on the CPU (with --platform cpu and the two "
                         "sizes of that backend)")
    args = ap.parse_args(argv)
    if args.fit is None:
        mix = run.BENCH / "traffic" / "workingset.json"
        args.fit = json.loads(mix.read_text())["catalogue"] / 2
    limit = int(args.reserve_bytes + (args.fit + 0.5) * args.slot_bytes)
    launcher = LAUNCHER.format(limit=limit)
    enter = serve.Serve.__enter__
    seen: dict = {}

    def forced(self):
        import subprocess

        with open(self.log, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-c", launcher, "serve", str(self.queue),
                 "--sm-config", str(self.dir / "sm.json")],
                cwd=str(self.root), stdout=log, stderr=subprocess.STDOUT)
        return self

    metrics = serve.Serve.metrics

    def keep(self):
        seen["text"] = metrics(self)
        return seen["text"]

    serve.Serve.__enter__, serve.Serve.metrics = forced, keep
    lines: list[str] = []
    try:
        run.run_cell(CELL, args.seed, args.seconds, False,
                     platform=args.platform, overrides=args.overrides,
                     emit=lines.append)
    finally:
        serve.Serve.__enter__, serve.Serve.metrics = enter, metrics
    out = json.loads(lines[-1])
    text = seen["text"]
    by_bytes = serve.metric_sum(text, "sm_residency_evictions_total",
                                'cause="bytes"')
    by_count = serve.metric_sum(text, "sm_residency_evictions_total",
                                'cause="count"')
    held = serve.metric_sum(text, "sm_residency_bytes", 'cache="backend"')
    budget = serve.metric_sum(text, "sm_residency_budget_bytes",
                              'tier="device"')
    print(f"forced_budget_on_chip: device limit {limit} bytes (reserve "
          f"{args.reserve_bytes} + {args.fit} + 0.5 sections of "
          f"{args.slot_bytes}); evictions by bytes {by_bytes}, by count "
          f"{by_count}; backends hold {held} of a budget of {budget}; "
          f"oom events {serve.metric_sum(text, 'sm_oom_events_total')}; "
          f"correct={out['correct']}", flush=True)
    print(lines[-1])
    ok = out["correct"] and by_bytes and not by_count and held <= budget
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
