#!/usr/bin/env python3
"""The control of ``correct``, at the cell's own size, on the chip:

    python3 benchmarks/tests/control_on_chip.py --workload <cell> \
        --seconds 20 --seeds 11 12 13 [--sound-seeds 21 22 ...]

Each ``--seeds`` run starts the child with the program's own lower-precision
path (``parallel.cube_dtype: bf16``, the step below the configuration's f32)
and must come out ``correct: false``; each ``--sound-seeds`` run is the cell
as configured and must come out ``correct: true``.  Every number compared is
on the ``correct:`` lines of the output, beside its limit: the readings the
limits in PERF.md were checked against.  Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import run  # noqa: E402

CONTROL = {"sm_config": {"parallel": {"cube_dtype": "bf16"}}}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bad = 0
    for kind, seeds, overrides, want in (
            ("control", args.seeds, CONTROL, False),
            ("sound", args.sound_seeds, None, True)):
        for seed in seeds:
            lines: list[str] = []
            run.T_START = run.time.time()
            run.run_cell(args.workload, seed, args.seconds, False,
                         overrides=overrides, emit=lines.append)
            got = json.loads(lines[-1])["correct"]
            print(f"control_on_chip: {kind} seed {seed}: correct={got} "
                  f"(must be {want})", flush=True)
            bad += got is not want
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
