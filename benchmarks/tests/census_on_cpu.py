#!/usr/bin/env python3
"""By hand, no chip: the plan census of a configuration's job over seeds.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/census_on_cpu.py \
        --config maldi-section-128-hmdb --seed 4100000001 --seeds 8 \
        [--formulas 4000] [--band-slice auto --peak-compaction auto]

For each seed: the cell's dataset (``benchmarks/datasets.py``), the
program's own ``MSMBasicSearch`` with the configuration's ``sm_config`` and
``ds_config`` up to ``presize`` (the ion table, its m/z order, the backend
build, the batch plans and the stream-wide fixpoint of the sticky
capacities: host work only), then stop before the first batch is scored.
Prints one JSON line a seed: what ``JaxBackend._plan_census`` said (the
attrs the served job's ``presize`` span carries), the capacities it settled
on, and per batch the band width and the window-union peak count the
chooser priced.  A cell whose census differs from seed to seed flips
executables and device seconds with the data: ISSUE 41's sizing rule (b).
Nothing here is a device number.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(BENCH))

import datasets  # noqa: E402


class _Planned(Exception):
    """Raised in place of the first scoring group."""


def census(cfg: dict, seed: int, cache: Path, iso: Path) -> dict:
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models import msm_jax
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    made = datasets.generate(cache, cfg["dataset"], seed)
    ds = SpectralDataset.from_imzml(made["path"])
    search = MSMBasicSearch(
        ds, made["formulas"], DSConfig.from_dict(cfg["ds_config"]),
        SMConfig.from_dict(cfg["sm_config"]), isocalc_cache_dir=str(iso),
        device_indices=(0,))                 # the cell's lease: one chip
    seen: dict = {}
    plan_census = msm_jax.JaxBackend._plan_census

    def recording(self, plans):
        out = plan_census(self, plans)
        seen.update(
            out, n_keep=self._n_keep, gc_width=self._gc_width,
            resident_slots=int(self._mz_host.size),
            band_floor=self._BAND_MIN,
            band_widths=[int(p[9][1]) for p in plans if p[9] is not None],
            union_peaks=[int(p[7][2]) for p in plans if p[7] is not None])
        return out

    def stop(*_a, **_k):
        raise _Planned

    msm_jax.JaxBackend._plan_census = recording
    search._score_group = stop
    try:
        search.search()
    except _Planned:
        pass
    finally:
        msm_jax.JaxBackend._plan_census = plan_census
    shutil.rmtree(Path(made["path"]).parent, ignore_errors=True)
    return {"seed": seed, "peaks": made["n_peaks"], **seen}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--formulas", type=int)
    ap.add_argument("--band-slice", choices=("auto", "on", "off"))
    ap.add_argument("--peak-compaction", choices=("auto", "on", "off"))
    args = ap.parse_args(argv)
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    if args.formulas:
        signal = round(cfg["dataset"]["present_fraction"]
                       * cfg["dataset"]["n_formulas"])
        cfg["dataset"].update(n_formulas=args.formulas,
                              present_fraction=signal / args.formulas)
    par = cfg["sm_config"]["parallel"]
    par["band_slice"] = args.band_slice or par["band_slice"]
    par["peak_compaction"] = args.peak_compaction or par["peak_compaction"]
    with tempfile.TemporaryDirectory(dir=ROOT / ".cache") as tmp:
        # one pattern cache for all seeds: the table does not depend on one
        iso = ROOT / ".cache" / "census_isocalc"
        for seed in range(args.seed, args.seed + args.seeds):
            print(json.dumps(census(cfg, seed, Path(tmp), iso)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
