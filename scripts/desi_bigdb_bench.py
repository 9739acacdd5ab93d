"""BASELINE eval config #5 at its FULL definition (VERDICT r4 item 4).

Whole-slide pixels x big molecular DB in ONE measured end-to-end job:
512x512 px = 262,144 pixels (~279M dataset peaks) scored against ~80k
formulas x (1 target + 20 decoy) adducts = ~1.68M ions — "DESI whole-slide
high-res, ChEBI + 20 decoy adducts" (SURVEY.md §6 config #5 [U]).  The
default bench's ``desi`` case runs the same pixel count at 500 formulas;
the cold-path script runs the same DB at 100x100 px; this is the first
measurement that combines both axes, which is where the HBM plan (pre-run
estimate ~2.2 GB resident peaks + per-batch band scratch; the measured run
came to 1.95 GB after window-union restriction — PERF.md), the sticky
band-bucket ladder over ~6.5k batches, and sustained-stream throughput
actually get stressed.

Reuses the default bench's 512x512 fixture (same generator parameters) and
the cold-path run's isocalc shard cache when present (same formula list,
adducts and FDR seed => identical (formula, adduct) pairs).  Run it solo
AFTER scripts/cold_path_bench.py for a warm-pattern measurement; pass a
fresh --work-dir for a cold one.

Prints ONE JSON line; logs to stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))


def run(*, n_formulas: int, nrows: int, ncols: int, decoy_sample_size: int,
        formula_batch: int, checkpoint_every: int, cache_dir: Path,
        work_dir: Path | None = None, fixture_formulas: int = 500,
        noise_peaks: int = 200) -> dict:
    from sm_distributed_tpu.engine.search_job import SearchJob
    from sm_distributed_tpu.io.fixtures import (
        expand_formula_list,
        generate_synthetic_dataset,
    )
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig
    from sm_distributed_tpu.utils.logger import logger

    cache_dir = Path(cache_dir)
    work_dir = Path(work_dir or cache_dir / "cold_path" / "work")

    # the default bench's DESI fixture, bit for bit (generator params from
    # bench.py::prepare) — the slide holds signal for ``fixture_formulas``
    # formulas; the other ~79.5k scored formulas still pay full extraction
    # + decoy ranking, which is the config-#5 point
    t0 = time.perf_counter()
    ds_path, _truth = generate_synthetic_dataset(
        cache_dir / f"bench_ds_{nrows}x{ncols}_f{fixture_formulas}",
        nrows=nrows, ncols=ncols,
        formulas=expand_formula_list(fixture_formulas),
        present_fraction=0.6, noise_peaks=noise_peaks, seed=7, reuse=True)
    logger.info("fixture: %dx%d px (%.1fs)", nrows, ncols,
                time.perf_counter() - t0)

    sm_config = SMConfig.from_dict({
        "backend": "jax_tpu",
        "fdr": {"decoy_sample_size": decoy_sample_size},
        "storage": {"results_dir": str(cache_dir / "desi_bigdb" / "results"),
                    "store_images": False},
        "work_dir": str(work_dir),
        "parallel": {"formula_batch": formula_batch,
                     "checkpoint_every": checkpoint_every},
    })
    ds_config = DSConfig.from_dict({
        "isotope_generation": {"adducts": ["+H"]},
        "image_generation": {"ppm": 3.0},
    })
    formulas = expand_formula_list(n_formulas)

    t0 = time.perf_counter()
    job = SearchJob("desi_bigdb", "desi-bigdb-config5", ds_path, ds_config,
                    sm_config, formulas=formulas)
    bundle = job.run()
    wall = time.perf_counter() - t0

    t = bundle.timings
    n_ions = int(bundle.all_metrics.shape[0])
    score_s = t.get("score", 0.0)
    return {
        "metric": "desi_bigdb_config5_wall_clock",
        "unit": "s",
        "value": round(wall, 1),
        "n_formulas": n_formulas,
        "n_ions": n_ions,
        "n_pixels": nrows * ncols,
        "score_s": round(score_s, 1),
        "score_ions_per_s": round(n_ions / score_s, 1) if score_s else None,
        "isocalc_s": round(t.get("isotope_patterns", 0.0), 1),
        "phases_s": {k: round(v, 1) for k, v in sorted(t.items())},
        "n_annotations_fdr10": int((bundle.annotations["fdr"] <= 0.1).sum())
        if len(bundle.annotations) else 0,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-formulas", type=int, default=80_000)
    ap.add_argument("--nrows", type=int, default=512)
    ap.add_argument("--ncols", type=int, default=512)
    ap.add_argument("--decoy-sample-size", type=int, default=20)
    ap.add_argument("--formula-batch", type=int, default=256,
                    help="256 keeps the flat-path histogram scratch inside "
                         "the HBM guard at 262k pixels (bench.py desi)")
    ap.add_argument("--checkpoint-every", type=int, default=64,
                    help="batches per checkpoint group (64 -> a group "
                         "boundary sync every ~16k ions; also exercises "
                         "mid-search checkpointing at BASELINE #5 scale)")
    ap.add_argument("--work-dir", default="",
                    help="job work dir (default: .cache/cold_path/work — "
                         "SHARES the cold-path run's isocalc shard cache)")
    args = ap.parse_args()

    from sm_distributed_tpu.utils.logger import init_logger

    init_logger()
    out = run(
        n_formulas=args.n_formulas, nrows=args.nrows, ncols=args.ncols,
        decoy_sample_size=args.decoy_sample_size,
        formula_batch=args.formula_batch,
        checkpoint_every=args.checkpoint_every,
        cache_dir=Path(__file__).parent.parent / ".cache",
        work_dir=Path(args.work_dir) if args.work_dir else None,
    )
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
