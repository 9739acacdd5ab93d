"""Per-batch + per-phase cost attribution of the DESI bench case.

Builds EXACTLY the bench.py `desi` workload (512x512 px, 500 formulas,
m/z-ordered stream, formula_batch=256) and attributes stream time:

1. per-batch serial fused timings (dispatch + forced readback),
2. probe_phases splits (extract / chaos / correlation / pattern) on
   representative batches (first, median-width, widest band),
3. the pipelined stream rate for reference.

Run on the real chip; needs the bench fixture cache (.cache/bench_ds_*).
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import numpy as np

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import (
    expand_formula_list,
    generate_synthetic_dataset,
)
from sm_distributed_tpu.models.msm_basic import (
    _slice_table,
    make_backend,
    maybe_order_table,
)
from sm_distributed_tpu.ops.fdr import FDR
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.utils.config import DSConfig, SMConfig
from sm_distributed_tpu.utils.logger import init_logger, logger

from profile_bench import _force, timeit  # noqa: E402  (same dir)


def build(formula_batch=256, nrows=512, ncols=512, n_formulas=500):
    cache_dir = Path(__file__).parent.parent / ".cache"
    formulas = expand_formula_list(n_formulas)
    work_dir = cache_dir / f"bench_ds_{nrows}x{ncols}_f{n_formulas}"
    path, truth = generate_synthetic_dataset(
        work_dir, nrows=nrows, ncols=ncols, formulas=formulas,
        present_fraction=0.6, noise_peaks=200, seed=7, reuse=True)
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})
    fdr = FDR(decoy_sample_size=20, target_adducts=("+H",), seed=42)
    assignment = fdr.decoy_adduct_selection(truth.formulas)
    pairs, flags = assignment.all_ion_tuples(truth.formulas, ("+H",))
    calc = IsocalcWrapper(ds_config.isotope_generation,
                          cache_dir=str(cache_dir / "isocalc"))
    table = calc.pattern_table(pairs, flags)
    table = maybe_order_table(table, "auto", formula_batch)
    b = formula_batch
    batches = [_slice_table(table, s, min(s + b, table.n_ions))
               for s in range(0, table.n_ions, b)]
    sm_config = SMConfig.from_dict(
        {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 20},
         "parallel": {"formula_batch": formula_batch}})
    backend = make_backend("jax_tpu", ds, ds_config, sm_config, table=table)
    return ds, table, batches, backend


def main(formula_batch=256):
    init_logger()
    ds, table, batches, backend = build(formula_batch=formula_batch)
    t0 = time.perf_counter()
    backend.warmup(batches)
    logger.info("warmup: %.1fs", time.perf_counter() - t0)

    # 1. serial per-batch fused timings
    per_batch = []
    for i, t in enumerate(batches):
        plan = backend._flat_plan(t)
        variant = backend._variant_for(plan[7], plan[9])
        width = plan[9][1] if plan[9] else 0
        t0 = time.perf_counter()
        out, _n = backend._dispatch(t, plan)
        _force(out)
        dt = time.perf_counter() - t0
        per_batch.append((i, variant, width, dt))
    tot = sum(p[3] for p in per_batch)
    logger.info("serial total: %.2fs over %d batches", tot, len(per_batch))
    for i, variant, width, dt in per_batch:
        logger.info("batch %2d %-7s band_w=%9d  %6.1f ms",
                    i, variant, width, dt * 1e3)

    # 2. phase splits on representative batches
    widths = [p[2] for p in per_batch]
    reps = {0, int(np.argsort(widths)[len(widths) // 2]),
            int(np.argmax(widths)), len(batches) - 1}
    for i in sorted(reps):
        phases, info = backend.probe_phases(batches[i])
        logger.info("batch %d probe info: %s", i, info)
        for name, fn in phases.items():
            timeit(f"b{i}:{name}", fn, reps=3)

    # 3. pipelined stream rate (one rep)
    t0 = time.perf_counter()
    backend.score_batches(batches)
    dt = time.perf_counter() - t0
    logger.info("pipelined stream: %.2fs -> %.1f ions/s",
                dt, table.n_ions / dt)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--formula-batch", type=int, default=256)
    a = ap.parse_args()
    main(formula_batch=a.formula_batch)
