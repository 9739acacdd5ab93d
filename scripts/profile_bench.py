"""Per-phase timing breakdown of the fused graph on the bench workload.

Times each stage (extraction, chaos, correlation, pattern match) via the
backend's OWN probe hooks (``JaxBackend.probe_phases`` — VERDICT r3 item 5:
the previous versions re-implemented backend internals from private plan
tuples and broke whenever the plan shape changed).  Each probed phase runs
the exact arrays, static shapes, and plain/compaction variant that
``score_batch`` dispatches.  Run on the real chip to attribute cost before
optimizing.
"""

from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import FIXTURE_FORMULAS, generate_synthetic_dataset
from sm_distributed_tpu.models.msm_basic import _slice_table
from sm_distributed_tpu.models.msm_jax import JaxBackend
from sm_distributed_tpu.ops.fdr import FDR
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.utils.config import DSConfig, SMConfig
from sm_distributed_tpu.utils.logger import init_logger, logger


def _force(out):
    """Force a host readback of ONE element (a dependent tiny dispatch),
    not the whole array: the timed region must end after the device is
    done, without copying a multi-GB image block to the host."""
    for x in jax.tree.leaves(out):
        np.asarray(x[(0,) * getattr(x, "ndim", 0)])


def timeit(name, fn, reps=5):
    _force(fn())                          # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    _force(out)
    dt = (time.perf_counter() - t0) / reps
    logger.info("%-28s %8.2f ms", name, dt * 1e3)
    return dt


def profile(nrows=64, ncols=64, formula_batch=512, noise_peaks=200, reps=5,
            cache_dir=None, n_formulas=None, batch_index=0):
    """Run the phase breakdown; returns {phase: seconds} for assertions.

    ``n_formulas``: expand the formula list like bench.py does (None = the
    50 curated fixture formulas).  ``batch_index`` picks which formula batch
    to profile — batch 0 holds every target window (all the signal), later
    batches are decoy-dominated, so their cost profiles differ."""
    from sm_distributed_tpu.io.fixtures import expand_formula_list

    init_logger()
    cache_dir = Path(cache_dir or Path(__file__).parent.parent / ".cache")
    formulas = (expand_formula_list(n_formulas) if n_formulas
                else FIXTURE_FORMULAS)
    # n_formulas mode mirrors bench.py's exact fixture params AND its cache
    # naming, so the profiler reuses bench datasets (a 512x512 generation
    # costs ~11 min on this host)
    name = (f"bench_ds_{nrows}x{ncols}_f{n_formulas}" if n_formulas
            else f"profile_ds_{nrows}x{ncols}")
    path, truth = generate_synthetic_dataset(
        cache_dir / name, nrows=nrows, ncols=ncols,
        formulas=formulas, present_fraction=0.6,
        noise_peaks=noise_peaks, seed=7, reuse=True,
    )
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]}, "image_generation": {"ppm": 3.0}}
    )
    sm_config = SMConfig.from_dict(
        {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 20},
         "parallel": {"formula_batch": formula_batch}}
    )

    fdr = FDR(decoy_sample_size=20, target_adducts=("+H",), seed=42)
    assignment = fdr.decoy_adduct_selection(truth.formulas)
    pairs, flags = assignment.all_ion_tuples(truth.formulas, ("+H",))
    calc = IsocalcWrapper(ds_config.isotope_generation,
                          cache_dir=str(cache_dir / "isocalc"))
    table = calc.pattern_table(pairs, flags)

    backend = JaxBackend(ds, ds_config, sm_config, restrict_table=table)
    b = backend.batch
    s0 = min(batch_index * b, max(table.n_ions - b, 0))
    sub = _slice_table(table, s0, min(s0 + b, table.n_ions))

    phases, info = backend.probe_phases(sub)
    logger.info("probe info: %s", info)
    timings = {name: timeit(name, fn, reps=reps)
               for name, fn in phases.items()}
    parts = sum(t for name, t in timings.items() if name != "fused_full")
    logger.info("sum of parts: %.2f ms (full %.2f ms)",
                parts * 1e3, timings["fused_full"] * 1e3)
    return timings


def main():
    profile()


if __name__ == "__main__":
    main()
