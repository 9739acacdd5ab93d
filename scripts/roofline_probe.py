"""Roofline probe for the fused score graph (ISSUE 3 satellite).

Replaces the per-phase-probe basis of PERF.md's "no headroom left"
claim with a measured ROOFLINE statement: the fused extract+score stream is
timed against this device's own measured peaks (reduction/copy bandwidth,
f32 matmul throughput) and the engine's minimum-work cost model
(``ops/imager_jax.py::fused_score_cost_model``).  The output is a bound —

    headroom_x = measured_seconds / max(bytes/peak_bw, flops/peak_flops)

— an UPPER bound on what any further tuning of the same algorithm could
recover (the model prices no padding, recompiles, or dispatch, and the
peaks are microbenchmark ceilings).

Usage::

    JAX_PLATFORMS=cpu python scripts/roofline_probe.py --tiny   # CI smoke
    python scripts/roofline_probe.py                            # bench case
    python scripts/roofline_probe.py --nrows 512 --ncols 512 \
        --n-formulas 500 --formula-batch 256                    # DESI case

Prints ONE JSON line on stdout; logs to stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))


def _median(xs):
    s = sorted(xs)
    return s[len(s) // 2]


def measure_device_peaks(bw_mb: int = 256, mm_n: int = 2048) -> dict:
    """Microbenchmark ceilings on the CURRENT device: effective bandwidth of
    a reduction and an elementwise copy over a ``bw_mb``-MB f32 array, and
    f32 (HIGHEST — the engine's matmul precision) matmul throughput."""
    import jax
    import jax.numpy as jnp

    n = bw_mb * (1 << 20) // 4
    x = jnp.arange(n, dtype=jnp.float32)
    red = jax.jit(lambda v: v.sum())
    cpy = jax.jit(lambda v: v * 2.0)
    red(x).block_until_ready()
    cpy(x).block_until_ready()
    red_dts, cpy_dts = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        red(x).block_until_ready()
        red_dts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        cpy(x).block_until_ready()
        cpy_dts.append(time.perf_counter() - t0)
    red_bw = 4 * n / _median(red_dts)            # bytes read
    cpy_bw = 12 * n / _median(cpy_dts)           # read + write (+RFO on CPU)

    a = jnp.ones((mm_n, mm_n), jnp.float32)
    mm = jax.jit(lambda u, v: jnp.dot(
        u, v, precision=jax.lax.Precision.HIGHEST))
    mm(a, a).block_until_ready()
    mm_dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        mm(a, a).block_until_ready()
        mm_dts.append(time.perf_counter() - t0)
    flops = 2.0 * mm_n**3 / _median(mm_dts)
    return dict(
        peak_reduction_gbps=red_bw / 1e9,
        peak_copy_gbps=cpy_bw / 1e9,
        peak_bw_gbps=max(red_bw, cpy_bw) / 1e9,
        peak_matmul_gflops=flops / 1e9,
        device=str(jax.devices()[0]),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nrows", type=int, default=64)
    ap.add_argument("--ncols", type=int, default=64)
    ap.add_argument("--n-formulas", type=int, default=250)
    ap.add_argument("--formula-batch", type=int, default=2048)
    ap.add_argument("--decoy-sample-size", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shape (16x16 px, 8 formulas, tiny "
                         "microbenches)")
    ap.add_argument("--cube-dtype", choices=("f32", "bf16"),
                    default="f32",
                    help="parallel.cube_dtype for the probed backend")
    ap.add_argument("--min-frac", type=float, default=0.0,
                    help="exit nonzero unless roofline_frac (= floor_s / "
                         "measured_s) >= this — the check_tier1 gate that "
                         "keeps measured-vs-model from regressing "
                         "catastrophically on whatever hardware runs CI")
    args = ap.parse_args()
    if args.tiny:
        args.nrows = args.ncols = 16
        args.n_formulas = 8
        args.formula_batch = 64
        args.decoy_sample_size = 4
        args.reps = 1

    from bench import BenchConfig, prepare
    from sm_distributed_tpu.models.msm_basic import make_backend
    from sm_distributed_tpu.ops.imager_jax import fused_score_cost_model
    from sm_distributed_tpu.utils.config import SMConfig
    from sm_distributed_tpu.utils.logger import init_logger, logger

    init_logger()
    cache_dir = Path(__file__).parent.parent / ".cache"
    cfg = BenchConfig("roofline", args.nrows, args.ncols, args.n_formulas,
                      args.formula_batch, args.decoy_sample_size,
                      reps=args.reps, baseline_ions=0)
    prep = prepare(cfg, cache_dir)
    table, ds = prep["table"], prep["ds"]

    sm_config = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "fdr": {"decoy_sample_size": args.decoy_sample_size},
         "parallel": {"formula_batch": args.formula_batch,
                      "cube_dtype": args.cube_dtype}})
    backend = make_backend("jax_tpu", ds, prep["ds_config"], sm_config,
                           table=table)
    batches = prep["batches"]
    if hasattr(backend, "warmup"):
        backend.warmup(batches)
    else:
        backend.score_batch(batches[0])

    dts = []
    for i in range(max(1, args.reps)):
        t0 = time.perf_counter()
        backend.score_batches(batches)
        dts.append(time.perf_counter() - t0)
        logger.info("rep %d: %.3fs (%.1f ions/s)", i, dts[-1],
                    table.n_ions / dts[-1])
    measured_s = _median(dts)

    peaks = measure_device_peaks(bw_mb=16 if args.tiny else 256,
                                 mm_n=256 if args.tiny else 2048)
    resident = getattr(backend, "_mz_host", None)
    resident_peaks = int(resident.size) if resident is not None else int(
        ds.n_peaks)
    model = fused_score_cost_model(
        n_pixels=ds.n_pixels,
        resident_peaks=resident_peaks,
        n_ions=table.n_ions,
        max_peaks=table.max_peaks,
        formula_batch=args.formula_batch,
        nlevels=prep["ds_config"].image_generation.nlevels,
        ordered=True,
        cube_dtype=args.cube_dtype,
    )
    t_bw = model["total_bytes"] / (peaks["peak_bw_gbps"] * 1e9)
    t_fl = model["matmul_flops"] / (peaks["peak_matmul_gflops"] * 1e9)
    floor_s = max(t_bw, t_fl)
    frac = floor_s / measured_s if measured_s > 0 else 0.0
    int_bytes = {"f32": 4, "bf16": 2}[args.cube_dtype]
    out = {
        "metric": "fused_score_roofline",
        "measured_s_per_rep": round(measured_s, 4),
        "ions_per_s": round(table.n_ions / measured_s, 1),
        "model": model,
        "peaks": {k: round(v, 2) for k, v in peaks.items()
                  if isinstance(v, float)},
        "device": peaks["device"],
        "roofline_floor_s": round(floor_s, 4),
        "bound": "bandwidth" if t_bw >= t_fl else "compute",
        "headroom_x": round(measured_s / floor_s, 2) if floor_s > 0 else None,
        "roofline_frac": round(frac, 4),
        "cube_dtype": args.cube_dtype,
        "resident_cube_bytes": int(resident_peaks * int_bytes),
        "n_ions": int(table.n_ions),
        "n_pixels": int(ds.n_pixels),
        "resident_peaks": resident_peaks,
    }
    print(json.dumps(out))
    if args.min_frac and frac < args.min_frac:
        logger.error(
            "roofline_frac %.4f below gate --min-frac %.4f "
            "(measured %.4fs vs model floor %.4fs)",
            frac, args.min_frac, measured_s, floor_s)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
