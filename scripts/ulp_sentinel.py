#!/usr/bin/env python
"""ULP-contract numerics sentinel (ISSUE 15 — the runtime half of numlint).

bf16 intensity compaction (and any other change of the scoring numerics)
is gated on FDR ranks staying bit-identical — or within a *declared*
tolerance — to the fp32/numpy oracle.  The static half of that gate is
the ``NUMERICS`` contract registries + the three numlint rules; this
script is the measurement:

1. score the spheroid fixture (the same deliberately off-lattice 9x11
   geometry tests/test_buckets.py pins: real row padding, real resident
   padding, targets + sampled decoys) on the lattice-bucketed jax
   backend AND the numpy oracle;
2. record per-MSM-component max-ULP drift (chaos, image correlation,
   pattern match, msm — ``analysis/numerics.component_drift``) and
   FDR-rank agreement into a ``NUMERICS_r*.json`` artifact;
3. gate three ways:
   - **rank identity** is HARD: any jax-vs-numpy FDR order or level
     difference fails the run outright;
   - **contract ceilings**: each component's measured drift must stay
     within ``analysis/numerics.COMPONENT_CONTRACTS`` (chaos is
     bit_exact = 0 ULPs);
   - **history banding** (perf_sentinel-style, rising drift regresses):
     the fresh drift is compared against the committed ``NUMERICS_r*``
     history medians — so a PR that moves spatial from 0 to 3 ULPs
     trips the sentinel even while the declared ceiling still holds.

``--self-check`` replays the newest committed artifact (must pass) and a
synthetically ceiling-busting copy (must fail) — the gate's gate.  Wired
into ``scripts/check_tier1.sh`` (always on).

Usage::

    python scripts/ulp_sentinel.py                    # measure + gate
    python scripts/ulp_sentinel.py --write NUMERICS_r01.json
    python scripts/ulp_sentinel.py --fresh art.json   # gate an artifact
    python scripts/ulp_sentinel.py --self-check
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from scripts import perf_sentinel  # noqa: E402

# the committed fixture identity: every parameter that shapes the scored
# arrays rides in the artifact, so a drifted fixture can't masquerade as
# drifted numerics
FIXTURE = {"nrows": 9, "ncols": 11, "present_fraction": 0.5,
           "noise_peaks": 12, "seed": 41, "n_formulas": 10,
           "decoy_sample_size": 2, "formula_batch": 8}


def measure(workdir: str | Path | None = None) -> dict:
    """Score the spheroid fixture on both backends and return the
    NUMERICS artifact (pure measurement — gating is :func:`gate`)."""
    import numpy as np
    import pandas as pd

    from sm_distributed_tpu.analysis import numerics
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.models.msm_basic import NumpyBackend, _slice_table
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.fdr import FDR
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import (
        DSConfig,
        IsotopeGenerationConfig,
        SMConfig,
    )

    fx = FIXTURE
    workdir = Path(workdir or tempfile.mkdtemp(prefix="ulp_sentinel_"))
    path, truth = generate_synthetic_dataset(
        workdir / "ds", nrows=fx["nrows"], ncols=fx["ncols"], formulas=None,
        present_fraction=fx["present_fraction"],
        noise_peaks=fx["noise_peaks"], seed=fx["seed"])
    ds = SpectralDataset.from_imzml(path)

    # a REAL search table: targets + sampled decoys, exactly the
    # population the FDR ranking runs over (mirrors MSMBasicSearch)
    formulas = truth.formulas[: fx["n_formulas"]]
    fdr = FDR(decoy_sample_size=fx["decoy_sample_size"],
              target_adducts=("+H",), seed=1)
    assignment = fdr.decoy_adduct_selection(formulas)
    pairs, flags = assignment.all_ion_tuples(formulas, ("+H",))
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table(pairs, flags)

    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict({
        "backend": "jax_tpu",
        "parallel": {"formula_batch": fx["formula_batch"]}})
    batch = fx["formula_batch"]

    def score_all(backend) -> np.ndarray:
        outs = backend.score_batches(
            [_slice_table(table, s, min(s + batch, table.n_ions))
             for s in range(0, table.n_ions, batch)])
        return np.concatenate(outs)

    import jax

    jx = JaxBackend(ds, dc, sm)
    got = score_all(jx)                  # lattice-bucketed jax scoring
    want = score_all(NumpyBackend(ds, dc))   # the fp32/numpy oracle
    drift = numerics.component_drift(got, want)

    # compacted path: the same scoring chain over the bf16-compacted
    # resident cube.  Its drift vs the plain-f32 jax path is DATA-level
    # (the cube lost mantissa bits), so it gates against
    # ops/quantize.py's declared compact_cube contract — not the
    # same-data COMPONENT_CONTRACTS — plus the same HARD
    # FDR-rank-identity bar vs the numpy oracle.
    from sm_distributed_tpu.ops.quantize import NUMERICS as _QN

    cube_ulps = numerics.contract_ulps(
        numerics.parse_policy(_QN["compact_cube"])["contract"])
    sm_bf16 = SMConfig.from_dict({
        "backend": "jax_tpu",
        "parallel": {"formula_batch": fx["formula_batch"],
                     "cube_dtype": "bf16"}})
    got_bf16 = score_all(JaxBackend(ds, dc, sm_bf16))
    drift_bf16 = numerics.component_drift(got_bf16, got)

    def ranks(metrics: np.ndarray):
        df = pd.DataFrame({"sf": table.sfs, "adduct": table.adducts,
                           "msm": metrics[:, 3]})
        ann = fdr.estimate_fdr(df, assignment)
        return ann.sort_values(["msm", "sf"], ascending=False)

    def rank_mismatches(r_got, r_ref) -> int:
        order = int(sum(
            a != b for a, b in zip(r_got.sf.tolist(), r_ref.sf.tolist())))
        levels_equal = bool(
            (r_got.fdr.to_numpy() == r_ref.fdr.to_numpy()).all() and
            (r_got.fdr_level.to_numpy() == r_ref.fdr_level.to_numpy()).all())
        return order if order else (0 if levels_equal else 1)

    r_np = ranks(want)
    mismatches = rank_mismatches(ranks(got), r_np)
    mismatches_bf16 = rank_mismatches(ranks(got_bf16), r_np)

    reg = numerics.registered()
    return {
        "kind": "numerics",
        "fixture": dict(fx),
        "backend": jax.default_backend(),
        "n_ions": int(table.n_ions),
        "lattice_rows": int(jx._nrows_b),        # proves padding engaged
        "dataset_rows": int(ds.nrows),
        "sm_numerics_max_ulp": drift,
        "fdr_rank_mismatches": mismatches,
        "fdr_ranks_identical": mismatches == 0,
        # bf16 cube: drift vs plain-f32 jax, gated by the compact_cube
        # data-level contract; rank identity vs the numpy oracle stays
        # the HARD bar
        "cube_dtype": "bf16",
        "cube_contract_ulps": int(cube_ulps),
        "sm_numerics_max_ulp_bf16": drift_bf16,
        "fdr_rank_mismatches_bf16": mismatches_bf16,
        "fdr_ranks_identical_bf16": mismatches_bf16 == 0,
        "component_contracts": dict(numerics.COMPONENT_CONTRACTS),
        "declared_contracts": sum(len(e) for e in reg.values()),
        "declared_modules": len(reg),
    }


def gate(artifact: dict, history_paths: list[str], tolerance: float,
         min_history: int, label: str) -> int:
    """The three-way gate over one NUMERICS artifact: hard rank identity,
    declared per-component ceilings, then history banding.  0 clean, 1
    violation/regression, 2 nothing comparable."""
    from sm_distributed_tpu.analysis import numerics

    rc = 0
    if artifact.get("fdr_rank_mismatches", 0) != 0 or \
            not artifact.get("fdr_ranks_identical", False):
        print(f"ulp_sentinel: {label}: FAIL — jax-vs-numpy FDR ranks "
              f"diverge ({artifact.get('fdr_rank_mismatches')} "
              f"mismatch(es)); rank identity is the HARD contract",
              file=sys.stderr)
        rc = 1
    if perf_sentinel.bf16_leg(artifact, "fdr_rank_mismatches", 0) != 0 or \
            not perf_sentinel.bf16_leg(artifact, "fdr_ranks_identical", True):
        print(f"ulp_sentinel: {label}: FAIL — compacted-vs-numpy "
              f"FDR ranks diverge "
              f"({perf_sentinel.bf16_leg(artifact, 'fdr_rank_mismatches')} mismatch(es)); "
              f"rank identity is the HARD contract", file=sys.stderr)
        rc = 1
    ceilings = {**numerics.COMPONENT_CONTRACTS,
                **artifact.get("component_contracts", {})}
    for comp, ulps in (artifact.get("sm_numerics_max_ulp") or {}).items():
        ceiling = ceilings.get(comp)
        if ceiling is not None and ulps > ceiling:
            print(f"ulp_sentinel: {label}: FAIL — {comp} drift {ulps} "
                  f"ULPs exceeds its declared contract of {ceiling}",
                  file=sys.stderr)
            rc = 1
    # bf16 drift is data-level — its ceiling is the compact_cube
    # contract the artifact itself carries (ops/quantize.py NUMERICS)
    cube_ceiling = artifact.get("cube_contract_ulps")
    for comp, ulps in (perf_sentinel.bf16_leg(artifact, "sm_numerics_max_ulp")
                       or {}).items():
        if cube_ceiling is not None and ulps > cube_ceiling:
            print(f"ulp_sentinel: {label}: FAIL — compacted {comp} "
                  f"drift {ulps} ULPs exceeds the compact_cube contract "
                  f"of {cube_ceiling}", file=sys.stderr)
            rc = 1
    band_rc = perf_sentinel.run_check(
        history_paths, perf_sentinel.normalize(artifact), tolerance,
        min_history, 0.0, f"ulp_sentinel {label}")
    if band_rc == 2 and not history_paths:
        # first run of a fresh checkout: ceilings + rank identity still
        # gate; banding starts once NUMERICS_r01.json is committed
        print("ulp_sentinel: no committed history — banding skipped "
              "(ceilings and rank identity still gated)", file=sys.stderr)
        band_rc = 0
    return rc or band_rc


def degrade(artifact: dict) -> dict:
    """A synthetically broken copy for --self-check: every component
    busts its ceiling and the rank contract breaks."""
    bad = json.loads(json.dumps(artifact))
    ulp = bad.get("sm_numerics_max_ulp") or {}
    ceilings = bad.get("component_contracts") or {}
    for comp in ulp:
        ulp[comp] = 2 * int(ceilings.get(comp, 0)) + 8
    ulp_bf16 = perf_sentinel.bf16_leg(bad, "sm_numerics_max_ulp") or {}
    for comp in ulp_bf16:
        ulp_bf16[comp] = 2 * int(bad.get("cube_contract_ulps", 0)) + 8
    bad["fdr_rank_mismatches"] = 1
    bad["fdr_ranks_identical"] = False
    for suffix in ("bf16", "fused"):
        if f"fdr_ranks_identical_{suffix}" in bad:
            bad[f"fdr_rank_mismatches_{suffix}"] = 1
            bad[f"fdr_ranks_identical_{suffix}"] = False
    return bad


def self_check(history_paths: list[str], tolerance: float,
               min_history: int) -> int:
    """Newest committed artifact must pass its own history; a degraded
    copy must fail — proving the sentinel can actually fire."""
    if not history_paths:
        print("ulp_sentinel: self-check: no NUMERICS_r*.json history",
              file=sys.stderr)
        return 2
    honest = perf_sentinel.load_artifact(history_paths[-1])
    rc = gate(honest, history_paths, tolerance, min_history,
              "self-check honest (latest history replay)")
    if rc != 0:
        print("ulp_sentinel: self-check FAILED — the newest committed "
              "artifact does not pass its own gate", file=sys.stderr)
        return 1
    rc_bad = gate(degrade(honest), history_paths, tolerance, min_history,
                  "self-check degraded (synthetic contract bust)")
    if rc_bad != 1:
        print(f"ulp_sentinel: self-check FAILED — a synthetic "
              f"ceiling-busting regression did not trip the gate "
              f"(rc={rc_bad})", file=sys.stderr)
        return 1
    print("ulp_sentinel: self-check OK — honest history passes, synthetic "
          "contract bust fires")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--history", default=None,
                    help="glob of NUMERICS history artifacts (default: "
                         "the repo's committed NUMERICS_r*.json)")
    ap.add_argument("--fresh", default=None,
                    help="gate an existing artifact instead of measuring")
    ap.add_argument("--write", default=None,
                    help="write the measured artifact to this path (the "
                         "committed-history workflow)")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="banding tolerance off the history median "
                         "(default 0.5 — ULP drift doubling flags)")
    ap.add_argument("--min-history", type=int, default=1)
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--self-check", action="store_true",
                    help="replay newest history honest + degraded — the "
                         "gate's gate")
    args = ap.parse_args(argv)

    pattern = args.history or str(REPO_ROOT / "NUMERICS_r*.json")
    history_paths = sorted(glob.glob(pattern))
    if args.self_check:
        if args.fresh:
            ap.error("--self-check takes no --fresh artifact")
        return self_check(history_paths, args.tolerance, args.min_history)

    if args.fresh:
        try:
            artifact = perf_sentinel.load_artifact(args.fresh)
        except (OSError, ValueError) as exc:
            print(f"ulp_sentinel: cannot load fresh artifact: {exc}",
                  file=sys.stderr)
            return 2
    else:
        artifact = measure()
    if args.as_json:
        print(json.dumps(artifact, indent=2))
    if args.write:
        Path(args.write).write_text(json.dumps(artifact, indent=2) + "\n")
        print(f"ulp_sentinel: wrote {args.write}")
    # a freshly written artifact should not band against a history that
    # already includes itself twice; still gate it fully
    return gate(artifact, history_paths, args.tolerance, args.min_history,
                "fresh measurement" if not args.fresh else
                f"fresh {args.fresh}")


if __name__ == "__main__":
    sys.exit(main())
