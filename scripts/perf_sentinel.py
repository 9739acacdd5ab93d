#!/usr/bin/env python
"""Perf-regression sentinel over a history of artifacts (ISSUE 6).

A PR that halves throughput or triples compile time ships unless a human
happens to eyeball the JSON.  This tool makes the measurement discipline
mechanical:

- **history** = a glob of earlier artifacts of one kind (``--history``):
  ``bench.py`` JSON lines (bare, or in a ``{"parsed": {...}}`` wrapper), a
  ``trace_report.py --json`` summary, or the ``ANALYSIS_r*`` /
  ``NUMERICS_r*`` / ``PROFILE_r*`` series this repo commits.  Device
  numbers have no committed history here: the driver's
  ``PERF_LEDGER.jsonl`` is their record;
- **fresh** = one new artifact of either kind;
- each comparable metric (headline/scale/desi ions/s, ``compile_s``,
  ``isocalc_s``, the pinned per-phase splits, trace phase/accounting
  seconds) is checked against the **median of its history values**:
  rates regress when they fall below ``median * (1 - tolerance)``, times
  when they rise above ``median * (1 + tolerance)``;
- sub-``--min-seconds`` medians are skipped (a 0.02 s isocalc wobbling to
  0.04 s is timer noise, not a regression), as are metrics with fewer than
  ``--min-history`` samples;
- exit codes for CI: 0 = clean, 1 = regression(s), 2 = nothing comparable
  (wrong artifact kind / empty history — a misconfigured gate must not
  pass silently).

``--self-check`` proves the sentinel fires: the newest history artifact is
replayed as an honest fresh run (must pass), then synthetically degraded by
``2 x tolerance`` in the bad direction (must flag regressions).  Wired into
``scripts/check_tier1.sh`` over the synthetic fixture history under
``tests/data/perf_history/``.

Usage::

    python scripts/perf_sentinel.py --history 'runs/*.json' --fresh out.json
    python scripts/perf_sentinel.py --history 'traces/*.json' \
        --fresh trace_summary.json --tolerance 0.4
    python scripts/perf_sentinel.py --history 'runs/*.json' --self-check
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
from pathlib import Path
from statistics import median


# bench-case keys, direction: "up" = higher is better (regression when the
# fresh value drops), "down" = lower is better (regression when it rises)
_BENCH_RATE_KEYS = ("value", "patterns_per_s", "pixels_per_s",
                    "numpy_floor_ions_per_s",
                    # multichip section (ISSUE 7): the N-chip sharded rate
                    # ("value" above), the same-run 1-chip reference, and
                    # the scaling ratio itself are all higher-is-better
                    "single_chip_ions_per_s", "speedup_vs_single_chip",
                    # ISSUE 16: the read-plane mixed cold/warm query rate
                    "reads_per_s",
                    # ISSUE 18: measured fraction of the roofline ceiling —
                    # falling further from the memory-bound floor is the
                    # regression direction
                    "roofline_frac",
                    # ISSUE 20: the profiler-MEASURED roofline (model floor
                    # over per-rep device seconds in the scoring kernels)
                    # and the scoring kernels' share of all captured device
                    # time — both fall when the kernels regress or when
                    # transfers start eating the device
                    "measured_roofline_frac", "kernel_time_frac")
_BENCH_TIME_KEYS = ("compile_s", "isocalc_s", "isocalc_cold_s",
                    "single_chip_compile_s",
                    # ISSUE 13: cleared-cache cold-start pins — the
                    # sentinel band-checks the COLD path, not just the
                    # warm headline
                    "cold_compile_s", "first_annotation_cold_s",
                    # ISSUE 16: read-plane median query latency
                    "read_p50_ms",
                    # ISSUE 18: compacted resident-cube HBM footprint —
                    # quietly growing back toward the f32 baseline is the
                    # regression direction (bytes, well past --min-seconds)
                    "resident_cube_bytes")
# nested bench cases ride along ("multichip" appears on --devices N runs)
_CASE_KEYS = ("scale", "desi", "multichip")


def load_artifact(path: str | Path) -> dict:
    """A bench JSON (bare or driver-wrapped) or trace_report summary."""
    data = json.loads(Path(path).read_text())
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]           # a driver's wrapper
    if not isinstance(data, dict):
        raise ValueError(f"{path}: artifact is not a JSON object")
    return data


def _num(v) -> float | None:
    return float(v) if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def _norm_bench_case(prefix: str, case: dict, out: dict) -> None:
    for k in _BENCH_RATE_KEYS:
        if (v := _num(case.get(k))) is not None:
            out[f"{prefix}.{k}"] = (v, "up")
    for k in _BENCH_TIME_KEYS:
        if (v := _num(case.get(k))) is not None:
            out[f"{prefix}.{k}"] = (v, "down")
    for phase, v in (case.get("phases") or {}).items():
        if (v := _num(v)) is not None:
            out[f"{prefix}.phases.{phase}"] = (v, "down")


def normalize(data: dict) -> dict[str, tuple[float, str]]:
    """Flatten an artifact into ``{metric: (value, direction)}``.  The
    artifact kinds produce disjoint namespaces (``headline.*``/``scale.*``
    vs ``trace.*`` vs ``analysis.*``), so comparing mismatched kinds yields
    zero comparable metrics — exit 2, not a silent pass."""
    out: dict[str, tuple[float, str]] = {}
    if "value" in data and "metric" in data:          # bench.py line
        _norm_bench_case("headline", data, out)
        for case in _CASE_KEYS:
            if isinstance(data.get(case), dict):
                _norm_bench_case(case, data[case], out)
    elif "total_s" in data or "accounting" in data:   # trace_report --json
        if (v := _num(data.get("total_s"))) is not None:
            out["trace.total_s"] = (v, "down")
        for phase, entry in (data.get("phases") or {}).items():
            if isinstance(entry, dict) and \
                    (v := _num(entry.get("seconds"))) is not None:
                out[f"trace.phases.{phase}"] = (v, "down")
        for k, v in (data.get("accounting") or {}).items():
            if (v := _num(v)) is not None:
                out[f"trace.accounting.{k}"] = (v, "down")
    elif "sm_analysis_findings_total" in data:        # smlint --json (ISSUE 12)
        # rule-count + compile-surface drift series: rising totals are the
        # regression direction (a growing baseline-suppressed count or a
        # quietly widening compile surface), so all are "down" metrics
        for rule, v in (data.get("sm_analysis_findings_total") or {}).items():
            if (v := _num(v)) is not None:
                out[f"analysis.findings.{rule}"] = (v, "down")
        for key in ("sm_compile_surface_sites_total",
                    "sm_compile_surface_entries_total",
                    "sm_compile_surface_modules_total",
                    # ISSUE 15: the numerics-contract census rides the same
                    # drift series — a rising violation count is lint debt,
                    # a quietly growing contract surface is reviewable drift
                    "sm_numerics_contracts_total",
                    "sm_numerics_violations_total"):
            if (v := _num(data.get(key))) is not None:
                out[f"analysis.{key[len('sm_'):]}"] = (v, "down")
    elif "sm_numerics_max_ulp" in data:               # ulp_sentinel (ISSUE 15)
        # per-MSM-component max-ULP drift vs the numpy oracle: RISING
        # drift regresses (the ulp-contract gate for bf16
        # compaction); rank mismatches are a hard 0
        for comp, v in (data.get("sm_numerics_max_ulp") or {}).items():
            if (v := _num(v)) is not None:
                out[f"numerics.max_ulp.{comp}"] = (v, "down")
        if (v := _num(data.get("fdr_rank_mismatches"))) is not None:
            out["numerics.fdr_rank_mismatches"] = (v, "down")
        # the bf16-cube leg rides the same drift series — rising
        # data-level drift regresses
        for comp, v in (bf16_leg(data, "sm_numerics_max_ulp") or {}).items():
            if (v := _num(v)) is not None:
                out[f"numerics.max_ulp_bf16.{comp}"] = (v, "down")
        if (v := _num(bf16_leg(data, "fdr_rank_mismatches"))) is not None:
            out["numerics.fdr_rank_mismatches_bf16"] = (v, "down")
    return out


def bf16_leg(artifact: dict, key: str, default=None):
    """``<key>_bf16`` of a NUMERICS artifact's compacted-cube leg.
    NUMERICS_r02 and older wrote the leg (then scored through the fused
    Pallas kernel, removed in PR 44) under ``<key>_fused``."""
    return artifact.get(f"{key}_bf16", artifact.get(f"{key}_fused", default))


def compare(history: list[dict[str, tuple[float, str]]],
            fresh: dict[str, tuple[float, str]],
            tolerance: float, min_history: int,
            min_seconds: float) -> tuple[list[dict], int]:
    """(findings, n_compared).  A finding is a regression row; metrics are
    compared only where the fresh artifact AND >= min_history history
    entries carry them."""
    findings = []
    n_compared = 0
    for name, (value, direction) in sorted(fresh.items()):
        past = [h[name][0] for h in history if name in h]
        if len(past) < min_history:
            continue
        med = median(past)
        if direction == "down" and med < min_seconds:
            continue                    # sub-noise-floor timing
        n_compared += 1
        if direction == "up":
            bound = med * (1.0 - tolerance)
            bad = value < bound
        else:
            bound = med * (1.0 + tolerance)
            bad = value > bound
        if bad:
            findings.append({
                "metric": name, "value": round(value, 4),
                "median": round(med, 4), "bound": round(bound, 4),
                "direction": direction, "n_history": len(past),
            })
    return findings, n_compared


def run_check(history_paths: list[str], fresh_norm: dict, tolerance: float,
              min_history: int, min_seconds: float,
              label: str, as_json: bool = False) -> int:
    history = []
    for p in history_paths:
        try:
            history.append(normalize(load_artifact(p)))
        except (OSError, ValueError) as exc:
            print(f"perf_sentinel: skipping unreadable history {p}: {exc}",
                  file=sys.stderr)
    findings, n_compared = compare(history, fresh_norm, tolerance,
                                   min_history, min_seconds)
    if as_json:
        print(json.dumps({"label": label, "compared": n_compared,
                          "history_files": len(history),
                          "tolerance": tolerance,
                          "regressions": findings}, indent=2))
    if n_compared == 0:
        print(f"perf_sentinel: {label}: NOTHING COMPARABLE — "
              f"{len(history)} history artifact(s), 0 shared metrics "
              f"with >= {min_history} samples", file=sys.stderr)
        return 2
    if findings:
        print(f"perf_sentinel: {label}: {len(findings)} regression(s) over "
              f"{n_compared} compared metric(s):", file=sys.stderr)
        for f in findings:
            arrow = "<" if f["direction"] == "up" else ">"
            print(f"  {f['metric']}: {f['value']} {arrow} bound "
                  f"{f['bound']} (median {f['median']} of "
                  f"{f['n_history']}, tol {tolerance:.0%})", file=sys.stderr)
        return 1
    print(f"perf_sentinel: {label}: OK — {n_compared} metric(s) within "
          f"±{tolerance:.0%} of the history median")
    return 0


def degrade(norm: dict[str, tuple[float, str]],
            tolerance: float) -> dict[str, tuple[float, str]]:
    """Synthetically regress every metric by 2x the tolerance — the
    self-check artifact that MUST trip the sentinel."""
    out = {}
    for name, (value, direction) in norm.items():
        factor = (1.0 - 2.0 * tolerance) if direction == "up" \
            else (1.0 + 2.0 * tolerance)
        out[name] = (max(0.0, value * factor), direction)
    return out


def self_check(history_paths: list[str], tolerance: float, min_history: int,
               min_seconds: float) -> int:
    """Prove the gate both passes honest runs and fires on regressions."""
    if not history_paths:
        print("perf_sentinel: self-check: no history artifacts found",
              file=sys.stderr)
        return 2
    honest = normalize(load_artifact(history_paths[-1]))
    rc = run_check(history_paths, honest, tolerance, min_history,
                   min_seconds, "self-check honest (latest history replay)")
    if rc != 0:
        print("perf_sentinel: self-check FAILED — the newest committed "
              "artifact does not pass against its own history",
              file=sys.stderr)
        return 1
    rc_bad = run_check(history_paths, degrade(honest, tolerance), tolerance,
                       min_history, min_seconds,
                       "self-check degraded (synthetic regression)")
    if rc_bad != 1:
        print("perf_sentinel: self-check FAILED — a synthetic "
              f"2x-tolerance regression did not trip the sentinel "
              f"(rc={rc_bad})", file=sys.stderr)
        return 1
    print("perf_sentinel: self-check OK — honest history passes, synthetic "
          "regression fires")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--history", required=True,
                    help="glob of history artifacts")
    ap.add_argument("--fresh", default=None,
                    help="the fresh bench.py / trace_report.py --json "
                         "artifact to judge")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional drift off the history median "
                         "(default 0.25)")
    ap.add_argument("--min-history", type=int, default=2,
                    help="history samples a metric needs before it is "
                         "compared (default 2)")
    ap.add_argument("--min-seconds", type=float, default=0.05,
                    help="time metrics whose history median is below this "
                         "are timer noise and skipped (default 0.05)")
    ap.add_argument("--json", action="store_true",
                    help="also print a machine-readable comparison")
    ap.add_argument("--self-check", action="store_true",
                    help="replay the newest history artifact (must pass) "
                         "and a synthetically degraded copy (must fail) — "
                         "the CI gate's gate")
    args = ap.parse_args(argv)

    history_paths = sorted(glob.glob(args.history))
    if args.self_check:
        if args.fresh:
            ap.error("--self-check takes no --fresh artifact")
        return self_check(history_paths, args.tolerance, args.min_history,
                          args.min_seconds)
    if not args.fresh:
        ap.error("give --fresh ARTIFACT (or --self-check)")
    try:
        fresh = normalize(load_artifact(args.fresh))
    except (OSError, ValueError) as exc:
        print(f"perf_sentinel: cannot load fresh artifact: {exc}",
              file=sys.stderr)
        return 2
    return run_check(history_paths, fresh, args.tolerance, args.min_history,
                     args.min_seconds, f"fresh {args.fresh}",
                     as_json=args.json)


if __name__ == "__main__":
    sys.exit(main())
