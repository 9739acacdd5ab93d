"""scripts/perf_sentinel.py — the CI perf-regression gate (ISSUE 6).

Covers artifact loading (driver wrapper vs bare bench line vs trace_report
summary), the median-band comparison in both directions, the noise floors
(min-seconds, min-history), the nothing-comparable guard, and the
self-check mode against the synthetic fixture history under tests/data.
"""

from __future__ import annotations

import json
from pathlib import Path

from scripts import perf_sentinel

REPO_ROOT = Path(__file__).resolve().parent.parent


def _bench(value, compile_s=10.0, scale_value=None, phases=None) -> dict:
    out = {"metric": "ions_scored_per_sec_per_chip", "unit": "ions/s",
           "value": value, "compile_s": compile_s, "isocalc_s": 0.02}
    if phases is not None:
        out["phases"] = phases
    if scale_value is not None:
        out["scale"] = {"value": scale_value, "compile_s": compile_s * 3}
    return out


def _write_history(tmp_path: Path, artifacts: list[dict],
                   wrap: bool = False) -> str:
    for i, art in enumerate(artifacts):
        body = {"n": i, "parsed": art} if wrap else art
        (tmp_path / f"hist_r{i:02d}.json").write_text(json.dumps(body))
    return str(tmp_path / "hist_r*.json")


def _run(history_glob: str, fresh: dict, tmp_path: Path, **flags) -> int:
    fp = tmp_path / "fresh.json"
    fp.write_text(json.dumps(fresh))
    argv = ["--history", history_glob, "--fresh", str(fp)]
    for k, v in flags.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    return perf_sentinel.main(argv)


# ------------------------------------------------------------------ loading
def test_load_artifact_unwraps_driver_format(tmp_path):
    p = tmp_path / "wrapped.json"
    p.write_text(json.dumps({"n": 5, "rc": 0, "parsed": {"value": 7,
                                                         "metric": "x"}}))
    assert perf_sentinel.load_artifact(p)["value"] == 7


def test_normalize_bench_and_cases():
    norm = perf_sentinel.normalize(_bench(
        1000.0, compile_s=5.0, scale_value=200.0,
        phases={"stream_s": 1.5, "compile_s": 5.0}))
    assert norm["headline.value"] == (1000.0, "up")
    assert norm["headline.compile_s"] == (5.0, "down")
    assert norm["headline.phases.stream_s"] == (1.5, "down")
    assert norm["scale.value"] == (200.0, "up")


def test_normalize_trace_report_summary():
    norm = perf_sentinel.normalize({
        "total_s": 12.0,
        "phases": {"score": {"count": 1, "seconds": 8.0}},
        "accounting": {"queue_wait_s": 0.5, "compute_s": 8.0},
    })
    assert norm["trace.total_s"] == (12.0, "down")
    assert norm["trace.phases.score"] == (8.0, "down")
    assert norm["trace.accounting.queue_wait_s"] == (0.5, "down")
    # the two artifact kinds share no metric names
    assert not set(norm) & set(perf_sentinel.normalize(_bench(1.0)))


# --------------------------------------------------------------- comparison
def test_honest_fresh_passes(tmp_path):
    hist = _write_history(tmp_path, [_bench(900), _bench(1000), _bench(1100)])
    assert _run(hist, _bench(1050), tmp_path) == 0


def test_rate_regression_fires(tmp_path):
    hist = _write_history(tmp_path, [_bench(900), _bench(1000), _bench(1100)])
    # median 1000, tol 0.25 -> bound 750
    assert _run(hist, _bench(700), tmp_path) == 1


def test_time_regression_fires(tmp_path):
    hist = _write_history(
        tmp_path, [_bench(1000, compile_s=10.0)] * 3, wrap=True)
    assert _run(hist, _bench(1000, compile_s=20.0), tmp_path) == 1
    assert _run(hist, _bench(1000, compile_s=11.0), tmp_path) == 0


def test_improvements_never_fire(tmp_path):
    hist = _write_history(tmp_path, [_bench(1000, compile_s=10.0)] * 3)
    assert _run(hist, _bench(5000, compile_s=1.0), tmp_path) == 0


def test_tolerance_is_configurable(tmp_path):
    hist = _write_history(tmp_path, [_bench(1000)] * 3)
    assert _run(hist, _bench(850), tmp_path) == 0          # within 25%
    assert _run(hist, _bench(850), tmp_path, tolerance=0.1) == 1


def test_min_seconds_floor_skips_timer_noise(tmp_path):
    # isocalc_s history median 0.02 s: a 2x wobble is not a regression
    hist = _write_history(tmp_path, [_bench(1000)] * 3)
    fresh = _bench(1000)
    fresh["isocalc_s"] = 0.04
    assert _run(hist, fresh, tmp_path) == 0


def test_min_history_guard(tmp_path):
    # a single history sample is not a band; the lone-but-comparable value
    # metric keeps the run from being "nothing comparable"
    hist = _write_history(tmp_path, [_bench(1000, scale_value=100.0)])
    fresh = _bench(1000, scale_value=10.0)                 # 10x scale drop
    assert _run(hist, fresh, tmp_path, min_history=2) == 2
    assert _run(hist, fresh, tmp_path, min_history=1) == 1


def test_nothing_comparable_is_an_error(tmp_path):
    # trace artifact vs bench history: disjoint namespaces -> exit 2
    hist = _write_history(tmp_path, [_bench(1000)] * 3)
    assert _run(hist, {"total_s": 5.0, "phases": {}}, tmp_path) == 2


def test_trace_history_vs_trace_fresh(tmp_path):
    mk = lambda total: {"total_s": total,
                        "phases": {"score": {"count": 1,
                                             "seconds": total * 0.8}},
                        "accounting": {"compute_s": total * 0.8}}
    hist = _write_history(tmp_path, [mk(10.0), mk(11.0), mk(9.0)])
    assert _run(hist, mk(10.5), tmp_path) == 0
    assert _run(hist, mk(30.0), tmp_path) == 1


def test_degrade_flips_both_directions():
    norm = {"a.value": (1000.0, "up"), "a.compile_s": (10.0, "down")}
    bad = perf_sentinel.degrade(norm, 0.25)
    assert bad["a.value"][0] == 500.0
    assert bad["a.compile_s"][0] == 15.0


# ---------------------------------------------------------------- self-check
FIXTURE_HISTORY = REPO_ROOT / "tests" / "data" / "perf_history"


def test_self_check_against_fixture_history():
    """The real CI gate (check_tier1.sh runs the same command): the
    synthetic fixture history must self-check."""
    assert sorted(FIXTURE_HISTORY.glob("bench_*.json")), \
        "fixture history disappeared"
    assert perf_sentinel.main(
        ["--self-check", "--history",
         str(FIXTURE_HISTORY / "bench_*.json")]) == 0


def test_self_check_fails_without_history(tmp_path):
    assert perf_sentinel.main(
        ["--self-check", "--history", str(tmp_path / "none_*.json")]) == 2
