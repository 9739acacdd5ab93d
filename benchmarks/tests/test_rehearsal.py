"""CPU rehearsal of the whole command, by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

Every cell at 8x8 px through ``run.run_cell`` with the platform assertion
handed ``cpu`` by the test (``run.py`` itself only ever passes on ``tpu``),
``--trace 1`` against the small recorded ``.xplane.pb`` in ``data/`` (a cell
on four chips gets four virtual CPU devices); the control (the program's own
``cube_dtype: bf16`` path) and a broken timed path must both come out
``correct: false``; a CPU run through ``run.py`` and a run in a directory that
holds only the benchmark exit non-zero without a result line.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check_manifest  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

SMALL = {"dataset": {"nrows": 8, "ncols": 8, "n_formulas": 40,
                     "noise_peaks": 60},
         "sm_config": {"parallel": {"formula_batch": 256}},
         "traffic": {"profile_seconds": 2, "profile_at_s": 1},
         "xplane": str(BENCH / "tests" / "data" / "small.xplane.pb"),
         "device_kind": "TPU v5 lite"}     # whose trace the recorded one is
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, chips, trace, monkeypatch, seed=2147483999, **kw):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={chips}")
    lines: list[str] = []
    overrides = run.merge(SMALL, kw.pop("overrides", {}))
    # under the profiler's Python tracer a job on this CPU takes seconds
    rc = run.run_cell(cell, seed, 20.0 if trace else 6.0, trace,
                      platform="cpu",
                      overrides=overrides, emit=lines.append, **kw)
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[0])


def test_manifest_passes_and_catches_pr22s_fault():
    assert check_manifest.check(MANIFEST, ROOT) == []
    bad = json.loads(json.dumps(MANIFEST))
    report = next(m for m in bad["end_to_end"] if m["name"] == "report_s")
    report["workloads"] = [CELLS[0][0]]     # store_s moves it in every cell
    found = check_manifest.check(bad, ROOT)
    assert any("which it should move, is not" in line for line in found)
    four = json.loads(json.dumps(MANIFEST))
    for w in four["workloads"]:
        w["chips"] = 4
    assert any("4 chips" in line for line in check_manifest.check(four, ROOT))


@pytest.mark.parametrize("cell,chips", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_on_cpu(cell, chips, trace, monkeypatch):
    out = rehearse(cell, chips, trace, monkeypatch)
    assert set(out) >= LINE_KEYS and out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    want = MANIFEST["per_layer"] if trace else MANIFEST["end_to_end"]
    names = {m["name"] for m in want if run.reports(m, cell)}
    if trace:
        # the recorded trace holds one chip: the kernel roofline needs a job
        # whose scoring lies inside that capture, which this run cannot have;
        # the readers of the program's own device spans (``device_scope``,
        # ``device_busy``) find none after a CPU capture, which has no
        # ``/device:TPU`` plane: test_device_span_layers.py holds them
        names -= {"score_roofline_pct", "lease_device_busy_pct",
                  "extract_device_s", "chaos_device_s", "moments_device_s",
                  "chaos_roofline_pct"}
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert names <= set(out["metrics"])
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_control_low_precision_is_not_correct(monkeypatch):
    out = rehearse(CELLS[0][0], 1, False, monkeypatch, overrides={
        "sm_config": {"parallel": {"cube_dtype": "bf16"}}})
    assert out["correct"] is False


def test_broken_timed_path_is_not_correct(monkeypatch):
    def alter_answers(work, sample):
        import pandas as pd

        path = work / "answers" / sample[-1]["msg_id"] / "all_metrics.parquet"
        df = pd.read_parquet(path)
        df.loc[df.index % 3 == 0, "spatial"] += 1e-3
        df.to_parquet(path)

    out = rehearse(CELLS[1][0], 1, False, monkeypatch,
                   before_check=alter_answers)
    assert out["correct"] is False


def test_cpu_run_through_the_command_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cache = ROOT / ".cache" / "bench" / "datasets"
    had = set(cache.glob("*"))
    try:
        proc = subprocess.run(
            [sys.executable, str(BENCH / "run.py"), "--workload",
             CELLS[0][0], "--seed", "5", "--seconds", "2", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    finally:
        for made in set(cache.glob("*")) - had:   # full-size sections
            shutil.rmtree(made, ignore_errors=True)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "platform 'cpu'" in proc.stderr


def test_bare_directory_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0][0],
         "--seed", "5", "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_trace_reduce_counts_nothing_twice():
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.0, 4.0, "jit_a(1)")]),
        ("XLA Ops", [(0.0, 3.0, "while"), (0.5, 1.5, "fusion.1"),
                     (2.0, 2.5, "fusion.1"), (3.5, 4.0, "copy")]),
        ("Steps", [(0.0, 4.0, "0")])]),
        ("/host:CPU", [("python", [(0.0, 9.0, "x")])])]
    out = trace_reduce.reduce_planes(planes)
    chip = out["chips"][0]
    assert chip["busy_s"] == pytest.approx(3.5)
    assert chip["gaps"] == [(3.0, 3.5)]
    assert dict(out["ops"]) == pytest.approx(
        {"while": 1.5, "fusion.1": 1.5, "copy": 0.5})
    assert out["modules"] == [{"chip": 0, "name": "jit_a(1)",
                               "start_s": 0.0, "dur_s": 4.0}]
