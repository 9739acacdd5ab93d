"""The whole-slide section as a deployment (ISSUE 30), at 16x16 px: ONE
in-process service with ``benchmarks/configs/maldi-slide-256.json``'s own
``sm_config`` and ``ds_config``.  One job makes the section resident; three
resubmits under the same ``ds_id`` (upstream's reprocess, the cell's
traffic) hit the residency cache.  Every stored report is compared with the
benchmark's plain reference (``benchmarks/oracle.py``, numpy/scipy, nothing
of the program) and is bit-identical to the first.  The same jobs' traces
and ``/metrics`` hold what this deployment added to the tracing: the kernel
geometry on ``backend_build`` (hit and miss) and ``sm_chaos_images_total``.
"""

from __future__ import annotations

import json
import shutil
import sys
import urllib.request
from pathlib import Path

import pandas as pd
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))

import datasets  # noqa: E402  (benchmarks/)
import jobtrace  # noqa: E402
import oracle  # noqa: E402
from serve import metric_sum  # noqa: E402  (benchmarks/serve.py)
from scripts.load_sweep import Harness  # noqa: E402

CONFIGS = REPO / "benchmarks" / "configs"
SLIDE = json.loads((CONFIGS / "maldi-slide-256.json").read_text())
SECTION = json.loads((CONFIGS / "maldi-section-128.json").read_text())
SMALL = json.loads(json.dumps(SLIDE))
SMALL["dataset"].update(nrows=16, ncols=16, n_formulas=20, noise_peaks=60)
SMALL["guarantees"]["oracle_sample_ions"] = 200
N_IONS = 20 * (1 + SLIDE["guarantees"]["decoys_per_target"])
RESUBMITS = 3


def test_the_file_is_the_128_section_but_for_its_pixels():
    """Every key outside the ones ISSUE 30 names equals
    ``maldi-section-128.json``'s: the two cells differ in pixels alone."""
    texts = {"name", "deployment", "source", "assumed"}
    assert set(SLIDE) == set(SECTION)
    for key in set(SLIDE) - texts - {"dataset"}:
        assert SLIDE[key] == SECTION[key], key
    for key in texts:
        assert SLIDE[key] != SECTION[key], key
    # n_formulas is in this set only if the sizing rule of ISSUE 30 chose
    # 250; then assumed.formulas carries the reading that forced it
    resized = {"nrows", "ncols"} | (
        {"n_formulas"} if SLIDE["dataset"]["n_formulas"] != 500 else set())
    assert set(SLIDE["dataset"]) == set(SECTION["dataset"])
    for key in set(SLIDE["dataset"]) - resized:
        assert SLIDE["dataset"][key] == SECTION["dataset"][key], key
    assert (SLIDE["dataset"]["nrows"], SLIDE["dataset"]["ncols"]) == (256, 256)
    assert SLIDE["dataset"]["n_formulas"] in (500, 250)
    if SLIDE["dataset"]["n_formulas"] == 250:
        assert "report_s" in SLIDE["assumed"]["formulas"]
    assert SLIDE["chips"] == 1 and len(SLIDE["source"]) <= 200
    assert SLIDE["reduced"] == ["formulas", "target_adducts"]   # not pixels
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"] if c["name"] == SLIDE["name"]]
    assert entry["source"] == SLIDE["source"]
    assert entry["reduced"] == SLIDE["reduced"]
    cell, = [w for w in manifest["workloads"] if w["config"] == SLIDE["name"]]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "slide256-reannotate", "reannotate", 1)


@pytest.fixture(scope="module")
def section(tmp_path_factory):
    return datasets.generate(tmp_path_factory.mktemp("slide"),
                             SMALL["dataset"], 3000)


def test_resident_reannotation_serves_the_reference_answer(tmp_path, section):
    sm = json.loads(json.dumps(SMALL["sm_config"]))
    sm["parallel"]["formula_batch"] = 256
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1})
    ids = [f"slide-{i}" for i in range(1 + RESUBMITS)]
    h = Harness(tmp_path, "slide", sm_overrides=sm)
    results, kept = tmp_path / "slide" / "results", tmp_path / "answers"
    try:
        scrapes = [h.metrics_text()]
        traces = {}
        for msg_id in ids:
            status, _hd, body = h.submit({
                "ds_id": "slide-ds", "msg_id": msg_id,
                "input_path": section["path"],
                "formulas": section["formulas"],
                "ds_config": SMALL["ds_config"]})
            assert status == 202, body
            row = h.wait_terminal([msg_id], timeout_s=300.0)[msg_id]
            assert (row["state"], row["attempts"]) == ("done", 1), row
            # a reprocess overwrites results/<ds_id>: keep each answer
            shutil.copytree(results / "slide-ds", kept / msg_id)
            scrapes.append(h.metrics_text())
            with urllib.request.urlopen(
                    f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
                traces[msg_id] = json.loads(r.read())["records"]
    finally:
        h.shutdown()

    # one job made the section resident, the three resubmits hit
    def dataset_hits(text):
        return metric_sum(text, "sm_residency_hits_total",
                          'cache="dataset"') or 0

    assert dataset_hits(scrapes[1]) == 0
    assert dataset_hits(scrapes[-1]) - dataset_hits(scrapes[1]) == RESUBMITS

    # backend_build says which kernel geometry the backend runs, on a cache
    # hit as on a miss: at 16x16 on this CPU the scan route, no Pallas block
    for i, msg_id in enumerate(ids):
        build, = jobtrace.spans(traces[msg_id], "backend_build")
        attrs = build["attrs"]
        assert attrs["cache_hit"] is (i > 0), (msg_id, attrs)
        assert {k: attrs[k] for k in (
            "pixels", "rows_bucket", "chaos_route", "chaos_block",
            "chaos_lane_fill_pct")} == {
            "pixels": 256, "rows_bucket": 16, "chaos_route": "scan",
            "chaos_block": [16, 16, 0], "chaos_lane_fill_pct": 100.0}
        # the guard's figure: (P + 1) rows x (2 x batch x K + 1) f32 columns
        assert attrs["hist_scratch_bytes"] == 4 * 257 * max(
            2 * 256 * 4 + 1, 4098)
        backends = {s["attrs"]["backend"]
                    for s in jobtrace.spans(traces[msg_id], "score_batch")}
        assert backends == {"jax_tpu"}

    # the counter rises by the job's ions a job, under the route's labels
    # (it is the process's: an earlier test's backends may have counted)
    label = 'images_per_program="0",route="scan"'   # as exposed: sorted
    counts = [metric_sum(s, "sm_chaos_images_total", label) or 0
              for s in scrapes]
    assert [b - a for a, b in zip(counts, counts[1:])] == \
        [N_IONS] * len(ids), counts

    # every report against the plain reference, by the cell's own limits,
    # and bit-identical to the first job's
    lim, cache, said = oracle.limits(SMALL["guarantees"]), {}, []
    for msg_id in ids:
        nums = oracle.compare_job(kept, msg_id, section, SMALL, 30, cache)
        assert oracle.decide(nums, lim, said.append), (msg_id, said)
        for table in ("all_metrics.parquet", "annotations.parquet"):
            got = pd.read_parquet(kept / msg_id / table)
            want = pd.read_parquet(kept / ids[0] / table)
            drop = [c for c in ("ds_id", "job_id") if c in got.columns]
            pd.testing.assert_frame_equal(
                got.drop(columns=drop), want.drop(columns=drop),
                check_exact=True, obj=f"{msg_id} vs {ids[0]}: {table}")


def test_chaos_image_counter_loses_no_update_between_workers():
    """Scheduler workers enqueue batches at once: 16 threads on 8 cores,
    the interpreter switching every microsecond, each counting 2,000
    batches of 3 ions through one geometry."""
    import threading

    from sm_distributed_tpu.models import msm_jax
    from sm_distributed_tpu.ops.chaos_pallas import chaos_geometry

    geo = chaos_geometry(256, 256)
    key = (geo.route, geo.images_per_program)
    before = msm_jax.chaos_image_events().get(key, 0)
    workers = [threading.Thread(target=lambda: [
        msm_jax._count_chaos_images(geo, 3) for _ in range(2000)])
        for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert msm_jax.chaos_image_events()[key] - before == 16 * 2000 * 3
