"""The four-chip host as a deployment (ISSUE 26), at 8x8 px on four forced
host devices: ONE in-process service with ``benchmarks/configs/
maldi-section-64-pool4.json``'s own ``sm_config`` (device pool 4, workers 8,
one chip a job), 8 submits over 4 distinct sections.  The answer may not
depend on the chip a job landed on: every stored report is compared with the
benchmark's plain reference (``benchmarks/oracle.py``, numpy/scipy, nothing
of the program) and is bit-identical to the same section's report from a
pool of ONE chip.  The same jobs' traces and ``/metrics`` hold what the
benchmark's three pool readers read: span ``pre_lease``, the held-seconds
counter and the wait histogram.
"""

from __future__ import annotations

import json
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
from layers import pool_chips_held_mean, pool_wait_s, pre_lease_s  # noqa: E402
from serve import metric_sum  # noqa: E402  (benchmarks/serve.py)
from scripts.load_sweep import Harness  # noqa: E402
from sm_distributed_tpu.utils import failpoints  # noqa: E402

CONFIG = json.loads(
    (REPO / "benchmarks/configs/maldi-section-64-pool4.json").read_text())
CONFIG["dataset"].update(nrows=8, ncols=8, n_formulas=20, noise_peaks=60)
CONFIG["guarantees"]["oracle_sample_ions"] = 200
N_SECTIONS, N_JOBS = 4, 8


def _serve(base: Path, name: str, sections, ids, **service):
    """One service over ``sections``, ``ids`` submitted at once; returns the
    Harness (still up), the terminal rows, every job's raw trace and the
    ``/metrics`` text from before the first submit."""
    sm = json.loads(json.dumps(CONFIG["sm_config"]))
    sm["parallel"]["formula_batch"] = 256
    sm["storage"] = {"store_images": True}
    sm["service"].update({"job_timeout_s": 300.0, "max_attempts": 1,
                          "admission": {"max_queue_depth": 64,
                                        "max_tenant_inflight": 64},
                          **service})
    h = Harness(base, name, sm_overrides=sm)
    try:
        before = h.metrics_text()
        for i, msg_id in enumerate(ids):
            ds = sections[i % len(sections)]
            status, _hd, body = h.submit({
                "ds_id": msg_id, "msg_id": msg_id, "input_path": ds["path"],
                "formulas": ds["formulas"], "ds_config": CONFIG["ds_config"]})
            assert status == 202, body
        rows = h.wait_terminal(ids, timeout_s=300.0)
        traces = {}
        for msg_id in ids:
            with urllib.request.urlopen(
                    f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
                traces[msg_id] = json.loads(r.read())["records"]
    except BaseException:
        h.shutdown()
        raise
    return h, rows, traces, before


def _span(records, name):
    (s,) = jobtrace.spans(records, name)
    return s


@pytest.fixture(scope="module")
def sections(tmp_path_factory):
    cache = tmp_path_factory.mktemp("sections")
    return [datasets.generate(cache, CONFIG["dataset"], 2600 + i)
            for i in range(N_SECTIONS)]


def test_pool_of_four_serves_the_reference_answer_on_every_chip(
        tmp_path, sections):
    assert CONFIG["sm_config"]["service"] == {
        "workers": 8, "device_pool_size": 4, "devices_per_job": 1}
    assert "profile_seconds" not in CONFIG
    ids = [f"p4-{i}" for i in range(N_JOBS)]
    # every scoring batch sleeps, so a hold outlasts the other jobs' parse
    # and the pool has to place four jobs at once
    failpoints.configure("device.score_batch=sleep:0.3")
    try:
        h, rows, traces, before = _serve(tmp_path, "pool4", sections, ids)
    finally:
        failpoints.configure(None)
    try:
        after = h.metrics_text()
        assert h.service.device_pool.in_use_count() == 0
    finally:
        h.shutdown()
    for msg_id in ids:
        assert rows[msg_id]["state"] == "done", rows[msg_id]
        assert rows[msg_id]["attempts"] == 1

    # the four chips were all leased, one to a job
    leased = {m: jobtrace.lease_devices(traces[m]) for m in ids}
    assert all(len(d) == 1 for d in leased.values()), leased
    assert {d[0] for d in leased.values()} == {0, 1, 2, 3}, leased

    # pre_lease: opens inside the attempt, closes where device_hold opens,
    # the host-only phases are its children, workers_busy counts this
    # process's attempts in flight when it opened
    busy = []
    for msg_id in ids:
        rec = traces[msg_id]
        attempt, pre, hold = (_span(rec, n) for n in
                              ("attempt", "pre_lease", "device_hold"))
        assert pre["parent_id"] == attempt["span_id"] == hold["parent_id"]
        # (bounds with room for eight threads under one GIL on shared cores)
        assert attempt["ts"] <= pre["ts"] <= attempt["ts"] + 5.0
        assert pre["ts"] + pre["dur"] <= hold["ts"] + 0.05
        assert hold["ts"] - (pre["ts"] + pre["dur"]) < 1.0
        for phase in ("stage_input", "read_dataset"):
            child = _span(rec, phase)
            assert child["parent_id"] == pre["span_id"]
            assert pre["ts"] <= child["ts"]
            assert child["ts"] + child["dur"] <= pre["ts"] + pre["dur"] + 1e-3
        for phase in ("score", "fdr", "store_results"):
            assert _span(rec, phase)["parent_id"] != pre["span_id"]
        busy.append(pre["attrs"]["workers_busy"])
    assert all(1 <= b <= N_JOBS for b in busy) and max(busy) >= 4, busy

    # what the three readers read, against the same jobs' traces
    run = {"jobs": [{"trace": traces[m]} for m in ids],
           "metrics_before": before, "metrics_after": after}
    holds = []
    for msg_id in ids:
        hold = _span(traces[msg_id], "device_hold")
        granted = jobtrace.event_ts(traces[msg_id], "device_token_acquired")
        holds.append((hold["ts"] + hold["dur"] - granted,
                      granted - hold["ts"]))
    assert pre_lease_s.read(run) == pytest.approx(pd.Series(
        [_span(traces[m], "pre_lease")["dur"] for m in ids]).median())
    # the histogram times first acquire -> grant, the trace device_hold's
    # start -> the acquired event: a few statements apart on each side
    assert pool_wait_s.read(run) == pytest.approx(
        sum(w for _h, w in holds) / N_JOBS, abs=0.25)
    assert metric_sum(after, "sm_device_pool_wait_seconds_count") == N_JOBS
    held = metric_sum(after, "sm_device_pool_held_seconds_total")
    assert held == pytest.approx(sum(h_ for h_, _w in holds), rel=0.02,
                                 abs=0.5)
    clock = metric_sum(after, "sm_device_pool_clock_seconds_total") \
        - metric_sum(before, "sm_device_pool_clock_seconds_total")
    assert metric_sum(before, "sm_device_pool_held_seconds_total") == 0
    assert pool_chips_held_mean.read(run) == pytest.approx(held / clock)
    assert 0.0 < held / clock <= 4.0

    # every report against the plain reference, by the cell's own limits
    results = tmp_path / "pool4" / "results"
    lim, cache = oracle.limits(CONFIG["guarantees"]), {}
    said: list[str] = []
    for i, msg_id in enumerate(ids):
        nums = oracle.compare_job(results, msg_id, sections[i % N_SECTIONS],
                                  CONFIG, 26, cache)
        assert oracle.decide(nums, lim, said.append), (msg_id, leased, said)

    # and bit-identical to the same section's report from a pool of ONE
    # (and so to the other chip's report of the same section)
    ones = [f"p1-{i}" for i in range(N_SECTIONS)]
    h1, rows1, traces1, _before = _serve(tmp_path, "pool1", sections, ones,
                                workers=2, device_pool_size=1)
    h1.shutdown()
    assert {tuple(jobtrace.lease_devices(t)) for t in traces1.values()} == \
        {(0,)}
    for i, msg_id in enumerate(ids):
        one = ones[i % N_SECTIONS]
        assert rows1[one]["state"] == "done", rows1[one]
        for table in ("all_metrics.parquet", "annotations.parquet"):
            got = pd.read_parquet(results / msg_id / table)
            want = pd.read_parquet(tmp_path / "pool1" / "results" / one / table)
            drop = [c for c in ("ds_id", "job_id") if c in got.columns]
            pd.testing.assert_frame_equal(
                got.drop(columns=drop), want.drop(columns=drop),
                check_exact=True, obj=f"{msg_id} on chip {leased[msg_id]} "
                                      f"vs {one}: {table}")
