"""The six per-layer readers PR 24 added, each on a hand-made ``run`` (what
``run.py::run_cell`` hands a reader: the in-window jobs with their raw trace
records).  ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``.

A reader returns None where the program appended no such span (the parent
of PR 24, an untraced run, a CPU capture), and the median over the jobs
whose lease hold lay wholly inside the capture where it did."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, dur=0.0, **attrs):
    return {"kind": "span", "name": name, "ts": 100.0, "dur": dur,
            "attrs": attrs}


def job(build, extract, write, device=None):
    """One job's trace: host spans always; ``device`` = (whole, busy_s,
    hold_s, {scope: device_s}) when a capture overlapped its hold."""
    rec = [span("backend_build", build, cache_hit=False),
           span("build_sort", build / 2),
           span("store_extract_images", extract, ions=3, bytes=12),
           span("store_write_images", write, format="npz", bytes=12),
           {"kind": "event", "name": "device_token_acquired", "ts": 100.0}]
    if device:
        whole, busy, hold, scopes = device
        rec.append(span("device_busy", hold, chip=0, busy_s=busy,
                        hold_s=hold, whole=whole))
        rec += [span("device_scope", 0.1, scope=s, chip=0, device_s=v,
                     n_ops=4, whole=whole) for s, v in scopes.items()]
        rec.append(span("device_idle", 1.0, chip=0, host="build_sort",
                        host_span_id="x"))
    return {"trace": rec}


SCOPES_A = {"sm_extract": 0.10, "sm_store_extract": 0.06, "sm_chaos": 0.12,
            "sm_moments": 0.02, "sm_epilogue": 0.01, "unscoped": 0.005}
SCOPES_B = {"sm_extract": 0.20, "sm_chaos": 0.10, "sm_fused": 0.04}
SCOPES_CUT = {"sm_extract": 9.0, "sm_chaos": 9.0, "sm_moments": 9.0}

HOST_ONLY = {"jobs": [job(2.0, 0.2, 1.0), job(3.0, 0.4, 1.2),
                      job(2.5, 0.3, 1.1), {"trace": None}]}
WITH_DEVICE = {"jobs": [
    job(2.0, 0.2, 1.0, (True, 0.30, 5.0, SCOPES_A)),
    job(3.0, 0.4, 1.2, (True, 0.34, 4.0, SCOPES_B)),
    job(2.5, 0.3, 1.1, (False, 9.0, 1.0, SCOPES_CUT)),   # cut by the edge
    job(2.6, 0.3, 1.1)]}                                  # outside the capture


@pytest.mark.parametrize("name,host_only,with_device", [
    ("backend_build_s", 2.5, 2.55),
    ("store_images_s", 1.4, 1.4),
    ("lease_device_busy_pct", None, (6.0 + 8.5) / 2),
    ("extract_device_s", None, (0.16 + 0.20) / 2),
    ("chaos_device_s", None, (0.12 + 0.10) / 2),
    ("moments_device_s", None, (0.03 + 0.04) / 2),
])
def test_reader(name, host_only, with_device):
    read = reader(name)
    assert read({"jobs": []}) is None
    assert read({"jobs": [{"trace": [span("score", 1.0)]}]}) is None
    got = read(HOST_ONLY)
    assert got is None if host_only is None else got == pytest.approx(host_only)
    assert read(WITH_DEVICE) == pytest.approx(with_device)


def test_every_new_metric_has_its_manifest_entry():
    import json

    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = ["backend_build_s", "store_images_s", "lease_device_busy_pct",
             "extract_device_s", "chaos_device_s", "moments_device_s"]
    order = [n for n in by_name if n in names]      # later metrics append
    assert order == names
    new = {n: by_name[n] for n in names}
    assert new["backend_build_s"]["workloads"] == ["section64-uploads"]
    assert all(m["source"] == "device_trace" for n, m in new.items()
               if "device" in n)
