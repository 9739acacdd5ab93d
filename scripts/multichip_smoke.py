#!/usr/bin/env python
"""Multichip smoke gate (ISSUE 7 satellite; wired into scripts/check_tier1.sh).

Proves the device-pool + pjit-sharded scale-out shape end to end on a
virtual 8-chip CPU mesh, through the REAL service stack (spool, scheduler,
admission, SearchJob, tracing):

1. a ``devices: 8`` submit claims the whole pool as one contiguous
   sub-mesh and scores through the GSPMD-sharded pixels×formulas path —
   its STORED annotations are oracle-checked against an in-process
   ``numpy_ref`` search of the same dataset/formulas (same FDR seed; msm
   to 1e-6, the documented sharded parity contract);
2. two 1-chip submits run concurrently: their traces must show device
   holds on DISTINCT chips with OVERLAPPING hold windows — the
   single-token serialization the pool replaced is provably gone;
3. the pool drains clean (no held chips, no waiters) and /metrics +
   /debug/timeseries expose per-chip in-use and the pool-wide ratio.

Exit 0 = gate passes.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

# the virtual 8-chip mesh must exist BEFORE jax initializes (scripts run
# outside tests/conftest.py, which does this same dance for pytest)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from scripts.load_sweep import Harness, _msg, build_fixtures  # noqa: E402
from sm_distributed_tpu.utils import failpoints  # noqa: E402

N_DEVICES = 8


def fail(msg: str) -> int:
    print(f"multichip_smoke: FAIL — {msg}", file=sys.stderr)
    return 1


def _get(h: Harness, path: str):
    with urllib.request.urlopen(h.base + path, timeout=30.0) as r:
        return json.loads(r.read())


def _trace_records(h: Harness, msg_id: str) -> list[dict]:
    return _get(h, f"/jobs/{msg_id}/trace?raw=1")["records"]


def _hold_window(records: list[dict], msg_id: str):
    """(devices, t_acquired, t_release) from a job's trace: the acquired
    event marks the grant; the device_hold span's end marks the release."""
    acq = [r for r in records
           if r["kind"] == "event" and r["name"] == "device_token_acquired"]
    hold = [r for r in records
            if r["kind"] == "span" and r["name"] == "device_hold"]
    if not acq or not hold:
        raise AssertionError(
            f"{msg_id}: trace lacks device hold evidence "
            f"(acquired={len(acq)}, hold={len(hold)})")
    devices = (acq[-1].get("attrs") or {}).get("devices")
    h = hold[-1]
    return devices, float(acq[-1]["ts"]), float(h["ts"]) + float(h["dur"])


def _numpy_oracle(h: Harness, fx: dict):
    """The same search on the same fixture, scored by the numpy_ref
    backend in-process — the golden annotations the sharded job must
    reproduce."""
    import dataclasses

    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.utils.config import DSConfig

    sm_np = dataclasses.replace(h.sm_config, backend="numpy_ref")
    ds = SpectralDataset.from_imzml(fx["fast"]["input_path"])
    search = MSMBasicSearch(
        ds, fx["fast"]["formulas"],
        DSConfig.from_dict(fx["fast"]["ds_config"]), sm_np)
    return search.search().annotations


def run(work: Path) -> int:
    if len(jax.devices()) < N_DEVICES:
        return fail(f"virtual mesh failed: {len(jax.devices())} devices")
    # lock-order detection (ISSUE 9): the multi-chip overlap scenario is
    # the densest lock population in the tree (pool cond + scheduler maps
    # + admission + metrics + telemetry, two jobs on distinct chips) —
    # instrument everything built below and fail on a cycle at the end
    from sm_distributed_tpu.analysis import lockorder

    lockorder.enable()
    fx = build_fixtures(work)
    h = Harness(work, "multichip_smoke", sm_overrides={
        "backend": "jax_tpu",
        "parallel": {"checkpoint_every": 0},
        "service": {"workers": 2, "device_pool_size": N_DEVICES,
                    "devices_per_job": 1},
    })
    try:
        # ---- 1. sub-mesh job over the whole pool, oracle-checked --------
        status, _hd, body = h.submit(
            _msg(fx, "fast", "mesh8", devices=N_DEVICES))
        if status != 202:
            return fail(f"mesh submit returned {status}: {body}")
        rows = h.wait_terminal(["mesh8"])
        if rows["mesh8"]["state"] != "done":
            return fail(f"mesh job state {rows['mesh8']['state']}: "
                        f"{rows['mesh8']['error']!r}")
        records = _trace_records(h, "mesh8")
        devices, _t0, _t1 = _hold_window(records, "mesh8")
        if devices != list(range(N_DEVICES)):
            return fail(f"mesh job lease devices {devices}, wanted all "
                        f"{N_DEVICES} chips")
        sharded_spans = [
            r for r in records if r["kind"] == "span"
            and r["name"] == "score_batch"
            and (r.get("attrs") or {}).get("backend") == "jax_tpu_sharded"]
        if not sharded_spans:
            return fail("mesh job trace has no jax_tpu_sharded score spans "
                        "— it did not take the pjit-sharded path")
        syncs = [r for r in records if r["kind"] == "span"
                 and r["name"] == "device_sync"
                 and (r.get("attrs") or {}).get("devices")]
        if not syncs or sorted(syncs[-1]["attrs"]["devices"]) != \
                list(range(N_DEVICES)):
            return fail(f"device_sync span lacks the {N_DEVICES} sub-mesh "
                        f"chip ids: {[s.get('attrs') for s in syncs][:2]}")

        from sm_distributed_tpu.engine.storage import AnnotationIndex, JobLedger

        stored = AnnotationIndex(
            JobLedger(h.sm_config.storage.results_dir)).search(ds_id="mesh8")
        golden = _numpy_oracle(h, fx)
        if stored.empty or golden.empty:
            return fail(f"no annotations to compare (stored={len(stored)}, "
                        f"golden={len(golden)})")
        g = golden.set_index(["sf", "adduct"]).sort_index()
        s = stored.set_index(["sf", "adduct"]).sort_index()
        if set(g.index) != set(s.index):
            return fail(f"annotation ion sets differ: sharded {set(s.index)}"
                        f" vs oracle {set(g.index)}")
        if not np.allclose(s["msm"].to_numpy(),
                           g.loc[s.index, "msm"].to_numpy(),
                           rtol=0, atol=1e-6):
            return fail("sharded msm scores diverge from the numpy oracle "
                        "beyond the 1e-6 parity contract")
        print(f"multichip_smoke: mesh job OK — {len(stored)} annotations "
              f"oracle-checked over mesh devices {devices}")

        # ---- 2. two 1-chip jobs hold DISTINCT chips CONCURRENTLY --------
        # deterministic overlap: every batch-group score sleeps, so each
        # job's device hold lasts >= the submit skew
        failpoints.configure("device.score_batch=sleep:0.6")
        try:
            for mid in ("one_a", "one_b"):
                status, _hd, body = h.submit(_msg(fx, "fast", mid))
                if status != 202:
                    return fail(f"{mid} submit returned {status}: {body}")
            rows = h.wait_terminal(["one_a", "one_b"])
        finally:
            failpoints.configure(None)
        bad = {m: (rows[m]["state"], rows[m]["error"])
               for m in ("one_a", "one_b") if rows[m]["state"] != "done"}
        if bad:
            return fail(f"1-chip jobs not done: {bad}")
        win = {m: _hold_window(_trace_records(h, m), m)
               for m in ("one_a", "one_b")}
        (dev_a, a0, a1), (dev_b, b0, b1) = win["one_a"], win["one_b"]
        if not dev_a or not dev_b or len(dev_a) != 1 or len(dev_b) != 1:
            return fail(f"1-chip leases wrong: {dev_a} / {dev_b}")
        if set(dev_a) & set(dev_b):
            return fail(f"both jobs granted chip(s) {set(dev_a) & set(dev_b)}"
                        " — the pool failed to pack them")
        if not (a0 < b1 and b0 < a1):
            return fail(f"holds did not overlap: a=[{a0:.3f},{a1:.3f}] "
                        f"b=[{b0:.3f},{b1:.3f}]")
        print(f"multichip_smoke: 1-chip jobs OK — chips {dev_a} and {dev_b} "
              f"held concurrently ({min(a1, b1) - max(a0, b0):.2f}s overlap)")

        # ---- 3. pool drained + occupancy surfaced ------------------------
        pool = h.service.device_pool
        if pool.in_use_count() or pool.waiters():
            return fail(f"pool not drained: {pool.snapshot()}")
        text = h.metrics_text()
        for needle in ("sm_device_pool_in_use", "sm_device_pool_grants_total",
                       "sm_device_pool_wait_seconds"):
            if needle not in text:
                return fail(f"/metrics lacks {needle}")
        h.service.telemetry.sample()     # don't wait for the 5 s cadence
        samples = _get(h, "/debug/timeseries")["samples"]
        if not any("device_pool_ratio" in s for s in samples):
            return fail("/debug/timeseries lacks device_pool_ratio")
        print("multichip_smoke: pool drained; per-chip + pool-wide "
              "occupancy on /metrics and /debug/timeseries")

        # ---- 4. lock-order graph over the whole smoke is acyclic ---------
        rep = lockorder.assert_no_cycles("multichip_smoke")
        print(f"multichip_smoke: lock-order clean "
              f"({rep['locks_instrumented']} locks, {rep['edges']} order "
              f"edges observed)")
        return 0
    finally:
        h.shutdown()
        lockorder.disable()


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--work", default=None,
                    help="working dir (default: a fresh tempdir)")
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args()
    if args.work:
        work = Path(args.work)
        work.mkdir(parents=True, exist_ok=True)
        return run(work)
    with tempfile.TemporaryDirectory(prefix="sm_multichip_smoke_") as d:
        rc = run(Path(d))
        if args.keep:
            print(f"multichip_smoke: work dir kept at {d}", file=sys.stderr)
        return rc


if __name__ == "__main__":
    sys.exit(main())
