#!/usr/bin/env python
"""Host-loss survival gate (ISSUE 17; wired into scripts/check_tier1.sh).

Proves the POD layer end to end on one box pretending to be a 2-host pod:
the in-process service is host ``h0`` (process 0) and a second REAL
scheduler process — spawned through scripts/replica_chaos.py
``--replica-serve --bare`` with ``SM_PROCESS_ID=1`` / ``SM_HOST_NAME=h1``
— is host ``h1``.  Both heartbeat the shared replica registry; the device
pool's two host domains map process ``i`` ↔ domain ``i``.

1. **golden**: a full-pool submit scores through the pjit-sharded mesh
   spanning both host domains fault-free — the golden report;
2. **host death mid-job**: a second full-pool job is slowed per scoring
   group, and once it holds its cross-host lease, host h1's process is
   SIGKILLed.  The host watchdog sees every process-1 registry beat go
   stale, evicts the whole host domain (``HealthTracker.evict_host`` —
   chips quarantined in one unit), and cancels the in-flight attempt
   (reason kind ``host_evicted``) into the normal retry path: the job
   resumes from its group checkpoint on the SHRUNKEN surviving-host mesh
   and its stored annotations are **bit-identical** to the full-pod
   golden.  Exactly-once spool census, no debris, bounded detection
   latency, and ``sm_pod_*`` metrics are asserted;
3. **host return**: the process is restarted; fresh process-1 beats make
   the watchdog readmit the host (re-probe cooldown zeroed — half-open),
   and the next full-pool submit holds chips on BOTH hosts again.

``--smoke`` runs the same stages on a 4-chip pool (2 chips/host); the
full gate uses 8 chips (4/host).  Exit 0 = gate passes.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

# the virtual 8-chip mesh must exist BEFORE jax initializes (same dance as
# device_chaos.py / tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

import jax  # noqa: E402
import pandas as pd  # noqa: E402

from scripts.chaos_sweep import _debris  # noqa: E402
from scripts.load_sweep import Harness, _msg, build_fixtures  # noqa: E402
from sm_distributed_tpu.service.leases import (  # noqa: E402
    owned_shards,
    shard_of,
)
from sm_distributed_tpu.utils import failpoints  # noqa: E402

HOSTS = 2
SHARDS = 8
SELF_RID = "r0"            # the in-process service (host h0, process 0)
CHILD_RID = "r1"           # the victim scheduler process (host h1, process 1)
CHILD_HOST = "h1"


def fail(msg: str) -> int:
    print(f"host_chaos: FAIL — {msg}", file=sys.stderr)
    return 1


def _get(h: Harness, path: str):
    with urllib.request.urlopen(h.base + path, timeout=30.0) as r:
        return json.loads(r.read())


def _trace_records(h: Harness, msg_id: str) -> list[dict]:
    return _get(h, f"/jobs/{msg_id}/trace?raw=1")["records"]


def _stored(h: Harness, ds_id: str) -> pd.DataFrame:
    p = Path(h.sm_config.storage.results_dir) / ds_id / "annotations.parquet"
    return pd.read_parquet(p).sort_values(
        ["sf", "adduct"]).reset_index(drop=True)


def _leases(records: list[dict]) -> list[tuple[float, list[int]]]:
    return [(float(r["ts"]), list((r.get("attrs") or {}).get("devices", [])))
            for r in records
            if r["kind"] == "event"
            and r["name"] == "device_token_acquired"]


def _pick_id(base: str, owned: set[int]) -> str:
    """A msg id in the SELF replica's shard partition — the bare victim
    must never claim (and null-complete) the real jobs."""
    for i in range(1000):
        cand = f"{base}{i}" if i else base
        if shard_of(cand, SHARDS) in owned:
            return cand
    raise RuntimeError(f"no shard-local id for {base!r}")


def _spawn_child(work: Path, sm_conf: Path, queue_dir: Path,
                 tag: str) -> subprocess.Popen:
    """Host h1: a real bare scheduler process sharing the spool + registry,
    identified as pod process 1 via the launcher env contract."""
    env = dict(os.environ)
    env.pop("SM_FAILPOINTS", None)
    env["SM_PROCESS_ID"] = "1"
    env["SM_HOST_NAME"] = CHILD_HOST
    log = work / "logs" / f"{CHILD_RID}.{tag}.log"
    log.parent.mkdir(parents=True, exist_ok=True)
    cmd = [sys.executable, str(REPO_ROOT / "scripts" / "replica_chaos.py"),
           "--replica-serve", str(queue_dir), str(sm_conf),
           "--replica-id", CHILD_RID, "--bare", "--null-sleep", "0.05",
           "--idle-exit", "600"]
    return subprocess.Popen(cmd, env=env, stdout=open(log, "w"),
                            stderr=subprocess.STDOUT, cwd=str(REPO_ROOT))


def _wait_child_alive(h: Harness, deadline_s: float = 30.0) -> bool:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            peers = _get(h, "/peers")["replicas"]
        except OSError:
            peers = []
        for p in peers:
            if p.get("replica_id") == CHILD_RID and p.get("alive") \
                    and p.get("process_id") == 1:
                return True
        time.sleep(0.1)
    return False


def _metric(text: str, prefix: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(prefix):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except (IndexError, ValueError):
                pass
    return total


def run(work: Path, smoke: bool) -> int:
    pool = 4 if smoke else 8
    per_host = pool // HOSTS
    survivors = list(range(per_host))
    evict_chips = list(range(per_host, pool))
    if len(jax.devices()) < pool:
        return fail(f"virtual mesh failed: {len(jax.devices())} devices")
    from sm_distributed_tpu.analysis import lockorder

    lockorder.enable()
    fx = build_fixtures(work)
    h = Harness(work, "host_chaos", sm_overrides={
        "backend": "jax_tpu",
        "parallel": {"formula_batch": 2, "checkpoint_every": 1},
        "service": {"workers": 1, "max_attempts": 3,
                    "device_pool_size": pool, "devices_per_job": pool,
                    "device_pool_hosts": HOSTS,
                    # LONG half-open cooldown: only the watchdog's
                    # host-return path (cooldown zeroed) can readmit
                    # within this gate's runtime
                    "health_reprobe_after_s": 60.0,
                    "backoff_base_s": 0.05, "backoff_max_s": 0.2,
                    "replicas": 2, "spool_shards": SHARDS,
                    "replica_heartbeat_interval_s": 0.2,
                    "replica_stale_after_s": 1.0,
                    "takeover_interval_s": 0.3,
                    "host_watchdog_interval_s": 0.2,
                    "host_stale_after_s": 1.0},
    })
    health = h.service.device_pool.health
    # the victim's own config: numpy_ref + tiny pool (its scheduler never
    # scores anything — the published jobs live in SELF's shards)
    child_sm = {
        "backend": "numpy_ref",
        "work_dir": str(work / "child_work"),
        "storage": {"results_dir": str(work / "child_results"),
                    "store_images": False},
        "service": {"workers": 1, "poll_interval_s": 0.05,
                    "device_pool_size": 2, "quarantine_after": 20,
                    "replicas": 2, "spool_shards": SHARDS,
                    "replica_heartbeat_interval_s": 0.2,
                    "replica_stale_after_s": 1.0,
                    "takeover_interval_s": 0.3},
    }
    sm_conf = work / "child_sm.json"
    sm_conf.write_text(json.dumps(child_sm, indent=2))
    owned = owned_shards(SELF_RID, {SELF_RID, CHILD_RID}, SHARDS)
    ids = {k: _pick_id(k, owned) for k in ("golden", "fault", "after")}
    child = _spawn_child(work, sm_conf, h.queue_dir, "a")
    try:
        if not _wait_child_alive(h):
            return fail(f"host {CHILD_HOST} (process 1) never appeared "
                        "alive on /peers")
        print(f"host_chaos: 2-host pod up — process 0 (self) + process 1 "
              f"({CHILD_HOST}, pid {child.pid}); pool {pool} chips, "
              f"{per_host}/host")

        # ---- 1. fault-free full-pod golden ------------------------------
        status, _hd, _b = h.submit(_msg(fx, "fast", ids["golden"],
                                        devices=pool))
        if status != 202:
            return fail(f"golden submit returned {status}")
        rows = h.wait_terminal([ids["golden"]])
        if rows[ids["golden"]]["state"] != "done":
            return fail(f"golden job {rows[ids['golden']]['state']}: "
                        f"{rows[ids['golden']]['error']!r}")
        golden = _stored(h, ids["golden"])
        g_leases = _leases(_trace_records(h, ids["golden"]))
        if not g_leases or g_leases[-1][1] != list(range(pool)):
            return fail(f"golden lease {g_leases}, wanted all {pool} chips")
        print(f"host_chaos: golden {pool}-chip cross-host job OK "
              f"({len(golden)} annotations)")

        # ---- 2. SIGKILL host h1 mid-sharded-job -------------------------
        # each scoring group sleeps so the kill + staleness horizon +
        # watchdog pass all land while the job still runs; the cancel
        # unwinds it at a cooperative checkpoint and the retry re-leases
        # the surviving host's chips
        failpoints.configure("device.score_batch=sleep:0.8")
        t_submit = time.time()
        try:
            status, _hd, _b = h.submit(_msg(fx, "fast", ids["fault"],
                                            devices=pool))
            if status != 202:
                return fail(f"fault submit returned {status}")
            deadline = time.time() + 60.0
            granted = False
            while time.time() < deadline and not granted:
                try:
                    granted = any(devs == list(range(pool)) for _ts, devs
                                  in _leases(_trace_records(h, ids["fault"])))
                except (OSError, ValueError, KeyError):
                    granted = False
                if not granted:
                    time.sleep(0.05)
            if not granted:
                return fail("fault job never acquired the full-pod lease")
            child.send_signal(signal.SIGKILL)     # host h1 dies mid-job
            t_kill = time.time()
            deadline = time.time() + 15.0
            while time.time() < deadline and \
                    health.snapshot()["host_evictions_total"] < 1:
                time.sleep(0.05)
            detect_s = time.time() - t_kill
            if health.snapshot()["host_evictions_total"] < 1:
                return fail("watchdog never evicted the dead host")
            if detect_s > 5.0:
                return fail(f"host eviction took {detect_s:.1f}s — "
                            "unbounded detection latency")
            rows = h.wait_terminal([ids["fault"]])
        finally:
            failpoints.configure(None)
        convergence_s = time.time() - t_submit
        if rows[ids["fault"]]["state"] != "done":
            return fail(f"fault job {rows[ids['fault']]['state']}: "
                        f"{rows[ids['fault']]['error']!r}")
        if rows[ids["fault"]]["attempts"] < 2:
            return fail("fault job finished in one attempt — the host "
                        "death never interrupted it")
        if convergence_s > 90.0:
            return fail(f"fault job took {convergence_s:.1f}s — "
                        "unbounded convergence")

        # exactly-once completion: one done/ copy, no other spool state
        spool = {s: sorted(p.name for p in (h.root / s).glob(
            f"{ids['fault']}.json"))
            for s in ("pending", "running", "done", "failed", "quarantine")}
        if spool["done"] != [f"{ids['fault']}.json"] or any(
                v for k, v in spool.items() if k != "done"):
            return fail(f"fault spool message lost/duplicated: {spool}")

        # bit-identical convergence on the surviving host's mesh
        got = _stored(h, ids["fault"])
        try:
            pd.testing.assert_frame_equal(got, golden, check_exact=True)
        except AssertionError as exc:
            return fail(f"{per_host}-chip rescore diverged from the "
                        f"{pool}-chip golden: " + str(exc).splitlines()[-1])

        # the whole domain went in one unit; later leases never touch it
        snap = health.snapshot()
        bad = [c["device"] for c in snap["chips"]
               if c["state"] != "quarantined" and c["device"] in evict_chips]
        if bad:
            return fail(f"evicted host's chips not quarantined: {bad}")
        records = _trace_records(h, ids["fault"])
        cancel_ts = [float(r["ts"]) for r in records if r["kind"] == "event"
                     and r["name"] == "cancel"
                     and (r.get("attrs") or {}).get("kind") == "host_evicted"]
        if not cancel_ts:
            return fail("no host_evicted cancel event in the fault trace")
        leases = _leases(records)
        after_evict = [devs for ts, devs in leases if ts > min(cancel_ts)]
        if not after_evict or after_evict[-1] != survivors:
            return fail(f"retry lease after host eviction was "
                        f"{after_evict}, wanted survivors {survivors}")
        if any(set(devs) & set(evict_chips) for devs in after_evict):
            return fail(f"a lease after the eviction touched the dead "
                        f"host's chips: {after_evict}")
        peers = _get(h, "/peers")
        if peers.get("evicted_hosts") != [1]:
            return fail(f"/peers evicted_hosts {peers.get('evicted_hosts')}"
                        ", wanted [1]")
        text = h.metrics_text()
        if _metric(text, "sm_pod_host_evictions_total") != 1:
            return fail("/metrics sm_pod_host_evictions_total != 1")
        if _metric(text, "sm_pod_processes") != 2:
            return fail("/metrics sm_pod_processes != 2")
        if _metric(text, 'sm_pod_process_up{process="1"}') != 0:
            return fail('/metrics sm_pod_process_up{process="1"} != 0')
        if _metric(text, 'sm_jobs_cancelled_total{reason="host_evicted"}') \
                < 1:
            return fail("/metrics recorded no host_evicted cancellation")
        print(f"host_chaos: host {CHILD_HOST} SIGKILLed mid-job; watchdog "
              f"evicted chips {evict_chips} in {detect_s:.1f}s; job resumed "
              f"from checkpoint on {survivors} — stored annotations "
              f"BIT-IDENTICAL to the {pool}-chip golden "
              f"({convergence_s:.1f}s submit→done)")

        # ---- 3. host return → half-open readmission ---------------------
        child = _spawn_child(work, sm_conf, h.queue_dir, "b")
        deadline = time.time() + 20.0
        while time.time() < deadline and \
                _get(h, "/peers").get("evicted_hosts") != []:
            time.sleep(0.1)
        if _get(h, "/peers").get("evicted_hosts") != []:
            return fail("watchdog never noticed the returned host")
        readmitted: set[int] = set()
        deadline = time.time() + 15.0
        while time.time() < deadline and not readmitted >= set(evict_chips):
            time.sleep(0.2)
            readmitted |= set(health.reprobe_due())
        if not readmitted >= set(evict_chips):
            return fail(f"chips {sorted(set(evict_chips) - readmitted)} "
                        "never readmitted after the host returned (the "
                        "60s cooldown should have been zeroed)")
        status, _hd, _b = h.submit(_msg(fx, "fast", ids["after"],
                                        devices=pool))
        if status != 202:
            return fail(f"post-return submit returned {status}")
        rows = h.wait_terminal([ids["after"]])
        if rows[ids["after"]]["state"] != "done":
            return fail(f"post-return job {rows[ids['after']]['state']}")
        leases = _leases(_trace_records(h, ids["after"]))
        if not leases or leases[-1][1] != list(range(pool)):
            return fail(f"post-return lease {leases}, wanted all "
                        f"{pool} chips")
        if _metric(h.metrics_text(), 'sm_pod_process_up{process="1"}') != 1:
            return fail('/metrics sm_pod_process_up{process="1"} != 1 '
                        "after the host returned")
        print(f"host_chaos: host {CHILD_HOST} RETURNED — chips "
              f"{evict_chips} readmitted half-open; next job spans both "
              "hosts again")

        # no tmp/heartbeat/lease debris (checkpoint shards from the
        # cancelled attempt are legitimate resume state, load_sweep rule)
        debris = [p for p in _debris([h.root, h.dir / "results",
                                      h.dir / "work"])
                  if ".ckpt." not in p]
        if debris:
            return fail(f"tmp/heartbeat/lease debris: {debris}")

        rep = lockorder.assert_no_cycles("host_chaos")
        print(f"host_chaos: lock-order clean "
              f"({rep['locks_instrumented']} locks, {rep['edges']} edges)")
        return 0
    finally:
        if child.poll() is None:
            child.kill()
        h.shutdown()
        lockorder.disable()


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: same stages on a 4-chip pool")
    ap.add_argument("--work", default=None)
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args()
    if args.work:
        work = Path(args.work)
        work.mkdir(parents=True, exist_ok=True)
        return run(work, smoke=args.smoke)
    with tempfile.TemporaryDirectory(prefix="sm_host_chaos_") as d:
        rc = run(Path(d), smoke=args.smoke)
        if args.keep:
            print(f"host_chaos: work dir kept at {d}", file=sys.stderr)
        return rc


if __name__ == "__main__":
    sys.exit(main())
