#!/usr/bin/env python
"""Compile census gate (ISSUE 12 tentpole; wired into scripts/check_tier1.sh).

Proves the engine's OBSERVED compile surface matches the DECLARED one
(``analysis/surface.py`` COMPILE_SURFACE registries) and is CLOSED under
repeated same-shaped traffic, through the REAL service stack:

1. the spheroid fixture runs through a real in-process service on the
   ``jax_tpu`` backend (single device) with the retrace tracer on — every
   XLA compilation must be attributed to a call site whose module carries
   a ``COMPILE_SURFACE`` registration (**zero unattributed compiles**;
   driver/test frames and ``<external>`` sites fail the gate);
2. a SECOND identical job (new dataset id, same file) re-runs on the
   resident backend of the first: it must add **zero census events of any
   kind** — no compile, no persistent-cache load, no new signature;
3. cross-SIZE closure (ISSUE 13 shape-bucket lattice, ISSUE 34 shared
   jits): a job on a DIFFERENT dataset (6x8 px vs 8x8 px) that shares the
   lattice bucket (row_bucket(6) == row_bucket(8) == 8; both peak counts
   under the 4096-slot floor) builds a FRESH backend, which finds the
   jitted scorers of its metric geometry in the process
   (``models/msm_jax.make_flat_jits``) and asks them for signatures they
   have run.  It must add **zero events of any kind** as well — before
   ISSUE 34 it re-traced, re-lowered and re-loaded every executable from
   the persistent cache — and the registry must count its backend
   ``shared`` (the stage is not vacuous): the signature set is closed
   across dataset SIZES, and the process keeps what it loaded;
4. a ``devices: 2`` submit on a virtual 2-chip CPU mesh exercises the
   pjit/shard_map SHARDED path — its compiles must attribute to the
   registered ``parallel/sharded.py`` surface the same way;
4. ``sm_compile_events_total`` / ``sm_compile_signatures`` are live on
   ``/metrics``, and the per-job trace carries ≥1 ``compile`` event (the
   cold compile is visible INSIDE the job that paid for it).

Exit 0 = gate passes.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request
from pathlib import Path

# the virtual 2-chip mesh must exist BEFORE jax initializes (same dance as
# multichip_smoke / tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=2")
os.environ["XLA_FLAGS"] = " ".join(_flags)

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from scripts.load_sweep import Harness, _msg, build_fixtures  # noqa: E402
from sm_distributed_tpu.analysis import retrace, surface  # noqa: E402
from sm_distributed_tpu.parallel.distributed import clear_compile_cache  # noqa: E402

N_DEVICES = 2

# site files allowed WITHOUT a COMPILE_SURFACE registration: none.  The
# census is the proof that this list stays empty — a compile attributed to
# scripts/, tests/, engine/, or "<external>" means a jit escaped the
# declared surface.
_SELF = "scripts/compile_census.py"


def fail(msg: str) -> int:
    print(f"compile_census: FAIL — {msg}", file=sys.stderr)
    return 1


def _unattributed(snap: dict) -> list[str]:
    """Observed sites whose module carries no COMPILE_SURFACE entry."""
    out = []
    for site in snap["sites"]:
        path = site.split(":", 1)[0]
        if path == _SELF:
            # the census's own harness frames never dispatch jitted code;
            # seeing one here is itself an attribution bug
            out.append(site)
        elif not surface.is_registered_path(path):
            out.append(site)
    return out


def _sig_set(snap: dict) -> set[tuple[str, str]]:
    return {(site, sig) for site, ent in snap["sites"].items()
            for sig in ent["signatures"]}


def _quiet_job(h, msg: dict, what: str, fresh_backend: bool) -> str | None:
    """Run one job whose geometry and signatures the process has already
    served; the reason it was not quiet, or None.  Quiet = the census
    records nothing at all (no compile, no persistent-cache load, hence no
    new signature).  With ``fresh_backend`` the job must also have BUILT a
    backend, one the registry counted as ``shared`` — the proof that the
    stage is not vacuous; without, it must have built none."""
    from sm_distributed_tpu.models.msm_jax import scoring_jit_events

    before, jits0 = retrace.snapshot(), scoring_jit_events()
    status, _hd, body = h.submit(msg)
    if status != 202:
        return f"{what} submit returned {status}: {body}"
    row = h.wait_terminal([body["msg_id"]])[body["msg_id"]]
    if row["state"] != "done":
        return f"{what} job state {row['state']}: {row['error']!r}"
    after, jits1 = retrace.snapshot(), scoring_jit_events()
    events = after["events_total"] - before["events_total"]
    loads = after["cache_hits_total"] - before["cache_hits_total"]
    new_sigs = _sig_set(after) - _sig_set(before)
    if events or loads or new_sigs:
        return (f"{what} job was NOT quiet on a geometry the process had "
                f"served: {events} compile(s), {loads} persistent-cache "
                f"load(s), {len(new_sigs)} new signature(s) "
                f"{sorted(new_sigs)[:5]} — its backend did not call the "
                f"jits the last one traced (models/msm_jax._SHARED_JITS)")
    shared = jits1["shared"] - jits0["shared"]
    built = jits1["built"] - jits0["built"]
    if shared != int(fresh_backend) or built:
        return (f"{what} job's backends were counted shared +{shared}, "
                f"built +{built}, not shared +{int(fresh_backend)} built +0 "
                f"— the stage did not run on the backend it is about")
    print(f"compile_census: {what} OK — "
          f"{'fresh backend, shared jits' if fresh_backend else 'resident backend'}"
          f", 0 compiles, 0 cache loads")
    return None


def run(work: Path) -> int:
    fx = build_fixtures(work)
    h = Harness(work, "compile_census", sm_overrides={
        "backend": "jax_tpu",
        "service": {"device_pool_size": N_DEVICES},
    })
    retrace.enable()   # harness init already bound the service metrics
    try:
        # ---- phase 1: first job = the cold surface (cleared cache, so
        # every executable is a compile event, not a cache load)
        clear_compile_cache(h.sm_config)
        retrace.reset()
        status, _hd, body = h.submit(_msg(fx, "fast", "census1"))
        if status != 202:
            return fail(f"submit 1 returned {status}: {body}")
        rows = h.wait_terminal([body["msg_id"]])
        if rows[body["msg_id"]]["state"] != "done":
            return fail(f"job 1 state {rows[body['msg_id']]['state']}: "
                        f"{rows[body['msg_id']]['error']!r}")
        snap1 = retrace.snapshot()
        if snap1["events_total"] == 0:
            return fail("no compile events observed — the tracer saw "
                        "nothing (vacuous census)")
        bad = _unattributed(snap1)
        if bad:
            return fail(
                "unattributed compiles — call sites outside any "
                f"COMPILE_SURFACE-registered module: {sorted(bad)}")

        # ---- phase 2: the identical job again (the resident backend)
        # adds ZERO events
        err = _quiet_job(h, _msg(fx, "fast", "census2"), "identical",
                         fresh_backend=False)
        if err:
            return fail(err)

        # ---- phase 2b: closure across dataset SIZES sharing a bucket
        # (ISSUE 13): a 6x8 fixture row-buckets to the same 8-row lattice
        # point as the 8x8 one (and both peak counts sit under the
        # 4096-slot floor), so its FRESH backend asks the shared jits for
        # exactly the signatures phase 1 left in the process: nothing is
        # traced, lowered, loaded or compiled again
        from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset

        mid_path, _mid_truth = generate_synthetic_dataset(
            work / "fx_mid", nrows=6, ncols=8, formulas=None,
            present_fraction=0.5, noise_peaks=30, seed=12)
        msg_x = dict(_msg(fx, "fast", "census_xsize"))
        msg_x["input_path"] = str(mid_path)      # same formulas, new size
        err = _quiet_job(h, msg_x, "cross-size (6x8 in the 8x8 bucket)",
                         fresh_backend=True)
        if err:
            return fail(err)

        # ---- phase 3: the sharded path attributes the same way
        status, _hd, body3 = h.submit(
            _msg(fx, "fast", "census3", devices=N_DEVICES))
        if status != 202:
            return fail(f"sharded submit returned {status}: {body3}")
        rows = h.wait_terminal([body3["msg_id"]])
        if rows[body3["msg_id"]]["state"] != "done":
            return fail(f"sharded job state {rows[body3['msg_id']]['state']}:"
                        f" {rows[body3['msg_id']]['error']!r}")
        snap3 = retrace.snapshot()
        bad = _unattributed(snap3)
        if bad:
            return fail(f"sharded path: unattributed compiles: {sorted(bad)}")
        sharded_sites = [s for s in snap3["sites"]
                         if s.startswith("sm_distributed_tpu/parallel/")]
        if not sharded_sites:
            return fail("the devices=2 job compiled nothing attributed to "
                        "parallel/ — the sharded surface went unobserved")

        # ---- phase 4: metrics + the compile trace event
        text = h.metrics_text()
        for name in ("sm_compile_events_total", "sm_compile_signatures"):
            if f"\n{name}{{" not in text and not any(
                    ln.startswith(name) for ln in text.splitlines()):
                return fail(f"{name} missing from /metrics")
        with urllib.request.urlopen(
                f"{h.base}/jobs/{body['msg_id']}/trace?raw=1",
                timeout=30.0) as r:
            records = json.loads(r.read())["records"]
        compiles = [rec for rec in records
                    if rec["kind"] == "event" and rec["name"] == "compile"]
        if not compiles:
            return fail("job 1's trace carries no `compile` event — the "
                        "cold compile is invisible to the job that paid it")

        census = {site: {"events": ent["events"],
                         "signatures": len(ent["signatures"])}
                  for site, ent in snap3["sites"].items()}
        print("compile_census: observed surface (site -> events/distinct):")
        for site, ent in sorted(census.items()):
            print(f"  {site}: {ent['events']} events, "
                  f"{ent['signatures']} signature(s)")
        print(f"compile_census: OK — {snap3['events_total']} compiles, "
              f"{snap3['signatures_total']} distinct signatures, all "
              f"attributed to {len(surface.registered())} registered "
              f"surface module(s); closed under repeat traffic; "
              f"{len(compiles)} compile event(s) on the job trace")
    finally:
        h.shutdown()
    return 0


def main() -> int:
    import shutil

    work = Path(tempfile.mkdtemp(prefix="sm_compile_census_"))
    try:
        return run(work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
