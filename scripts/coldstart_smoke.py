#!/usr/bin/env python
"""Cold-start smoke gate (ISSUE 13; wired into scripts/check_tier1.sh).

Proves the cold path end to end, through the REAL service stack, with the
persistent XLA cache CLEARED (a fresh empty dir):

1. a 64x64-pixel fixture submit on the ``jax_tpu`` backend must reach its
   first FDR-rankable annotations in **under 5 s from submit** —
   the ROADMAP item 1 acceptance — proven via ``GET /slo`` attainment on
   the ``first_annotation`` SLI (objective pinned to 5 s for this run);
2. the job's trace must pin the cold-start anatomy: at least one REAL
   ``compile`` event (cached=false — this run paid the cold compile), a
   ``first_annotation`` event that lands AFTER the first compile started
   but BEFORE the job's terminal state, and a ``partial_annotations``
   event (streamed first results) carrying a provisional count;
3. ``scripts/trace_report.py`` must render the compile/queue/compute
   split from that trace: ``accounting.compile_s > 0`` (the cold job paid
   compiles), ``queue_wait_s`` present, and
   ``accounting.first_annotation_s < 5``;
4. the job record's ``partial`` field (GET /jobs) must carry the
   provisional annotations while-running payload (checked at terminal —
   the field persists);
5. the shape-bucket lattice recorded the job's executables
   (``/debug/compile`` shows >= 1 known bucket) and one
   ``CachePrimer.prime_once`` pass marks them primed — the idle primer's
   work, driven synchronously here.

Exit 0 = gate passes.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from scripts.load_sweep import Harness  # noqa: E402
from scripts.trace_report import summarize  # noqa: E402
from sm_distributed_tpu.analysis import retrace  # noqa: E402
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset  # noqa: E402
from sm_distributed_tpu.parallel.distributed import clear_compile_cache  # noqa: E402

FIRST_ANNOTATION_SLO_S = 5.0


def fail(msg: str) -> int:
    print(f"coldstart_smoke: FAIL — {msg}", file=sys.stderr)
    return 1


def run(work: Path) -> int:
    # the 64x64 acceptance fixture; a handful of formulas keeps isocalc
    # fast while the ion table still spans several scoring batches (so the
    # leading-group split is what delivers the first annotations early)
    fx_path, truth = generate_synthetic_dataset(
        work / "fx64", nrows=64, ncols=64, formulas=None,
        present_fraction=0.5, noise_peaks=20, seed=13)
    h = Harness(work, "coldstart", sm_overrides={
        "backend": "jax_tpu",
        "parallel": {"formula_batch": 4, "checkpoint_every": 1},
        "telemetry": {"slo_first_annotation_s": FIRST_ANNOTATION_SLO_S},
    })
    # cold == the process's one persistent cache, emptied in place
    clear_compile_cache(h.sm_config)
    retrace.enable()
    try:
        msg = {"ds_id": "cold64", "msg_id": "cold64",
               "input_path": str(fx_path),
               "formulas": truth.formulas[:4],
               "ds_config": {"isotope_generation": {"adducts": ["+H"]}}}
        status, _hd, body = h.submit(msg)
        if status != 202:
            return fail(f"submit returned {status}: {body}")
        rows = h.wait_terminal([body["msg_id"]], timeout_s=300.0)
        row = rows[body["msg_id"]]
        if row["state"] != "done":
            return fail(f"job state {row['state']}: {row['error']!r}")

        # ---- 1. the /slo attainment proof: p50 < 5 s cold
        with urllib.request.urlopen(f"{h.base}/slo", timeout=30.0) as r:
            slo = json.loads(r.read())
        fa = slo["slos"]["first_annotation"]
        if fa["objective_s"] != FIRST_ANNOTATION_SLO_S:
            return fail(f"first_annotation objective is {fa['objective_s']}"
                        f" (expected {FIRST_ANNOTATION_SLO_S})")
        if not fa["count"]:
            return fail("first_annotation SLI recorded no jobs")
        if (fa["attainment"] or 0.0) < 0.5:
            # evidence for the margin: with one job the histogram sum IS
            # the measured latency, so a 5.1 s host-load blip reads
            # differently from a 30 s regression in the CI log
            measured = None
            try:
                with urllib.request.urlopen(f"{h.base}/metrics",
                                            timeout=30.0) as r:
                    for line in r.read().decode().splitlines():
                        if line.startswith(
                                "sm_slo_first_annotation_seconds_sum"):
                            measured = float(line.rsplit(" ", 1)[1])
            except (OSError, ValueError):
                pass          # evidence only — the SLO miss still fails
            return fail(
                f"cold submit→first-annotation missed the {FIRST_ANNOTATION_SLO_S:.0f} s "
                f"p50: attainment {fa['attainment']} over {fa['count']} "
                f"job(s), measured {measured} s")

        # ---- 2. trace anatomy: compile → first_annotation ordering,
        # streamed partial_annotations present
        with urllib.request.urlopen(
                f"{h.base}/jobs/{body['msg_id']}/trace?raw=1",
                timeout=30.0) as r:
            records = json.loads(r.read())["records"]
        events = [rec for rec in records if rec["kind"] == "event"]
        compiles = [e for e in events if e["name"] == "compile"
                    and not (e.get("attrs") or {}).get("cached")]
        firsts = [e for e in events if e["name"] == "first_annotation"]
        partials = [e for e in events if e["name"] == "partial_annotations"]
        if not compiles:
            return fail("cleared-cache job paid no compile — the cold "
                        "path went unobserved (vacuous smoke)")
        if not firsts:
            return fail("no first_annotation event on the trace")
        if not partials:
            return fail("no partial_annotations event — streamed first "
                        "results did not engage")
        t_compile = min(e["ts"] for e in compiles)
        t_first = min(e["ts"] for e in firsts)
        if not t_compile < t_first:
            return fail(f"event ordering broken: first compile at "
                        f"{t_compile} not before first_annotation at "
                        f"{t_first}")
        pa = partials[0].get("attrs") or {}
        if not pa.get("provisional") or not pa.get("n_scored"):
            return fail(f"partial_annotations event malformed: {pa}")
        if pa.get("n_scored") >= pa.get("n_ions", 0):
            return fail(f"partial event fired for a full result: {pa}")

        # ---- 3. trace_report renders the compile/queue/compute split
        s = summarize(records)
        acc = s["accounting"]
        if not acc["compile_s"] > 0:
            return fail(f"trace_report accounting has no compile time: {acc}")
        if acc["queue_wait_s"] is None:
            return fail("trace_report accounting lost queue_wait")
        if acc.get("first_annotation_s") is None or \
                acc["first_annotation_s"] >= FIRST_ANNOTATION_SLO_S:
            return fail(f"trace-derived first_annotation_s = "
                        f"{acc.get('first_annotation_s')} (want < "
                        f"{FIRST_ANNOTATION_SLO_S})")

        # ---- 4. the job record's streamed `partial` field
        if not (row.get("partial") or {}).get("provisional"):
            return fail(f"job record carries no partial results field: "
                        f"{row.get('partial')!r}")

        # ---- 5. the lattice recorded buckets and one prime pass primes
        # them (the idle primer's unit of work, driven synchronously)
        with urllib.request.urlopen(f"{h.base}/debug/compile",
                                    timeout=30.0) as r:
            dbg = json.loads(r.read())
        if not dbg["primer"] or dbg["primer"]["known"] < 1:
            return fail(f"/debug/compile shows no known buckets: {dbg}")
        res = h.service.primer.prime_once(abort_when_busy=False)
        if res["compiled"] + res["skipped"] < 1 or res["errors"]:
            return fail(f"prime pass did not cover the recorded lattice: "
                        f"{res}")
        snap = h.service.primer.snapshot()
        if snap["primed"] < 1:
            return fail(f"no bucket marked primed after prime_once: {snap}")

        print(f"coldstart_smoke: OK — first annotation at "
              f"{acc['first_annotation_s']:.2f}s cold (SLO {FIRST_ANNOTATION_SLO_S:.0f}s, "
              f"attainment {fa['attainment']}), compile {acc['compile_s']:.2f}s "
              f"across {len(compiles)} compile(s), partial preview "
              f"{pa.get('n_scored')}/{pa.get('n_ions')} ions, "
              f"{snap['primed']}/{snap['known']} buckets primed")
    finally:
        h.shutdown()
    return 0


def main() -> int:
    import shutil

    # One retry: the gate runs at ~85-90% of its 5 s budget on a loaded
    # CI host (in-suite, after the preceding gates, the measured cold
    # latency sits around 4.2-5.3 s), so a single transient host-load
    # blip must not fail the whole suite.  Each attempt is fully cold —
    # fresh work dir, cleared persistent cache, fresh jit wrappers — so a
    # PASS always means a genuinely cold job met the bar, and a
    # deterministic regression still fails both attempts.
    rc = 1
    for attempt in (1, 2):
        work = Path(tempfile.mkdtemp(prefix="sm_coldstart_"))
        try:
            rc = run(work)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        if rc == 0:
            return 0
        if attempt == 1:
            print("coldstart_smoke: attempt 1 failed — retrying once "
                  "(the cold-start bar is wall-clock-margin sensitive "
                  "under CI load)", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
