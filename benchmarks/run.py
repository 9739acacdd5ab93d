#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: this process (which NEVER imports jax) reads the cell's files from
``BENCHMARK.json``, makes or finds the cell's datasets for the seed, starts
ONE ``engine.cli serve`` child that alone owns the chips, asserts that it
runs on ``tpu`` with the cell's chip count, warms up every dataset of the
catalogue, drives the cell's traffic over HTTP for ``--seconds``, stops the
child (exit 0 required), checks stored outputs against the plain reference,
and prints the result as the last line of stdout.  Any other platform, a
child that dies, a cell with no finished job: non-zero exit, no result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time
from pathlib import Path

T_START = time.time()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import datasets  # noqa: E402
import jobtrace  # noqa: E402
import oracle  # noqa: E402
import traffic as traffic_gen  # noqa: E402
from serve import (TERMINAL, BenchFailure, Serve, check,  # noqa: E402
                   hidden_routes, http, identity, metric_max, metric_sum)

DATASET_CACHE_LIMIT = 6 << 30        # bytes of generated datasets kept
# no warm-up round over a pool's placements starts later than this after the
# process did: a round that compiles takes ~45 s, the window, the stop and
# the check ~85 s more, and a run has 360 s; a checkout's first run, which
# compiles every program on every chip, has 1200 s
WARM_UP_ROUNDS_UNTIL_S = 215.0
FIRST_RUN_ROUNDS_UNTIL_S = 900.0


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def load_cell(root: Path, workload: str) -> dict:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    check(workload in cells, f"no workload {workload!r} in BENCHMARK.json")
    cell = dict(cells[workload])
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["config"] = json.loads((root / conf["file"]).read_text())
    cell["traffic_name"] = cell["traffic"]
    cell["traffic"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["manifest"] = manifest
    return cell


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out.get(k, {}), v) if isinstance(v, dict) else v
    return out


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def nearest_rank(values: list[float], q: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def capture(serve: Serve, at: float, seconds: float, out: dict) -> None:
    """One ``GET /debug/profile`` inside the window (the call blocks for the
    whole capture, so it has a thread of its own)."""
    time.sleep(max(0.0, at - time.time()))
    out["t_send"] = time.time()
    status, body = http(serve.base, "GET",
                        f"/debug/profile?seconds={seconds}",
                        timeout=seconds + 300.0)
    out["t_recv"] = time.time()
    out["status"], out["body"] = status, body


def reduce_trace(work: Path, xplane: str | None = None) -> dict:
    """The ``.xplane.pb`` the capture left (or the recorded one a test
    hands in), reduced in a CPU-only helper."""
    found = [xplane] if xplane else sorted(
        (work / "work" / "profiles").rglob("*.xplane.pb"))
    check(found, f"the capture left no .xplane.pb under {work}/work/profiles")
    out = work / "device_trace.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "trace_reduce.py"), str(found[-1]),
         str(out)], env=env, capture_output=True, text=True, timeout=600)
    check(proc.returncode == 0,
          f"trace_reduce failed: {proc.stderr[-2000:]}")
    return json.loads(out.read_text())


def read_layers(cell: dict, run: dict) -> dict:
    out = {}
    for m in cell["manifest"]["per_layer"]:
        if not reports(m, cell["name"]):
            continue
        spec = importlib.util.spec_from_file_location(
            f"layer_{m['name']}", BENCH / "layers" / f"{m['name']}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(device: dict, cap: dict, jobs: list[dict]) -> dict:
    gaps = []
    for chip in device["chips"]:
        edges = list(chip["gaps"])
        if chip["first_s"] is None:
            edges.append((0.0, cap["seconds"]))
        else:
            edges += [(0.0, chip["first_s"]),
                      (chip["last_s"], cap["seconds"])]
        for a, b in edges:
            if b - a > 0:
                what = jobtrace.what_host_did(jobs, cap["t0"] + a,
                                              cap["t0"] + b)
                gaps.append([f"{what} (chip {chip['chip']})", b - a])
    gaps.sort(key=lambda g: -g[1])
    programs: dict[str, float] = {}
    for m in device["modules"]:
        programs[m["name"]] = programs.get(m["name"], 0.0) + m["dur_s"]
    ops = [[f"program {n}", s] for n, s in programs.items()] + \
        [[f"op {n}", s] for n, s in device["ops"]]
    ops.sort(key=lambda o: -o[1])
    return {"device_ops": ops[:10], "idle_gaps": gaps[:10]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", overrides: dict | None = None,
             before_check=None, root: Path = ROOT, emit=print) -> int:
    """One run of one cell.  ``platform``, ``overrides`` and ``before_check``
    exist for the tests and the control under ``benchmarks/tests``; the
    command line sets none of them."""
    check((root / "sm_distributed_tpu").is_dir(),
          f"{root} holds no sm_distributed_tpu: nothing to measure")
    cell = load_cell(root, workload)
    overrides = overrides or {}
    cfg = cell["config"] = merge(cell["config"],
                                 {k: v for k, v in overrides.items()
                                  if k in ("sm_config", "ds_config",
                                           "dataset")})
    tr = cell["traffic"] = merge(cell["traffic"], overrides.get("traffic"))
    chips = cell["chips"]
    clients, n_cat = traffic_gen.sizes(tr, chips)
    say(f"cell {workload}: config {cfg['name']} x traffic "
        f"{cell['traffic_name']} on {chips} chip(s), {clients} client(s), "
        f"catalogue {n_cat}, seed {seed}, window {seconds}s, trace {trace}")

    work = root / ".cache" / "bench" / "work" / workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    cap: dict = {}
    with Serve(root, work, cfg["sm_config"]) as serve:
        # (1) datasets for the seed, made while the child starts up
        cache = root / ".cache" / "bench" / "datasets"
        catalogue = datasets.generate_many(
            cache, cfg["dataset"], [seed + i for i in range(n_cat)],
            procs=min(n_cat, os.cpu_count() or 1)
            if cfg["dataset"]["nrows"] * cfg["dataset"]["ncols"] >= 4096 else 1)
        datasets.prune(cache, [d["path"] for d in catalogue], DATASET_CACHE_LIMIT)
        say(f"datasets ready at {time.time() - T_START:.1f}s: "
            f"{[d['n_peaks'] for d in catalogue]} peaks")

        serve.ready()
        # (2) the platform assertion
        ident = identity(serve, platform, chips)
        say(f"serve: platform={ident['platform']} kind={ident['device_kind']} "
            f"count={ident['device_count']} versions={ident['versions']}")
        driver = traffic_gen.Driver(serve, catalogue, cfg["ds_config"],
                                    f"s{seed}", tr["poll_ms"],
                                    tr.get("ds_id") == "same",
                                    work / "answers")
        try:
            # (3) warm-up: every dataset of the catalogue once, and where
            # the pool places jobs over several chips once on each placement
            # (an executable is compiled and loaded per chip)
            service = cfg["sm_config"].get("service", {})
            placements = chips // int(service.get("devices_per_job", 1))
            if placements > 1:
                first_run: list[bool] = []

                def go_on() -> bool:
                    if not first_run:
                        # asked after the first round: more compiles than
                        # one new program on every chip = the cache did not
                        # hold the base programs (read on the chip: 6 of 8
                        # where chip 0's came with the machine)
                        n = metric_sum(serve.metrics(),
                                       "sm_compile_events_total") or 0
                        first_run.append(n > placements)
                    return time.time() - T_START < (
                        FIRST_RUN_ROUNDS_UNTIL_S if first_run[0]
                        else WARM_UP_ROUNDS_UNTIL_S)

                walls, missing = driver.each_placement(
                    placements, lambda j: jobtrace.lease_devices(
                        serve.trace(j["msg_id"])), go_on)
                say(f"warm-up: {n_cat} datasets x {placements} placements, "
                    f"round walls {walls}, {missing} (dataset, placement) "
                    "pair(s) not covered")
            else:
                driver.run(clients, count=n_cat)
            warm = list(driver.jobs)
            for j in warm:
                check(j["row"]["state"] == "done"
                      and j["row"]["attempts"] == 1,
                      f"warm-up job {j['msg_id']} ended "
                      f"{j['row']['state']} after {j['row']['attempts']} "
                      f"attempt(s): {j['row'].get('error')}")
            # the DISTINCT ions a job scores (a decoy that two target
            # adducts sampled is scored once), from the first warm-up job's
            # kept assignment; oracle.py holds the stored table to it
            targets = cfg["ds_config"]["isotope_generation"]["adducts"]
            decoys_per = cfg["guarantees"]["decoys_per_target"]
            n_formulas = len(catalogue[0]["formulas"])
            n_ions = cell["n_ions"] = oracle.distinct_ions(
                work / "answers" / warm[0]["msg_id"], n_formulas, targets,
                decoys_per)
            say(f"{n_ions} ions a job (distinct; nominal "
                f"{n_formulas * len(targets) * (1 + decoys_per)} = "
                f"{n_formulas} formulas x {len(targets)} target adduct(s) x "
                f"{1 + decoys_per})")
            before = serve.metrics()
            setup_s = time.time() - T_START
            say(f"set-up done at {setup_s:.1f}s: warm-up walls "
                f"{[round(j['t_end'] - j['t_submit'], 1) for j in warm[:n_cat]]}; "
                f"compiles {metric_sum(before, 'sm_compile_events_total') or 0:.0f}, "
                "loads from the persistent cache "
                f"{metric_sum(before, 'sm_compile_cache_hits_total') or 0:.0f}")

            # (4) the window
            t0 = time.time()
            prof = None
            if trace:
                secs = min(float(cfg.get("profile_seconds",
                                         tr["profile_seconds"])),
                           max(1.0, seconds - tr["profile_at_s"] - 1.0))
                prof = threading.Thread(target=capture, args=(
                    serve, t0 + tr["profile_at_s"], secs, cap))
                prof.start()
            driver.run(clients, until=t0 + seconds)
            t1 = time.time()
            after = serve.metrics()
            if prof is not None:
                prof.join()
                check(cap.get("status") == 200,
                      f"/debug/profile -> {cap.get('status')}: "
                      f"{str(cap.get('body'))[:300]}")
        finally:
            driver.close()

        # (5) what the window finished
        sent = [j for j in driver.jobs if j["t_submit"] >= t0]
        ended = [j for j in sent if j["done"].is_set() and j["t_end"] <= t1]
        stuck = [j for j in sent if j not in ended
                 and t1 - j["t_submit"] > seconds]
        ok_jobs = [j for j in ended if j["row"]["state"] == "done"
                   and j["row"]["attempts"] == 1]
        attempted = len(ended) + len(stuck)
        failed = attempted - len(ok_jobs)
        check(ok_jobs, f"no job finished inside the {seconds}s window "
              f"({len(sent)} sent)")
        for j in ended:
            say(f"job {j['msg_id']}: state={j['row']['state']} "
                f"attempts={j['row']['attempts']} "
                f"first={(j['t_partial'] or j['t_end']) - j['t_submit']:.3f}s "
                f"report={j['t_end'] - j['t_submit']:.3f}s "
                f"partial_seen={j['t_partial'] is not None}")

        # (6) traces, guarantees as the service counts them
        picked = sorted(ok_jobs, key=lambda j: j["n"])[seed % len(ok_jobs)]
        sample = [warm[0], picked]
        for j in ok_jobs + sample:
            if "trace" not in j:
                j["trace"] = serve.trace(j["msg_id"])
        faults = hidden_routes(serve, chips)
        for j in sample:
            backends = sorted({s.get("attrs", {}).get("backend")
                               for s in jobtrace.spans(j["trace"],
                                                       "score_batch")})
            if backends != ["jax_tpu"]:
                faults.append(f"job {j['msg_id']} scored by {backends}")
            rows = serve.get(f"/datasets/{j['ds_id']}/annotations"
                             "?order=msm&dir=desc&limit=5")["rows"]
            if not rows:
                faults.append(f"done job {j['ds_id']} is not readable")
        done_dir = serve.queue / "sm_annotate" / "done"
        for j in ok_jobs:
            n = len(list(done_dir.glob(f"{j['msg_id']}*")))
            if n != 1:
                faults.append(f"{n} entries in done/ for {j['msg_id']}")

        def delta(name, label=""):
            return (metric_sum(after, name, label) or 0) - \
                (metric_sum(before, name, label) or 0)

        hits = delta("sm_residency_hits_total", 'cache="dataset"')
        misses = delta("sm_residency_misses_total", 'cache="dataset"')
        say(f"window: {len(sent)} sent, {len(ended)} ended, "
            f"{len(ok_jobs)} done on one attempt, {len(stuck)} stuck; "
            f"residency dataset hits {hits:.0f} misses {misses:.0f} "
            f"(the mix promises {tr['expect_residency_hit_pct']}% hits); "
            f"compiles {delta('sm_compile_events_total'):.0f}, loads from "
            f"the persistent cache "
            f"{delta('sm_compile_cache_hits_total'):.0f}")
        variants: dict[str, int] = {}
        for r in picked["trace"]:
            if r.get("kind") == "event" and r["name"] == "batch_variant":
                key = f"{r['attrs'].get('variant')}@b{r['attrs'].get('b')}"
                variants[key] = variants.get(key, 0) + 1
        say(f"variants dispatched by {picked['msg_id']}: {variants}")
        peak = metric_max(serve.metrics(), "sm_device_hbm_peak_bytes")

        # (7) stop the child: in-flight jobs are cancelled, exit 0 required
        # a job still running after the shipped 30 s drain would be left in
        # running/ for a takeover, so wait until each cancel has landed
        left = [j for j in sent if not j["done"].is_set()]
        for j in left:
            http(serve.base, "DELETE", f"/jobs/{j['msg_id']}")
        deadline = time.time() + 180.0
        while left and time.time() < deadline:
            rows = serve.jobs()
            left = [j for j in left if rows.get(j["msg_id"], {}).get("state")
                    not in TERMINAL]
            time.sleep(0.2)
        say(f"serve: SIGTERM -> exit 0 in {serve.sigterm():.1f}s, "
            "running/ empty")

    # (6, outside the window and off the chip) outputs against the reference
    if before_check is not None:
        before_check(work, sample)
    for f in faults:
        say(f"correct: broken guarantee: {f}")
    say(oracle.line("broken_guarantees", len(faults), 0))
    compared = {"broken_guarantees": {"value": len(faults), "limit": 0}}
    correct = oracle.check_jobs(work / "answers", sample, cfg, seed, say,
                                compared) and not faults

    first = [(j["t_partial"] or j["t_end"]) - j["t_submit"] for j in ok_jobs]
    report = [j["t_end"] - j["t_submit"] for j in ok_jobs]
    span = max(j["t_end"] for j in ok_jobs) - t0
    say(f"samples: {len(ok_jobs)} jobs; report_s sorted "
        f"{[round(r, 3) for r in sorted(report)]}")
    e2e = {
        "first_annotation_s": statistics.median(first),
        "report_s": statistics.median(report),
        "report_p95_s": nearest_rank(report, 0.95),
        "ions_per_s": n_ions * len(ok_jobs) / span / chips,
        "setup_s": setup_s,
    }
    device = {"platform": ident["platform"], "kind": ident["device_kind"],
              "count": ident["device_count"],
              "memory_peak_bytes": None if peak is None else int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    run = {"cell": cell, "jobs": ok_jobs, "metrics_before": before,
           "metrics_after": after, "device": None, "capture": None,
           "device_kind": overrides.get("device_kind", ident["device_kind"])}
    if trace:
        reduced = reduce_trace(work, overrides.get("xplane"))
        window = {"t0": cap["t_send"], "seconds": cap["body"]["duration_s"]}
        run.update(device=reduced, capture=window)
        result["metrics"] = read_layers(cell, run)
        busy = sum(c["busy_s"] for c in reduced["chips"]) / chips
        device.update(busy_s=busy, window_s=window["seconds"])
        result["breakdown"] = breakdown(reduced, window, ok_jobs)
        say(f"capture: {window['seconds']:.3f}s sent at window+"
            f"{cap['t_send'] - t0:.2f}s, call took "
            f"{cap['t_recv'] - cap['t_send']:.2f}s; chips "
            f"{[(c['chip'], round(c['busy_s'], 4), c['n_ops']) for c in reduced['chips']]}")
        say("end-to-end of this traced run (not reported): "
            + json.dumps(e2e))
    else:
        say("layers this untraced run can read (host clock, counters): "
            + json.dumps({k: v["value"]
                          for k, v in read_layers(cell, run).items()}))
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell["manifest"]["end_to_end"]
            if reports(m, workload)}
    result["device"] = device
    # every number compared beside its limit: the result line's last key and
    # the last lines of standard error
    result["compared"] = compared
    say(f"whole run {time.time() - T_START:.1f}s")
    for name, c in compared.items():
        print(oracle.line(name, c["value"], c["limit"]), file=sys.stderr,
              flush=True)
    emit(json.dumps(result))
    return 0


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except BenchFailure as exc:
        print(f"bench: FAIL - {exc}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
