#!/usr/bin/env python
"""Trace-driven performance report (ISSUE 5 capstone).

Renders a per-job trace (utils/tracing.py JSONL, or fetched live from a
running service) into the standard perf artifact for this repo:

- the **phase breakdown** — wall clock per pipeline phase, as a share of
  the root ``submit`` span (submit → terminal);
- the **accounting split** — queue wait (submit → first attempt), one
  ``claim`` line a claim (seconds after the submit, and ``woken_by``:
  ``submit`` = the POST woke the dispatcher, ``poll`` = its timed scan
  found the message), device-
  token wait (device_hold start → token acquired), device-token hold, and
  compute (the ``score`` phase), so a throughput cliff shows WHERE the
  time moved (scheduler? token contention? device?);
- the **slowest batches** — the top score_batch spans with backend/ion
  counts, the needle for per-batch regressions;
- the **build and store split** — ``read_dataset`` with the ingest's two
  spans (``parse_index``: the imzML index; ``read_ibd``: the bulk read of
  the ibd), ``prepare_resident`` (the dataset-only
  half of the build, made before the lease) with its two ``prepare_*``
  children and the road each took (``hmax``, ``occupancy``, ``sort``),
  ``backend_build`` with its four ``build_*`` children under ``score``,
  the four ``store_*`` children under ``store_results`` (beside
  ``store_write_images`` the ``chunks`` the export reached the writer
  in); what a table of
  the job's size cost: ``isotope_prefetch_setup {formulas, ions, cache}``
  with ``decoy_selection`` and ``pattern_cache_load {shards, entries,
  bytes}``, ``isotope_patterns {ions, cached, computed, gen_s}``,
  ``presize`` / ``score_plan {batches, executables, band_buckets,
  variants, slots, peaks}`` (the last two: the capacity slots the planned
  extractions are handed and the peaks really inside them),
  ``fdr {ions, targets, decoys, rankings}`` with its ``fdr_rank`` children
  (one a target adduct: the adducts, the targets and the decoy entries
  ranked, summed), ``store_tables {rows, bytes}`` with ``store_assignment
  {rows, bytes}`` (the decoy assignment the job ranked by);
- the **device split**, when a ``/debug/profile`` capture overlapped the
  job's lease hold: device seconds per ``jax.named_scope``, busy share of
  the hold per chip, and the longest idle gaps with the program span that
  covers each (``device_scope`` / ``device_busy`` / ``device_idle`` spans);
- the **CPU split**: beside every wall time the seconds the span's thread
  was on a core (``cpu``) and off it (``dur - cpu``: blocked on the chip, a
  file, a lock, or queued for the interpreter), a table of every span name
  in order of first appearance, and one line that splits the lease hold
  after the grant into ran / ``device_sync`` / stalled / unnamed (``hold``
  in ``--json``; ``benchmarks/layers/hold_stall_s.py`` and
  ``hold_unnamed_s.py`` read the same two numbers);
- attempts (with timeout/abandon flags) and event counts (retries,
  cancels, failpoints, breaker flips).

Every future perf PR attaches this report instead of a bare before/after
total.  Usage::

    python scripts/trace_report.py WORKDIR/traces/<trace_id>.jsonl
    python scripts/trace_report.py --url http://127.0.0.1:8685 --job MSG_ID
    python scripts/trace_report.py TRACE.jsonl --json      # machine-readable
    python scripts/trace_report.py TRACE.jsonl --validate  # schema-gate too
    python scripts/trace_report.py TRACE.jsonl --by-replica  # attribution
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from sm_distributed_tpu.utils import tracing  # noqa: E402

# phases in pipeline order (anything else traced as a phase appends after)
_PHASE_ORDER = ("stage_input", "read_dataset", "prepare_resident",
                "isotope_prefetch_setup", "decoy_selection",
                "isotope_patterns", "score", "fdr", "store_results")
# listed among the phases though phase_timer does not emit it: the job's
# last host-only step before it asks for the chip (engine/search_job.py)
_STEPS = ("prepare_resident", "isotope_prefetch_setup")
_TOP_BATCHES = 10
# the spans that split the two phases a job spends most of its lease in
# (models/msm_basic.py + models/msm_jax.py, engine/search_job.py)
_CHILDREN = {
    "read_dataset": ("parse_index", "read_ibd"),
    "prepare_resident": ("prepare_quantize", "prepare_sort"),
    "isotope_prefetch_setup": ("decoy_selection", "pattern_cache_load"),
    "score": ("backend_build", "build_sort", "build_restrict",
              "build_pad_compact", "build_device_put", "presize",
              "score_plan"),
    "fdr": ("fdr_rank",),
    "store_results": ("store_select", "store_extract_images",
                      "store_write_images", "store_tables",
                      "store_assignment"),
}
# children printed with their attrs: which road the layout took
# (io/dataset.py: ``{hmax, occupancy: walk|search}``, ``{sort: packed}``),
# and what a table of the job's size cost (models/msm_basic.py, ops/
# isocalc.py, models/msm_jax.py, engine/storage.py: the cache read back,
# the batch plans and the executables they mint, the tables' rows)
_CHILD_ATTRS = _CHILDREN["prepare_resident"] + (
    "decoy_selection", "pattern_cache_load", "backend_build", "presize",
    "score_plan", "store_write_images", "store_tables", "store_assignment")
# of the image writer's attrs, the one that says how the export reached it
# (engine/storage.py: ``chunks``); of the build's, whether it was a lookup
# and what the residency held after it (engine/residency.py; the datasets'
# three are on the prepare_resident line)
_CHILD_ATTR_KEYS = {
    "store_write_images": ("chunks",),
    "backend_build": ("cache_hit", "residency_entries", "residency_bytes",
                      "residency_evicted")}


def load_records(args) -> list[dict]:
    if args.url:
        import urllib.request

        url = f"{args.url.rstrip('/')}/jobs/{args.job}/trace?raw=1"
        with urllib.request.urlopen(url, timeout=30.0) as r:
            body = json.loads(r.read())
        return body.get("records", [])
    return tracing.read_trace(args.trace)


def _spans(records, name=None):
    for r in records:
        if r.get("kind") == "span" and (name is None or r.get("name") == name):
            yield r


def _events(records, name=None):
    for r in records:
        if r.get("kind") == "event" and (name is None or r.get("name") == name):
            yield r


# appended under device_hold by a /debug/profile capture: device time, not
# spans of the job's thread
_INJECTED = ("device_scope", "device_busy", "device_idle")


def _granted_hold(records: list[dict]) -> tuple[dict, dict] | None:
    """The first ``device_hold`` span with its ``device_token_acquired``
    event inside it."""
    for hold in sorted(_spans(records, "device_hold"), key=lambda r: r["ts"]):
        end = hold["ts"] + float(hold["dur"])
        for e in _events(records, "device_token_acquired"):
            if hold["ts"] <= e["ts"] <= end:
                return hold, e
    return None


def _descendants(records: list[dict], root_id: str) -> list[dict]:
    """The spans below ``root_id``, the ``device_*`` spans a capture appended
    left out."""
    kids: dict[str, list[dict]] = {}
    for r in _spans(records):
        if r["name"] not in _INJECTED:
            kids.setdefault(r.get("parent_id", ""), []).append(r)
    out, todo = [], [root_id]
    while todo:
        found = kids.get(todo.pop(), ())
        out.extend(found)
        todo.extend(r["span_id"] for r in found)
    return out


def hold_split(records: list[dict]) -> dict | None:
    """The lease hold after the grant, split four ways (seconds):

    - ``ran``: the job's thread on a core (``device_hold.cpu`` less the
      event ``device_token_acquired``'s ``wait_cpu_s``);
    - ``device_sync``: the thread waiting for the chip's scores;
    - ``stalled``: the rest, the thread neither running nor in
      ``device_sync`` (the export's fetch, a file, a lock, the GIL);
    - ``unnamed``: what no descendant span of ``device_hold`` covers
      (independent of the three above, which sum to ``held``).

    None without a grant inside a ``device_hold`` span; ``ran`` and
    ``stalled`` None on a trace whose spans carry no ``cpu``."""
    found = _granted_hold(records)
    if found is None:
        return None
    hold, grant = found
    t0, end = grant["ts"], hold["ts"] + float(hold["dur"])
    held = end - t0
    under = _descendants(records, hold["span_id"])
    covered, edge = 0.0, t0
    for a, b in sorted((max(t0, r["ts"]), min(end, r["ts"] + float(r["dur"])))
                       for r in under):
        if b > edge:
            covered += b - max(a, edge)
            edge = b
    sync = sum(float(r["dur"]) for r in under if r["name"] == "device_sync")
    out = {"held_s": held, "device_sync_s": sync, "ran_s": None,
           "stalled_s": None, "unnamed_s": held - covered}
    if "cpu" in hold:
        ran = float(hold["cpu"]) - float(
            (grant.get("attrs") or {}).get("wait_cpu_s", 0.0))
        out.update(ran_s=ran, stalled_s=held - ran - sync)
    return {k: None if v is None else round(v, 6) for k, v in out.items()}


def _agg(found: list[dict]) -> dict:
    """Count, wall seconds and thread CPU seconds of some spans (``cpu_s``
    None where none of them carries ``cpu``)."""
    cpus = [float(r["cpu"]) for r in found if "cpu" in r]
    return {"count": len(found),
            "seconds": round(sum(float(r["dur"]) for r in found), 6),
            "cpu_s": round(sum(cpus), 6) if cpus else None}


def span_table(records: list[dict]) -> list[dict]:
    """Every span name in order of first appearance: count, wall, cpu, and
    whether it ran under the lease hold."""
    found = _granted_hold(records)
    under = {r["span_id"] for r in _descendants(
        records, found[0]["span_id"])} if found else set()
    by_name: dict[str, list[dict]] = {}
    for r in sorted(_spans(records), key=lambda r: r["ts"]):
        if r["name"] not in _INJECTED:
            by_name.setdefault(r["name"], []).append(r)
    return [{"name": name, **_agg(group),
             "under_hold": group[0]["span_id"] in under}
            for name, group in by_name.items()]


def summarize(records: list[dict]) -> dict:
    """The report's data model (also what --json prints)."""
    root = max(_spans(records, "submit"),
               key=lambda r: float(r.get("dur", 0.0)), default=None)
    total = float(root["dur"]) if root else sum(
        float(r.get("dur", 0.0)) for r in _spans(records)
        if not r.get("parent_id"))
    by_phase: dict[str, list[dict]] = {}
    for r in _spans(records):
        if (r.get("attrs") or {}).get("phase") or r["name"] in _STEPS:
            by_phase.setdefault(r["name"], []).append(r)
    phases = {}
    for name, found in by_phase.items():
        phases[name] = _agg(found)
        attrs = {k: v for k, v in (found[0].get("attrs") or {}).items()
                 if k != "phase"}
        if attrs:
            phases[name]["attrs"] = attrs
    attempts = sorted(_spans(records, "attempt"), key=lambda r: r["ts"])
    # queue wait: submit start -> first attempt start (requeues/retries put
    # later attempts' wait inside the root too, reported via attempts[])
    queue_wait = (attempts[0]["ts"] - root["ts"]) if (root and attempts) \
        else None
    holds = list(_spans(records, "device_hold"))
    token_hold = sum(float(r["dur"]) for r in holds)
    token_wait = 0.0
    acquired = sorted(_events(records, "device_token_acquired"),
                      key=lambda r: r["ts"])
    for h in sorted(holds, key=lambda r: r["ts"]):
        acq = next((e for e in acquired
                    if h["ts"] <= e["ts"] <= h["ts"] + float(h["dur"])), None)
        if acq is not None:
            token_wait += acq["ts"] - h["ts"]
    batches = sorted(_spans(records, "score_batch"),
                     key=lambda r: float(r["dur"]), reverse=True)
    events: dict[str, int] = {}
    for r in _events(records):
        events[r["name"]] = events.get(r["name"], 0) + 1
    worker_spans = list(_spans(records, "isocalc_chunk"))
    children = {}
    for name in (n for names in _CHILDREN.values() for n in names):
        found = list(_spans(records, name))
        if found and name != "fdr_rank":
            children[name] = _agg(found)
            if name in _CHILD_ATTRS:
                # the first's; of a job's score_plans (one a group) the one
                # that planned the most batches
                said = max(found, key=lambda r: (r.get("attrs") or {}).get(
                    "batches", 0))
                keys = _CHILD_ATTR_KEYS.get(name, said.get("attrs") or ())
                if attrs := {k: v for k, v in (said.get("attrs") or {}).items()
                             if k in keys}:
                    children[name]["attrs"] = attrs
    # the final rankings, one a target adduct, as ONE line (partial_fdr
    # ranks a prefix the same way: left out)
    final = {r["span_id"] for r in _spans(records, "fdr")}
    ranked = [r for r in _spans(records, "fdr_rank")
              if r.get("parent_id") in final]
    if ranked:
        children["fdr_rank"] = {**_agg(ranked), "attrs": {
            "adducts": ",".join(r["attrs"]["adduct"] for r in ranked),
            "targets": sum(r["attrs"]["targets"] for r in ranked),
            "decoy_entries": sum(r["attrs"]["decoy_entries"]
                                 for r in ranked)}}
    device = {"scopes": {}, "busy": [], "idle": []}
    for r in _spans(records, "device_scope"):
        a = r["attrs"]
        device["scopes"][a["scope"]] = round(
            device["scopes"].get(a["scope"], 0.0) + a["device_s"], 6)
    for r in _spans(records, "device_busy"):
        device["busy"].append({k: r["attrs"][k] for k in
                               ("chip", "busy_s", "hold_s", "whole")})
    for r in sorted(_spans(records, "device_idle"),
                    key=lambda r: -float(r["dur"])):
        device["idle"].append({"chip": r["attrs"]["chip"],
                               "host": r["attrs"]["host"],
                               "seconds": round(float(r["dur"]), 6)})
    return {
        "trace_id": records[0].get("trace_id", "") if records else "",
        "job_id": next((r["job_id"] for r in records if r.get("job_id")), ""),
        "state": (root.get("attrs") or {}).get("state", "") if root else "",
        "total_s": total,
        "phases": phases,
        "hold": hold_split(records),
        "spans": span_table(records),
        "accounting": {
            "queue_wait_s": round(queue_wait, 6)
            if queue_wait is not None else None,
            # one entry a claim (a retry or a takeover claims again): how
            # long after the submit, and what ended the dispatcher's idle
            # wait for it (``submit`` = woken by the POST, ``poll`` = found
            # by the timed scan; absent on traces from before PR 43)
            "claims": [{
                "after_submit_s": round(e["ts"] - root["ts"], 6)
                if root else None,
                "woken_by": (e.get("attrs") or {}).get("woken_by"),
            } for e in sorted(_events(records, "claim"),
                              key=lambda r: r["ts"])],
            "device_token_wait_s": round(token_wait, 6),
            "device_token_hold_s": round(token_hold, 6),
            "compute_s": round(phases.get("score", {}).get("seconds", 0.0), 6),
            # XLA compile split (ISSUE 13): real backend compiles vs
            # persistent-cache loads, from the retrace tracer's `compile`
            # events (analysis/retrace.py) — the cold-start cost this
            # job itself paid, and what a primed cache turned into loads
            "compile_s": round(sum(
                float((r.get("attrs") or {}).get("dur_s", 0.0))
                for r in _events(records, "compile")
                if not (r.get("attrs") or {}).get("cached")), 6),
            "compile_cache_load_s": round(sum(
                float((r.get("attrs") or {}).get("dur_s", 0.0))
                for r in _events(records, "compile")
                if (r.get("attrs") or {}).get("cached")), 6),
            # warm-start attribution (ISSUE 18): jaxpr tracing and MLIR
            # lowering run on every compile-cache miss even when the
            # executable then loads off the persistent cache — the part
            # of a "warm" start the cache cannot remove
            "compile_trace_s": round(sum(
                float((r.get("attrs") or {}).get("dur_s", 0.0))
                for r in _events(records, "compile_trace")), 6),
            "compile_lower_s": round(sum(
                float((r.get("attrs") or {}).get("dur_s", 0.0))
                for r in _events(records, "compile_lower")), 6),
            "isocalc_gen_s": round(sum(
                float(r["dur"]) for r in _spans(records, "isocalc_gen")), 6),
            # submit → first FDR-rankable annotations (the streamed
            # first-results latency, matching sm_slo_first_annotation)
            "first_annotation_s": round(
                min((e["ts"] for e in _events(records, "first_annotation")),
                    default=root["ts"] if root else 0.0)
                - (root["ts"] if root else 0.0), 6)
            if root and any(_events(records, "first_annotation")) else None,
        },
        "attempts": [{
            "attempt": (r.get("attrs") or {}).get("attempt"),
            "seconds": round(float(r["dur"]), 6),
            "timed_out": bool((r.get("attrs") or {}).get("timed_out")),
            "abandoned": bool((r.get("attrs") or {}).get("abandoned")),
        } for r in attempts],
        "slowest_batches": [{
            "seconds": round(float(r["dur"]), 6),
            "backend": (r.get("attrs") or {}).get("backend", ""),
            "ions": (r.get("attrs") or {}).get("ions"),
            "pid": r.get("pid"), "tid": r.get("tid"),
        } for r in batches[:_TOP_BATCHES]],
        "children": children,
        "device": device if device["busy"] else None,
        "n_batches": len(batches),
        "n_isocalc_worker_spans": len(worker_spans),
        "events": events,
        "n_records": len(records),
    }


def by_replica(records: list[dict]) -> dict:
    """Per-replica attribution (ISSUE 20) from the ISSUE-8 replica stamps.

    A trace that survived a takeover (or had device_scope spans appended
    by a profiling replica) holds records from several processes; this
    groups the work by WHO ran it.  Records emitted before replica
    identity existed (or by non-service tooling) land under "-".
    """
    out: dict[str, dict] = {}
    for r in records:
        rid = str(r.get("replica") or "-")
        b = out.setdefault(rid, {
            "spans": 0, "events": 0, "seconds": 0.0, "attempts": 0,
            "device_s": 0.0, "phases": {}, "pids": set(),
        })
        if r.get("pid") is not None:
            b["pids"].add(r["pid"])
        if r.get("kind") == "span":
            b["spans"] += 1
            dur = float(r.get("dur", 0.0))
            b["seconds"] += dur
            if r.get("name") == "attempt":
                b["attempts"] += 1
            elif r.get("name") == "device_scope":
                b["device_s"] += float(r["attrs"]["device_s"])
            if (r.get("attrs") or {}).get("phase"):
                ph = b["phases"]
                ph[r["name"]] = ph.get(r["name"], 0.0) + dur
        elif r.get("kind") == "event":
            b["events"] += 1
    for b in out.values():
        b["pids"] = sorted(b["pids"])
        b["seconds"] = round(b["seconds"], 6)
        b["device_s"] = round(b["device_s"], 6)
        b["phases"] = {k: round(v, 6) for k, v in sorted(b["phases"].items())}
    return out


def render_by_replica(br: dict) -> str:
    lines = ["", "per-replica attribution:"]
    lines.append(f"  {'replica':<14} {'spans':>6} {'events':>7} "
                 f"{'span-s':>10} {'attempts':>8} {'device-s':>10}  phases")
    for rid in sorted(br):
        b = br[rid]
        phases = ", ".join(f"{k}={v:.3f}s" for k, v in b["phases"].items())
        lines.append(f"  {rid:<14} {b['spans']:>6} {b['events']:>7} "
                     f"{b['seconds']:>10.3f} {b['attempts']:>8} "
                     f"{b['device_s']:>10.3f}  {phases or '-'}")
    return "\n".join(lines)


def _pct(part: float, total: float) -> str:
    return f"{100.0 * part / total:5.1f}%" if total > 0 else "    -"


def _cpu(v: dict) -> str:
    """`` cpu 0.123s off 0.456s`` beside a wall time (nothing for records
    from before spans carried ``cpu``)."""
    if v.get("cpu_s") is None:
        return ""
    return (f"  cpu {v['cpu_s']:8.3f}s "
            f"off {max(0.0, v['seconds'] - v['cpu_s']):8.3f}s")


def _road(v: dict) -> str:
    return "".join(f"  {k}={a}" for k, a in v.get("attrs", {}).items()
                   if k != "error")


def render(s: dict) -> str:
    lines = []
    head = f"trace {s['trace_id']}"
    if s["job_id"]:
        head += f" · job {s['job_id']}"
    if s["state"]:
        head += f" · {s['state']}"
    lines.append(head)
    lines.append(f"total (submit → terminal): {s['total_s']:.3f}s over "
                 f"{s['n_records']} records")
    lines.append("")
    lines.append("phase breakdown:")
    total = s["total_s"]
    ordered = [p for p in _PHASE_ORDER if p in s["phases"]]
    ordered += [p for p in sorted(s["phases"]) if p not in ordered]
    for p in ordered:
        v = s["phases"][p]
        lines.append(f"  {p:<22} {v['seconds']:9.3f}s "
                     f"{_pct(v['seconds'], total)}  x{v['count']:<3}{_cpu(v)}"
                     + _road(v))
        for c in _CHILDREN.get(p, ()):
            if c in s.get("children", {}):
                v = s["children"][c]
                pad = "      " if c.startswith("build_") \
                    or c == "store_assignment" else "    "
                lines.append(f"{pad}{c:<{26 - len(pad)}}{v['seconds']:8.3f}s "
                             f"{_pct(v['seconds'], total)}  x{v['count']:<3}"
                             f"{_cpu(v)}{_road(v)}")
    if not ordered:
        lines.append("  (no phase spans)")
    lines.append("")
    if s.get("spans"):
        lines.append("spans (wall, thread cpu, off-core = wall - cpu; "
                     "* = under the lease hold):")
        for v in s["spans"]:
            lines.append(f"  {'*' if v['under_hold'] else ' '} "
                         f"{v['name']:<22} {v['seconds']:9.3f}s  "
                         f"x{v['count']:<3}{_cpu(v)}")
        lines.append("")
    h = s.get("hold")
    if h:
        def sec(v):
            return "n/a" if v is None else f"{v:.3f}s"

        lines.append(
            f"lease hold after the grant {h['held_s']:.3f}s = ran "
            f"{sec(h['ran_s'])} + device_sync {sec(h['device_sync_s'])} + "
            f"stalled {sec(h['stalled_s'])}; under no named span "
            f"{sec(h['unnamed_s'])}")
        lines.append("")
    if s.get("device"):
        d = s["device"]
        lines.append("device (from a /debug/profile capture):")
        for b in d["busy"]:
            lines.append(
                f"  chip {b['chip']}: busy {b['busy_s']:.4f}s of a "
                f"{b['hold_s']:.3f}s lease hold "
                f"({_pct(b['busy_s'], b['hold_s']).strip()})"
                + ("" if b["whole"] else "  [hold cut by the capture]"))
        for scope, sec in sorted(d["scopes"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  {scope:<22} {sec:9.4f}s")
        for g in d["idle"][:8]:
            lines.append(f"  idle {g['seconds']:8.3f}s on chip {g['chip']} "
                         f"during {g['host']}")
        lines.append("")
    a = s["accounting"]
    lines.append("accounting (where the wall went):")
    if a["queue_wait_s"] is not None:
        lines.append(f"  queue wait             {a['queue_wait_s']:9.3f}s "
                     f"{_pct(a['queue_wait_s'], total)}")
    for c in a.get("claims", ()):
        at = c["after_submit_s"]
        lines.append("  claim                  "
                     + (f"{at:9.3f}s {_pct(at, total)}" if at is not None
                        else "      n/a")
                     + f"  woken_by={c['woken_by'] or '-'}")
    lines.append(f"  device-token wait      {a['device_token_wait_s']:9.3f}s "
                 f"{_pct(a['device_token_wait_s'], total)}")
    lines.append(f"  device-token hold      {a['device_token_hold_s']:9.3f}s "
                 f"{_pct(a['device_token_hold_s'], total)}")
    lines.append(f"  compute (score)        {a['compute_s']:9.3f}s "
                 f"{_pct(a['compute_s'], total)}")
    lines.append(f"  xla compile            {a['compile_s']:9.3f}s "
                 f"{_pct(a['compile_s'], total)}")
    lines.append(f"  xla cache loads        {a['compile_cache_load_s']:9.3f}s "
                 f"{_pct(a['compile_cache_load_s'], total)}")
    lines.append(f"  jaxpr trace            "
                 f"{a.get('compile_trace_s', 0.0):9.3f}s "
                 f"{_pct(a.get('compile_trace_s', 0.0), total)}")
    lines.append(f"  mlir lower             "
                 f"{a.get('compile_lower_s', 0.0):9.3f}s "
                 f"{_pct(a.get('compile_lower_s', 0.0), total)}")
    if a.get("first_annotation_s") is not None:
        lines.append(f"  first annotation at    "
                     f"{a['first_annotation_s']:9.3f}s "
                     f"{_pct(a['first_annotation_s'], total)}")
    lines.append(f"  isocalc generation     {a['isocalc_gen_s']:9.3f}s "
                 f"(overlaps other phases)")
    lines.append("")
    if s["attempts"]:
        flags = ", ".join(
            f"#{at['attempt']}: {at['seconds']:.3f}s"
            + (" TIMED-OUT" if at["timed_out"] else "")
            + (" ABANDONED" if at["abandoned"] else "")
            for at in s["attempts"])
        lines.append(f"attempts ({len(s['attempts'])}): {flags}")
    if s["n_batches"]:
        lines.append(f"slowest batches (of {s['n_batches']}):")
        for b in s["slowest_batches"]:
            lines.append(f"  {b['seconds']:9.3f}s  {b['backend']:<16} "
                         f"ions={b['ions']}  pid={b['pid']}")
    lines.append(f"isocalc worker spans: {s['n_isocalc_worker_spans']}")
    if s["events"]:
        lines.append("events: " + ", ".join(
            f"{k} x{v}" for k, v in sorted(s["events"].items())))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", nargs="?", default=None,
                    help="per-job trace JSONL file")
    ap.add_argument("--url", default=None,
                    help="live service base URL (with --job)")
    ap.add_argument("--job", default=None, help="msg_id to fetch from --url")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable summary")
    ap.add_argument("--validate", action="store_true",
                    help="also schema-validate every record (exit 1 on any "
                         "problem) — the trace smoke gate's mode")
    ap.add_argument("--by-replica", action="store_true",
                    help="append the per-replica attribution table (who ran "
                         "each span, incl. appended device_scope time)")
    args = ap.parse_args(argv)
    if bool(args.url) == bool(args.trace):
        ap.error("give exactly one of TRACE or --url/--job")
    if args.url and not args.job:
        ap.error("--url needs --job")
    records = load_records(args)
    if not records:
        print("trace_report: no records found", file=sys.stderr)
        return 1
    if args.validate:
        problems = tracing.validate_records(records)
        if problems:
            print("trace_report: schema problems:\n  "
                  + "\n  ".join(problems), file=sys.stderr)
            return 1
    summary = summarize(records)
    if args.by_replica:
        summary["by_replica"] = by_replica(records)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        out = render(summary)
        if args.by_replica:
            out += render_by_replica(summary["by_replica"])
        print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
