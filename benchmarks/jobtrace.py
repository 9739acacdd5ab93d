"""Arithmetic over one job's raw trace records (``GET /jobs/<id>/trace?raw=1``,
``utils/tracing.py``): the split of ``scripts/trace_report.py`` into queue,
lease wait and hold, copied for the per-layer readers."""

from __future__ import annotations

import statistics

PHASES = ("stage_input", "read_dataset", "isotope_patterns", "score", "fdr",
          "store_results")


def spans(records: list[dict], name: str) -> list[dict]:
    return [r for r in records if r.get("kind") == "span"
            and r["name"] == name]


def event_ts(records: list[dict], name: str) -> float | None:
    ts = [r["ts"] for r in records if r.get("kind") == "event"
          and r["name"] == name]
    return min(ts) if ts else None


def span_sum(records: list[dict], *names: str) -> float | None:
    found = [s["dur"] for n in names for s in spans(records, n)]
    return sum(found) if found else None


def score_interval(records: list[dict]) -> tuple[float, float] | None:
    """Lease granted -> end of the last ``device_sync``: the scoring of a
    job as the device sees it (a ``score_batch`` span alone measures the
    enqueue).  Holds the backend build on a residency miss."""
    t0 = event_ts(records, "device_token_acquired")
    syncs = spans(records, "device_sync")
    if t0 is None or not syncs:
        return None
    return t0, max(s["ts"] + s["dur"] for s in syncs)


def queue_wait(records: list[dict]) -> float | None:
    """submit -> claim, plus device_hold start -> lease granted."""
    sub = spans(records, "submit")
    claim = event_ts(records, "claim")
    hold = spans(records, "device_hold")
    lease = event_ts(records, "device_token_acquired")
    if not sub or claim is None or not hold or lease is None:
        return None
    return max(0.0, claim - sub[0]["ts"]) + max(0.0, lease - hold[0]["ts"])


def lease_devices(records: list[dict]) -> list[int]:
    for r in records:
        if r.get("kind") == "event" and r["name"] == "device_token_acquired":
            return list(r.get("attrs", {}).get("devices", []))
    return []


def median_over_jobs(jobs: list[dict], fn) -> float | None:
    vals = [v for v in (fn(j["trace"]) for j in jobs if j.get("trace"))
            if v is not None]
    return statistics.median(vals) if vals else None


def what_host_did(jobs: list[dict], t0: float, t1: float) -> str:
    """The job-trace span or phase that covers most of wall interval
    [t0, t1], a job holding the lease first: ``queue``, a phase,
    ``backend build`` (lease granted -> first batch enqueued), or
    ``between jobs``."""
    best, best_cover = "between jobs", 0.0

    def offer(name, a, b, weight=1.0):
        nonlocal best, best_cover
        cover = (min(b, t1) - max(a, t0)) * weight
        if cover > best_cover:
            best, best_cover = name, cover

    for j in jobs:
        rec = j.get("trace") or []
        for name in PHASES:
            for s in spans(rec, name):
                a, b = s["ts"], s["ts"] + s["dur"]
                if name == "score":
                    first = [x["ts"] for x in spans(rec, "score_batch")]
                    lease = event_ts(rec, "device_token_acquired")
                    if first and lease is not None:
                        offer("backend build", lease, min(first), 2.0)
                        a = min(first)
                offer(name, a, b, 2.0 if name in ("score", "fdr",
                                                  "store_results") else 1.0)
        sub, claim = spans(rec, "submit"), event_ts(rec, "claim")
        if sub and claim is not None:
            offer("queue", sub[0]["ts"], claim, 0.5)
        hold, lease = spans(rec, "device_hold"), \
            event_ts(rec, "device_token_acquired")
        if hold and lease is not None:
            offer("queue", hold[0]["ts"], lease, 0.5)
    return best
