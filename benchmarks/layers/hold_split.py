"""Arithmetic over one job's lease hold after the grant, from the spans'
wall time (``dur``) and their thread's CPU time (``cpu``, which
``utils/tracing.py`` writes since PR 35): the same split
``scripts/trace_report.py::hold_split`` prints, kept apart from the program.
A trace whose ``device_hold`` carries no ``cpu`` (the parent of PR 35) reads
as None."""
import statistics

# appended under device_hold by a /debug/profile capture: device time, not
# spans of the job's thread
INJECTED = ("device_scope", "device_busy", "device_idle")


def split(records: list[dict]) -> dict | None:
    """``{"held", "ran", "sync", "stalled", "unnamed"}`` in seconds for the
    first ``device_hold`` span with an event ``device_token_acquired``
    inside it: ``held`` = grant -> end of the hold; ``ran`` =
    ``device_hold.cpu`` less the event's ``wait_cpu_s``; ``sync`` = the sum
    of the ``device_sync`` spans under the hold; ``stalled`` = held - ran -
    sync; ``unnamed`` = held less the union of the hold's descendant spans
    clipped to [grant, end]."""
    spans = [r for r in records if r.get("kind") == "span"
             and r["name"] not in INJECTED]
    grants = [r for r in records if r.get("kind") == "event"
              and r["name"] == "device_token_acquired"]
    for hold in sorted((s for s in spans if s["name"] == "device_hold"),
                       key=lambda s: s["ts"]):
        end = hold["ts"] + hold["dur"]
        grant = next((e for e in grants if hold["ts"] <= e["ts"] <= end),
                     None)
        if grant is not None:
            break
    else:
        return None
    if "cpu" not in hold:
        return None
    t0, held = grant["ts"], end - grant["ts"]
    kids: dict[str, list[dict]] = {}
    for s in spans:
        kids.setdefault(s.get("parent_id", ""), []).append(s)
    named, sync, todo = [], 0.0, [hold["span_id"]]
    while todo:
        for s in kids.get(todo.pop(), ()):
            todo.append(s["span_id"])
            named.append((max(t0, s["ts"]), min(end, s["ts"] + s["dur"])))
            if s["name"] == "device_sync":
                sync += s["dur"]
    covered, edge = 0.0, t0
    for a, b in sorted(named):
        if b > edge:
            covered += b - max(a, edge)
            edge = b
    ran = hold["cpu"] - grant.get("attrs", {}).get("wait_cpu_s", 0.0)
    return {"held": held, "ran": ran, "sync": sync,
            "stalled": held - ran - sync, "unnamed": held - covered}


def median_over_jobs(run, key: str) -> float | None:
    vals = [s[key] for s in (split(j["trace"]) for j in run["jobs"]
                             if j.get("trace")) if s is not None]
    return statistics.median(vals) if vals else None
