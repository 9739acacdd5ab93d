"""Arithmetic over the device spans ``GET /debug/profile`` appends to the
trace of every job whose lease hold overlaps the capture
(``analysis/profiling.py``): ``device_scope`` per chip and
``jax.named_scope``, ``device_busy`` per chip.  Only jobs whose hold lies
WHOLLY inside the capture count (``attrs.whole``); a program that appends no
such span (the parent of PR 24) reads as None."""
import statistics


def whole(records: list[dict], name: str) -> list[dict]:
    return [r["attrs"] for r in records if r.get("kind") == "span"
            and r["name"] == name and r.get("attrs", {}).get("whole")]


def median_over_whole_jobs(run, fn) -> float | None:
    """Median of ``fn(records)`` over the jobs with a whole ``device_busy``."""
    vals = [fn(j["trace"]) for j in run["jobs"]
            if whole(j.get("trace") or [], "device_busy")]
    return statistics.median(vals) if vals else None


def scope_seconds(run, *scopes: str) -> float | None:
    """Device self time under the named scopes, summed per job."""
    return median_over_whole_jobs(run, lambda rec: sum(
        a["device_s"] for a in whole(rec, "device_scope")
        if a["scope"] in scopes))
