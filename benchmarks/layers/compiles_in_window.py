"""Compile cache: backend compiles inside the window (should be 0; loads
from the persistent cache are printed beside it on an earlier line)."""
from serve import metric_sum


def read(run):
    # the family appears with its first event: absent means none counted
    after = metric_sum(run["metrics_after"], "sm_compile_events_total")
    before = metric_sum(run["metrics_before"], "sm_compile_events_total")
    return (after or 0) - (before or 0)
