"""Kernels: the share of the packed chaos kernel's programs that counted
their block without labels, in percent: window delta of counter
``sm_chaos_programs_total{path="sparse"}`` over that of both paths (the
kernel's own flag a program, summed on the device and read off the scored
blocks the host fetches: ``models/msm_jax.py::_count_chaos_programs``).  What
is missing to 100 are the programs that flooded labels: a present target's
image, and the images that share a block with one.  None where the program
has no such counter."""
from serve import metric_sum

COUNTER = "sm_chaos_programs_total"


def _window_delta(run, label: str) -> float | None:
    after = metric_sum(run["metrics_after"], COUNTER, label)
    before = metric_sum(run["metrics_before"], COUNTER, label)
    return None if after is None else after - (before or 0.0)


def read(run):
    programs = _window_delta(run, "")
    sparse = _window_delta(run, 'path="sparse"')
    if not programs or sparse is None:
        return None
    return 100.0 * sparse / programs
