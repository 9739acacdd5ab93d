"""Arithmetic over the two ``/metrics`` scrapes at the window's ends."""
from serve import metric_sum


def window_delta(run, name: str) -> float | None:
    """What the family ``name`` grew by over the window, summed over its
    samples; None where the program does not expose it (a family that shows
    its first sample inside the window grew from nothing)."""
    after = metric_sum(run["metrics_after"], name)
    before = metric_sum(run["metrics_before"], name)
    return None if after is None else after - (before or 0.0)
