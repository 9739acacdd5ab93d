"""Residency: dataset-cache hits over hits + misses inside the window."""
from serve import metric_sum


def read(run):
    def delta(name):
        return (metric_sum(run["metrics_after"], name, 'cache="dataset"') or 0) \
            - (metric_sum(run["metrics_before"], name, 'cache="dataset"') or 0)

    hits = delta("sm_residency_hits_total")
    misses = delta("sm_residency_misses_total")
    return 100.0 * hits / (hits + misses) if hits + misses else None
