"""Residency: entries evicted inside the window, whatever the cache and the
rule: window delta of counter ``sm_residency_evictions_total{cache=,
cause="count"|"bytes"}`` (``engine/residency.py``).  0 where the working set
fits its budget, as ``compiles_in_window`` is 0 where the executables were
warm; an eviction in the window is a job that stages, parses, prepares or
builds again.  None where the program has no such counter."""
from layers.counters import window_delta


def read(run):
    return window_delta(run, "sm_residency_evictions_total")
