"""Host interpreter: CPU seconds of the serve process (every thread, user +
system) a finished job cost: the window delta of
``sm_process_cpu_seconds_total`` over the in-window jobs.  Under one
interpreter its reciprocal is the ceiling of jobs a second."""
from layers.counters import window_delta


def read(run):
    cpu = window_delta(run, "sm_process_cpu_seconds_total")
    return cpu / len(run["jobs"]) if cpu is not None and run["jobs"] else None
