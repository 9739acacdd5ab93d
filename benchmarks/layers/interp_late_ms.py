"""Host interpreter: mean wait for the interpreter during the capture, in
ms: the window delta of ``sm_interp_probe_late_seconds_total`` over that of
``sm_interp_probe_wakeups_total`` (a thread that wakes every 10 ms and
measures how late: ~0.1 idle, ~5 one thread hogging, more a queue).  None
where the run had no capture."""
from layers.counters import window_delta


def read(run):
    late = window_delta(run, "sm_interp_probe_late_seconds_total")
    wakes = window_delta(run, "sm_interp_probe_wakeups_total")
    return 1000.0 * late / wakes if late is not None and wakes else None
