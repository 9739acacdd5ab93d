"""Admission queue and device lease: how many of the pool's chips were under
a job lease, on average over the window: the window delta of
``sm_device_pool_held_seconds_total`` summed over devices, over the delta of
``sm_device_pool_clock_seconds_total`` (the pool reads both from one clock at
the scrape, open leases counted up to it).  0 .. the pool's size."""
from layers.counters import window_delta


def read(run):
    held = window_delta(run, "sm_device_pool_held_seconds_total")
    clock = window_delta(run, "sm_device_pool_clock_seconds_total")
    return held / clock if held is not None and clock else None
