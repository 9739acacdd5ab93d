"""Admission queue and device lease: mean wait of the window's lease grants,
first acquire -> grant: the window delta of
``sm_device_pool_wait_seconds_sum`` over that of ``..._count``."""
from layers.counters import window_delta


def read(run):
    waited = window_delta(run, "sm_device_pool_wait_seconds_sum")
    grants = window_delta(run, "sm_device_pool_wait_seconds_count")
    return waited / grants if waited is not None and grants else None
