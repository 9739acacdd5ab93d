"""Isotope patterns: window delta of counter ``sm_isocalc_patterns_total``
(``ops/isocalc.py``: patterns computed cold, not read back from the cache).
0 when the cache holds, as ``compiles_in_window`` is for executables."""
from layers.counters import window_delta


def read(run):
    return window_delta(run, "sm_isocalc_patterns_total")
