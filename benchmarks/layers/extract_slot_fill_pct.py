"""Scoring: how full the extractions' capacity slots were, in percent: window
delta of counter ``sm_extract_peaks_total`` (peaks inside the dispatched
batches' bands or window-union runs) over that of ``sm_extract_slots_total``
(the band's ``w_cap``, the sticky compact capacity or every resident slot:
``models/msm_jax.py::JaxBackend._extract_load``), summed over the variants.
What is missing to 100 is what the band floor, the band ladder and the
sticky compact capacity pad.  None where the program has no such counters."""
from layers.counters import window_delta


def read(run):
    slots = window_delta(run, "sm_extract_slots_total")
    peaks = window_delta(run, "sm_extract_peaks_total")
    if not slots or peaks is None:
        return None
    return 100.0 * peaks / slots
