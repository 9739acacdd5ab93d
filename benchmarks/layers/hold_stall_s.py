"""Admission queue and device lease: the time under the lease in which the
job's thread neither ran nor waited for the chip's scores: (``device_hold``
end - event ``device_token_acquired``) - (``device_hold.cpu`` -
``wait_cpu_s``) - the ``device_sync`` spans; the export's fetch, files,
locks and the wait for the interpreter.  Median over jobs; None where the
spans carry no ``cpu``."""
from layers import hold_split


def read(run):
    return hold_split.median_over_jobs(run, "stalled")
