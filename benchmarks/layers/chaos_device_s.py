"""Kernels: device self time of the measure of chaos per job, the scope
``sm_chaos``, median over the jobs wholly inside the capture."""
from layers import device_spans


def read(run):
    return device_spans.scope_seconds(run, "sm_chaos")
