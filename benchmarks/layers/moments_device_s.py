"""Kernels: device self time of the moments pass and the metric epilogue per
job, the scopes ``sm_moments`` + ``sm_epilogue`` + ``sm_fused``, median over
the jobs wholly inside the capture."""
from layers import device_spans


def read(run):
    return device_spans.scope_seconds(run, "sm_moments", "sm_epilogue",
                                      "sm_fused")
