"""Kernels: device self time of image extraction per job, the scopes
``sm_extract`` (scoring) and ``sm_store_extract`` (the store's
re-extraction), median over the jobs wholly inside the capture."""
from layers import device_spans


def read(run):
    return device_spans.scope_seconds(run, "sm_extract", "sm_store_extract")
