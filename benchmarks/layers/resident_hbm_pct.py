"""Residency: the share of the cell's chip memory that the resident
backends' arrays fill at the window's end, in percent: gauge
``sm_residency_bytes{cache="backend"}`` (``engine/residency.py``, the sum of
``JaxBackend.resident_bytes`` over what the store holds) over gauge
``sm_device_hbm_limit_bytes`` (``service/telemetry.py``: the allocator's
``bytes_limit``) of a chip, times the cell's chips.  What the working set
weighs on the device, by the program's own arithmetic and not by the
allocator's reading (``memory_peak_bytes`` in the result line is that).
None where the program has no such gauge."""
from serve import metric_max, metric_sum


def read(run):
    text = run["metrics_after"]
    held = metric_sum(text, "sm_residency_bytes", 'cache="backend"')
    limit = metric_max(text, "sm_device_hbm_limit_bytes")
    if held is None or not limit:
        return None
    return 100.0 * held / (limit * run["cell"]["chips"])
