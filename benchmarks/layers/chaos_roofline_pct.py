"""Kernels: the least time the chip could take for the measure of chaos of
one job (``chaos_counts.py``, ``counts.least_seconds``, ``peaks.json``) over
the device self time a job spent under the scope ``sm_chaos``
(``chaos_device_s``'s reading: median over the jobs wholly inside the
capture).  Bytes bound it.  Never clamped.  What makes chaos comparable
across section sizes: ``chaos_device_s`` grows with the pixels, this should
not."""
import chaos_counts
import counts
from layers import device_spans


def read(run):
    spent = device_spans.scope_seconds(run, "sm_chaos")
    if not spent:
        return None
    cfg = run["cell"]["config"]
    pixels = cfg["dataset"]["nrows"] * cfg["dataset"]["ncols"]
    n_ions = run["cell"]["n_ions"]
    least, _ = counts.least_seconds(
        run["device_kind"], chaos_counts.chaos_bytes(n_ions, pixels),
        chaos_counts.chaos_ops(
            n_ions, pixels,
            cfg["ds_config"]["image_generation"].get("nlevels", 30)))
    return 100.0 * least / spent
