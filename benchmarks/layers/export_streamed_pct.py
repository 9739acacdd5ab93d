"""FDR and store: the share of the jobs that stored images whose export
reached the writer as a stream of more than one chunk, in percent: window
delta of counter ``sm_store_exports_total{path="streamed"}`` over that of
both paths (``engine/storage.py::store_ion_images`` counts one a job:
``streamed`` where the device handed the images over in more than one chunk
and the writer took them as they landed, ``whole`` where one chunk or a
whole array held them all).  The chunk size is a constant in bytes, so this
reads 100 in the cells with large images and 0 in a 64x64 one: listed for
the three large-image cells and one 64x64 control.  None where the program
has no such counter."""
from serve import metric_sum

COUNTER = "sm_store_exports_total"


def _window_delta(run, label: str) -> float | None:
    after = metric_sum(run["metrics_after"], COUNTER, label)
    before = metric_sum(run["metrics_before"], COUNTER, label)
    return None if after is None else after - (before or 0.0)


def read(run):
    exports = _window_delta(run, "")
    streamed = _window_delta(run, 'path="streamed"')
    if not exports or streamed is None:
        return None
    return 100.0 * streamed / exports
