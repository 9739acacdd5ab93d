"""One reader per per-layer metric: ``layers/<metric name>.py`` with
``read(run) -> float | None``.  ``run`` holds the in-window jobs with their
raw trace records, the ``/metrics`` text at both ends of the window, the
reduced device trace of the capture and the cell with its files and ``n_ions`` (``run.py::run_cell`` builds it).
A reader that finds nothing to read returns None and the metric is left out
of the line."""
