"""Scoring: the span ``backend_build`` (``models/msm_basic.py``: residency
lookup, and on a miss the sort, window restriction, lattice pad, compaction
and ``device_put`` of ``JaxBackend.__init__``), median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(rec, "backend_build"))
