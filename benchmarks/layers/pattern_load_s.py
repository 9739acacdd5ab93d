"""Isotope patterns: span ``isotope_prefetch_setup`` (``models/msm_basic.py::
IsotopePrefetch``: the decoy draw and the reload of the pattern cache's
shards for the job's whole table, on the prefetch thread; the job joins it
under its lease), median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(
            rec, "isotope_prefetch_setup"))
