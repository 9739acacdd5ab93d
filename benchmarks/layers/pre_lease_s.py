"""Stage and parse: span ``pre_lease`` (``engine/search_job.py``), the
attempt's host-only work before the job asks the pool for a chip (stage,
parse, the start of the isotope patterns), median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(rec, "pre_lease"))
