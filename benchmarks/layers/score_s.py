"""Scoring: lease granted -> end of the last device_sync, median per job.
Holds the backend build on a residency miss."""
import jobtrace


def read(run):
    def dur(rec):
        iv = jobtrace.score_interval(rec)
        return None if iv is None else iv[1] - iv[0]

    return jobtrace.median_over_jobs(run["jobs"], dur)
