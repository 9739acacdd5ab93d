"""Admission queue and device lease: submit -> claim plus device_hold start
-> lease granted, median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(run["jobs"], jobtrace.queue_wait)
