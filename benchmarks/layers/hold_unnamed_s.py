"""Admission queue and device lease: what of a lease hold after the grant no
descendant span of ``device_hold`` covers: the seconds the trace cannot name
yet.  Median over jobs; None where the spans carry no ``cpu`` (a trace from
before the spans that name them)."""
from layers import hold_split


def read(run):
    return hold_split.median_over_jobs(run, "unnamed")
