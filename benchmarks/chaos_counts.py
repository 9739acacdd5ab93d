"""What the ALGORITHM needs for the measure of chaos of one job, from shapes
only: the numerator of ``chaos_roofline_pct``, the part of ``counts.py``'s
job that is chaos alone.  Not what the kernel does: no lane padding, no
sweeps to a fixpoint, no warm starts.

bytes:  every principal image read once (ions x pixels x 4 B); the counts
        that come back are one number an ion.
ops:    per principal-image pixel one compare per chaos level and one label
        update (``counts.job_ops``'s last term).
"""

from __future__ import annotations


def chaos_bytes(n_ions: int, pixels: int) -> float:
    return float(n_ions * pixels * 4)


def chaos_ops(n_ions: int, pixels: int, nlevels: int) -> float:
    return float(2 * nlevels * n_ions * pixels)
