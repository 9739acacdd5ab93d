"""Admission queue and device lease: how much of a lease hold the chip was
busy, 100 x busy_s / hold_s over the job's ``device_busy`` spans (one per
leased chip), median over the jobs wholly inside the capture."""
from layers import device_spans


def read(run):
    def pct(rec):
        busy = device_spans.whole(rec, "device_busy")
        return 100.0 * sum(a["busy_s"] for a in busy) \
            / sum(a["hold_s"] for a in busy)

    return device_spans.median_over_whole_jobs(run, pct)
