"""FDR and store: the span ``store_assignment`` under ``store_tables``
(``engine/storage.py``: the write of ``target_decoy_add.parquet``, the decoy
assignment the job ranked by), median per job.  None where the program
stores none."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(rec, "store_assignment"))
