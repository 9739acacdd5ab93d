"""FDR and store: the spans ``store_extract_images`` + ``store_write_images``
under the ``store_results`` phase (``engine/search_job.py``), median per
job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(
            rec, "store_extract_images", "store_write_images"))
