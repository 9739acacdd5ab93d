"""FDR and store: phases fdr + store_results, median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"], lambda rec: jobtrace.span_sum(rec, "fdr", "store_results"))
