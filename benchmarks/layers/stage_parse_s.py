"""Stage and parse: phases stage_input + read_dataset, median per job."""
import jobtrace


def read(run):
    return jobtrace.median_over_jobs(
        run["jobs"],
        lambda rec: jobtrace.span_sum(rec, "stage_input", "read_dataset"))
