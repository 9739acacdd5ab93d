"""FDR and store: the ``fdr_rank`` spans under the ``fdr`` phase
(``ops/fdr.py::FDR.estimate_fdr``: one a target adduct, the merge of its
sampled decoys' scores and the q-values), summed per job, median over jobs.
The provisional ranking's (under ``partial_fdr``) are not counted.  None
where the program has no such span."""
import jobtrace


def _ranked(rec):
    final = {s["span_id"] for s in jobtrace.spans(rec, "fdr")}
    found = [s["dur"] for s in jobtrace.spans(rec, "fdr_rank")
             if s.get("parent_id") in final]
    return sum(found) if found else None


def read(run):
    return jobtrace.median_over_jobs(run["jobs"], _ranked)
