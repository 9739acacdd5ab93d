"""Scoring: the host's fixed cost of one batch, in ms: the spans ``presize``
+ ``score_plan`` (the batch plans, ``models/msm_basic.py`` and
``models/msm_jax.py::score_batches``) + ``score_batch {enqueue: true}`` (the
dispatches) of a job, over ``presize``'s ``batches``; median per job.  A job
of one group has no ``presize`` span and reads as None."""
import jobtrace


def read(run):
    def per_batch(rec):
        presize = jobtrace.spans(rec, "presize")
        batches = sum(s.get("attrs", {}).get("batches", 0) for s in presize)
        if not batches:
            return None
        host = sum(s["dur"] for s in presize
                   + jobtrace.spans(rec, "score_plan"))
        host += sum(s["dur"] for s in jobtrace.spans(rec, "score_batch")
                    if s.get("attrs", {}).get("enqueue"))
        return 1000.0 * host / batches

    return jobtrace.median_over_jobs(run["jobs"], per_batch)
