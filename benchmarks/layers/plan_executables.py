"""Compile cache: distinct executables a job's planned stream settled on:
attr ``executables`` of span ``presize`` (``models/msm_jax.py::
JaxBackend._plan_census``: distinct (extraction variant, static batch, band
bucket)); median per job.  Each is one compile, or one load from the
persistent cache, for the first job of its geometry in a process.  None for
a job of one group (no ``presize`` span) or a program without the attr."""
import jobtrace


def read(run):
    def executables(rec):
        said = [s["attrs"]["executables"] for s in jobtrace.spans(rec, "presize")
                if "executables" in s.get("attrs", {})]
        return max(said) if said else None

    return jobtrace.median_over_jobs(run["jobs"], executables)
