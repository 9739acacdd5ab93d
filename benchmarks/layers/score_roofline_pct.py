"""Kernels: the least time the chip could take for the scoring of the jobs
that lie WHOLLY inside the capture (``counts.py``, ``peaks.json``), over the
device time of the programs that ran on the job's leased chip during that
job's scoring (lease granted -> last device_sync).  A job cut by the
capture's edge is left out on both sides.  Never clamped."""
import counts
import jobtrace


def read(run):
    dev, cap = run.get("device"), run.get("capture")
    if not dev or not cap:
        return None
    cfg = run["cell"]["config"]
    least = spent = 0.0
    for job in run["jobs"]:
        iv = jobtrace.score_interval(job.get("trace") or [])
        if iv is None or iv[0] < cap["t0"] or iv[1] > cap["t0"] + cap["seconds"]:
            continue
        lease = set(jobtrace.lease_devices(job["trace"]))
        a, b = iv[0] - cap["t0"], iv[1] - cap["t0"]
        dur = sum(m["dur_s"] for m in dev["modules"]
                  if m["chip"] in lease and a <= m["start_s"]
                  and m["start_s"] + m["dur_s"] <= b)
        if dur <= 0:
            continue
        ds = job["dataset"]
        n_ions = run["cell"]["n_ions"]
        k = cfg["guarantees"]["isotope_peaks"]
        px = ds["nrows"] * ds["ncols"]
        t, _ = counts.least_seconds(
            run["device_kind"],
            counts.job_bytes(ds["n_peaks"], n_ions, k, px,
                             cfg["sm_config"]["parallel"]["cube_dtype"]),
            counts.job_ops(ds["n_peaks"], n_ions, k, px,
                           cfg["ds_config"]["image_generation"].get("nlevels", 30)))
        least += t
        spent += dur
    return 100.0 * least / spent if spent > 0 else None
