"""Device: 1 - union of device-op intervals over the capture, mean over the
cell's chips (a chip with no op in the capture is idle all of it)."""


def read(run):
    dev, cap = run.get("device"), run.get("capture")
    if not dev or not cap or not dev["chips"]:
        return None
    busy = sum(c["busy_s"] for c in dev["chips"]) / run["cell"]["chips"]
    return 100.0 * (1.0 - busy / cap["seconds"])
