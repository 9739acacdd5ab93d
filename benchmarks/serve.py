"""The system under test as the benchmark sees it: ONE ``engine.cli serve``
child that alone owns the cell's chips, driven over HTTP.  The driver of
``chip_smoke.py`` (PR 21), copied so that later PRs cannot change the
yardstick.  Nothing here imports jax."""

from __future__ import annotations

import json
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

TERMINAL = ("done", "failed", "cancelled", "quarantined")


class BenchFailure(Exception):
    """The run cannot produce a result (not the same as ``correct: false``)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def http(base: str, method: str, path: str, body=None, timeout=60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(base + path, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw, status = r.read(), r.status
    except urllib.error.HTTPError as e:
        raw, status = e.read(), e.code
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw.decode(errors="replace")


def metric_sum(text: str, name: str, label: str = "") -> float | None:
    """Sum of a family's samples (those carrying ``label`` when given); None
    when the family is not exposed at all."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(f"# TYPE {name} "):
            seen = True
        elif line.startswith(name) and line[len(name):len(name) + 1] in "{ ":
            if label and label not in line:
                continue
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


def metric_max(text: str, name: str) -> float | None:
    vals = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith(name) and line[len(name):len(name) + 1] in "{ "]
    return max(vals) if vals else None


class Serve:
    """One ``engine.cli serve`` child with ``sm_config`` written to a fresh
    directory."""

    def __init__(self, root: Path, work: Path, sm_config: dict):
        self.root = root
        self.dir = work
        self.queue = work / "queue"
        self.results = work / "results"
        self.log = work / "serve.log"
        sm = json.loads(json.dumps(sm_config))
        sm.setdefault("storage", {})["results_dir"] = str(self.results)
        sm["work_dir"] = str(work / "work")
        sm.setdefault("service", {})["http_port"] = 0
        self.sm = sm
        (work / "sm.json").write_text(json.dumps(sm, indent=1))
        self.proc: subprocess.Popen | None = None
        self.base = ""

    def __enter__(self) -> "Serve":
        with open(self.log, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "sm_distributed_tpu.engine.cli",
                 "serve", str(self.queue), "--sm-config",
                 str(self.dir / "sm.json")],
                cwd=str(self.root), stdout=log, stderr=subprocess.STDOUT)
        return self

    def ready(self) -> None:
        """Block until the child announces its admin API."""
        deadline = time.time() + 300.0
        pat = re.compile(r"admin API on (http://[\w.\-]+:\d+)")
        while time.time() < deadline:
            m = pat.search(self.log_text())
            if m:
                self.base = m.group(1)
                return
            if self.proc.poll() is not None:
                break
            time.sleep(0.1)
        self.stop()
        self.fail_with_log("serve never announced its admin API")

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self, grace: float = 10.0) -> None:
        """Make sure the child has ended (the clean path is ``sigterm``)."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def log_text(self) -> str:
        try:
            return self.log.read_text(errors="replace")
        except OSError:
            return ""

    def fail_with_log(self, msg: str):
        tail = "\n".join(self.log_text().splitlines()[-40:])
        raise BenchFailure(f"{msg}; end of {self.log}:\n{tail}")

    def get(self, path: str, expect: int = 200, timeout: float = 60.0):
        status, body = http(self.base, "GET", path, timeout=timeout)
        check(status == expect, f"GET {path} -> {status}: {str(body)[:300]}")
        return body

    def metrics(self) -> str:
        return self.get("/metrics")

    def submit(self, msg: dict) -> str:
        status, body = http(self.base, "POST", "/submit", msg)
        check(status == 202, f"POST /submit -> {status}: {body}")
        return body["msg_id"]

    def jobs(self) -> dict:
        return {r["msg_id"]: r for r in self.get("/jobs")}

    def alive(self) -> None:
        if self.proc.poll() is not None:
            self.fail_with_log(f"serve exited {self.proc.returncode} "
                               "with the run in progress")

    def trace(self, msg_id: str) -> list[dict]:
        return self.get(f"/jobs/{msg_id}/trace?raw=1")["records"]

    def sigterm(self, timeout: float = 120.0) -> float:
        """Graceful stop: exit 0 and nothing left in running/."""
        t0 = time.time()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.fail_with_log(f"serve ignored SIGTERM for {timeout:.0f}s")
        check(rc == 0, f"serve exited {rc} on SIGTERM")
        left = list((self.queue / "sm_annotate" / "running").glob("*"))
        check(not left, f"running/ not empty after SIGTERM: {left}")
        return time.time() - t0


def identity(serve: Serve, platform: str, chips: int) -> dict:
    """The platform assertion: the child runs on ``platform`` and holds at
    least the cell's ``chips`` (``service.device_pool_size`` in the
    configuration leases out exactly that many), or the run ends without a
    result - never a CPU number."""
    ident = serve.get("/debug/devices")["runtime"]
    check(ident["platform"] == platform,
          f"serve runs on platform {ident['platform']!r}, not {platform!r}")
    check(ident["device_count"] >= chips,
          f"serve holds {ident['device_count']} device(s), the cell asks "
          f"for {chips}")
    return ident


def hidden_routes(serve: Serve, chips: int) -> list[str]:
    """Routes that hide the device, as the service itself counts them
    (``chip_smoke.py::check_served_state``): each is a broken guarantee."""
    text = serve.metrics()
    bad = []
    for name, why in (
            ("sm_breaker_degraded_total", "a job degraded to numpy"),
            ("sm_oom_events_total", "a batch hit an OOM backoff"),
            ("sm_pallas_interpret_total",
             "a Pallas kernel ran in interpret mode")):
        v = metric_sum(text, name)
        if v is None:
            bad.append(f"/metrics does not expose {name}")
        elif v != 0:
            bad.append(f"{name} = {v}: {why}")
    if (metric_sum(text, "sm_breaker_state") or 0) != 0:
        bad.append("a device breaker is not closed (sm_breaker_state)")
    dev = serve.get("/debug/devices")
    open_ = {k: b["state"] for k, b in dev["breakers"].items()
             if b["state"] != "closed"}
    if open_:
        bad.append(f"breakers not closed: {open_}")
    if dev["size"] != chips:
        bad.append(f"device pool holds {dev['size']} chips, not {chips}")
    return bad
