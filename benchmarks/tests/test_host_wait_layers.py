"""The four per-layer readers PR 35 added, each on a ``run`` as
``run.py::run_cell`` hands it to a reader.
``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_host_wait_layers.py -q``.

Three inputs: a hand-made job trace whose split is computed in the comments
beside it; ``data/host_wait_job.trace.jsonl``, one in-window job of a traced
``section64-uploads`` run on the chip (PR 35, seed 3500001001; the job whose
stall is the window's median, its lease hold inside the capture, so the
``device_*`` spans a capture appends are there to be left out), whose split is
held to what the PROGRAM's own ``scripts/trace_report.py::hold_split`` reads
from the same records (the benchmark's reader shares no code with it); and
``data/host_wait_metrics_{before,after}.txt``, two ``/metrics`` scrapes of an
in-process service on XLA-CPU around one 1.5 s capture and two jobs, cut to
the families near the ones read.  A trace from before the spans carried
``cpu`` (``data/slide256_job.trace.jsonl``, PR 30) reads None everywhere."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

NEW = ("hold_stall_s", "hold_unnamed_s", "host_cpu_per_job_s",
       "interp_late_ms")


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_trace(name: str) -> list[dict]:
    return [json.loads(line) for line in
            (DATA / name).read_text().splitlines() if line.strip()]


def span(name, ts, dur, sid, parent, cpu=None, **attrs):
    rec = {"kind": "span", "name": name, "ts": ts, "dur": dur,
           "span_id": sid, "parent_id": parent, "attrs": attrs}
    if cpu is not None:
        rec["cpu"] = cpu
    return rec


def made_job(shift=0.0):
    """The hold opens at 100.0, is granted at 101.0 after 0.01 s of polling
    CPU, and ends at 104.0: held 3.0 s.  Named under it: ``search_init``
    [101.0, 101.1], ``score`` [101.2, 102.4] with ``device_sync`` [101.9,
    102.3] below it, ``fdr`` [102.4, 102.7], ``store_results`` [102.7, 103.9],
    and a ``device_busy`` span a capture appended (not the job's thread: not
    counted).  Union of the named = 0.1 + 1.2 + 0.3 + 1.2 = 2.8, so unnamed
    = 0.2 (101.1-101.2 and 103.9-104.0).  ``device_hold.cpu`` 0.91 less
    ``wait_cpu_s`` 0.01 = ran 0.90; ``device_sync`` 0.4; stalled = 3.0 - 0.9
    - 0.4 = 1.7.  ``shift`` lengthens the hold's unnamed tail AND the stall;
    ``pre_lease`` before the grant and ``finish_job`` after the hold count
    for nothing."""
    t = 100.0
    return {"trace": [
        span("attempt", t - 1.0, 6.0 + shift, "a", "root"),
        span("pre_lease", t - 0.9, 0.9, "p", "a", cpu=0.8),
        span("device_hold", t, 4.0 + shift, "h", "a", cpu=0.91),
        {"kind": "event", "name": "device_token_acquired", "ts": t + 1.0,
         "span_id": "h", "attrs": {"devices": [0], "wait_cpu_s": 0.01}},
        span("search_init", t + 1.0, 0.1, "s0", "h", cpu=0.05),
        span("score", t + 1.2, 1.2, "s1", "h", cpu=0.3, phase=True),
        span("score_group", t + 1.25, 1.1, "s2", "s1", cpu=0.25),
        span("device_sync", t + 1.9, 0.4, "s3", "s2", cpu=0.001),
        span("fdr", t + 2.4, 0.3, "s4", "h", cpu=0.03, phase=True),
        span("store_results", t + 2.7, 1.2, "s5", "h", cpu=0.4, phase=True),
        span("device_busy", t + 1.0, 3.0, "d0", "h", chip=0, busy_s=0.3,
             hold_s=3.0, whole=True),
        span("finish_job", t + 4.0 + shift, 0.05, "f", "a", cpu=0.01),
    ]}


RECORDED = {"jobs": [{"trace": load_trace("host_wait_job.trace.jsonl")}]}
BEFORE_PR35 = {"jobs": [{"trace": load_trace("slide256_job.trace.jsonl")}],
               "metrics_before": "sm_jobs_total 1\n",
               "metrics_after": "sm_jobs_total 4\n"}
SCRAPES = {"jobs": [{"msg_id": "a"}, {"msg_id": "b"}],
           "metrics_before": (DATA / "host_wait_metrics_before.txt").read_text(),
           "metrics_after": (DATA / "host_wait_metrics_after.txt").read_text()}


@pytest.mark.parametrize("name,want", [
    ("hold_stall_s", 1.7 + 0.3),          # median of 1.7, 2.0, 2.6
    ("hold_unnamed_s", 0.2 + 0.3),
])
def test_span_readers_on_the_hand_made_jobs(name, want):
    run = {"jobs": [made_job(), made_job(0.3), made_job(0.9),
                    {"trace": None}]}
    assert reader(name)(run) == pytest.approx(want)
    assert reader(name)({"jobs": [made_job()]}) == pytest.approx(want - 0.3)


def test_span_readers_on_the_recorded_job():
    """One job of a traced ``section64-uploads`` run on the chip: the
    benchmark's split equals the program's own report of the same records,
    and the trace names all but a few milliseconds of the hold."""
    from scripts import trace_report

    want = trace_report.hold_split(RECORDED["jobs"][0]["trace"])
    stall, unnamed = (reader(n)(RECORDED) for n in NEW[:2])
    assert stall == pytest.approx(want["stalled_s"], abs=2e-6)
    assert unnamed == pytest.approx(want["unnamed_s"], abs=2e-6)
    assert 0.0 <= unnamed <= 0.03 < want["held_s"]
    assert want["ran_s"] + want["device_sync_s"] + stall \
        == pytest.approx(want["held_s"], abs=5e-6)


def test_counter_readers_on_the_recorded_scrapes():
    # sm_process_cpu_seconds_total 6.60 -> 6.98 over 2 in-window jobs
    assert reader("host_cpu_per_job_s")(SCRAPES) == pytest.approx(0.19)
    # late 0 -> 0.04624777571007144 s over 0 -> 146 wakes: 0.3168 ms
    assert reader("interp_late_ms")(SCRAPES) == pytest.approx(
        1000.0 * 0.04624777571007144 / 146)
    # an untraced run: the families are there and the probe never woke
    quiet = dict(SCRAPES, metrics_after=SCRAPES["metrics_before"])
    assert reader("interp_late_ms")(quiet) is None
    assert reader("host_cpu_per_job_s")(quiet) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_run_of_the_parent(name):
    """PR 34's program: spans without ``cpu``, no process or probe family."""
    assert reader(name)(BEFORE_PR35) is None
    assert reader(name)({"jobs": [], "metrics_before": "",
                         "metrics_after": ""}) is None


def test_every_new_metric_has_its_manifest_entry():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = ["section64-uploads", "section128-reannotate",
             "section64-uploads-pack4", "slide256-reannotate"]
    # by name, not by position: later PRs append after them
    new = {m["name"]: m for m in manifest["per_layer"] if m["name"] in NEW}
    assert tuple(new) == NEW
    assert all(m["workloads"][:4] == cells for m in new.values())
    assert {n: (m["layer"], m["source"], m["moves"], m["unit"])
            for n, m in new.items()} == {
        "hold_stall_s": ("admission queue leases", "program_span",
                         "report_s", "s"),
        "hold_unnamed_s": ("admission queue leases", "program_span",
                           "report_s", "s"),
        "host_cpu_per_job_s": ("host interpreter", "program_counter",
                               "ions_per_s", "s"),
        "interp_late_ms": ("host interpreter", "program_counter",
                           "report_s", "ms")}
    assert all((BENCH / "layers" / f"{n}.py").is_file() for n in NEW)
