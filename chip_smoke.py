#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served annotation path still
starts, scores and answers correctly on ONE TPU chip.

    python3 chip_smoke.py

drives the system's main path once, through the entry points a user calls,
at the ``scale`` deployment's real size, and checks the answers against the
numpy oracle.  It exits 0 and prints, as the LAST line of stdout,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

only when every leg passed on a TPU.  Any other platform (this sandbox's
CPU included), any failed check, any leg's non-zero exit: non-zero exit and
no result line.  There is no flag and no environment variable that relaxes
that; the CPU tests call the legs below as functions and pass the platform
they expect.

One process per chip.  This process never imports jax.  Each leg that needs
the chip is a child that owns it alone and has exited before the next one
starts:

1. ``kernels``  every Pallas kernel compiled by Mosaic (``interpret=False``)
   and compared with its jnp/numpy oracle inside its declared NUMERICS
   contract, on seeded inputs, at the pixel counts of the headline (4,096),
   scale (65,536) and desi (262,144) deployments, strips at 1024x1024.
   The two datasets are generated by numpy-only children meanwhile.
2. ``serve``    one ``python -m sm_distributed_tpu.engine.cli serve``
   process with the shipped ``parallel.*`` defaults, driven over HTTP:
   identity from ``GET /debug/devices``; four submits (256x256 cold, the
   same again, 64x64, and 64x64 against a 97-formula DB — one batch, the
   ``plain`` variant); per job the backend and extraction variant of
   every batch, compiles vs persistent-cache hits; two reads per dataset;
   ``/metrics``; SIGTERM -> exit 0 with ``running/`` empty.
3. ``four_chip`` when the service reported >= 4 TPU chips: four concurrent
   one-chip jobs on four distinct chips, then one ``devices: 4`` job through
   the sharded backend.  Otherwise ``four_chip: not run (N devices)``.
4. ``oracle``   numpy only: per-ion (chaos, spatial, spectral, msm) of every
   stored result against ``NumpyBackend`` inside the NUMERICS component
   contracts, FDR levels identical, every ground-truth positive at
   FDR <= 0.1.  The whole table for 64x64; a seeded 1,000-ion sample for
   256x256 (its FDR is then re-derived from the served msm of all ions).

``python3 chip_smoke.py --leg NAME`` runs one leg by hand (``desi`` is the
512x512 deployment, by hand only — it does not fit the time limit).  Every
chip leg makes the same platform assertion.
"""

from __future__ import annotations

import json
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".cache" / "chip_smoke"
PLATFORM = "tpu"

# the `scale` / headline / desi deployments (bench.py's cells): one seeded
# spheroid fixture at three sizes, 500 formulas x (+H and 20 decoy adducts),
# ppm 3, K=4 isotope peaks
N_FORMULAS = 500
DS_CONFIG = {"isotope_generation": {"adducts": ["+H"]},
             "image_generation": {"ppm": 3.0}}
SIZES = {"headline": 64, "scale": 256, "desi": 512}
ORACLE_SAMPLE = {"headline": None, "scale": 1000, "desi": 300}
SMALL_DB = 97                 # formulas: 2,037 ions, one formula_batch
SEED = 7


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ datasets
def make_dataset(work: Path, name: str, side: int | None = None,
                 n_formulas: int = N_FORMULAS, noise_peaks: int = 200):
    """The deployment's dataset, generated from the seed (or found again:
    generation is deterministic and ``reuse`` checks its parameters).
    numpy only.  Returns (imzml path, formulas, ground-truth positives)."""
    from sm_distributed_tpu.io.fixtures import (
        expand_formula_list,
        generate_synthetic_dataset,
    )

    side = side or SIZES[name]
    path, truth = generate_synthetic_dataset(
        work / "datasets" / name, nrows=side, ncols=side,
        formulas=expand_formula_list(n_formulas), present_fraction=0.6,
        noise_peaks=noise_peaks, seed=SEED, reuse=True)
    return Path(path), list(truth.formulas), list(truth.present)


# ------------------------------------------------------------- child legs
def run_child(leg: str, work: Path, *args: str, timeout: float) -> None:
    """One leg as a child of its own; returns when it has EXITED 0."""
    cmd = [sys.executable, str(Path(__file__).resolve()), "--leg", leg,
           str(work), *args]
    t0 = time.time()
    proc = subprocess.Popen(cmd)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"leg {leg} still running after {timeout:.0f}s")
    finally:
        stop_process(proc)
    check(rc == 0, f"leg {leg} exited {rc}")
    say(f"leg {leg}: ok in {time.time() - t0:.1f}s")


def stop_process(proc: subprocess.Popen, grace: float = 10.0) -> None:
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


# ------------------------------------------------------------ identity
def jax_identity() -> dict:
    """Device identity as JAX reports it — for the legs that ARE the
    process holding the chip."""
    import jax

    from sm_distributed_tpu.utils.devicemem import runtime_identity

    jax.devices()                     # bring the backend up
    return runtime_identity()


def require_platform(ident: dict, platform: str, who: str) -> None:
    v = ident["versions"]
    say(f"{who}: platform={ident['platform']} "
        f"device_kind={ident['device_kind']} count={ident['device_count']} "
        f"jax={v['jax']} jaxlib={v['jaxlib']} libtpu={v['libtpu']}")
    check(ident["platform"] == platform,
          f"{who} runs on platform {ident['platform']!r}, not {platform!r} "
          f"— this smoke only passes on the chip")


# ------------------------------------------------------------ kernel leg
def _contract(module, site: str) -> int:
    from sm_distributed_tpu.analysis.numerics import contract_ulps, parse_policy

    return contract_ulps(parse_policy(module.NUMERICS[site])["contract"])


# |delta| of an assembled correlation (a value in [0, 1]) that N f32 ulps
# at 1.0 amount to — how the moment kernels' ulp(N) contracts are proven
# (tests/test_moments.py): raw centered sums cancel, their ratio does not
def _corr_atol(ulps: int) -> float:
    return ulps * 2.0 ** -24


def _corr(normsq, dots):
    import numpy as np

    normsq = np.asarray(normsq, np.float64)
    dots = np.asarray(dots, np.float64)
    denom = np.sqrt(np.maximum(normsq[..., 0:1] * normsq, 0))
    return np.where(denom > 0, dots / np.maximum(denom, 1e-30), 0.0)


def _ion_images(rng, n: int, k: int, p: int):
    """Seeded integer-grid images (what extraction emits): sparse small
    integers, so every pixel sum is exact in f32 in any order."""
    import numpy as np

    return (rng.integers(0, 50, size=(n, k, p))
            * (rng.random((n, k, p)) < 0.4)).astype(np.float32)


def kernel_moments(p: int, interpret: bool, masked: bool, n: int = 8,
                   k: int = 4) -> str:
    import numpy as np

    from sm_distributed_tpu.ops import moments_pallas as mp

    rng = np.random.default_rng(SEED)
    imgs = _ion_images(rng, n, k, p)
    n_real = p
    if masked:
        n_real = p - p // 8           # a lattice-padded tail of zero pixels
        imgs[:, :, n_real:] = 0.0
        got = mp.batch_moments_pallas_masked(
            imgs, np.int32(n_real), interpret=interpret)
        site = "batch_moments_pallas_masked"
    else:
        got = mp.batch_moments_pallas(imgs, interpret=interpret)
        site = "batch_moments_pallas"
    sums, normsq, dots, vmax, nn = [np.asarray(x) for x in got]
    real = imgs[:, :, :n_real].astype(np.float64)
    cent = real - real.mean(-1, keepdims=True)
    ulps = _contract(mp, site)
    check(np.array_equal(sums, real.sum(-1).astype(np.float32)),
          f"{site} P={p}: pixel sums differ from the f64 oracle")
    check(np.array_equal(vmax, real[:, 0].max(-1).astype(np.float32))
          and np.array_equal(nn, (real[:, 0] > 0).sum(-1)),
          f"{site} P={p}: vmax / positive count differ")
    err = np.abs(_corr(normsq, dots)
                 - _corr((cent * cent).sum(-1),
                         (cent[:, 0:1] * cent).sum(-1))).max()
    check(err <= _corr_atol(ulps),
          f"{site} P={p}: correlation off by {err:.3g} > ulp({ulps})")
    return f"sums/vmax/nn exact, correlation err {err:.2g} <= ulp({ulps})"


def _chaos_oracle(img2d, nlevels: int) -> int:
    import numpy as np
    from scipy import ndimage

    s4 = [[0, 1, 0], [1, 1, 1], [0, 1, 0]]
    img = np.maximum(img2d.astype(np.float32), 0.0)
    vmax = img.max()
    return sum(
        ndimage.label(img > vmax * (np.float32(li) / np.float32(nlevels)),
                      structure=s4)[1] for li in range(nlevels))


def kernel_chaos(side: int, interpret: bool, strips: bool, n: int = 6,
                 nlevels: int = 30, strip_rows=None) -> str:
    import numpy as np

    from sm_distributed_tpu.ops import chaos_pallas as cp

    rng = np.random.default_rng(SEED)
    p = side * side
    imgs = np.where(rng.random((n, p)) < 0.35, rng.random((n, p)),
                    0).astype(np.float32)
    # one structured image (a noisy blob: few large components), one empty
    yy, xx = np.mgrid[0:side, 0:side]
    blob = np.clip(1.0 - np.hypot(yy - side / 2, xx - side / 2)
                   / (side / 2), 0, 1)
    imgs[0] = (blob * (0.8 + 0.4 * rng.random(blob.shape))).ravel()
    imgs[1] = 0.0
    if strips:
        got = cp.chaos_count_sums_strips(
            imgs, nrows=side, ncols=side, nlevels=nlevels,
            interpret=interpret, strip_rows=strip_rows)
        route = "strips"
    else:
        # one more program of images holding a few isolated pixels each (a
        # decoy's noise): the kernel's label-free path, and its flag
        rp, cpad, ib = cp._pack_geometry(side, side, 512)
        sparse = np.zeros((-n % ib + ib, side, side), np.float32)
        for img in sparse[-n % ib:]:
            img[2 * rng.integers(0, side // 2, 9),
                2 * rng.integers(0, side // 2, 9)] = rng.integers(1, 31, 9)
        imgs = np.concatenate([imgs, sparse.reshape(-1, p)])
        n = len(imgs)
        got, flood = cp.chaos_count_sums(imgs, nrows=side, ncols=side,
                                         nlevels=nlevels, interpret=interpret)
        route = "lean" if rp * cpad * ib > cp._MAX_CELLS else "packed"
        m = imgs.reshape(n, side, side) > 0
        pair = (m[:, :, 1:] & m[:, :, :-1]).any(axis=(1, 2)) \
            | (m[:, 1:] & m[:, :-1]).any(axis=(1, 2))
        want_flood = pair.reshape(-1, ib).any(axis=1).astype(int).tolist()
        check(np.asarray(flood).tolist() == want_flood
              and want_flood[-1] == 0,
              f"chaos {route} {side}x{side}: flood flags "
              f"{np.asarray(flood).tolist()} != {want_flood}")
    got = np.asarray(got)
    want = [_chaos_oracle(imgs[i].reshape(side, side), nlevels)
            for i in range(n)]
    check(got.tolist() == want,
          f"chaos {route} {side}x{side}: component counts {got.tolist()} "
          f"!= scipy {want}")
    return f"{route}: {n} images x {nlevels} levels bit-exact vs scipy"


def kernel_cases(interpret: bool) -> list[tuple[str, object]]:
    """(label, thunk) per kernel x shape.  ``interpret`` selects the CPU
    test sizes (tests/test_chip_smoke.py); the chip runs the real ones."""
    if interpret:
        pixels, sides, strips = [256], [16], (24, 8)
    else:
        pixels, sides, strips = [4096, 65536, 262144], [64, 256, 512], \
            (1024, None)
    cases: list[tuple[str, object]] = []
    for p in pixels:
        for masked in (False, True):
            name = "moments_masked" if masked else "moments"
            cases.append((f"{name} P={p}", lambda p=p, m=masked:
                          kernel_moments(p, interpret, m)))
    for side in sides:
        cases.append((f"chaos {side}x{side}", lambda s=side:
                      kernel_chaos(s, interpret, strips=False)))
    cases.append((f"chaos_strips {strips[0]}x{strips[0]}", lambda:
                  kernel_chaos(strips[0], interpret, strips=True, n=3,
                               strip_rows=strips[1])))
    return cases


def kernels_leg(platform: str, interpret: bool = False) -> None:
    require_platform(jax_identity(), platform, "kernels leg")
    for label, thunk in kernel_cases(interpret):
        t0 = time.time()
        detail = thunk()
        say(f"kernel {label}: compiled by "
            f"{'the interpreter' if interpret else 'Mosaic'}, {detail} "
            f"({time.time() - t0:.1f}s)")


# ------------------------------------------------------------- serve leg
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


class Serve:
    """One ``engine.cli serve`` child: the process that owns the chip(s)."""

    def __init__(self, work: Path, name: str, sm_overrides: dict | None = None):
        self.dir = work / name
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        self.queue = self.dir / "queue"
        self.results = self.dir / "results"
        self.log = self.dir / "serve.log"
        # shipped defaults for every parallel.* knob: what a user gets
        sm = {"backend": "jax_tpu",
              "storage": {"results_dir": str(self.results)},
              "work_dir": str(self.dir / "work"),
              "service": {"http_port": 0}}
        for key, val in (sm_overrides or {}).items():
            sm[key] = {**sm.get(key, {}), **val} if isinstance(val, dict) \
                else val
        self.sm = sm
        (self.dir / "sm.json").write_text(json.dumps(sm, indent=1))
        self.proc = None
        self.base = ""

    def __enter__(self) -> "Serve":
        with open(self.log, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "sm_distributed_tpu.engine.cli",
                 "serve", str(self.queue), "--sm-config",
                 str(self.dir / "sm.json")],
                cwd=str(ROOT), stdout=log, stderr=subprocess.STDOUT)
        deadline = time.time() + 300.0
        pat = re.compile(r"admin API on (http://[\w.\-]+:\d+)")
        while time.time() < deadline:
            m = pat.search(self.log_text())
            if m:
                self.base = m.group(1)
                return self
            if self.proc.poll() is not None:
                break
            time.sleep(0.2)
        self.fail_with_log("serve never announced its admin API")

    def __exit__(self, *exc) -> None:
        if self.proc is not None:
            stop_process(self.proc)

    def log_text(self) -> str:
        try:
            return self.log.read_text(errors="replace")
        except OSError:
            return ""

    def fail_with_log(self, msg: str):
        tail = "\n".join(self.log_text().splitlines()[-40:])
        raise SmokeFailure(f"{msg}; end of {self.log}:\n{tail}")

    def get(self, path: str, expect: int = 200):
        status, body = http(self.base, "GET", path)
        check(status == expect, f"GET {path} -> {status}: {str(body)[:300]}")
        return body

    def metrics(self) -> str:
        return self.get("/metrics")

    def submit(self, msg: dict) -> str:
        status, body = http(self.base, "POST", "/submit", msg)
        check(status == 202, f"POST /submit -> {status}: {body}")
        return body["msg_id"]

    def wait(self, msg_ids: list[str], timeout: float) -> dict:
        deadline = time.time() + timeout
        while True:
            rows = {r["msg_id"]: r for r in self.get("/jobs")}
            if all(rows.get(m, {}).get("state") in
                   ("done", "failed", "cancelled", "quarantined")
                   for m in msg_ids):
                return rows
            if self.proc.poll() is not None:
                self.fail_with_log(f"serve exited {self.proc.returncode} "
                                   "with jobs in flight")
            if time.time() > deadline:
                self.fail_with_log(f"jobs {msg_ids} not terminal after "
                                   f"{timeout:.0f}s")
            time.sleep(0.5)

    def sigterm(self) -> None:
        """Graceful stop: exit 0 and nothing left in running/."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=180.0)
        except subprocess.TimeoutExpired:
            self.fail_with_log("serve ignored SIGTERM for 180s")
        check(rc == 0, f"serve exited {rc} on SIGTERM")
        left = list((self.queue / "sm_annotate" / "running").glob("*"))
        check(not left, f"running/ not empty after SIGTERM: {left}")
        say("serve: SIGTERM -> exit 0, running/ empty")


def metric_sum(text: str, name: str) -> float | None:
    """Sum of a family's samples; None when the family is not exposed at
    all (a registered family with no sample yet counts 0)."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(f"# TYPE {name} "):
            seen = True
        elif line.startswith(name) and line[len(name):len(name) + 1] in "{ ":
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


def job_report(serve: Serve, msg_id: str, row: dict, before: str,
               after: str, wall: float, *, sharded: bool = False) -> dict:
    """Print and check one finished job: state, attempts, the backend and
    extraction variant of every batch, the lease against where the batches
    ran, compiles vs persistent-cache hits."""
    check(row["state"] == "done",
          f"job {msg_id} ended {row['state']}: {row.get('error')}")
    check(row["attempts"] == 1,
          f"job {msg_id} needed {row['attempts']} attempts: "
          f"{row.get('error')}")
    recs = serve.get(f"/jobs/{msg_id}/trace?raw=1")["records"]
    spans = [r for r in recs if r.get("kind") == "span"]
    events = [r for r in recs if r.get("kind") == "event"]
    batches = [s for s in spans if s["name"] == "score_batch"]
    check(batches, f"job {msg_id}: no score_batch span on its trace")
    want = "jax_tpu_sharded" if sharded else "jax_tpu"
    backends = sorted({s.get("attrs", {}).get("backend") for s in batches})
    check(backends == [want],
          f"job {msg_id}: batches scored by {backends}, not only {want}")
    by_span = {e["span_id"]: e["attrs"] for e in events
               if e["name"] == "batch_variant"}
    variants = [by_span.get(s["span_id"], {}) for s in batches]
    check(all(v.get("variant") for v in variants),
          f"job {msg_id}: a batch carries no batch_variant event")
    lease = next((e.get("attrs", {}).get("devices") for e in events
                  if e["name"] == "device_token_acquired"), None)
    check(lease, f"job {msg_id}: no device lease on its trace")
    if sharded:
        ran_on = sorted({d for s in spans if s["name"] == "device_sync"
                         for d in s.get("attrs", {}).get("devices", [])})
    else:
        ran_on = sorted({s["attrs"].get("device") for s in batches})
    check(ran_on == sorted(lease),
          f"job {msg_id}: lease {lease} but batches ran on {ran_on}")
    compiles = (metric_sum(after, "sm_compile_events_total") or 0) - \
        (metric_sum(before, "sm_compile_events_total") or 0)
    hits = (metric_sum(after, "sm_compile_cache_hits_total") or 0) - \
        (metric_sum(before, "sm_compile_cache_hits_total") or 0)
    tally: dict[str, int] = {}
    for v in variants:
        key = f"{v['variant']}@b{v.get('b')}"
        tally[key] = tally.get(key, 0) + 1
    say(f"job {msg_id}: state=done attempts=1 wall={wall:.1f}s "
        f"backend={want} batches={len(batches)} variants={tally} "
        f"lease={lease} ran_on={ran_on} compiles={int(compiles)} "
        f"cache_hits={int(hits)}")
    return {"msg_id": msg_id, "compiles": int(compiles), "hits": int(hits),
            "lease": lease, "wall": wall,
            "variants": {v["variant"] for v in variants}}


def run_job(serve: Serve, ds_id: str, path: Path, formulas: list[str],
            timeout: float, **extra) -> dict:
    before = serve.metrics()
    t0 = time.time()
    msg_id = serve.submit({"ds_id": ds_id, "msg_id": ds_id,
                           "input_path": str(path), "formulas": formulas,
                           "ds_config": DS_CONFIG, **extra})
    row = serve.wait([msg_id], timeout)[msg_id]
    return job_report(serve, msg_id, row, before, serve.metrics(),
                      time.time() - t0, sharded=extra.get("devices", 1) > 1)


def read_twice(serve: Serve, ds_id: str) -> None:
    q = f"/datasets/{ds_id}/annotations?order=msm&dir=desc&limit=5"
    cold, warm = serve.get(q), serve.get(q)
    rows = cold["rows"]
    check(rows, f"read of {ds_id} returned no annotation")
    check(cold == warm, f"read of {ds_id} changed between two calls")
    msm = [r["msm"] for r in rows]
    check(msm == sorted(msm, reverse=True),
          f"read of {ds_id} is not msm-descending: {msm}")
    say(f"read {ds_id}: 2 reads answered, top msm {msm[0]:.4f}")


def check_served_state(serve: Serve, ident: dict) -> None:
    """No route that hides the device was taken, as the service itself
    counts them."""
    text = serve.metrics()
    for name, why in (
            ("sm_breaker_degraded_total", "a job degraded to numpy"),
            ("sm_oom_events_total", "a batch hit an OOM backoff"),
            ("sm_pallas_interpret_total",
             "a Pallas kernel ran in interpret mode")):
        v = metric_sum(text, name)
        check(v is not None, f"/metrics does not expose {name}")
        check(v == 0, f"{name} = {v}: {why}")
    check((metric_sum(text, "sm_breaker_state") or 0) == 0,
          "a device breaker is not closed (sm_breaker_state)")
    dev = serve.get("/debug/devices")
    bad = {k: b["state"] for k, b in dev["breakers"].items()
           if b["state"] != "closed"}
    check(not bad, f"breakers not closed: {bad}")
    check(dev["size"] == ident["device_count"],
          f"device pool holds {dev['size']} chips, jax reports "
          f"{ident['device_count']}")
    done = metric_sum(text, 'sm_jobs_total')
    say(f"served state: degraded=0 oom=0 interpret=0 breakers closed "
        f"pool={dev['size']} chips sm_jobs_total={done}")


def serve_identity(serve: Serve, platform: str) -> dict:
    ident = serve.get("/debug/devices")["runtime"]
    require_platform(ident, platform, "serve")
    return ident


def serve_leg(work: Path, platform: str, big: str = "scale",
              small: str = "headline", sm_overrides: dict | None = None,
              job_timeout: float = 900.0, datasets: dict | None = None) -> dict:
    """The main path: three submits through one ``serve`` process."""
    datasets = datasets or {n: make_dataset(work, n) for n in (big, small)}
    with Serve(work, "serve", sm_overrides) as serve:
        ident = serve_identity(serve, platform)
        big_path, formulas, _ = datasets[big]
        cold = run_job(serve, f"{big}_cold", big_path, formulas, job_timeout)
        warm = run_job(serve, f"{big}_again", big_path, formulas, job_timeout)
        check(warm["compiles"] == 0,
              f"the second submit of the same dataset compiled "
              f"{warm['compiles']} program(s)")
        small_path, small_formulas, _ = datasets[small]
        run_job(serve, small, small_path, small_formulas, job_timeout)
        one = run_job(serve, f"{small}_small_db", small_path,
                      small_formulas[:SMALL_DB], job_timeout)
        check(one["variants"] == {"plain"},
              f"the small-DB job left the plain variant: {one['variants']}")
        for ds_id in (f"{big}_cold", f"{big}_again", small,
                      f"{small}_small_db"):
            read_twice(serve, ds_id)
        check_served_state(serve, ident)
        serve.sigterm()
    say(f"serve leg: {big} cold {cold['wall']:.1f}s "
        f"({cold['compiles']} compiles, {cold['hits']} cache hits), again "
        f"{warm['wall']:.1f}s ({warm['hits']} cache hits)")
    return ident


def desi_leg(work: Path, platform: str) -> None:
    """BASELINE #5's whole-slide size, by hand: one 512x512 submit.  The
    flat path's histogram scratch is 17 GiB at the shipped
    formula_batch=2048 and the backend refuses it up front, so this is
    bench.py's desi setting (256)."""
    path, formulas, _ = make_dataset(work, "desi")
    with Serve(work, "serve_desi",
               {"parallel": {"formula_batch": 256}}) as serve:
        ident = serve_identity(serve, platform)
        run_job(serve, "desi", path, formulas, 3000.0)
        read_twice(serve, "desi")
        check_served_state(serve, ident)
        serve.sigterm()


def four_chip_leg(work: Path, platform: str, n_chips: int = 4,
                  job_timeout: float = 900.0, sm_overrides: dict | None = None,
                  dataset: tuple | None = None) -> None:
    """Packing and sharding on one multi-chip host."""
    path, formulas = dataset or make_dataset(work, "headline")[:2]
    with Serve(work, "serve_four", {**(sm_overrides or {}),
                                    "service": {"workers": n_chips}}) as serve:
        ident = serve_identity(serve, platform)
        check(ident["device_count"] >= n_chips,
              f"four-chip leg on {ident['device_count']} device(s)")
        # (a) N concurrent one-chip jobs hold N distinct chips
        before = serve.metrics()
        t0 = time.time()
        ids = [serve.submit({"ds_id": f"pack{i}", "msg_id": f"pack{i}",
                             "input_path": str(path), "formulas": formulas,
                             "ds_config": DS_CONFIG}) for i in range(n_chips)]
        rows = serve.wait(ids, job_timeout)
        after = serve.metrics()
        leases = [tuple(job_report(serve, m, rows[m], before, after,
                                   time.time() - t0)["lease"]) for m in ids]
        check(all(len(le) == 1 for le in leases)
              and len(set(leases)) == n_chips,
              f"{n_chips} concurrent one-chip jobs did not hold {n_chips} "
              f"distinct chips: {leases}")
        resident = sorted(set(re.findall(
            r"flat peaks resident: .* on \{(\w+)\(id=(\d+)", serve.log_text())))
        check(len(resident) == n_chips
              and all(kind.lower().startswith(platform) for kind, _ in
                      resident),
              f"resident arrays did not land on {n_chips} distinct "
              f"{platform} devices: {resident}")
        say(f"four_chip packing: leases {sorted(leases)}, resident on "
            f"{resident}")
        # (b) one devices=N job through the sharded backend
        run_job(serve, "sharded", path, formulas, job_timeout,
                devices=n_chips)
        read_twice(serve, "sharded")
        check_served_state(serve, ident)
        serve.sigterm()


# ------------------------------------------------------------ oracle leg
def oracle_check(results: Path, work_dir: Path, ds_ids: list[str],
                 path: Path, formulas: list[str], present: list[str],
                 sample: int | None) -> None:
    """Stored results of ``ds_ids`` (all jobs over ONE dataset) against the
    numpy oracle.  numpy only."""
    import numpy as np
    import pandas as pd

    from sm_distributed_tpu.analysis.numerics import (
        COMPONENT_CONTRACTS,
        component_report,
    )
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.models.msm_basic import NumpyBackend
    from sm_distributed_tpu.ops.fdr import FDR
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    t0 = time.time()
    ds_config = DSConfig.from_dict(DS_CONFIG)
    sm = SMConfig()
    served = {d: pd.read_parquet(results / d / "all_metrics.parquet")
              for d in ds_ids}
    first = served[ds_ids[0]]
    check(len(first) == len(formulas) * (1 + sm.fdr.decoy_sample_size),
          f"{ds_ids[0]}: {len(first)} ions stored, expected "
          f"{len(formulas)} x {1 + sm.fdr.decoy_sample_size}")
    n = len(first)
    idx = (np.arange(n) if sample is None or sample >= n else np.sort(
        np.random.default_rng(SEED).choice(n, size=sample, replace=False)))
    sub = first.iloc[idx]
    calc = IsocalcWrapper(ds_config.isotope_generation,
                          cache_dir=str(work_dir / "isocalc_cache"))
    table = calc.pattern_table(list(zip(sub.sf, sub.adduct)),
                               list(sub.is_target))
    check(list(table.sfs) == list(sub.sf), "oracle ion table out of order")
    want = NumpyBackend(SpectralDataset.from_imzml(path),
                        ds_config).score_batch(table)
    fdr = FDR(decoy_sample_size=sm.fdr.decoy_sample_size,
              target_adducts=ds_config.isotope_generation.adducts,
              seed=sm.fdr.seed)
    assignment = fdr.decoy_adduct_selection(formulas)
    cols = ["chaos", "spatial", "spectral", "msm"]
    for ds_id, df in served.items():
        check(list(df.sf) == list(first.sf)
              and list(df.adduct) == list(first.adduct),
              f"{ds_id}: ion table differs from {ds_ids[0]}")
        got = df.iloc[idx][cols].to_numpy()
        check(np.isfinite(got).all(), f"{ds_id}: non-finite metrics")
        report = component_report(got, want)
        drift = {c: f"{r['max_ulp']} ulp / {r['max_abs']:.1e}"
                 for c, r in report.items()}
        over = {c: r for c, r in report.items() if r["outside"]}
        check(not over,
              f"{ds_id}: ions outside the NUMERICS contracts "
              f"{COMPONENT_CONTRACTS}: {over}")
        # FDR from the oracle's msm where it covers the table, else
        # re-derived from the served msm of all ions
        msm = want[:, 3] if len(idx) == n else df.msm.to_numpy()
        ref = fdr.estimate_fdr(
            pd.DataFrame({"sf": df.sf, "adduct": df.adduct, "msm": msm}),
            assignment)
        ann = pd.read_parquet(results / ds_id / "annotations.parquet")
        both = ann.merge(ref, on=["sf", "adduct"], suffixes=("", "_ref"))
        check(len(both) == len(ann) == len(ref)
              and (both.fdr_level == both.fdr_level_ref).all(),
              f"{ds_id}: FDR levels differ from the oracle's")
        found = set(ann[(ann.fdr_level <= 0.1) & (ann.adduct == "+H")].sf)
        missing = sorted(set(present) - found)
        check(not missing,
              f"{ds_id}: {len(missing)} ground-truth positives above "
              f"FDR 0.1: {missing[:8]}")
        say(f"oracle {ds_id}: {len(idx)} of {n} ions "
            f"({'whole table' if len(idx) == n else 'seeded sample'}) "
            f"worst (ulp / abs) {drift} inside {COMPONENT_CONTRACTS}; FDR levels "
            f"identical ({len(ann)} annotations); all {len(present)} "
            f"ground-truth positives at FDR <= 0.1")
    say(f"oracle leg over {ds_ids}: {time.time() - t0:.1f}s")


def oracle_leg(work: Path, plan: list[dict]) -> None:
    for item in plan:
        path, formulas, present = make_dataset(work, item["dataset"])
        formulas = formulas[:item.get("n_formulas")]
        serve_dir = work / item["serve"]
        oracle_check(serve_dir / "results", serve_dir / "work",
                     item["ds_ids"], path, formulas,
                     [sf for sf in present if sf in set(formulas)],
                     ORACLE_SAMPLE[item["dataset"]])


# ------------------------------------------------------------------ main
# what the oracle leg compares: stored results of each serve run, per dataset
SERVE_PLAN = [
    {"dataset": "scale", "serve": "serve",
     "ds_ids": ["scale_cold", "scale_again"]},
    {"dataset": "headline", "serve": "serve", "ds_ids": ["headline"]},
    {"dataset": "headline", "serve": "serve",
     "ds_ids": ["headline_small_db"], "n_formulas": SMALL_DB},
]
FOUR_CHIP_PLAN = {"dataset": "headline", "serve": "serve_four",
                  "ds_ids": [f"pack{i}" for i in range(4)] + ["sharded"]}


def leg_main(leg: str, work: Path, args: list[str]) -> int:
    if leg == "dataset":
        make_dataset(work, args[0])
    elif leg == "kernels":
        kernels_leg(PLATFORM)
    elif leg == "serve":
        serve_leg(work, PLATFORM)
        run_child("oracle", work, json.dumps(SERVE_PLAN), timeout=900)
    elif leg == "four_chip":
        four_chip_leg(work, PLATFORM)
        run_child("oracle", work, json.dumps([FOUR_CHIP_PLAN]), timeout=900)
    elif leg == "desi":
        desi_leg(work, PLATFORM)
        run_child("oracle", work, json.dumps([
            {"dataset": "desi", "serve": "serve_desi", "ds_ids": ["desi"]}]),
            timeout=3000)
    elif leg == "oracle":
        oracle_leg(work, json.loads(args[0]))
    else:
        raise SmokeFailure(f"unknown leg {leg!r}")
    return 0


def run_all(work: Path) -> int:
    t0 = time.time()
    work.mkdir(parents=True, exist_ok=True)
    gens = [subprocess.Popen([sys.executable, str(Path(__file__).resolve()),
                              "--leg", "dataset", str(work), name])
            for name in ("headline", "scale")]
    try:
        run_child("kernels", work, timeout=600)
        for name, gen in zip(("headline", "scale"), gens):
            check(gen.wait(timeout=600) == 0,
                  f"generating the {name} dataset failed")
        say(f"datasets ready at {time.time() - t0:.1f}s")
    finally:
        for gen in gens:
            stop_process(gen)
    # this process only DRIVES the serve children (HTTP): still off jax
    ident = serve_leg(work, PLATFORM)
    plan = list(SERVE_PLAN)
    if ident["device_count"] >= 4:
        four_chip_leg(work, PLATFORM)
        plan.append(FOUR_CHIP_PLAN)
    else:
        say(f"four_chip: not run ({ident['device_count']} devices)")
    run_child("oracle", work, json.dumps(plan), timeout=600)
    check("jax" not in sys.modules, "the driver process imported jax")
    check(ident["platform"] == PLATFORM,
          f"identity platform {ident['platform']!r}")
    say(f"all legs passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": ident["platform"], "kind": ident["device_kind"],
        "count": ident["device_count"]}}), flush=True)
    return 0


def main(argv: list[str]) -> int:
    try:
        if argv[:1] == ["--leg"]:
            return leg_main(argv[1], Path(argv[2]), argv[3:])
        if argv:
            raise SmokeFailure(f"unknown arguments {argv}")
        return run_all(WORK)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL — {exc}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
