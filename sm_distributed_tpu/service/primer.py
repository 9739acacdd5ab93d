"""Ahead-of-time XLA cache primer (ISSUE 13, tentpole front 2).

PR 12 made the compile surface declared and attributed; the shape-bucket
lattice (``ops/buckets.py``) makes it CLOSED — every dataset size maps
into a finite set of executables identified by recorded ``BucketSpec``s.
This module walks that set and compiles it into the persistent XLA cache
**before traffic arrives**, so a cold submit loads executables from disk
instead of paying the cold XLA compile:

- :func:`prime_spec` AOT-compiles ONE spec: it takes the very jit a real
  backend of the spec's geometry calls (``models/msm_jax.make_flat_jits``
  keeps one set of ``jax.jit`` objects a geometry — the same objects, not
  equal ones, so closure and static_argnames cannot drift) and lowers
  it against ``jax.ShapeDtypeStruct`` avals derived from the spec, so the
  persistent-cache entry it writes is byte-for-byte the entry a later job
  looks up.  No device arrays are materialized and no device time is
  spent — compilation is host work, which is why the primer can run while
  chips serve traffic without ever touching a device-pool lease;
- :class:`CachePrimer` is the scheduler-idle background thread
  (``service.prime`` config): it waits for the spool to sit idle, primes
  un-primed specs one at a time (re-checking idleness between specs — a
  real job arriving pauses the cycle at the next spec boundary), and
  records progress per spec in ``prime_manifest.json`` next to the cache,
  so a primer killed mid-cycle resumes where it stopped and a second run
  is a no-op;
- ``scripts/prime_cache.py`` drives the same :func:`prime_once` offline
  (deploy-time priming), and ``GET /debug/compile`` serves
  :meth:`CachePrimer.snapshot` — the primed-vs-missing bucket view.

Sharded (multi-chip lease) specs prime too (ISSUE 14 — the follow-up
PR 13 left): a recorded mesh-shaped spec carries its full lease topology
(mesh axes, per-shard pixel capacity, every host-plan shape), so
:func:`prime_spec` rebuilds the byte-identical ``jit(shard_map(step))``
program over a mesh of the first ``devices`` local chips and AOT-compiles
it — including the SHRUNKEN meshes a post-quarantine re-lease produces,
which record their own topology-keyed spec at first dispatch and are warm
for every later job of that lease shape.  A host with fewer visible
devices than the mesh skips the spec (``skipped:devices``); legacy
manifest entries (recorded before the topology fields exist, or naming a
variant since removed) skip as ``skipped:legacy_spec``.  The
``sm_prime_*`` metric family is documented in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

from ..analysis.numerics import numerics_surface
from ..analysis.surface import compile_surface
from ..ops import buckets as shape_buckets
from ..ops.quantize import CUBE_DTYPES
from ..utils.logger import logger

# No jax.jit call sites live here — the jitted programs come from
# models/msm_jax.make_flat_jits (registered in THAT module's surface), which
# hands the primer the objects the backends of a geometry call.
# This declaration attributes the AOT ``.compile()`` frames the retrace
# tracer sees when the primer pays a compile (scripts/compile_census.py
# requires every observed site's module to carry a registry).
COMPILE_SURFACE = compile_surface(__name__, {
    "prime_spec":
        "statics=closure(recorded BucketSpec statics); buckets=the "
        "ops/buckets lattice itself — the primer only ever compiles "
        "specs the backends recorded (flat AND mesh-shaped sharded, "
        "keyed on lease topology), so its surface is a subset of "
        "models/msm_jax's plus parallel/sharded's",
})

# Declared numerics contract (ISSUE 15): the primer rebuilds the
# BYTE-identical program a recorded spec dispatched (same function
# objects, same partial closure, same statics), so a primed executable
# is bit-for-bit the one a later real job looks up — priming can never
# change results.
NUMERICS = numerics_surface(__name__, {
    "prime_spec":
        "contract=bit_exact; test=tests/test_buckets.py::"
        "test_primer_idempotent_and_resumable",
})


def _resident_dtype(spec: dict):
    """The resident intensity aval's dtype: a spec records ``cube_dtype``
    only when it is not f32 (``JaxBackend._bucket_spec``)."""
    import numpy as np

    if spec.get("cube_dtype") == "bf16":
        import ml_dtypes  # jax dependency; baked into the image

        return ml_dtypes.bfloat16
    return np.float32


def _flat_lower_call(spec: dict):
    """(jitted fn, positional ShapeDtypeStruct avals, static kwargs) for
    one recorded flat-path spec — the exact calling convention of
    ``JaxBackend._dispatch`` for that variant."""
    import jax
    import numpy as np

    from ..models.msm_jax import make_flat_jits

    S = jax.ShapeDtypeStruct
    i32, f32 = np.int32, np.float32
    n, g = int(spec["n_resident"]), int(spec["g"])
    c, wc = int(spec["c"]), int(spec["wc"])
    b, k = int(spec["b"]), int(spec["k"])
    common = {
        "nrows": int(spec["nrows"]), "ncols": int(spec["ncols"]),
        "nlevels": int(spec["nlevels"]),
        "do_preprocessing": bool(spec["do_preprocessing"]),
        "q": float(spec["q"]),
    }
    fn = make_flat_jits(common)[spec["variant"]]
    resident = [S((n,), i32), S((n,), _resident_dtype(spec))]
    # ``w``: the b*k image rows ``inv`` permutes
    plan = [S((c,), i32), S((c, wc), i32), S((c, wc), i32),
            S((int(spec["w"]),), i32),
            S((b, k), f32), S((b,), i32), S((), i32)]
    statics = dict(gc_width=int(spec["gc_width"]), b=b, k=k)
    if spec["variant"] == "plain":
        args = resident + [S((g,), i32)] + plan
    elif spec["variant"] == "band":
        args = resident + [S((), i32), S((g,), i32)] + plan
        statics["w_cap"] = int(spec["w_cap"])
    elif spec["variant"] == "compact":
        r_pad = int(spec["r_pad"])
        args = resident + [S((r_pad,), i32), S((r_pad,), i32), S((), i32),
                           S((g,), i32)] + plan
        statics["n_keep"] = int(spec["n_keep"])
    else:
        raise ValueError(f"unknown flat variant {spec['variant']!r}")
    return fn, args, statics


def _sharded_lower_call(spec: dict):
    """(jitted mesh step, positional sharded ShapeDtypeStruct avals) for
    one recorded sharded spec — the exact calling convention of
    ``ShardedJaxBackend._dispatch`` for that variant, rebuilt over a mesh
    of the first ``spec['devices']`` local chips (the pool hands leases
    out host-major, so the primed assignment matches the common case)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import FORMULAS_AXIS, PIXELS_AXIS
    from ..parallel.sharded import build_sharded_score_factory

    n_dev = int(spec["devices"])
    pix, form = int(spec["mesh_pix"]), int(spec["mesh_form"])
    mesh = Mesh(
        np.array(jax.local_devices()[:n_dev]).reshape(pix, form),
        (PIXELS_AXIS, FORMULAS_AXIS))
    make = build_sharded_score_factory(
        mesh,
        p_loc=int(spec["p_loc"]),
        nrows=int(spec["nrows"]), ncols=int(spec["ncols"]),
        nlevels=int(spec["nlevels"]),
        do_preprocessing=bool(spec["do_preprocessing"]),
        q=float(spec["q"]))
    n_keep, w_cap = int(spec["n_keep"]), int(spec["w_cap"])
    fn = make(int(spec["gc_width"]), n_keep, w_cap)
    i32, f32 = np.int32, np.float32
    n, b, k = int(spec["n_resident"]), int(spec["b"]), int(spec["k"])
    g, c = int(spec["g"]), int(spec["c"])
    wc, w = int(spec["wc"]), int(spec["w"])
    r_pad = int(spec["r_pad"])

    def S(shape, dtype, part):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, part))

    in_dtype = _resident_dtype(spec)
    # run/band plan blocks mirror ShardedJaxBackend._dispatch: compact
    # ships (S, F*r_pad) run lists, band/plain ship (S, F) dummies/starts
    rp_w = form * r_pad if n_keep else form
    args = [
        S((pix, n), i32, P(PIXELS_AXIS, None)),            # px_s
        S((pix, n), in_dtype, P(PIXELS_AXIS, None)),       # in_s
        S((pix, g), i32, P(PIXELS_AXIS, FORMULAS_AXIS)),   # pos
        S((c,), i32, P(FORMULAS_AXIS)),                    # starts
        S((c, wc), i32, P(FORMULAS_AXIS, None)),           # r_lo_loc
        S((c, wc), i32, P(FORMULAS_AXIS, None)),           # r_hi_loc
        S((w,), i32, P(FORMULAS_AXIS)),                    # inv
        S((b, k), f32, P(FORMULAS_AXIS, None)),            # theor_ints
        S((b,), i32, P(FORMULAS_AXIS)),                    # n_valid
        S((pix, rp_w), i32, P(PIXELS_AXIS, FORMULAS_AXIS)),  # run_pos
        S((pix, rp_w), i32, P(PIXELS_AXIS, FORMULAS_AXIS)),  # run_delta
        S((pix, form), i32, P(PIXELS_AXIS, FORMULAS_AXIS)),  # n_b
        S((1,), i32, P(None)),                             # n_real
    ]
    return fn, args


def prime_spec(spec: dict, sm_config=None) -> str:
    """AOT-compile one recorded BucketSpec into the persistent XLA cache.
    Returns ``"compiled"`` or ``"skipped:<reason>"``; raises on a real
    compile failure (the caller counts it as an error).

    ``sm_config`` (when given) points the persistent cache first —
    without a cache dir the compile would only warm this process."""
    kind = spec.get("kind")
    if kind not in ("flat", "sharded"):
        return f"skipped:{kind or 'unknown'}"
    # manifests outlive releases: an entry recorded under a resident dtype
    # or a scoring variant (the fused Pallas one, until PR 44) this program
    # no longer has names an executable nothing will look up
    if (spec.get("cube_dtype") or "f32") not in CUBE_DTYPES:
        return "skipped:cube_dtype"
    if spec.get("variant") == "fused":
        return "skipped:legacy_spec"
    if sm_config is not None:
        from ..parallel.distributed import compile_cache_path, enable_compile_cache

        enable_compile_cache(sm_config)
        cache_dir = compile_cache_path(sm_config)
        if cache_dir is not None:
            # XLA's cache writer skips (with a warning) when the dir is
            # missing — a primed-into-nothing cycle would claim success
            Path(cache_dir).mkdir(parents=True, exist_ok=True)
    if kind == "sharded":
        # topology-keyed mesh specs (ISSUE 14): skip gracefully where the
        # host cannot hold the mesh, or the entry predates the fields
        if any(spec.get(key) in (None, "None", "", 0)
               for key in ("mesh_pix", "mesh_form", "p_loc", "w", "k", "g",
                           "c", "wc")):
            return "skipped:legacy_spec"  # pre-topology manifest entry
        import jax

        if jax.local_device_count() < int(spec["devices"]):
            return "skipped:devices"
        fn, args = _sharded_lower_call(spec)
        fn.lower(*args).compile()
        return "compiled"
    fn, args, statics = _flat_lower_call(spec)
    fn.lower(*args, **statics).compile()
    return "compiled"


def _env_key() -> str:
    """The environment a primed entry is valid for (a cache entry compiled
    under another jax/backend is a different cache entry)."""
    import jax

    dev = jax.devices()[0]
    return f"{jax.__version__}|{dev.platform}|{dev.device_kind}"


class _PrimeManifest:
    """Per-spec prime progress, persisted next to the XLA cache so an
    interrupted primer resumes and a second run is a no-op (smlint
    guarded-by)."""

    _GUARDED_BY = {"_done": "_lock"}

    def __init__(self, cache_dir: Path | None):
        self._lock = threading.Lock()
        self._path = (Path(cache_dir) / "prime_manifest.json"
                      if cache_dir is not None else None)
        self._done: dict[str, str] = {}
        if self._path is not None:
            try:
                raw = json.loads(self._path.read_text())
                self._done = {str(k): str(v)
                              for k, v in raw.get("primed", {}).items()}
            except (OSError, ValueError):
                pass                  # absent/corrupt = nothing primed

    def primed(self, key: str, env: str) -> bool:
        with self._lock:
            return self._done.get(key) == env

    def mark(self, key: str, env: str) -> None:
        with self._lock:
            self._done[key] = env
            snapshot = dict(self._done)
        if self._path is None:
            return
        tmp = self._path.with_name(self._path.name + ".tmp")
        try:
            self._path.parent.mkdir(parents=True, exist_ok=True)
            tmp.write_text(json.dumps({"primed": snapshot}))
            os.replace(tmp, self._path)
        except OSError:
            logger.warning("could not write prime manifest %s", self._path,
                           exc_info=True)

    def count(self) -> int:
        with self._lock:
            return len(self._done)


class CachePrimer:
    """Scheduler-idle background primer (``service.prime``).

    ``busy``: a zero-arg callable returning True while real work is in
    flight (pending spool depth or live claims) — a prime cycle starts
    only after ``idle_after_s`` of continuous idleness and re-checks
    between specs, so priming never delays a job (and never touches a
    device-pool lease: AOT lowering is host-side compilation)."""

    _GUARDED_BY = {"_status": "_lock", "_cycles": "_lock",
                   "_last_cycle_s": "_lock"}

    def __init__(self, sm_config, busy=None, metrics=None):
        from ..parallel.distributed import compile_cache_path

        self.sm_config = sm_config
        self.cfg = sm_config.service.prime
        self.busy = busy or (lambda: False)
        self._cache_dir = compile_cache_path(sm_config)
        shape_buckets.bind_manifest_dir(self._cache_dir)
        self._manifest = _PrimeManifest(self._cache_dir)
        self._lock = threading.Lock()
        self._status: dict[str, str] = {}      # spec_key -> last outcome
        self._cycles = 0
        self._last_cycle_s = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._metrics = metrics
        if metrics is not None:
            self.m_compiled = metrics.counter(
                "sm_prime_compiled_total",
                "Bucket executables AOT-compiled into the persistent "
                "XLA cache by the primer")
            self.m_skipped = metrics.counter(
                "sm_prime_skipped_total",
                "Primer specs skipped (already primed, non-flat kind, "
                "or cycle aborted)", ("reason",))
            self.m_errors = metrics.counter(
                "sm_prime_errors_total",
                "Primer compile attempts that raised")
            self.m_cycles = metrics.counter(
                "sm_prime_cycles_total", "Idle prime cycles run")
            self.g_known = metrics.gauge(
                "sm_prime_known_buckets",
                "Bucket specs recorded in the lattice manifest")
            self.g_primed = metrics.gauge(
                "sm_prime_primed_buckets",
                "Bucket specs proven primed for this environment")
            self.g_last = metrics.gauge(
                "sm_prime_last_cycle_seconds",
                "Wall clock of the most recent prime cycle")

    # ---------------------------------------------------------------- specs
    def known_specs(self) -> list[dict]:
        """Recorded specs: this process's registry folded with the
        persisted bucket manifest (other replicas/processes record too)."""
        specs = {shape_buckets.spec_key(s): s
                 for s in shape_buckets.recorded_specs()}
        if self._cache_dir is not None:
            for s in shape_buckets.load_manifest(self._cache_dir):
                specs.setdefault(shape_buckets.spec_key(s), s)
        return list(specs.values())

    # ---------------------------------------------------------------- prime
    def prime_once(self, max_specs: int | None = None,
                   abort_when_busy: bool = True) -> dict:
        """One prime cycle: compile every known, un-primed, flat spec.
        Returns ``{compiled, skipped, errors, aborted}``.  Idempotent —
        primed specs are skipped via the prime manifest, so an
        interrupted cycle resumes exactly where it stopped."""
        env = _env_key()
        out = {"compiled": 0, "skipped": 0, "errors": 0, "aborted": False}
        limit = max_specs if max_specs is not None else (
            self.cfg.max_specs_per_cycle or None)
        t0 = time.perf_counter()
        for spec in self.known_specs():
            if self._stop.is_set() or (abort_when_busy and self.busy()):
                # a real job arrived: yield immediately — the next idle
                # cycle resumes from the manifest
                out["aborted"] = True
                break
            if limit is not None and out["compiled"] >= limit:
                out["aborted"] = True
                break
            key = shape_buckets.spec_key(spec)
            if self._manifest.primed(key, env):
                out["skipped"] += 1
                self._note(key, "primed", "already_primed")
                continue
            try:
                status = prime_spec(spec, sm_config=self.sm_config)
            except Exception:
                out["errors"] += 1
                self._note(key, "error", None)
                if self._metrics is not None:
                    self.m_errors.inc()
                logger.warning("primer: compile failed for %s", key,
                               exc_info=True)
                continue
            if status == "compiled":
                out["compiled"] += 1
                self._manifest.mark(key, env)
                self._note(key, "primed", None)
                if self._metrics is not None:
                    self.m_compiled.inc()
                logger.info("primer: compiled bucket %s", key)
            else:
                out["skipped"] += 1
                if self._note(key, status, status.split(":", 1)[-1]):
                    logger.info("primer: %s bucket %s", status, key)
        dt = time.perf_counter() - t0
        with self._lock:
            self._cycles += 1
            self._last_cycle_s = dt
        if self._metrics is not None:
            self.m_cycles.inc()
            self.g_last.set(dt)
            self._refresh_gauges()
        return out

    def _note(self, key: str, status: str, skip_reason: str | None) -> bool:
        """Record one spec's outcome; True when it is news for this spec."""
        with self._lock:
            changed = self._status.get(key) != status
            self._status[key] = status
        if skip_reason and self._metrics is not None:
            self.m_skipped.labels(reason=skip_reason).inc()
        return changed

    def _refresh_gauges(self) -> None:
        self.g_known.set(len(self.known_specs()))
        self.g_primed.set(self._manifest.count())

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        """The ``GET /debug/compile`` body's primer half: every known
        bucket with its primed/missing status."""
        env = _env_key()
        with self._lock:
            status = dict(self._status)
            cycles, last = self._cycles, self._last_cycle_s
        buckets = []
        primed = missing = 0
        for spec in self.known_specs():
            key = shape_buckets.spec_key(spec)
            if self._manifest.primed(key, env):
                st = "primed"
                primed += 1
            else:
                st = status.get(key, "missing")
                if not st.startswith("skipped"):
                    st = "missing"
                missing += 1
            buckets.append({**spec, "status": st})
        return {
            "enabled": bool(self.cfg.enabled),
            "env": env,
            "cache_dir": (str(self._cache_dir)
                          if self._cache_dir is not None else None),
            "known": len(buckets),
            "primed": primed,
            "missing": missing,
            "cycles": cycles,
            "last_cycle_s": round(last, 3),
            "buckets": buckets,
        }

    # ------------------------------------------------------------ lifecycle
    def _loop(self) -> None:
        idle_since: float | None = None
        while not self._stop.is_set():
            if self.busy():
                idle_since = None
            elif idle_since is None:
                idle_since = time.time()
            elif time.time() - idle_since >= self.cfg.idle_after_s:
                try:
                    res = self.prime_once()
                except Exception:
                    logger.warning("primer cycle failed", exc_info=True)
                    res = {"aborted": True}
                # everything known is primed: sleep the rescan interval;
                # an aborted cycle retries as soon as idleness returns
                if not res.get("aborted"):
                    self._stop.wait(self.cfg.interval_s)
                idle_since = None
            self._stop.wait(min(0.5, self.cfg.idle_after_s or 0.5))

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="cache-primer")
        self._thread.start()
        logger.info("primer: idle cache priming up (idle_after=%.1fs)",
                    self.cfg.idle_after_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
