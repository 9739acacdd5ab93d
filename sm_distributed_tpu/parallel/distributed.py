"""Multi-host runtime — the DCN analog of the reference's Spark cluster.

Reference: ``sm_config['spark']`` carries the cluster master address and
executor settings [U] (SURVEY.md #20, §5.8).  The TPU-native equivalent is
single-controller JAX: every host process calls
``jax.distributed.initialize(coordinator, num_processes, process_id)`` and
``jax.devices()`` then spans all hosts; the ("pixels", "formulas") mesh and
its collectives (all_to_all over ICI within a slice, DCN across slices) need
no further changes — shard_map code is topology-agnostic.

Launch (one process per host), e.g.:

    SM_COORDINATOR=host0:8476 SM_NUM_PROCESSES=4 SM_PROCESS_ID=$i \
        python -m sm_distributed_tpu.engine.cli run ...

or set ``parallel.coordinator_address`` / ``num_processes`` / ``process_id``
in the engine config.  On Cloud TPU pods, plain ``jax.distributed
.initialize()`` auto-discovers everything; we pass explicit values only when
configured.  Single-process (the default) is a strict no-op.

Managed runtime (ISSUE 17): this module is no longer a fire-once shim —

- **launch-race tolerance**: every host process races the coordinator's
  bind at pod startup, so ``initialize`` retries with exponential backoff
  (``parallel.init_retries`` / ``init_backoff_s``) before the failure is
  considered real.  The ``dist.initialize`` failpoint sits inside each
  attempt (docs/RECOVERY.md); a retried-then-successful init records the
  ``dist.init_retry`` recovery event.
- **shutdown/reset seam**: ``shutdown()`` tears the runtime down
  (``jax.distributed.shutdown()`` when live) and clears the idempotence
  latch so repeated in-process pod tests don't leak coordinator state.
- **process identity**: ``process_identity()`` resolves this process's
  ``(process_id, host)`` — stamped into tracing records
  (``utils/tracing.set_process``), telemetry samples, and ``/peers``.
  ``SM_HOST_NAME`` names the simulated host on CPU pods.
- **simulation seam**: ``SM_DIST_SIMULATE=1`` skips the real
  ``jax.distributed.initialize`` call while exercising the whole managed
  path (settings resolution, retry ladder, identity) — what the chaos
  harness's single-box "hosts" use; the real 2-process init is covered by
  the slow multi-process test (tests/test_distributed.py).
"""

from __future__ import annotations

import os
import socket
import sys
import time
from pathlib import Path

from ..utils.config import ParallelConfig
from ..utils.failpoints import failpoint, record_recovery, register_failpoint
from ..utils.logger import logger

FP_DIST_INIT = register_failpoint(
    "dist.initialize",
    "inside each jax.distributed.initialize attempt (raise here is the "
    "coordinator-not-yet-up launch race; the backoff ladder retries)")

_initialized = False
_simulated = False


# Where the persistent XLA compilation cache lives is decided from OUTSIDE
# the program: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
# (JAX reads it at import; the engine then sets no directory in code), else
# one fixed directory inside the checkout.  Never the work dir, a temp
# name, a pid or the time: the cache only pays off when the next process —
# or the next machine handed the same directory — looks in the same place,
# and JAX opens exactly one cache per process (the first directory wins;
# later ``jax_compilation_cache_dir`` updates are ignored).
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".cache" / "xla_cache"


def compile_cache_path(sm_config) -> Path | None:
    """The persistent-cache directory, or None when
    ``parallel.compile_cache_dir`` is "off".  Shared by
    ``enable_compile_cache`` and everything kept NEXT to the cache (warmup,
    bucket and prime manifests)."""
    if sm_config.parallel.compile_cache_dir == "off":
        return None
    placed = os.environ.get(CACHE_DIR_ENV)
    return Path(placed) if placed else _CHECKOUT_CACHE


def enable_compile_cache(sm_config) -> None:
    """Turn the persistent compilation cache on at ``compile_cache_path``
    so a dataset's second job (same shapes) skips the compile.  Idempotent
    (jax.config.update is)."""
    path = compile_cache_path(sm_config)
    if path is None:
        return
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    # persist EVERY compile (ISSUE 13): the default 1.0 s floor means fast
    # compiles are never written — which is exactly what made a "primed"
    # cache unreliable (the warmup manifest's entries==0 special case
    # exists because of it).  Entries are small; the disk-budget governor
    # and retention GC bound the directory like any other cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def clear_compile_cache(sm_config) -> Path | None:
    """Empty the persistent-cache directory IN PLACE — executables and the
    manifests beside them — and return it.  For cold-start measurements
    and the CPU smokes that must prove a cold compile: the directory is
    fixed for the life of the process, so "a fresh cache" means clearing
    this one, not pointing at another."""
    path = compile_cache_path(sm_config)
    if path is not None and path.is_dir():
        for entry in path.iterdir():
            if entry.is_file():
                entry.unlink(missing_ok=True)
    return path


def resolve_distributed_settings(cfg: ParallelConfig) -> tuple[str, int, int]:
    """(coordinator, num_processes, process_id) from env (priority) or cfg."""
    coord = os.environ.get("SM_COORDINATOR", cfg.coordinator_address)
    n_proc = int(os.environ.get("SM_NUM_PROCESSES", cfg.num_processes))
    proc_id = int(os.environ.get("SM_PROCESS_ID", cfg.process_id))
    return coord, n_proc, proc_id


def initialize_kwargs(coord: str, n_proc: int, proc_id: int) -> dict:
    """The exact kwargs handed to jax.distributed.initialize — factored out
    so the mapping stays unit-testable without spawning processes (omitted
    keys let JAX auto-discover on Cloud TPU pods)."""
    kwargs: dict = {}
    if coord:
        kwargs["coordinator_address"] = coord
    if n_proc > 1:
        kwargs["num_processes"] = n_proc
    if proc_id >= 0:
        kwargs["process_id"] = proc_id
    return kwargs


def is_initialized() -> bool:
    """True after a successful ``maybe_initialize_distributed`` (real or
    simulated) until ``shutdown()``."""
    return _initialized


def process_identity() -> dict:
    """This process's pod identity ``{"process_id": int, "host": str}``.

    ``process_id``: ``SM_PROCESS_ID`` env when set (the launcher contract),
    else the live ``jax.process_index()`` once the runtime is up, else 0.
    ``host``: ``SM_HOST_NAME`` env (the simulated-pod seam — a single box
    pretending to be several hosts names them apart) or the real hostname.
    """
    pid = -1
    env = os.environ.get("SM_PROCESS_ID")
    if env is not None:
        try:
            pid = int(env)
        except ValueError:
            pid = -1
    if pid < 0:
        mod = sys.modules.get("jax")
        if mod is not None and _initialized and not _simulated:
            try:
                pid = int(mod.process_index())
            except Exception as exc:  # pragma: no cover - defensive
                logger.debug("process_identity: jax.process_index "
                             "unavailable (%s); defaulting to 0", exc)
                pid = -1
    host = os.environ.get("SM_HOST_NAME") or socket.gethostname()
    return {"process_id": max(0, pid), "host": host}


def maybe_initialize_distributed(cfg: ParallelConfig) -> bool:
    """Initialize the multi-host runtime when configured; returns True when
    the runtime came (or already was) up.  Idempotent; single-process
    settings (num_processes <= 1 and no coordinator) are a no-op.

    Coordinator-not-yet-up is the NORMAL launch race, not an error: each
    attempt that raises backs off ``init_backoff_s * 2^attempt`` (capped at
    30 s) up to ``init_retries`` retries before the exception propagates.
    """
    global _initialized, _simulated
    coord, n_proc, proc_id = resolve_distributed_settings(cfg)
    if n_proc <= 1 and not coord:
        return False
    if _initialized:
        return True
    kwargs = initialize_kwargs(coord, n_proc, proc_id)
    retries = max(0, int(getattr(cfg, "init_retries", 5)))
    backoff = max(0.0, float(getattr(cfg, "init_backoff_s", 1.0)))
    simulate = os.environ.get("SM_DIST_SIMULATE", "") not in ("", "0")
    logger.info("initializing multi-host runtime: %s%s", kwargs,
                " (SM_DIST_SIMULATE: no real coordinator)" if simulate else "")
    attempt = 0
    while True:
        try:
            failpoint(FP_DIST_INIT)
            if not simulate:
                import jax

                jax.distributed.initialize(**kwargs)
            break
        except Exception as exc:
            if attempt >= retries:
                logger.error(
                    "multi-host init failed after %d attempt(s): %s",
                    attempt + 1, exc)
                raise
            delay = min(backoff * (2 ** attempt), 30.0)
            attempt += 1
            logger.warning(
                "multi-host init attempt %d failed (%s: %s) — coordinator "
                "not up yet?  retrying in %.2fs (%d retr%s left)",
                attempt, type(exc).__name__, exc, delay,
                retries - attempt + 1, "y" if retries - attempt + 1 == 1
                else "ies")
            if delay > 0:
                time.sleep(delay)
    if attempt:
        record_recovery("dist.init_retry")
    _initialized = True
    _simulated = simulate
    ident = process_identity()
    logger.info("multi-host runtime up: process %d on host %s",
                ident["process_id"], ident["host"])
    return True


def shutdown() -> None:
    """Tear the runtime down and reset the idempotence latch (the
    test/repeated-pod seam): calls ``jax.distributed.shutdown()`` when this
    process really initialized it; a failure there is logged, not raised —
    the latch clears either way so the next init starts clean."""
    global _initialized, _simulated
    if _initialized and not _simulated:
        try:
            import jax

            jax.distributed.shutdown()
        except Exception as exc:
            logger.warning("jax.distributed.shutdown failed: %s", exc)
    _initialized = False
    _simulated = False
