"""Device memory introspection (HBM occupancy) with a graceful CPU fallback.

TPU/GPU PJRT devices expose ``Device.memory_stats()`` — a dict with
``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit`` (names vary
slightly by runtime; the accessors below normalize the common aliases).
CPU devices return ``None`` (or raise), and a process that never imported
jax has nothing to report at all: every function here degrades to
``None``-valued fields instead of failing, so telemetry and bench pinning
work identically on a laptop and on a v5e pod slice.

Deliberately import-light: ``jax`` is only touched if it is ALREADY
imported (``sys.modules`` check) — sampling device stats from the
service's telemetry thread must never be the thing that initializes a
PJRT client (which would break fork-based floors and pay a multi-second
startup inside a metrics scrape).
"""

from __future__ import annotations

import sys

from .logger import logger

# memory_stats key aliases across PJRT runtimes
_IN_USE_KEYS = ("bytes_in_use", "bytes_used")
_PEAK_KEYS = ("peak_bytes_in_use", "peak_bytes")
_LIMIT_KEYS = ("bytes_limit", "bytes_reservable_limit")


def _pick(stats: dict, keys: tuple[str, ...]):
    for k in keys:
        v = stats.get(k)
        if isinstance(v, (int, float)):
            return int(v)
    return None


def jax_if_loaded():
    """The jax module if this process already initialized it, else None."""
    return sys.modules.get("jax")


def device_stats(force_import: bool = False) -> list[dict]:
    """One dict per local device: ``{id, kind, platform, bytes_in_use,
    peak_bytes, limit_bytes}`` — the byte fields are ``None`` when the
    platform exposes no memory stats (CPU, or a runtime without the API).

    Returns ``[]`` when jax is unavailable or uninitializable.  By default
    only an ALREADY-imported jax is used (see module docstring);
    ``force_import`` opts into importing it (bench, CLI probes).
    """
    jax = jax_if_loaded()
    if jax is None:
        if not force_import:
            return []
        try:
            import jax  # noqa: F811
        except Exception as exc:
            logger.debug("devicemem: jax import failed (%s); no device "
                         "stats", exc)
            return []
    try:
        devices = jax.local_devices()
    except Exception as exc:
        logger.debug("devicemem: jax.local_devices() failed (%s); no "
                     "device stats", exc)
        return []
    out = []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception as exc:  # CPU backends raise or return None
            logger.debug("devicemem: memory_stats() unavailable on %r (%s)",
                         d, exc)
            stats = None
        stats = stats if isinstance(stats, dict) else {}
        out.append({
            "id": int(getattr(d, "id", len(out))),
            "kind": str(getattr(d, "device_kind", "unknown")),
            "platform": str(getattr(d, "platform", "unknown")),
            "bytes_in_use": _pick(stats, _IN_USE_KEYS),
            "peak_bytes": _pick(stats, _PEAK_KEYS),
            "limit_bytes": _pick(stats, _LIMIT_KEYS),
        })
    return out


def hbm_summary(force_import: bool = False) -> dict:
    """Cross-device roll-up for bench pinning and phase capture:
    ``{device_kind, device_count, hbm_bytes_in_use, hbm_peak_bytes,
    hbm_limit_bytes}``.  Byte fields are ``None`` when NO device reports
    memory stats (the pinned-``null`` contract in bench JSON); in_use/limit
    sum across devices, peak takes the max (peaks are per-device
    high-water marks and do not add meaningfully)."""
    per = device_stats(force_import=force_import)
    in_use = [d["bytes_in_use"] for d in per if d["bytes_in_use"] is not None]
    peaks = [d["peak_bytes"] for d in per if d["peak_bytes"] is not None]
    limits = [d["limit_bytes"] for d in per if d["limit_bytes"] is not None]
    return {
        "device_kind": per[0]["kind"] if per else None,
        "device_count": len(per),
        "hbm_bytes_in_use": sum(in_use) if in_use else None,
        "hbm_peak_bytes": max(peaks) if peaks else None,
        "hbm_limit_bytes": sum(limits) if limits else None,
    }


def hbm_peak_bytes() -> int | None:
    """Max per-device peak HBM, or ``None`` without memory stats — the
    one-liner phase capture calls on every phase exit."""
    peaks = [d["peak_bytes"] for d in device_stats()
             if d["peak_bytes"] is not None]
    return max(peaks) if peaks else None


def runtime_identity() -> dict:
    """What JAX runs on in THIS process, as JAX reports it: ``platform`` /
    ``device_kind`` of the first device, ``device_count``, and the
    installed jax / jaxlib / libtpu versions.  ``platform`` is
    ``None`` when jax was never imported here — same import-light rule as
    ``device_stats``.  ``GET /debug/devices`` serves it, so a driver that
    must stay off the chip (``chip_smoke.py``) can ask the process that
    holds it."""
    from importlib import metadata

    versions = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            versions[dist] = None
    per = device_stats()
    return {
        "platform": per[0]["platform"] if per else None,
        "device_kind": per[0]["kind"] if per else None,
        "device_count": len(per),
        "versions": versions,
    }
