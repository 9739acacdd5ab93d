"""Device-mesh construction — the TPU-native replacement for the reference's
Spark cluster topology.

The reference expresses parallelism as Spark settings (``spark.master``,
executor counts — ``sm_config['spark']`` [U], SURVEY.md #20) and its data
layout as RDD partitions over the pixel axis plus broadcast peak tables
(SURVEY.md §2d).  Here the same two degrees of freedom are mesh axes:

- ``"pixels"``  — shards the spectral cube's pixel dimension (the RDD
  partition analog; BASELINE config #5: >200k-pixel DESI slide on v4-32).
- ``"formulas"`` — shards the formula-batch dimension (the analog of
  parallelizing over (sf, adduct) pairs; BASELINE config #4).

Axis sizes come from ``SMConfig.parallel`` where ``-1`` means "use all
remaining devices".  A 1x1 mesh degrades gracefully to the single-device
fused graph (models/msm_jax.py).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from ..utils.config import ParallelConfig

PIXELS_AXIS = "pixels"
FORMULAS_AXIS = "formulas"


def resolve_axis_sizes(n_devices: int, cfg: ParallelConfig) -> tuple[int, int]:
    """(pixels, formulas) axis sizes using exactly their product <= n_devices.

    ``-1`` entries absorb all devices left over after the explicit axes.
    Both -1: all devices go to the pixel axis (the dominant data axis).
    """
    pix, form = cfg.pixels_axis, cfg.formulas_axis
    if pix < -1 or form < -1 or pix == 0 or form == 0:
        raise ValueError(
            f"mesh axis sizes must be -1 or positive, got pixels_axis={pix}, "
            f"formulas_axis={form}")
    if pix == -1 and form == -1:
        pix, form = n_devices, 1
    elif pix == -1:
        if n_devices % form:
            raise ValueError(f"formulas_axis={form} does not divide {n_devices} devices")
        pix = n_devices // form
    elif form == -1:
        if n_devices % pix:
            raise ValueError(f"pixels_axis={pix} does not divide {n_devices} devices")
        form = n_devices // pix
    if pix * form > n_devices:
        raise ValueError(
            f"mesh {pix}x{form} needs {pix * form} devices, only {n_devices} available"
        )
    return pix, form


def make_mesh(cfg: ParallelConfig, devices=None, hosts: int = 1) -> Mesh:
    """Build the ("pixels", "formulas") mesh from config + available devices.

    ``hosts`` (ISSUE 11) declares the host×chip topology the device list
    came from (a ``jax.distributed``-style multi-host pool, simulated on
    CPU).  The device order is host-major, so with ``hosts`` dividing the
    pixels axis each host's chips form a contiguous block of pixel shards
    — cross-host (DCN) traffic is confined to the pixel-axis reductions
    and a whole-host failure takes out a contiguous, re-computable shard
    range instead of a stripe through every shard.  A topology the grid
    cannot honor is logged and ignored (topology is an optimization, never
    a reason to fail the job)."""
    devices = list(devices if devices is not None else jax.devices())
    pix, form = resolve_axis_sizes(len(devices), cfg)
    if hosts > 1:
        from ..utils.logger import logger

        if pix % hosts:
            logger.warning(
                "make_mesh: %d hosts does not divide the %d-shard pixels "
                "axis; host blocks will straddle mesh rows", hosts, pix)
        else:
            logger.info("make_mesh: %dx%d mesh over %d host(s) "
                        "(%d pixel shard(s) per host)",
                        pix, form, hosts, pix // hosts)
    dev_grid = np.array(devices[: pix * form]).reshape(pix, form)
    return Mesh(dev_grid, (PIXELS_AXIS, FORMULAS_AXIS))


def host_topology(device_indices, chips_per_host) -> dict[int, tuple]:
    """Group a lease's chip indices by host failure domain:
    ``{host: (chip, ...)}`` — what the fleet controller (and a sub-mesh
    lease) uses to reason about host-level blast radius.

    ``chips_per_host`` is either the legacy int (equal hosts of that many
    chips) or, since ISSUE 17, explicit per-host ``(lo, hi)`` ranges
    (``service/health.py::split_host_ranges``) so ragged pools attribute
    every chip to the right host instead of the integer-division guess."""
    ranges = None
    if not isinstance(chips_per_host, int):
        ranges = [(int(lo), int(hi)) for lo, hi in chips_per_host]
    out: dict[int, list[int]] = {}
    for i in device_indices or ():
        i = int(i)
        if ranges is None:
            out.setdefault(i // max(1, int(chips_per_host)), []).append(i)
            continue
        for h, (lo, hi) in enumerate(ranges):
            if lo <= i < hi:
                out.setdefault(h, []).append(i)
                break
        else:
            out.setdefault(len(ranges) - 1 if ranges else 0, []).append(i)
    return {h: tuple(sorted(v)) for h, v in sorted(out.items())}


def global_device_order(devices=None) -> list:
    """The pod-wide host-major device list: ``jax.devices()`` sorted by
    ``(process_index, id)``.  JAX documents no enumeration order across
    processes, so the pool's chip index -> Device mapping goes through this
    one seam — stable under permuted enumeration, and chips of one process
    form a contiguous index run (the host failure domain the pool's
    ``hosts`` dimension names).  Unit-testable with fake device objects."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return sorted(devs, key=lambda d: (int(getattr(d, "process_index", 0)),
                                       int(getattr(d, "id", 0))))


def lease_devices(device_indices) -> list | None:
    """Map a device-pool lease's chip indices (``DeviceLease.devices``) to
    jax Device objects for a sub-mesh.

    ``None`` -> ``None`` (the caller meshes over ALL local devices, the
    pre-pool behavior).  In a multi-process runtime the pool indexes the
    GLOBAL host-major order (``global_device_order``) — a lease's chips may
    live in other processes (ISSUE 17); single-process keeps the local
    list.  Indices beyond the visible device count — a simulated pool
    larger than the host, e.g. the CI smoke's 8-chip pool on a smaller box
    — are dropped with a warning; an empty result falls back to ``None``
    rather than failing the job over a telemetry-grade mismatch.
    """
    if device_indices is None:
        return None
    from ..utils.logger import logger

    try:
        multi = jax.process_count() > 1
    except Exception as exc:  # pragma: no cover - uninitialized backend
        logger.debug("lease_devices: jax backend not up (%s); "
                     "assuming single-process", exc)
        multi = False
    devs = global_device_order() if multi else jax.local_devices()
    picked = [devs[i] for i in device_indices if 0 <= int(i) < len(devs)]
    if len(picked) < len(list(device_indices)):
        logger.warning(
            "device lease %s exceeds the %d visible jax devices; %s",
            tuple(device_indices), len(devs),
            f"using {len(picked)} chip(s)" if picked
            else "falling back to the config mesh")
    return picked or None
