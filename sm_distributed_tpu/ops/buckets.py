"""Canonical shape-bucket lattice (ISSUE 13 tentpole).

Every jitted executable's signature is a function of a handful of shape
parameters: the padded scoring batch ``b``, the resident peak count ``N``,
the pixel-grid geometry ``(nrows, ncols)``, and the sticky plan statics
(``gc_width``, ``n_keep``, ``w_cap`` — already laddered in
``ops/imager_jax.py``).  PR 12 made the surface *declared*; this module
makes it **closed under all traffic**: the raw dataset-dependent values are
snapped to one small power-of-two-ish lattice, so every dataset size maps
into a finite signature set that can be enumerated, AOT-compiled into the
persistent XLA cache (``service/primer.py``), and proven closed by
``scripts/compile_census.py``.

The lattice is the QUARTER-POINT ladder ``{1, 1.25, 1.5, 1.75} x 2^e``
(bounded padding waste 25%, expected ~11%, ~4 buckets per octave — the
coarser sibling of ``imager_jax.band_bucket``'s eighth ladder, chosen
because every extra point here is an extra executable the primer must
compile).  Three masked paddings ride it:

- **peaks** (``peak_bucket``): resident sorted-peak arrays pad with the
  existing ``MZ_PAD_Q`` sentinel / overflow-pixel / zero-intensity slots —
  the exact mechanism ``prepare_flat_sharded_arrays`` already uses for its
  1024-multiple rounding, just snapped to the shared ladder;
- **pixel rows** (``row_bucket``): the image grid pads with whole ZERO
  rows at the bottom; component counts, maxima and positive counts are
  exactly invariant, and the one non-invariant op — the correlation's
  mean over pixels — takes the REAL pixel count as a *traced* scalar
  (``ops/metrics_jax.batch_metrics(n_real=...)``), so padded scoring is
  bit-identical to unpadded.  Columns are the lattice's base dimension
  (bucketing them would renumber pixel indices); a bucket is therefore
  keyed ``(row_bucket(nrows), ncols)``;
- **batch** (``batch_bucket_down``): pad-to batch sizes snap DOWN (padding
  up could exceed a proven-fitting HBM footprint), so OOM-shrunk caps
  (``models/oom.py``) land on lattice points shared with the primer's
  enumeration.

``BucketSpec`` records one concrete executable's identity — variant,
statics, and argument shapes — into a process-global registry persisted
next to the persistent XLA cache (``bucket_manifest.json``), which is what
``scripts/prime_cache.py`` and the scheduler-idle primer enumerate and
``GET /debug/compile`` reports as primed vs missing.
"""

from __future__ import annotations

import json
import os
import threading
from pathlib import Path

# ---------------------------------------------------------------- lattice

# floors: below these the padding waste is noise and a single bucket is
# cheaper than many tiny executables
PEAK_FLOOR = 4096       # resident-peak arrays (slots are 8 bytes)
ROW_FLOOR = 8           # image rows
PIXEL_FLOOR = 64        # flat pixel counts (oom shape keys)
EXPORT_FLOOR = 64       # rows of the store's image export (kept ions)
# bytes of f32 flat rows in one chunk of the store's image export: the device
# hands the export over in pieces of this size, each on the link at once, so
# the host compresses and writes one while the next lands (~10 chunks at
# 256x256 px, 3 at 128x128, one for every 64x64 export of ~300 ions)
EXPORT_CHUNK_BYTES = 32 << 20


def pow2ish(n: int, floor: int = 1) -> int:
    """Smallest quarter-ladder point ({1, 1.25, 1.5, 1.75} x 2^e) >= n,
    with a floor.  The shared canonical rounding — every shape bucket in
    the engine goes through this one ladder."""
    n = max(int(n), 1)
    cap = max(int(floor), 1)
    while cap < n:
        cap <<= 1
    if cap > floor and cap >= 8:
        # quarter points live between cap/2 and cap
        for quarters in (5, 6, 7):
            mid = (cap >> 3) * quarters
            if n <= mid:
                return mid
    return cap


def pow2ish_down(n: int, floor: int = 1) -> int:
    """Largest quarter-ladder point <= n (>= floor) — the DOWN-snap used
    for pad-to batch sizes, where rounding up would grow a proven-fitting
    memory footprint.  Ladder points: powers of two, plus the 5/8, 6/8,
    7/8 points of every octave at or above 8 (matching ``pow2ish``)."""
    n = max(int(n), 1)
    f = max(int(floor), 1)
    if n <= f:
        return f
    best = f
    cap = 1
    while cap <= n:
        if cap >= f:
            best = max(best, cap)
        if cap >= 8 and cap > f:
            for eighths in (5, 6, 7):
                pt = (cap >> 3) * eighths
                if f <= pt <= n:
                    best = max(best, pt)
        cap <<= 1
    # the octave just above n can still hold in-range quarter points
    if cap >= 8 and cap > f:
        for eighths in (5, 6, 7):
            pt = (cap >> 3) * eighths
            if f <= pt <= n:
                best = max(best, pt)
    return best


def peak_bucket(n_peaks: int) -> int:
    """Lattice capacity for a resident sorted-peak array."""
    return pow2ish(n_peaks, PEAK_FLOOR)


def row_bucket(nrows: int) -> int:
    """Lattice row count for the image grid (columns stay exact)."""
    return pow2ish(nrows, ROW_FLOOR)


def pixel_bucket(n_pixels: int) -> int:
    """Lattice point for a flat pixel count — the oom safe-batch
    ``shape_key`` granularity, so a learned batch transfers to every
    dataset size sharing the bucket."""
    return pow2ish(n_pixels, PIXEL_FLOOR)


def batch_bucket_down(batch: int) -> int:
    """Largest lattice point <= ``batch`` — pad-to batch sizes and
    OOM-shrunk caps snap DOWN so padding never grows a proven-fitting
    HBM footprint."""
    return pow2ish_down(batch, 1)


def export_bucket(n_ions: int, batch: int) -> int:
    """Padded row count of the store's image export: the lattice point of
    the KEPT ion count, never above the scoring ``batch`` (a proven-fitting
    footprint, OOM-shrunk or not) — the program's shape follows what is
    fetched, not ``formula_batch``."""
    return min(int(batch), pow2ish(n_ions, EXPORT_FLOOR))


def export_chunk_rows(n_pixels: int) -> int:
    """Flat image rows in one chunk of the store's export at ``n_pixels``
    columns: ``EXPORT_CHUNK_BYTES`` of f32, in whole rows and a multiple of
    8 of them, so that every chunk's bit mask ends on a byte whatever the
    pixel count is.  A static of the export's program: it follows the
    (bucketed) pixel count alone, never the number of rows exported."""
    return max(8, EXPORT_CHUNK_BYTES // (4 * int(n_pixels)) // 8 * 8)


def export_statics(n_pixels: int) -> dict:
    """The statics of the export's program at ``n_pixels`` columns."""
    return {"n_pixels": int(n_pixels),
            "chunk_rows": export_chunk_rows(n_pixels)}


def buckets_enabled(parallel_cfg) -> bool:
    """``parallel.shape_buckets`` knob: "auto"/"on" enable the lattice,
    "off" keeps the exact legacy shapes (tests compare the two)."""
    return getattr(parallel_cfg, "shape_buckets", "auto") != "off"


def effective_batch(parallel_cfg) -> int:
    """The pad-to scoring batch: ``parallel.formula_batch`` snapped DOWN
    to the lattice when buckets are on (both the slicing side —
    ``MSMBasicSearch`` — and the padding side — the jax backends — call
    this, so they can never disagree)."""
    b = max(1, parallel_cfg.formula_batch)
    return batch_bucket_down(b) if buckets_enabled(parallel_cfg) else b


# ---------------------------------------------------------- spec registry

_SPEC_KEYS = (
    # identity of one concrete executable in the lattice
    "kind",               # "flat" | "sharded" | "chunked"
    "variant",            # "plain" | "compact" | "band" | "step"
    "nrows", "ncols",     # bucketed rows x exact columns (metric geometry)
    "nlevels", "do_preprocessing", "q",
    "n_resident",         # bucketed resident peak slots (per shard row)
    "b", "k",             # padded batch x isotope peaks
    "gc_width",           # sticky chunk-band ladder point
    "n_keep", "r_pad",    # compact-variant capacities (0 = n/a)
    "w_cap",              # band-variant capacity (0 = n/a)
    "g", "c", "wc",       # bound-grid / chunk-plan shapes
    "devices",            # lease shape: chip count (1 = single device)
    # sharded (mesh-shaped) executables only — absent (None) on flat specs
    # so pre-existing manifest keys stay stable within a kind:
    "mesh_pix", "mesh_form",  # mesh axis sizes (pixels x formulas)
    "p_loc",              # per-shard pixel capacity (whole bucketed rows)
    # both kinds: the rows ``inv`` permutes (the total window count)
    "w",
    # recorded only when parallel.cube_dtype != "f32", so f32 spec keys
    # stay byte-stable:
    "cube_dtype",         # "bf16" resident intensity dtype
)


def spec_key(spec: dict) -> str:
    """Stable identity string for one BucketSpec (manifest/dedup key)."""
    return "|".join(f"{k}={spec.get(k)}" for k in _SPEC_KEYS)


class _SpecRegistry:
    """Process-global registry of observed bucket specs, write-through to
    ``<compile_cache>/bucket_manifest.json`` (smlint guarded-by)."""

    _GUARDED_BY = {"_specs": "_lock", "_dir": "_lock"}
    _MAX = 256                        # manifest bound (oldest dropped)

    def __init__(self):
        self._lock = threading.Lock()
        self._specs: dict[str, dict] = {}
        self._dir: Path | None = None

    def set_dir(self, cache_dir) -> None:
        """Bind the persistence directory (the persistent XLA cache dir)
        and fold any previously persisted manifest in."""
        if cache_dir is None:
            return
        path = Path(cache_dir) / "bucket_manifest.json"
        loaded: dict[str, dict] = {}
        try:
            raw = json.loads(path.read_text())
            for ent in raw.get("specs", []):
                if isinstance(ent, dict):
                    loaded[spec_key(ent)] = ent
        except (OSError, ValueError):
            pass                      # absent/corrupt manifest = empty
        with self._lock:
            self._dir = Path(cache_dir)
            for k, v in loaded.items():
                self._specs.setdefault(k, v)

    def record(self, spec: dict) -> bool:
        """Record one observed spec; returns True when it is new.  New
        specs write through to the manifest (atomic tmp+replace); a failed
        write is logged by the caller's layer, never raised."""
        key = spec_key(spec)
        with self._lock:
            if key in self._specs:
                return False
            self._specs[key] = dict(spec)
            while len(self._specs) > self._MAX:
                self._specs.pop(next(iter(self._specs)))
            snapshot = list(self._specs.values())
            directory = self._dir
        if directory is not None:
            _write_manifest(directory, snapshot)
        return True

    def specs(self) -> list[dict]:
        with self._lock:
            return [dict(s) for s in self._specs.values()]

    def reset(self) -> None:
        with self._lock:
            self._specs.clear()
            self._dir = None


def _write_manifest(directory: Path, specs: list[dict]) -> None:
    path = directory / "bucket_manifest.json"
    tmp = path.with_name(path.name + ".tmp")
    try:
        directory.mkdir(parents=True, exist_ok=True)
        tmp.write_text(json.dumps({"specs": specs}))
        os.replace(tmp, path)
    except OSError:
        from ..utils.logger import logger

        logger.warning("could not write bucket manifest %s", path,
                       exc_info=True)


_registry = _SpecRegistry()


def bind_manifest_dir(cache_dir) -> None:
    """Point the spec registry's persistence at the persistent XLA cache
    directory (called by the backends alongside enable_compile_cache)."""
    _registry.set_dir(cache_dir)


def record_spec(spec: dict) -> bool:
    """Record one observed executable spec (backends call this at
    dispatch time, deduped); returns True when new."""
    return _registry.record(spec)


def recorded_specs() -> list[dict]:
    return _registry.specs()


def load_manifest(cache_dir) -> list[dict]:
    """Read a persisted bucket manifest without touching the process
    registry (the prime_cache CLI's entry point)."""
    path = Path(cache_dir) / "bucket_manifest.json"
    try:
        raw = json.loads(path.read_text())
    except (OSError, ValueError):
        return []
    return [e for e in raw.get("specs", []) if isinstance(e, dict)]


def reset() -> None:
    """Forget recorded specs and the bound manifest dir (tests)."""
    _registry.reset()
