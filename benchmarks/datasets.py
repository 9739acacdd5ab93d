"""Seeded synthetic MALDI sections, generated in bulk (numpy only).

The benchmark's own copy of ``sm_distributed_tpu/io/fixtures.py``'s spheroid
(same formulas, spatial patterns, noise model and imzML layout the engine
parses), vectorised over pixels so that a new seed costs seconds instead of
minutes.  A dataset is cached under ``<cache>/<hash of parameters+seed>/`` and
found again when the parameters match; generation is deterministic in them.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
import uuid
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from reference.isocalc import isotope_peaks  # noqa: E402

# 50 plausible small-molecule sum formulas (metabolite-like, HMDB-style)
SEED_FORMULAS = [
    "C6H12O6", "C6H13NO2", "C5H9NO4", "C9H11NO2", "C3H7NO3",
    "C4H9NO3", "C5H11NO2", "C6H14N4O2", "C6H9N3O2", "C11H12N2O2",
    "C4H7NO4", "C5H5N5", "C5H5N5O", "C10H13N5O4", "C10H13N5O5",
    "C9H13N3O5", "C10H12N2O6", "C4H6O5", "C4H6O4", "C6H8O7",
    "C3H4O3", "C4H4O4", "C5H8O5", "C7H6O2", "C7H8N4O2",
    "C8H10N4O2", "C10H16N5O13P3", "C10H15N5O10P2", "C10H14N5O7P",
    "C21H27N7O14P2",
    "C16H32O2", "C18H36O2", "C18H34O2", "C18H32O2", "C20H32O2",
    "C5H11O8P", "C6H13O9P", "C3H9O6P", "C8H20NO6P", "C5H14NO4P",
    "C23H38N7O17P3S", "C9H16O4", "C24H50NO7P", "C26H54NO7P", "C42H82NO8P",
    "C40H80NO8P", "C44H84NO8P", "C27H46O", "C19H28O2", "C18H24O2",
]


ADDUCT_STREAM = 0xADD     # the adduct draw's own stream beside the seed's


def formula_list(n: int) -> list[str]:
    """Deterministic list of ``n`` plausible CHNO(PS) sum formulas."""
    out = list(dict.fromkeys(SEED_FORMULAS))
    seen = set(out)
    c, h_off, nn, o = 7, 0, 0, 2
    while len(out) < n:
        h = c + 2 - h_off % 5 + nn
        sf = f"C{c}H{max(2, h)}" + (f"N{nn}" if nn else "") + \
            (f"O{o}" if o else "")
        if sf not in seen:
            out.append(sf)
            seen.add(sf)
        c += 1
        if c > 40:
            c = 7
            o += 1
            if o > 12:
                o = 0
                nn += 1
            h_off += 1
    return out[:n]


def _spatial_patterns(n: int, nrows: int, ncols: int,
                      rng: np.random.Generator) -> np.ndarray:
    """(n, nrows*ncols) structured images in [0, 1]: blob, ring, gradient."""
    yy, xx = np.mgrid[0:nrows, 0:ncols]
    r = np.hypot(yy - nrows / 2, xx - ncols / 2) / (min(nrows, ncols) / 2)
    base = np.stack([
        np.clip(1.0 - r, 0, 1) ** 1.5,
        np.exp(-(((r - 0.6) / 0.15) ** 2)),
        np.clip(xx / ncols + 0.1 * np.sin(yy / 3), 0, 1),
    ]).reshape(3, -1)
    imgs = base[np.arange(n) % 3] * (0.8 + 0.4 * rng.random((n, nrows * ncols)))
    return imgs / imgs.max(axis=1, keepdims=True)


def _write_imzml(path: Path, nrows: int, ncols: int, lens: np.ndarray,
                 mzs: np.ndarray, ints: np.ndarray) -> None:
    """Processed-mode imzML 1.1: all f64 m/z arrays, then all f32 intensity
    arrays, each spectrum naming its two external offsets."""
    uid = uuid.UUID(bytes=hashlib.md5(mzs[:4096].tobytes()).digest())
    with open(path.with_suffix(".ibd"), "wb") as ibd:
        ibd.write(uid.bytes)
        ibd.write(np.ascontiguousarray(mzs, "<f8").tobytes())
        ibd.write(np.ascontiguousarray(ints, "<f4").tobytes())
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    mz_off = 16 + 8 * starts
    int_off = 16 + 8 * int(lens.sum()) + 4 * starts

    def array(group, off, n, width):
        return (
            '<binaryDataArray encodedLength="0">'
            f'<referenceableParamGroupRef ref="{group}"/>'
            f'<cvParam cvRef="IMS" accession="IMS:1000102" name="external '
            f'offset" value="{off}"/>'
            f'<cvParam cvRef="IMS" accession="IMS:1000103" name="external '
            f'array length" value="{n}"/>'
            f'<cvParam cvRef="IMS" accession="IMS:1000104" name="external '
            f'encoded length" value="{n * width}"/><binary/>'
            '</binaryDataArray>')

    out = [
        '<?xml version="1.0" encoding="ISO-8859-1"?>',
        '<mzML xmlns="http://psi.hupo.org/ms/mzml" version="1.1">',
        '<cvList count="2"><cv id="MS" fullName="PSI MS"/>'
        '<cv id="IMS" fullName="Imaging MS Ontology"/></cvList>',
        '<fileDescription><fileContent>',
        '<cvParam cvRef="IMS" accession="IMS:1000031" name="processed"/>',
        '<cvParam cvRef="IMS" accession="IMS:1000080" name="universally '
        f'unique identifier" value="{{{uid}}}"/>',
        '</fileContent></fileDescription>',
        '<referenceableParamGroupList count="2">',
        '<referenceableParamGroup id="mzArray">'
        '<cvParam cvRef="MS" accession="MS:1000514" name="m/z array"/>'
        '<cvParam cvRef="MS" accession="MS:1000523" name="64-bit float"/>'
        '</referenceableParamGroup>',
        '<referenceableParamGroup id="intensityArray">'
        '<cvParam cvRef="MS" accession="MS:1000515" name="intensity array"/>'
        '<cvParam cvRef="MS" accession="MS:1000521" name="32-bit float"/>'
        '</referenceableParamGroup>',
        '</referenceableParamGroupList>',
        '<scanSettingsList count="1"><scanSettings id="scan1">'
        f'<cvParam cvRef="IMS" accession="IMS:1000042" name="max count of '
        f'pixels x" value="{ncols}"/>'
        f'<cvParam cvRef="IMS" accession="IMS:1000043" name="max count of '
        f'pixels y" value="{nrows}"/></scanSettings></scanSettingsList>',
        f'<run id="run1"><spectrumList count="{lens.size}">',
    ]
    for i in range(lens.size):
        n = int(lens[i])
        out.append(
            f'<spectrum id="spectrum={i}" index="{i}" '
            f'defaultArrayLength="{n}"><scanList count="1"><scan>'
            f'<cvParam cvRef="IMS" accession="IMS:1000050" name="position x" '
            f'value="{i % ncols + 1}"/>'
            f'<cvParam cvRef="IMS" accession="IMS:1000051" name="position y" '
            f'value="{i // ncols + 1}"/></scan></scanList>'
            '<binaryDataArrayList count="2">'
            + array("mzArray", int(mz_off[i]), n, 8)
            + array("intensityArray", int(int_off[i]), n, 4)
            + '</binaryDataArrayList></spectrum>')
    out.append('</spectrumList></run></mzML>')
    path.write_text("\n".join(out))


def generate(cache: Path, params: dict, seed: int) -> dict:
    """The dataset of (``params``, ``seed``) under ``cache``: made or found.
    Returns {"path", "formulas", "present", "n_peaks", "nrows", "ncols"} and,
    where ``params`` has a list of ``adducts``, "present_ions"
    ([[sf, adduct], ...], in ``present``'s order)."""
    key = hashlib.sha256(json.dumps(
        {"params": params, "seed": int(seed), "v": 1},
        sort_keys=True).encode()).hexdigest()[:16]
    out_dir = Path(cache) / key
    meta_path = out_dir / "meta.json"
    if meta_path.exists():
        return json.loads(meta_path.read_text())
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)

    nrows, ncols = params["nrows"], params["ncols"]
    n_px = nrows * ncols
    rng = np.random.default_rng(int(seed))
    formulas = formula_list(params["n_formulas"])
    n_present = max(1, round(params["present_fraction"] * len(formulas)))
    present = [str(sf) for sf in rng.permutation(formulas)[:n_present]]
    k = params["n_peaks"]
    pk_mz = np.zeros((n_present, k))
    pk_int = np.zeros((n_present, k))
    # with ``adducts`` each formula with signal carries it under exactly ONE
    # adduct of the list: the list cycled to length and shuffled (every seed
    # the same count an adduct, in another order) by a stream of its own, so
    # a configuration without the key draws nothing new and keeps its bytes
    # and its cache key
    adducts = [params["adduct"]] * n_present if "adducts" not in params \
        else [str(a) for a in np.random.default_rng(
            [int(seed), ADDUCT_STREAM]).permutation(
            np.resize(params["adducts"], n_present))]
    for i, sf in enumerate(present):
        mzs, ints = isotope_peaks(sf, adducts[i], n_peaks=k)
        pk_mz[i, :mzs.size], pk_int[i, :ints.size] = mzs, ints
    amp = _spatial_patterns(n_present, nrows, ncols, rng)
    f_ix, p_ix = np.nonzero(amp > 0.02)
    valid = pk_int[f_ix] > 0                              # (n_sig, k)
    jitter = 1.0 + params["mz_jitter_ppm"] * 1e-6 * rng.standard_normal(
        valid.shape)
    sig_mz = (pk_mz[f_ix] * jitter)[valid]
    sig_int = (amp[f_ix, p_ix][:, None] * pk_int[f_ix]
               * (0.9 + 0.2 * rng.random(valid.shape)))[valid]
    sig_px = np.broadcast_to(p_ix[:, None], valid.shape)[valid]
    noise = params["noise_peaks"]
    mzs = np.concatenate([sig_mz, rng.uniform(80.0, 1000.0, n_px * noise)])
    ints = np.concatenate([sig_int, rng.exponential(2.0, n_px * noise)])
    px = np.concatenate([sig_px, np.repeat(np.arange(n_px), noise)])
    order = np.argsort(px * 4096.0 + mzs, kind="stable")
    mzs, ints, px = mzs[order], ints[order].astype(np.float32), px[order]
    lens = np.bincount(px, minlength=n_px)
    path = out_dir / "section.imzML"
    _write_imzml(path, nrows, ncols, lens, mzs, ints)
    meta = {"path": str(path), "formulas": formulas, "present": present,
            "n_peaks": int(mzs.size), "nrows": nrows, "ncols": ncols,
            "seed": int(seed)}
    if "adducts" in params:
        meta["present_ions"] = [list(ion) for ion in zip(present, adducts)]
    meta_path.write_text(json.dumps(meta))
    return meta


def _generate_star(args):
    return generate(*args)


def generate_many(cache: Path, params: dict, seeds: list[int],
                  procs: int) -> list[dict]:
    """Several seeds at once, in numpy-only worker processes."""
    jobs = [(str(cache), params, s) for s in seeds]
    if procs <= 1 or len(jobs) == 1:
        return [generate(*j) for j in jobs]
    from multiprocessing import get_context

    with get_context("spawn").Pool(min(procs, len(jobs))) as pool:
        return pool.map(_generate_star, jobs)


def prune(cache: Path, keep: list[str], limit_bytes: int) -> None:
    """Drop datasets this run does not use once the cache passes
    ``limit_bytes`` (every new seed adds a catalogue)."""
    cache = Path(cache)
    if not cache.is_dir():
        return
    keep_dirs = {Path(p).parent.name for p in keep}
    dirs = [d for d in cache.iterdir() if d.is_dir()]
    size = sum(f.stat().st_size for d in dirs for f in d.iterdir())
    if size <= limit_bytes:
        return
    for d in sorted(dirs, key=lambda d: d.stat().st_mtime):
        if d.name not in keep_dirs:
            shutil.rmtree(d, ignore_errors=True)
