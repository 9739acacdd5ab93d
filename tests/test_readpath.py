"""The result read path (ISSUE 16): columnar segment queries vs a
brute-force scan, cross-dataset cohorts, atomic republish, tile
bit-identity against engine/png.py, the governed LRU cache, and read
admission."""

from __future__ import annotations

import itertools
import json

import numpy as np
import pandas as pd
import pytest

from sm_distributed_tpu.engine.index import (
    CursorError,
    SegmentReader,
    publish_segment,
)
from sm_distributed_tpu.engine.png import PngGenerator
from sm_distributed_tpu.engine.storage import SearchResultsStore
from sm_distributed_tpu.service.readpath import ReadCache, ReadPath
from sm_distributed_tpu.utils import failpoints
from sm_distributed_tpu.utils.config import ReadPathConfig


@pytest.fixture(autouse=True)
def _reset_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


# ------------------------------------------------------------------ fixtures
def _annotations(n: int, seed: int = 0) -> pd.DataFrame:
    """A synthetic annotation table with ties, NaNs, and repeated formulas —
    the shapes that break naive sort/filter/pagination code."""
    rng = np.random.default_rng(seed)
    sfs = [f"C{i % 7 + 1}H{i % 5 + 2}O{i % 3}" for i in range(n)]
    adducts = [("+H", "+Na", "+K")[i % 3] for i in range(n)]
    msm = np.round(rng.uniform(0, 1, n), 2)       # rounding makes ties
    msm[:: max(1, n // 5)] = 0.5                  # and guarantees a few
    fdr = np.round(rng.uniform(0, 0.5, n), 3)
    fdr_level = rng.choice([0.05, 0.1, 0.2, 0.5, np.nan], n)
    return pd.DataFrame({
        "sf": sfs, "adduct": adducts, "msm": msm, "fdr": fdr,
        "fdr_level": fdr_level,
        "chaos": rng.uniform(0, 1, n), "spatial": rng.uniform(0, 1, n),
        "spectral": rng.uniform(0, 1, n)})


def _publish(results_dir, ds_id: str, n: int, seed: int = 0,
             job_id: int = 1) -> pd.DataFrame:
    d = results_dir / ds_id
    d.mkdir(parents=True, exist_ok=True)
    df = _annotations(n, seed)
    mzs = {(r.sf, r.adduct): 100.0 + i
           for i, r in enumerate(df.itertuples())}
    publish_segment(d, ds_id, job_id, df, mzs)
    return df


def _brute_rows(df: pd.DataFrame) -> list[dict]:
    """Row dicts straight off the pandas table (NaN -> None) — the
    independent ground truth the segment must reproduce."""
    rows = []
    for i, r in enumerate(df.itertuples()):
        rows.append({"sf": r.sf, "adduct": r.adduct, "mz": 100.0 + i,
                     "msm": r.msm, "fdr": r.fdr,
                     "fdr_level": None if np.isnan(r.fdr_level)
                     else r.fdr_level,
                     "chaos": r.chaos, "spatial": r.spatial,
                     "spectral": r.spectral})
    return rows


def _brute_query(rows, *, sf=None, adduct=None, max_fdr_level=None,
                 min_msm=None, mz_min=None, mz_max=None,
                 order="msm", direction="desc"):
    """Filter + total-order sort, written independently of the engine."""
    out = []
    for r in rows:
        if sf is not None and r["sf"] != sf:
            continue
        if adduct is not None and r["adduct"] != adduct:
            continue
        if max_fdr_level is not None and (
                r["fdr_level"] is None or r["fdr_level"] > max_fdr_level):
            continue
        if min_msm is not None and (
                r["msm"] is None or r["msm"] < min_msm):
            continue
        if mz_min is not None and (r["mz"] is None or r["mz"] < mz_min):
            continue
        if mz_max is not None and (r["mz"] is None or r["mz"] > mz_max):
            continue
        out.append(r)

    def key(r):
        v = r[order]
        if order != "sf" and v is None:
            v = float("-inf")
        return (v, r["sf"], r["adduct"])

    out.sort(key=key, reverse=(direction == "desc"))
    return out


def _paged(reader, ds_id, *, limit=7, **kw):
    """Walk every page through the cursor protocol, collecting rows."""
    rows, cursor, pages = [], None, 0
    while True:
        res = reader.query(ds_id, limit=limit, cursor=cursor, **kw)
        rows.extend(res["rows"])
        pages += 1
        assert pages < 100, "cursor never terminated"
        if res["next_cursor"] is None:
            return rows, res["total"]
        cursor = res["next_cursor"]


# --------------------------------------------------- parity vs brute force
def test_query_parity_vs_brute_force_scan(tmp_path):
    df = _publish(tmp_path, "ds1", n=60, seed=3)
    truth = _brute_rows(df)
    reader = SegmentReader(tmp_path)
    filters = [
        {},
        {"sf": truth[0]["sf"]},
        {"adduct": "+Na"},
        {"max_fdr_level": 0.1},
        {"min_msm": 0.5},
        {"mz_min": 110.0, "mz_max": 140.0},
        {"sf": truth[0]["sf"], "adduct": truth[0]["adduct"],
         "max_fdr_level": 0.5},
    ]
    for kw, order, direction in itertools.product(
            filters, ("msm", "mz", "fdr", "sf"), ("asc", "desc")):
        expect = _brute_query(truth, order=order, direction=direction, **kw)
        got, total = _paged(reader, "ds1", limit=7, order=order,
                            direction=direction, **kw)
        strip = [{k: v for k, v in r.items()
                  if k not in ("ds_id", "job_id")} for r in got]
        approx = [{k: (pytest.approx(v) if isinstance(v, float) else v)
                   for k, v in r.items()} for r in strip]
        assert total == len(expect), (kw, order, direction)
        assert approx == expect, (kw, order, direction)


def test_pagination_is_stable_and_duplicate_free(tmp_path):
    _publish(tmp_path, "ds1", n=41, seed=5)
    reader = SegmentReader(tmp_path)
    rows, total = _paged(reader, "ds1", limit=4, order="msm",
                         direction="desc")
    assert total == 41 and len(rows) == 41
    keys = [(r["msm"], r["sf"], r["adduct"]) for r in rows]
    assert len(set(keys)) == len(keys)          # keyset: no dup, no skip
    assert keys == sorted(keys, reverse=True)


def test_cursor_minted_under_other_order_rejected(tmp_path):
    _publish(tmp_path, "ds1", n=10)
    reader = SegmentReader(tmp_path)
    res = reader.query("ds1", order="msm", direction="desc", limit=3)
    cur = res["next_cursor"]
    assert cur is not None
    with pytest.raises(CursorError):
        reader.query("ds1", order="mz", direction="desc", cursor=cur)
    with pytest.raises(CursorError):
        reader.query("ds1", order="msm", direction="asc", cursor=cur)
    with pytest.raises(CursorError):
        reader.query("ds1", cursor="!!!not-a-cursor!!!")


# ------------------------------------------------------------------ cohort
def test_cohort_across_three_datasets(tmp_path):
    dfs = {ds: _publish(tmp_path, ds, n=30, seed=i)
           for i, ds in enumerate(("a", "b", "c"))}
    reader = SegmentReader(tmp_path)
    sf = dfs["a"]["sf"].iloc[0]                  # formula grid is shared
    res = reader.cohort(sf)
    assert res["sf"] == sf and res["n_datasets"] == 3
    per_ds = {d["ds_id"]: d["rows"] for d in res["datasets"]}
    assert set(per_ds) == {"a", "b", "c"}
    for ds, df in dfs.items():
        assert len(per_ds[ds]) == int((df["sf"] == sf).sum())
        assert all(r["sf"] == sf for r in per_ds[ds])
        msms = [r["msm"] for r in per_ds[ds]]
        assert msms == sorted(msms, reverse=True)
    assert res["n_rows"] == sum(len(v) for v in per_ds.values())


# --------------------------------------------------------- atomic republish
def test_reannotation_atomically_replaces_segment(tmp_path):
    _publish(tmp_path, "ds1", n=20, seed=1, job_id=1)
    reader = SegmentReader(tmp_path)
    v1 = reader.query("ds1")
    _publish(tmp_path, "ds1", n=35, seed=2, job_id=2)
    v2 = reader.query("ds1")
    assert (v1["job_id"], v1["total"]) == (1, 20)
    assert (v2["job_id"], v2["total"]) == (2, 35)
    assert v2["published_at"] >= v1["published_at"]
    assert not list((tmp_path / "ds1").glob("*.tmp"))


def test_crashed_publish_leaves_previous_segment_served(tmp_path):
    _publish(tmp_path, "ds1", n=12, seed=1, job_id=1)
    failpoints.configure("index.segment_commit=raise:OSError@1")
    with pytest.raises(OSError):
        _publish(tmp_path, "ds1", n=30, seed=2, job_id=2)
    reader = SegmentReader(tmp_path)
    res = reader.query("ds1")
    assert (res["job_id"], res["total"]) == (1, 12)   # old segment intact


# ----------------------------------------------------------------- tiles
def _write_csr_v1(path, images, ions, nrows, ncols):
    """`ion_images.npz` as every store before PR 25 wrote it: the CSR triple,
    every member deflated, no `layout` member."""
    flat = images.reshape(images.shape[0] * images.shape[1], -1)
    nz = flat != 0
    indptr = np.zeros(flat.shape[0] + 1, dtype=np.int64)
    np.cumsum(nz.sum(axis=1), out=indptr[1:])
    np.savez_compressed(
        path, data=flat[nz].astype(np.float32),
        indices=np.nonzero(nz)[1].astype(np.int32), indptr=indptr,
        shape=np.array([images.shape[0], images.shape[1], nrows, ncols]),
        ions=np.array([f"{sf}|{adduct}" for sf, adduct in ions]))


def _store_images(tmp_path, ds_id="ds1", n_ions=3, k=2, nrows=6, ncols=5,
                  layout="bitmask_v1"):
    rng = np.random.default_rng(7)
    images = rng.uniform(0, 1, (n_ions, k, nrows * ncols)).astype(np.float32)
    images[images < 0.3] = 0.0                  # sparsity, like real tiles
    ions = [(f"C{i}H{i + 1}", "+H") for i in range(n_ions)]
    store = SearchResultsStore.__new__(SearchResultsStore)
    store.results_dir = tmp_path
    store.image_format = "npz"
    d = tmp_path / ds_id
    d.mkdir(parents=True, exist_ok=True)
    store.ds_dir = lambda _ds: d
    if layout == "csr_v1":
        _write_csr_v1(d / "ion_images.npz", images, ions, nrows, ncols)
    else:
        store.store_ion_images(ds_id, images, ions, nrows, ncols)
    with np.load(d / "ion_images.npz") as z:
        assert ("indptr" in z.files) == (layout == "csr_v1")
        assert ("layout" in z.files) == (layout != "csr_v1")
    return images.reshape(n_ions, k, nrows, ncols), ions


@pytest.mark.parametrize("layout", ["bitmask_v1", "csr_v1"])
def test_tile_bytes_bit_identical_to_direct_render(tmp_path, layout):
    images, ions = _store_images(tmp_path, layout=layout)
    dense, got_ions = SearchResultsStore.load_ion_images(
        tmp_path / "ds1" / "ion_images.npz")
    assert got_ions == ions and np.array_equal(dense, images)
    rp = ReadPath(tmp_path, ReadPathConfig())
    for i, (sf, adduct) in enumerate(ions):
        for k in range(images.shape[1]):
            status, body, _hd = rp.handle_tile(
                "ds1", f"{sf}|{adduct}", {"k": [str(k)]})
            assert status == 200
            assert body == PngGenerator().render(images[i, k])
    status, _body, _hd = rp.handle_tile("ds1", "XX|+H", {})
    assert status == 404
    status, _body, _hd = rp.handle_tile("ds1", f"{ions[0][0]}|+H",
                                        {"k": ["99"]})
    assert status == 404
    status, _body, _hd = rp.handle_tile("ds1", "no-pipe-here", {})
    assert status == 400


def test_both_npz_layouts_render_the_same_tile_bytes(tmp_path):
    """A deployment's old CSR file and the same images re-stored by today's
    writer are one answer to a reader: same dense array, same PNG bytes."""
    new, ions = _store_images(tmp_path / "new")
    old, _ions = _store_images(tmp_path / "old", layout="csr_v1")
    assert np.array_equal(new, old)
    tiles = []
    for root in (tmp_path / "new", tmp_path / "old"):
        rp = ReadPath(root, ReadPathConfig())
        tiles.append([rp.handle_tile("ds1", f"{sf}|{ad}", {"k": [str(k)]})[:2]
                      for sf, ad in ions for k in range(new.shape[1])])
    assert tiles[0] == tiles[1]
    assert all(status == 200 for status, _body in tiles[0])


def test_tile_disk_tier_round_trip(tmp_path):
    images, ions = _store_images(tmp_path)
    disk = tmp_path / "tile_cache"
    rp = ReadPath(tmp_path, ReadPathConfig(), disk_dir=disk)
    sf, adduct = ions[0]
    status, body, _hd = rp.handle_tile("ds1", f"{sf}|{adduct}", {})
    assert status == 200
    spilled = list(disk.glob("*.png"))
    assert len(spilled) == 1 and spilled[0].read_bytes() == body
    # a fresh ReadPath (restart) serves the same bytes from the disk tier
    rp2 = ReadPath(tmp_path, ReadPathConfig(), disk_dir=disk)
    status, body2, _hd = rp2.handle_tile("ds1", f"{sf}|{adduct}", {})
    assert status == 200 and body2 == body
    assert rp2.snapshot()["cache"]["entries"] == 1


# ------------------------------------------------------------------ cache
def test_read_cache_lru_eviction_and_bounds():
    c = ReadCache(max_bytes=100, max_entries=3)
    c.put(("a",), "A", 40)
    c.put(("b",), "B", 40)
    assert c.get(("a",)) == "A"                 # refresh a
    c.put(("c",), "C", 40)                      # 120 > 100: evict LRU = b
    assert c.get(("b",)) is None and c.get(("a",)) == "A"
    c.put(("d",), "D", 10)
    c.put(("e",), "E", 10)                      # entry cap 3: evict oldest
    s = c.stats()
    assert s["entries"] <= 3 and s["bytes"] <= 100 and s["evictions"] >= 2
    c.put(("huge",), "X", 1000)                 # can never fit: not cached
    assert c.get(("huge",)) is None


def test_warm_query_is_a_cache_hit_and_republish_invalidates(tmp_path):
    _publish(tmp_path, "ds1", n=10, seed=1, job_id=1)
    rp = ReadPath(tmp_path, ReadPathConfig())
    s1, b1, _h = rp.handle_annotations("ds1", {})
    s2, b2, _h = rp.handle_annotations("ds1", {})
    assert s1 == s2 == 200 and b2 is b1         # literally the cached object
    stats = rp.snapshot()["cache"]
    assert stats["hits"] == 1 and stats["misses"] >= 1
    _publish(tmp_path, "ds1", n=25, seed=2, job_id=2)
    s3, b3, _h = rp.handle_annotations("ds1", {})
    assert s3 == 200 and b3["job_id"] == 2 and b3["total"] == 25


def test_cache_fill_failure_never_fails_the_read(tmp_path):
    _publish(tmp_path, "ds1", n=10)
    rp = ReadPath(tmp_path, ReadPathConfig())
    failpoints.configure("read.cache_fill=raise:OSError@1")
    s1, b1, _h = rp.handle_annotations("ds1", {})
    assert s1 == 200 and b1["total"] == 10      # read answered anyway
    assert rp.snapshot()["cache"]["entries"] == 0
    s2, b2, _h = rp.handle_annotations("ds1", {})   # retry warms it
    assert s2 == 200
    assert rp.snapshot()["cache"]["entries"] == 1


class _DenyingGovernor:
    def __init__(self):
        self.calls = 0

    def allow_read_cache_fill(self):
        self.calls += 1
        return False


def test_governor_denied_fill_serves_but_does_not_cache(tmp_path):
    _publish(tmp_path, "ds1", n=10)
    gov = _DenyingGovernor()
    rp = ReadPath(tmp_path, ReadPathConfig(), governor=gov)
    for _ in range(2):
        status, body, _h = rp.handle_annotations("ds1", {})
        assert status == 200 and body["total"] == 10
    assert gov.calls == 2                       # both reads tried to fill
    assert rp.snapshot()["cache"]["entries"] == 0


# --------------------------------------------------------------- admission
def test_read_admission_sheds_structured_429(tmp_path):
    _publish(tmp_path, "ds1", n=10)
    rp = ReadPath(tmp_path, ReadPathConfig(max_concurrent=1,
                                           retry_after_s=2.0))
    assert rp._admit()                          # occupy the only slot
    try:
        status, body, headers = rp.handle_annotations("ds1", {})
        assert status == 429
        assert body["reason"] == "read_overload" and not body["accepted"]
        assert body["retry_after_s"] == 2.0
        assert headers["Retry-After"] == "2"
        assert rp.snapshot()["sheds"] == 1
    finally:
        rp._release()
    status, _b, _h = rp.handle_annotations("ds1", {})   # slot free again
    assert status == 200


def test_bad_requests_are_structured_400s(tmp_path):
    _publish(tmp_path, "ds1", n=10)
    rp = ReadPath(tmp_path, ReadPathConfig(page_size=20, page_size_max=50))
    for params in ({"limit": ["0"]}, {"limit": ["9999"]},
                   {"limit": ["nope"]}, {"fdr": ["zz"]},
                   {"order": ["bogus"]}, {"dir": ["sideways"]},
                   {"cursor": ["@@@"]}):
        status, body, _h = rp.handle_annotations("ds1", params)
        assert status == 400, params
        assert body["error"] == "bad_request" and body["detail"]
    status, body, _h = rp.handle_cohort({})     # cohort requires sf
    assert status == 400
    status, body, _h = rp.handle_annotations("never-published", {})
    assert status == 404 and body["error"] == "not_found"


def test_metrics_and_snapshot_surface_read_activity(tmp_path):
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    _publish(tmp_path, "ds1", n=10)
    reg = MetricsRegistry()
    rp = ReadPath(tmp_path, ReadPathConfig(), metrics=reg)
    rp.handle_annotations("ds1", {})
    rp.handle_annotations("ds1", {})
    rp.handle_annotations("missing", {})
    text = reg.expose()
    assert 'sm_read_requests_total{endpoint="annotations",outcome="ok"} 2' \
        in text
    assert 'outcome="http_404"' in text
    assert 'sm_read_cache_hits_total{kind="annotations"} 1' in text
    assert "sm_read_latency_seconds_bucket" in text
    assert "sm_read_cache_entries 1" in text
