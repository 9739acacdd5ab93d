"""Which path the packed chaos kernel's programs took, from the kernel to
``/metrics`` (ISSUE 48): the flag a program rides under the batch's metric
rows, so the scores' own fetch brings it; ``sm_chaos_programs_total{path=}``
counts it; ``benchmarks/layers/chaos_sparse_pct.py`` reads the window's
share.  The packed route needs Mosaic, so the backend test steers it into
Pallas interpret mode here, in the test.
"""

from __future__ import annotations

import functools
import importlib.util
import sys
import threading
from collections import OrderedDict
from pathlib import Path

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models import msm_jax
from sm_distributed_tpu.models.msm_basic import _slice_table
from sm_distributed_tpu.ops import chaos_pallas, metrics_jax
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.service.metrics import MetricsRegistry
from sm_distributed_tpu.service.server import AnnotationService
from sm_distributed_tpu.utils.config import (
    DSConfig, IsotopeGenerationConfig, SMConfig)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

from serve import metric_sum  # noqa: E402  (benchmarks/serve.py)

COUNTER = "sm_chaos_programs_total"
BATCH = 32


@pytest.fixture(scope="module")
def section(tmp_path_factory):
    path, truth = generate_synthetic_dataset(
        tmp_path_factory.mktemp("programs"), nrows=16, ncols=16,
        formulas=None, present_fraction=0.3, noise_peaks=40, seed=48)
    ds = SpectralDataset.from_imzml(path)
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H", "+Na")))
    # the formulas with signal first: the leading batch holds every blob,
    # the others a decoy's few noise pixels an image
    present = set(truth.present)
    formulas = sorted(truth.formulas, key=lambda sf: sf not in present)
    table = calc.pattern_table(
        [(sf, a) for sf in formulas for a in ("+H", "+Na")])
    return ds, table


def _backend(ds):
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict({"backend": "jax_tpu", "parallel": {
        "formula_batch": BATCH}})
    return msm_jax.JaxBackend(ds, dc, sm)


def _flood_programs(principal: np.ndarray, side: int, ib: int) -> int:
    """Programs of ``ib`` consecutive images (zero images after the last)
    in which some image has two 4-adjacent pixels above 0."""
    m = principal.reshape(-1, side, side) > 0
    pair = (m[:, :, 1:] & m[:, :, :-1]).any(axis=(1, 2)) \
        | (m[:, 1:] & m[:, :-1]).any(axis=(1, 2))
    pair = np.concatenate([pair, np.zeros(-len(pair) % ib, bool)])
    return int(pair.reshape(-1, ib).any(axis=1).sum())


def test_a_batch_brings_its_program_counts_with_its_scores(
        section, monkeypatch):
    ds, table = section
    tables = [_slice_table(table, s, min(s + BATCH, table.n_ions))
              for s in range(0, table.n_ions, BATCH)]
    assert len(tables) >= 3 and tables[-1].n_ions < BATCH
    want = _backend(ds).score_batches(tables)          # the scan route

    # the packed kernel through the interpreter, on fresh scoring jits
    packed = functools.partial(chaos_pallas.chaos_geometry, pallas=True)
    monkeypatch.setattr(metrics_jax, "chaos_dispatch",
                        lambda nrows, ncols, use_pallas=None: packed(
                            nrows, ncols))
    monkeypatch.setattr(msm_jax, "chaos_dispatch",
                        lambda nrows, ncols: packed(nrows, ncols))
    monkeypatch.setattr(chaos_pallas, "chaos_count_sums", functools.partial(
        chaos_pallas.chaos_count_sums, interpret=True))
    monkeypatch.setattr(msm_jax, "_SHARED_JITS", OrderedDict())
    fetches = []
    fetch = msm_jax.to_numpy_global
    monkeypatch.setattr(msm_jax, "to_numpy_global",
                        lambda arr: fetches.append(arr.shape) or fetch(arr))
    backend = _backend(ds)
    geo = backend.chaos_geometry
    assert (geo.route, geo.images_per_program) == ("packed", 32)
    before = msm_jax.chaos_program_events()
    got = backend.score_batches(tables)
    after = msm_jax.chaos_program_events()

    # the same rows, the same bits, and ONE fetch a batch: the counts rode
    # as the row under the batch's own
    for g, w, t in zip(got, want, tables):
        assert g.shape == (t.n_ions, 4)
        np.testing.assert_array_equal(g, w)
    assert fetches == [(BATCH + 1, 4)] * len(tables)
    # what the kernel said is what the images say (one program a batch at
    # 32 images of 16x16 a block)
    images = [backend.extract_ion_images(t)[:, 0, :] for t in tables]
    flood = sum(_flood_programs(im, 16, 32) for im in images)
    assert 0 < flood < len(tables)
    assert after["flood"] - before["flood"] == flood
    assert after["sparse"] - before["sparse"] == len(tables) - flood

    # the single-batch API counts too, and keeps its rows
    one = backend.score_batch(tables[-1])
    np.testing.assert_array_equal(one, want[-1])
    last = msm_jax.chaos_program_events()
    assert sum(last.values()) - sum(after.values()) == 1

    # and the scrape exposes the process's counts under path=
    reg = MetricsRegistry()
    reg.add_collector(AnnotationService._collect_chaos_programs)
    text = reg.expose()
    for path in ("sparse", "flood"):
        assert metric_sum(text, COUNTER, f'path="{path}"') == last[path]


def test_routes_without_programs_count_none(section):
    """The scan route (this CPU's) has no programs: the row reads 0 / 0."""
    ds, table = section
    before = msm_jax.chaos_program_events()
    _backend(ds).score_batch(_slice_table(table, 0, BATCH))
    assert msm_jax.chaos_program_events() == before


def test_program_counter_loses_no_update_between_fetch_threads():
    """``fetch_scored_batches`` tallies from its pool's threads, and two
    scheduler workers fetch at once: 16 threads on 8 cores, the interpreter
    switching every microsecond, 2,000 blocks each."""
    block = np.zeros((9, 4), np.float32)
    block[-1, :2] = (3, 1)
    before = msm_jax.chaos_program_events()
    workers = [threading.Thread(target=lambda: [
        msm_jax._count_chaos_programs(block) for _ in range(2000)])
        for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    after = msm_jax.chaos_program_events()
    assert after["sparse"] - before["sparse"] == 16 * 2000 * 3
    assert after["flood"] - before["flood"] == 16 * 2000 * 1


def _exposed(sparse: int, flood: int) -> str:
    return (f"# HELP {COUNTER} programs\n# TYPE {COUNTER} counter\n"
            f'{COUNTER}{{path="flood"}} {float(flood)}\n'
            f'{COUNTER}{{path="sparse"}} {float(sparse)}\n')


def test_the_reader_reads_the_window_share():
    spec = importlib.util.spec_from_file_location(
        "layer_chaos_sparse_pct",
        REPO / "benchmarks" / "layers" / "chaos_sparse_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = {"metrics_before": _exposed(100, 10),
           "metrics_after": _exposed(1000, 110)}
    assert mod.read(run) == pytest.approx(100.0 * 900 / 1000)
    # the family's first samples inside the window grew from nothing
    assert mod.read({**run, "metrics_before": ""}) == pytest.approx(
        100.0 * 1000 / 1110)
    # nothing to read: a program without the counter (the parent commit's),
    # a window in which no packed program ran
    assert mod.read({"metrics_before": "", "metrics_after": ""}) is None
    assert mod.read({**run, "metrics_before": run["metrics_after"]}) is None
    manifest = __import__("json").loads((REPO / "BENCHMARK.json").read_text())
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "chaos_sparse_pct"]
    assert entry == {
        "name": "chaos_sparse_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "ions_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]]}
