"""Mesh-sharded runtime tests on the virtual 8-device CPU mesh — the analog
of the reference testing its Spark code in ``local[*]`` mode (SURVEY.md §4):
the real collective/sharding code paths run single-machine."""

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
from sm_distributed_tpu.utils.config import (
    DSConfig,
    IsotopeGenerationConfig,
    ParallelConfig,
    SMConfig,
)


@pytest.fixture(scope="module")
def fixture_ds(tmp_path_factory):
    out = tmp_path_factory.mktemp("dsp")
    path, truth = generate_synthetic_dataset(
        out, nrows=10, ncols=14, present_fraction=0.5, noise_peaks=50, seed=31,
    )
    return SpectralDataset.from_imzml(path), truth


def _table(truth, n=16):
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    return calc.pattern_table([(sf, "+H") for sf in truth.formulas[:n]])


def test_resolve_axis_sizes():
    from sm_distributed_tpu.parallel.mesh import resolve_axis_sizes

    assert resolve_axis_sizes(8, ParallelConfig(pixels_axis=-1, formulas_axis=1)) == (8, 1)
    assert resolve_axis_sizes(8, ParallelConfig(pixels_axis=-1, formulas_axis=2)) == (4, 2)
    assert resolve_axis_sizes(8, ParallelConfig(pixels_axis=2, formulas_axis=-1)) == (2, 4)
    assert resolve_axis_sizes(8, ParallelConfig(pixels_axis=-1, formulas_axis=-1)) == (8, 1)
    assert resolve_axis_sizes(1, ParallelConfig(pixels_axis=-1, formulas_axis=1)) == (1, 1)
    with pytest.raises(ValueError):
        resolve_axis_sizes(8, ParallelConfig(pixels_axis=-1, formulas_axis=3))
    with pytest.raises(ValueError):
        resolve_axis_sizes(4, ParallelConfig(pixels_axis=8, formulas_axis=1))


def test_make_mesh_axes():
    from sm_distributed_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(ParallelConfig(pixels_axis=4, formulas_axis=2))
    assert mesh.axis_names == ("pixels", "formulas")
    assert dict(mesh.shape) == {"pixels": 4, "formulas": 2}


@pytest.mark.parametrize("pix,form", [(8, 1), (4, 2), (2, 4)])
def test_sharded_matches_single_device(fixture_ds, pix, form):
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm_sharded = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 32, "pixels_axis": pix, "formulas_axis": form}}
    )
    sm_single = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 32, "pixels_axis": 1, "formulas_axis": 1}}
    )
    got = ShardedJaxBackend(ds, dc, sm_sharded).score_batch(table)
    want = JaxBackend(ds, dc, sm_single).score_batch(table)
    # BIT-EXACT: the all_to_all hands each device full-pixel images whose
    # values are exact integers on the shared intensity grid, and metrics
    # run the identical code on identical bits — sharding cannot change
    # results, at any mesh shape.  This is the single-PROCESS half of the
    # parity contract; the multi-process half (chaos bit-exact,
    # spatial/spectral 1e-6 — cross-process lowering fuses f32 reductions
    # differently) is asserted by
    # test_distributed.py::test_two_process_distributed_real.
    np.testing.assert_array_equal(got, want)


def test_sharded_window_restriction_bit_exact(fixture_ds):
    """Per-shard window-union restriction must leave sharded scores
    bit-identical (dropped peaks match no window of the search)."""
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 32, "pixels_axis": 4,
                      "formulas_axis": 2}})
    full = ShardedJaxBackend(ds, dc, sm)
    restricted = ShardedJaxBackend(ds, dc, sm, restrict_table=table)
    assert restricted._mz_shards.shape[1] < full._mz_shards.shape[1]
    np.testing.assert_array_equal(
        restricted.score_batch(table), full.score_batch(table))


def test_sharded_with_preprocessing(fixture_ds):
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth, n=8)
    dc = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"do_preprocessing": True}}
    )
    sm = SMConfig.from_dict(
        {"parallel": {"formula_batch": 16, "pixels_axis": 4, "formulas_axis": 2}}
    )
    sm1 = SMConfig.from_dict(
        {"parallel": {"formula_batch": 16, "pixels_axis": 1, "formulas_axis": 1}}
    )
    got = ShardedJaxBackend(ds, dc, sm).score_batch(table)
    want = JaxBackend(ds, dc, sm1).score_batch(table)
    np.testing.assert_array_equal(got, want)


def test_make_jax_backend_selects_sharded(fixture_ds):
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend, make_jax_backend

    ds, _ = fixture_ds
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    multi = make_jax_backend(ds, dc, SMConfig.from_dict({"parallel": {"formula_batch": 16}}))
    assert isinstance(multi, ShardedJaxBackend)
    single = make_jax_backend(
        ds, dc,
        SMConfig.from_dict(
            {"parallel": {"formula_batch": 16, "pixels_axis": 1, "formulas_axis": 1}}
        ),
    )
    assert isinstance(single, JaxBackend)


def test_sharded_batch_divisibility(fixture_ds):
    # formula_batch not divisible by the formulas axis gets rounded up
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"parallel": {"formula_batch": 5, "pixels_axis": 2, "formulas_axis": 4}}
    )
    backend = ShardedJaxBackend(ds, dc, sm)
    assert backend.batch % 4 == 0
    out = backend.score_batch(_table(truth, n=6))
    assert out.shape == (6, 4)
    assert np.isfinite(out).all()


def test_dryrun_multichip_driver_path():
    """The driver-facing entry: must force its own virtual CPU mesh in a
    fresh subprocess (VERDICT round-1 item 1) and exit 0 even when the
    calling process has a different platform configured."""
    import os
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    try:
        from __graft_entry__ import dryrun_multichip
    finally:
        sys.path.remove(repo_root)
    dryrun_multichip(4)


def test_sharded_hbm_guard(fixture_ds):
    """The mesh path must fail EARLY with guidance (not OOM opaquely) when
    the per-shard histogram scratch would blow HBM."""
    from sm_distributed_tpu.parallel.mesh import make_mesh
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    # oversize: huge formula batch on one formula shard -> per-shard scratch
    # 4 * (p_loc+1) * 2*B*K explodes past 8 GiB
    sm_big = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 300_000_000, "pixels_axis": 4,
                      "formulas_axis": 2}})
    with pytest.raises(ValueError, match="per-shard histogram scratch"):
        ShardedJaxBackend(ds, ds_config, sm_big,
                          mesh=make_mesh(sm_big.parallel))


@pytest.mark.parametrize("pix,form", [(4, 2), (2, 4)])
def test_sharded_peak_compaction_bit_exact(fixture_ds, pix, form):
    """Mesh-path per-batch peak compaction (each device gathers only its
    shard's in-window peaks) must leave every scored bit unchanged —
    forced on vs off, incl. with the search-union restriction active."""
    from sm_distributed_tpu.parallel.mesh import make_mesh
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth)

    def mk(mode, restrict=None):
        sm = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 32, "pixels_axis": pix,
                          "formulas_axis": form, "peak_compaction": mode}})
        return ShardedJaxBackend(ds, DSConfig.from_dict(
            {"isotope_generation": {"adducts": ["+H"]}}), sm,
            mesh=make_mesh(sm.parallel), restrict_table=restrict)

    plain = mk("off").score_batch(table)
    np.testing.assert_array_equal(mk("on").score_batch(table), plain)
    np.testing.assert_array_equal(
        mk("on", restrict=table).score_batch(table), plain)
    # streams mixing both variants (auto) still agree
    half = _table(truth, n=8)
    b_auto = mk("auto")
    outs = b_auto.score_batches([table, half])
    np.testing.assert_array_equal(outs[0], plain)
    np.testing.assert_array_equal(outs[1], mk("off").score_batch(half))


def test_sharded_extract_ion_images_matches_numpy(fixture_ds):
    """Mesh-path device image export must equal the numpy extractor bit for
    bit (shared integer grids) — annotated-image export on multi-chip runs
    no longer re-extracts on CPU."""
    from sm_distributed_tpu.ops.imager_np import SortedPeakView, extract_ion_images
    from sm_distributed_tpu.parallel.mesh import make_mesh
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth, n=10)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 8, "pixels_axis": 4,
                      "formulas_axis": 2}})
    backend = ShardedJaxBackend(ds, dc, sm, mesh=make_mesh(sm.parallel))
    got = backend.extract_ion_images(table)     # n=10 > batch=8: batches too
    view = SortedPeakView.prepare(ds, 3.0)
    want = extract_ion_images(view, table, 3.0)
    np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("pix,form", [(8, 1), (4, 2), (2, 4)])
def test_sharded_band_slice_bit_exact(fixture_ds, pix, form):
    """Mesh-path band-slice extraction (each device scatters a contiguous
    dynamic slice of its shard's sorted peaks — the cell's window-union
    rank band) must leave every scored bit unchanged vs the plain sharded
    path AND vs the single-device backend, at every mesh shape."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.parallel.mesh import make_mesh
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = _table(truth)

    def mk(band, restrict=None):
        sm = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 32, "pixels_axis": pix,
                          "formulas_axis": form, "band_slice": band,
                          "peak_compaction": "off"}})
        return ShardedJaxBackend(ds, DSConfig.from_dict(
            {"isotope_generation": {"adducts": ["+H"]}}), sm,
            mesh=make_mesh(sm.parallel), restrict_table=restrict)

    plain = mk("off").score_batch(table)
    b_on = mk("on")
    np.testing.assert_array_equal(b_on.score_batch(table), plain)
    assert any(k[2] for k in b_on._fns), "band executable not exercised"
    np.testing.assert_array_equal(
        mk("on", restrict=table).score_batch(table), plain)
    sm1 = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 32, "pixels_axis": 1,
                      "formulas_axis": 1}})
    single = JaxBackend(ds, DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]}}), sm1).score_batch(table)
    np.testing.assert_array_equal(plain, single)


def test_sharded_ordered_multibatch_stream(fixture_ds):
    """A multi-batch m/z-ORDERED stream through the mesh path (the
    BASELINE #5 configuration: pixel-sharded + ordered + band machinery)
    must match the single-device backend on the same ordered table across
    ALL batches and variant modes, under the documented parity contract:
    chaos BIT-exact (integer component counts), spatial/spectral/MSM to
    1e-6 — at this stream's shapes (formula_batch=8, 1-ion all_to_all
    sub-blocks) XLA fuses the f32 correlation reductions differently than
    the single-device program, the same caveat as the multi-process path
    (README parity contract; measured ~2e-7).  Within ONE mesh program
    shape the band/compact/plain variants stay bit-exact
    (test_sharded_band_slice_bit_exact)."""
    from sm_distributed_tpu.models.msm_basic import (
        _slice_table,
        order_table_by_mz,
    )
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.parallel.mesh import make_mesh
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend

    ds, truth = fixture_ds
    table = order_table_by_mz(_table(truth, n=24))
    b = 8
    batches = [_slice_table(table, s, min(s + b, table.n_ions))
               for s in range(0, table.n_ions, b)]
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm1 = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": b, "pixels_axis": 1,
                      "formulas_axis": 1}})
    want = JaxBackend(ds, dc, sm1, restrict_table=table).score_batches(batches)
    for band in ("auto", "on"):
        sm = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": b, "pixels_axis": 4,
                          "formulas_axis": 2, "band_slice": band}})
        backend = ShardedJaxBackend(ds, dc, sm, mesh=make_mesh(sm.parallel),
                                    restrict_table=table)
        backend.warmup(batches)
        got = backend.score_batches(batches)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[:, 0], w[:, 0])   # chaos: exact
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-6)
        if band == "on":
            assert any(k[2] for k in backend._fns), "band path not exercised"
