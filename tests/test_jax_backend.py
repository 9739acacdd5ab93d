"""JAX-backend parity tests vs the numpy_ref oracle (SURVEY.md §7 build plan
item 8: golden-report comparison between backends — identical FDR ranks,
metric tolerance)."""

import json
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
from sm_distributed_tpu.utils.config import DSConfig, SMConfig


@pytest.fixture(scope="module")
def fixture_ds(tmp_path_factory):
    out = tmp_path_factory.mktemp("dsj")
    path, truth = generate_synthetic_dataset(
        out, nrows=12, ncols=12, formulas=None, present_fraction=0.5,
        noise_peaks=60, seed=23,
    )
    return SpectralDataset.from_imzml(path), truth


def test_cc_count_matches_scipy():
    import jax.numpy as jnp
    from scipy import ndimage
    from sm_distributed_tpu.ops.metrics_jax import _cc_count

    rng = np.random.default_rng(0)
    structure4 = np.array([[0, 1, 0], [1, 1, 1], [0, 1, 0]])
    for density in (0.1, 0.3, 0.5, 0.7, 0.9):
        for _ in range(5):
            mask = rng.random((17, 23)) < density
            want = ndimage.label(mask, structure=structure4)[1]
            got = int(_cc_count(jnp.asarray(mask.ravel()), 17, 23))
            assert got == want, f"density={density}: {got} != {want}"
    # serpentine worm: one long snaking component (stresses propagation depth —
    # geodesic length ~ R*C/2 across a 16x16 grid) plus one isolated pixel
    mask = np.zeros((16, 16), dtype=bool)
    for r in range(0, 16, 2):
        mask[r, :] = True                       # full horizontal runs
        if r + 1 < 16:                          # connectors alternate sides
            mask[r + 1, 15 if (r // 2) % 2 == 0 else 0] = True
    mask[15, 15] = False
    mask[15, 0] = False
    mask[13, 7] = mask[13, 8] = False           # keep rows 12/14 joined only via edge
    want = ndimage.label(mask, structure=structure4)[1]
    assert want >= 1
    got = int(_cc_count(jnp.asarray(mask.ravel()), 16, 16))
    assert got == want
    # explicit single-serpentine check on a bigger grid
    snake = np.zeros((20, 20), dtype=bool)
    for r in range(0, 20, 2):
        snake[r, :] = True
        if r + 1 < 20:
            snake[r + 1, 19 if (r // 2) % 2 == 0 else 0] = True
    want = ndimage.label(snake, structure=structure4)[1]
    assert want == 1                            # truly one serpentine component
    got = int(_cc_count(jnp.asarray(snake.ravel()), 20, 20))
    assert got == want


def test_chaos_batch_matches_numpy():
    import jax.numpy as jnp
    from sm_distributed_tpu.ops.metrics_jax import measure_of_chaos_batch
    from sm_distributed_tpu.ops.metrics_np import measure_of_chaos

    rng = np.random.default_rng(3)
    imgs = []
    yy, xx = np.mgrid[0:14, 0:14]
    imgs.append(np.exp(-((yy - 7) ** 2 + (xx - 7) ** 2) / 9.0) * (rng.random((14, 14)) > 0.1))
    imgs.append((rng.random((14, 14)) < 0.3) * rng.random((14, 14)))
    imgs.append(np.zeros((14, 14)))
    imgs.append(np.ones((14, 14)))
    batch = np.stack([im.ravel().astype(np.float32) for im in imgs])
    got = np.asarray(measure_of_chaos_batch(jnp.asarray(batch), 14, 14, nlevels=30)[0])
    want = np.array([measure_of_chaos(im.astype(np.float32), 30) for im in imgs])
    np.testing.assert_allclose(got, want, atol=1e-6)


_SCALE = np.float32(2.0 ** -7)      # images are counts times a power of two


def _hotspot_rows(kind: str) -> tuple[np.ndarray, np.ndarray]:
    """``(block, real)``: the f32 block handed to ``hotspot_clip_batch`` and
    the rows the numpy definition is asked about (the same block, but for
    ``bucket_pads``, whose real rows end where the zero pads begin)."""
    rng = np.random.default_rng(52)
    p = 200
    if kind == "dense":
        rows = rng.integers(1, 40000, (5, p))
        rows[:, ::7] = 0
    elif kind == "handful":
        rows = np.zeros((6, p), np.int64)
        for row in rows:
            idx = rng.choice(p, rng.integers(3, 12), replace=False)
            row[idx] = rng.integers(1, 90000, idx.size)
    elif kind == "m0_m1_m2":
        rows = np.zeros((3, p), np.int64)
        rows[1, 17] = 77
        rows[2, [3, 150]] = [900, 5]
    elif kind == "all_equal":
        rows = np.stack([np.full(p, 321), np.full(p, 1)])
    elif kind == "ties_at_cutoff":
        # the cutoff's two neighbours inside one run of equal pixels, at
        # the run's first and last slot, and one slot past it
        rows = np.stack([np.sort(rng.integers(0, 3, p)) * 1000,
                         np.r_[np.full(p - 2, 50), 60, 60],
                         np.r_[np.full(p - 3, 50), 60, 60, 70],
                         np.r_[np.zeros(p - 100, int), np.full(98, 50), 60, 70],
                         rng.integers(0, 4, p) * 500])
    elif kind == "bucket_pads":
        rows = rng.integers(0, 5000, (4, 144))          # a 12x12 section
        rows[1, 5:] = 0
    elif kind == "valid_masked_block":
        rows = rng.integers(0, 9000, (3, 4, p))
        rows[..., ::3] = 0
        n_valid = np.array([4, 2, 0])
        rows = np.where((np.arange(4)[None, :] < n_valid[:, None])[..., None],
                        rows, 0)                        # as batch_metrics masks
    else:
        raise AssertionError(kind)
    real = rows.astype(np.float32) * _SCALE
    block = real
    if kind == "bucket_pads":                           # 12 rows -> the 16-row bucket
        block = np.concatenate([real, np.zeros((4, 192 - 144), np.float32)], 1)
    return block, real


@pytest.mark.parametrize("kind", [
    "dense", "handful", "m0_m1_m2", "all_equal", "ties_at_cutoff",
    "bucket_pads", "valid_masked_block"])
@pytest.mark.parametrize("q", [99.0, 95.0, 50.0, 100.0, 1.0])
def test_hotspot_clip_batch_matches_numpy(q, kind):
    """``hotspot_clip_batch``'s ``bit_exact`` contract (``NUMERICS``): every
    clipped pixel has the BITS of the numpy definition's, ``np.minimum(img,
    hotspot_percentile_f32(np.sort(img[img > 0]), q))`` in f32 - over every
    kind of row the selection has an edge in, and the zeros the lattice and
    the ``valid`` mask hand it."""
    import jax.numpy as jnp
    from sm_distributed_tpu.ops.metrics_jax import hotspot_clip_batch
    from sm_distributed_tpu.ops.metrics_np import hotspot_clip

    block, real = _hotspot_rows(kind)
    got = np.asarray(hotspot_clip_batch(jnp.asarray(block), q))
    assert got.dtype == np.float32 and got.shape == block.shape
    want = np.zeros_like(block)                         # f32, pads stay zero
    for img, out in zip(real.reshape(-1, real.shape[-1]),
                        want.reshape(-1, want.shape[-1])):
        out[:img.size] = hotspot_clip(img, q)           # that expression
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    if q < 100.0 and kind in ("dense", "handful"):
        assert (got < block).any()                      # something was clipped


def _metrics_args():
    """``batch_metrics``' arguments for three ions of four 8x8 images."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    images = rng.integers(0, 300, (3, 4, 64)).astype(np.float32)
    theor = np.tile(np.array([1.0, 0.5, 0.2, 0.1], np.float32), (3, 1))
    return (jnp.asarray(images), jnp.asarray(theor),
            jnp.asarray(np.array([4, 3, 0], np.int32)), 8, 8)


def test_the_clipping_program_holds_no_sort():
    """``batch_metrics`` under ``do_preprocessing`` lowers to a program
    without a ``sort`` op: the clip's two order statistics are selected."""
    import jax
    from sm_distributed_tpu.ops.metrics_jax import batch_metrics

    images, theor, n_valid, nrows, ncols = _metrics_args()
    fn = jax.jit(lambda a, b, c: batch_metrics(
        a, b, c, nrows, ncols, do_preprocessing=True, q=99.0))
    text = fn.lower(images, theor, n_valid).as_text()
    assert "stablehlo.while" in text                    # the selection's loop
    assert "stablehlo.sort" not in text


def test_without_the_flag_no_program_reaches_the_clip(monkeypatch):
    """Without ``do_preprocessing`` a trace of ``batch_metrics`` never calls
    ``hotspot_clip_batch``: the programs of every configuration but the
    hot-spot one cannot change with it."""
    import jax
    from sm_distributed_tpu.ops import metrics_jax

    def reached(*_a, **_k):
        raise AssertionError("hotspot_clip_batch traced without the flag")

    monkeypatch.setattr(metrics_jax, "hotspot_clip_batch", reached)
    images, theor, n_valid, nrows, ncols = _metrics_args()
    out, _programs = jax.jit(lambda a, b, c: metrics_jax.batch_metrics(
        a, b, c, nrows, ncols, do_preprocessing=False))(images, theor, n_valid)
    assert out.shape == (3, 4)
    with pytest.raises(AssertionError, match="without the flag"):
        jax.jit(lambda a, b, c: metrics_jax.batch_metrics(
            a, b, c, nrows, ncols, do_preprocessing=True))(
            images, theor, n_valid)


def test_batch_metrics_stays_where_the_compile_cache_knows_it():
    """A tripwire beside ``tests/test_export_stream.py``'s: the moments and
    chaos kernels are traced from ``batch_metrics``, whose file and LINE
    their Mosaic payloads carry, so a line added above it re-keys every
    scoring executable of every configuration in a persistent compile
    cache.  The clip's selection stands below it for that reason.  Whoever
    has to move it: move the pin, and say so in ``CHANGES.md``."""
    from sm_distributed_tpu.ops import metrics_jax

    assert metrics_jax.batch_metrics.__code__.co_firstlineno == 282
    assert metrics_jax._kth_largest_bits.__code__.co_firstlineno > 282
    assert metrics_jax._next_above_bits.__code__.co_firstlineno > 282


@pytest.mark.parametrize("restricted", [False, True],
                         ids=["all_peaks", "window_union"])
@pytest.mark.parametrize("ds_name", ["fixture_ds", "offgrid_ds"])
def test_extraction_parity(request, ds_name, restricted):
    """``extract_images_flat`` against the numpy oracle, bit for bit: over
    every resident peak, and over the peaks ``restrict_flat_to_windows``
    keeps (what a served backend holds)."""
    import jax.numpy as jnp
    from sm_distributed_tpu.ops.imager_jax import (
        extract_images_flat, flat_bound_ranks, restrict_flat_to_windows,
        window_rank_grid,
    )
    from sm_distributed_tpu.ops.imager_np import extract_ion_images
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.ops.quantize import quantize_window
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = request.getfixturevalue(ds_name)
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:20]])

    want = extract_ion_images(ds, table, ppm=3.0)

    scale = ds.intensity_quantization(3.0)[1]
    lo, hi = quantize_window(table.mzs, 3.0)
    grid, r_lo, r_hi = window_rank_grid(lo, hi)
    mz_s, px_s, in_s, _scale = ds.flat_sorted(3.0)
    if restricted:
        n_all = int(np.count_nonzero(in_s))
        mzk, pxk, ink, n_eff = restrict_flat_to_windows(
            mz_s[None], px_s[None], in_s[None], lo, hi,
            overflow_row=ds.n_pixels)
        mz_s, px_s, in_s = mzk[0], pxk[0], ink[0]
        assert 0 < n_eff < n_all           # the restriction dropped peaks
    got = np.asarray(
        extract_images_flat(jnp.asarray(px_s), jnp.asarray(in_s),
                            jnp.asarray(flat_bound_ranks(mz_s, grid)),
                            jnp.asarray(r_lo), jnp.asarray(r_hi),
                            n_pixels=ds.n_pixels)
    ).reshape(table.n_ions, table.max_peaks, -1)
    # BIT-EXACT image parity: shared m/z + integer-intensity grids make every
    # per-(pixel, window) sum an exactly-representable f32 integer, so any
    # summation order (scatter trees, matmul, bincount) gives the same bits;
    # dequantization is an exact power-of-two division.
    np.testing.assert_array_equal(got / np.float32(scale), want)


def _run(ds, formulas, backend, decoy_n=6, seed=9, batch=64,
         preprocessing=False, adducts=("+H",)):
    sm_config = SMConfig.from_dict(
        {"backend": backend, "fdr": {"decoy_sample_size": decoy_n, "seed": seed},
         "parallel": {"formula_batch": batch}}
    )
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": list(adducts)},
         "image_generation": {"ppm": 3.0, "do_preprocessing": preprocessing}}
    )
    return MSMBasicSearch(ds, formulas, ds_config, sm_config).search()


@pytest.mark.parametrize("preprocessing", [False, True])
def test_backend_parity_metrics_and_ranks(fixture_ds, preprocessing):
    ds, truth = fixture_ds
    formulas = truth.formulas
    b_np = _run(ds, formulas, "numpy_ref", preprocessing=preprocessing)
    b_jx = _run(ds, formulas, "jax_tpu", preprocessing=preprocessing)

    m_np = b_np.all_metrics.set_index(["sf", "adduct"]).sort_index()
    m_jx = b_jx.all_metrics.set_index(["sf", "adduct"]).sort_index()
    assert list(m_np.index) == list(m_jx.index)
    # chaos is EXACT, with preprocessing on or off: identical integer
    # images, the shared single-op-f32 hotspot cutoff (bit-identical
    # clipped images — VERDICT r2 item 4), identical f32 threshold grid,
    # integer component counts, identical f32 mean/normalize
    np.testing.assert_array_equal(
        m_jx["chaos"].to_numpy(), m_np["chaos"].to_numpy(),
        err_msg="chaos must be bit-identical between backends")
    tols = [("spatial", 1e-6), ("spectral", 1e-6), ("msm", 1e-6)]
    for col, tol in tols:
        np.testing.assert_allclose(
            m_jx[col].to_numpy(), m_np[col].to_numpy(), atol=tol,
            err_msg=f"metric {col} diverges between backends",
        )

    # IDENTICAL FDR ranks (north star) — exact annotation order, no tie
    # escape hatch, and exact fdr/fdr_level agreement
    a_np = b_np.annotations
    a_jx = b_jx.annotations
    assert list(zip(a_np.sf, a_np.adduct)) == list(zip(a_jx.sf, a_jx.adduct)), (
        "annotation order differs between backends")
    np.testing.assert_array_equal(a_np.fdr.to_numpy(), a_jx.fdr.to_numpy())
    np.testing.assert_array_equal(
        a_np.fdr_level.to_numpy(), a_jx.fdr_level.to_numpy())


def test_backend_parity_multi_adduct(fixture_ds):
    """Cross-backend rank parity with the reference's full default target
    adduct set {+H, +Na, +K} (per-adduct FDR ranking, 3x the windows/ions
    of the +H-only tests)."""
    ds, truth = fixture_ds
    formulas = truth.formulas[:12]
    adducts = ("+H", "+Na", "+K")
    b_np = _run(ds, formulas, "numpy_ref", decoy_n=4, seed=7, adducts=adducts)
    b_jx = _run(ds, formulas, "jax_tpu", decoy_n=4, seed=7, adducts=adducts)
    a_np, a_jx = b_np.annotations, b_jx.annotations
    assert set(a_np.adduct) == set(adducts)
    assert list(zip(a_np.sf, a_np.adduct)) == list(zip(a_jx.sf, a_jx.adduct))
    np.testing.assert_array_equal(
        a_np.fdr_level.to_numpy(), a_jx.fdr_level.to_numpy())
    m_np = b_np.all_metrics.set_index(["sf", "adduct"]).sort_index()
    m_jx = b_jx.all_metrics.set_index(["sf", "adduct"]).sort_index()
    assert list(m_np.index) == list(m_jx.index)
    np.testing.assert_array_equal(
        m_jx["chaos"].to_numpy(), m_np["chaos"].to_numpy())
    np.testing.assert_allclose(
        m_jx["msm"].to_numpy(), m_np["msm"].to_numpy(), atol=1e-6)


def test_jax_checkpointed_search_matches_plain(fixture_ds, tmp_path):
    """Checkpoint-grouped scoring (backend.presize + per-group
    score_batches) must produce the same annotations as one ungrouped
    stream on the jax backend."""
    import pandas.testing as pdt

    ds, truth = fixture_ds
    formulas = truth.formulas[:10]
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    def run(extra):
        sm_config = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "fdr": {"decoy_sample_size": 4, "seed": 3},
             "parallel": {"formula_batch": 16, **extra}})
        return MSMBasicSearch(
            ds, formulas, ds_config, sm_config,
            checkpoint_dir=str(tmp_path) if extra else None,
        ).search().annotations

    plain = run({})
    grouped = run({"checkpoint_every": 1})
    pdt.assert_frame_equal(grouped, plain)


def test_window_union_restriction_bit_exact(fixture_ds):
    """Dropping peaks outside the union of the search's windows must leave
    every scored bit unchanged (dropped peaks match no window)."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:15]])
    sm_config = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 32}})
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})
    full = JaxBackend(ds, ds_config, sm_config)
    restricted = JaxBackend(ds, ds_config, sm_config, restrict_table=table)
    assert restricted._mz_host.size < full._mz_host.size  # actually dropped
    a = full.score_batch(table)
    b = restricted.score_batch(table)
    np.testing.assert_array_equal(a, b)
    # device ion-image export equally exact
    np.testing.assert_array_equal(
        full.extract_ion_images(table), restricted.extract_ion_images(table))


def test_negative_mode_end_to_end_parity(tmp_path_factory):
    """Negative ion mode (charge=-1, -H target adduct — the reference's
    polarity '-' datasets): signal present at [M-H]- m/z must be found, and
    backend ranks must stay identical."""
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    out = tmp_path_factory.mktemp("dsneg")
    iso = IsotopeGenerationConfig(adducts=("-H",), charge=-1)
    path, truth = generate_synthetic_dataset(
        out, nrows=10, ncols=10, formulas=None, present_fraction=0.5,
        noise_peaks=40, seed=31, adduct="-H", iso_cfg=iso,
    )
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["-H"], "charge": -1},
         "image_generation": {"ppm": 3.0}})
    res = {}
    for backend in ("numpy_ref", "jax_tpu"):
        sm_config = SMConfig.from_dict(
            {"backend": backend, "fdr": {"decoy_sample_size": 4, "seed": 2},
             "parallel": {"formula_batch": 64}})
        res[backend] = MSMBasicSearch(
            ds, list(truth.formulas), ds_config, sm_config).search().annotations
    a_np, a_jx = res["numpy_ref"], res["jax_tpu"]
    assert set(a_np.adduct) == {"-H"}
    # present formulas score strongly in negative mode
    present = a_np[a_np.sf.isin(truth.present)]
    assert (present.msm > 0.2).all()
    assert list(zip(a_np.sf, a_np.adduct)) == list(zip(a_jx.sf, a_jx.adduct))
    np.testing.assert_array_equal(
        a_np.fdr_level.to_numpy(), a_jx.fdr_level.to_numpy())


def test_jax_batch_padding_consistency(fixture_ds):
    # results must not depend on formula_batch (padding correctness)
    ds, truth = fixture_ds
    formulas = truth.formulas[:10]
    r_small = _run(ds, formulas, "jax_tpu", batch=4).all_metrics
    r_big = _run(ds, formulas, "jax_tpu", batch=64).all_metrics
    pd.testing.assert_frame_equal(
        r_small.sort_values(["sf", "adduct"]).reset_index(drop=True),
        r_big.sort_values(["sf", "adduct"]).reset_index(drop=True),
    )


def test_peak_compaction_bit_exact(fixture_ds):
    """Per-batch peak compaction (histogram only the peaks inside the
    current batch's window union) must leave every scored bit unchanged —
    forced on vs forced off, across multiple batches and with the search
    window-union restriction also active."""
    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:15]])
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    def mk(mode, restrict=None):
        sm_config = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 8, "peak_compaction": mode}})
        return JaxBackend(ds, ds_config, sm_config, restrict_table=restrict)

    batches = [_slice_table(table, s, min(s + 8, table.n_ions))
               for s in range(0, table.n_ions, 8)]
    plain = mk("off").score_batches(batches)
    compact = mk("on").score_batches(batches)
    for a, b in zip(plain, compact):
        np.testing.assert_array_equal(a, b)
    # compaction on top of the search-union restriction
    compact_r = mk("on", restrict=table).score_batches(batches)
    for a, b in zip(plain, compact_r):
        np.testing.assert_array_equal(a, b)
    # auto mode end-to-end: full search parity vs numpy oracle path
    b_on = _run(ds, truth.formulas[:10], "jax_tpu", batch=8)
    b_np = _run(ds, truth.formulas[:10], "numpy_ref", batch=8)
    a_on, a_np = b_on.annotations, b_np.annotations
    assert list(zip(a_on.sf, a_on.adduct)) == list(zip(a_np.sf, a_np.adduct))


def test_band_slice_bit_exact(fixture_ds):
    """Contiguous band-slice extraction (scatter a dynamic slice of the
    resident peaks instead of gathering packed runs) must leave every
    scored bit unchanged — forced on vs off, with and without the search
    window-union restriction, on an m/z-ORDERED table (its natural regime)
    AND the unordered table (stress: wide bands, clamped w_start,
    clipped padding bounds)."""
    from sm_distributed_tpu.models.msm_basic import (
        _slice_table, order_table_by_mz,
    )
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:15]])
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    def mk(mode, restrict=None):
        sm_config = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 8, "band_slice": mode}})
        return JaxBackend(ds, ds_config, sm_config, restrict_table=restrict)

    for t in (order_table_by_mz(table), table):
        batches = [_slice_table(t, s, min(s + 8, t.n_ions))
                   for s in range(0, t.n_ions, 8)]
        plain = mk("off").score_batches(batches)
        band = mk("on").score_batches(batches)
        for a, b in zip(plain, band):
            np.testing.assert_array_equal(a, b)
        band_r = mk("on", restrict=t).score_batches(batches)
        plain_r = mk("off", restrict=t).score_batches(batches)
        for a, b in zip(plain_r, band_r):
            np.testing.assert_array_equal(a, b)


def test_batch_peak_band_plan():
    """Host band plan: [start, start+width) must cover exactly the rank
    span of the window union, and clipped padding bounds keep windows
    empty (all-padding batches get a zero-width band)."""
    from sm_distributed_tpu.ops.imager_jax import (
        batch_peak_band, merged_window_bounds,
    )

    rng = np.random.default_rng(12)
    for _ in range(20):
        mz = np.sort(rng.integers(0, 10_000, size=300)).astype(np.int32)
        lo = rng.integers(0, 9_900, size=20).astype(np.int32)
        hi = lo + rng.integers(0, 60, size=20).astype(np.int32)
        start, width = batch_peak_band(mz, lo, hi)
        flat = merged_window_bounds(lo, hi)
        if flat.size == 0:
            assert (start, width) == (0, 0)
            continue
        inside = (mz >= flat[0]) & (mz < flat[-1])
        idx = np.nonzero(inside)[0]
        if idx.size:
            assert start <= idx[0] and idx[-1] < start + width
        # every in-union peak is inside the band
        member_lo = np.searchsorted(flat, mz, side="right") % 2 == 1
        kept_idx = np.nonzero(member_lo)[0]
        if kept_idx.size:
            assert start <= kept_idx[0] and kept_idx[-1] < start + width
    # all-padding batch
    assert batch_peak_band(
        np.arange(10, dtype=np.int32),
        np.zeros(3, np.int32), np.zeros(3, np.int32)) == (0, 0)


def test_order_table_by_mz_results_invariant(fixture_ds):
    """parallel.order_ions="mz" (the default) reorders the ion table before
    batching; the SET of (sf, adduct) -> metrics results must be identical
    to order_ions="table"."""
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch

    ds, truth = fixture_ds
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    def run(order):
        sm = SMConfig.from_dict(
            {"backend": "jax_tpu", "fdr": {"decoy_sample_size": 3},
             "parallel": {"formula_batch": 8, "order_ions": order}})
        return MSMBasicSearch(ds, list(truth.formulas[:10]), ds_config,
                              sm).search()

    a = run("mz").all_metrics.set_index(["sf", "adduct"]).sort_index()
    b = run("table").all_metrics.set_index(["sf", "adduct"]).sort_index()
    pd.testing.assert_frame_equal(a, b)


def test_maybe_order_table_gate(fixture_ds):
    """The auto gate orders at >=6 batches and keeps table order below;
    'mz'/'table' force; bad values are rejected at config load."""
    from sm_distributed_tpu.models.msm_basic import (
        maybe_order_table, order_table_by_mz,
    )
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    _, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:12]])
    ordered = order_table_by_mz(table)
    assert list(ordered.mzs[:, 0]) == sorted(table.mzs[:, 0])

    def same(a, b):
        return a.sfs == b.sfs and np.array_equal(a.mzs, b.mzs)

    # 12 ions: batch=2 -> 6 batches (orders); batch=4 -> 3 batches (keeps)
    assert same(maybe_order_table(table, "auto", 2), ordered)
    assert same(maybe_order_table(table, "auto", 4), table)
    assert same(maybe_order_table(table, "mz", 1000), ordered)
    assert same(maybe_order_table(table, "table", 1), table)
    with pytest.raises(ValueError, match="order_ions"):
        SMConfig.from_dict({"parallel": {"order_ions": "off"}})
    with pytest.raises(ValueError, match="band_slice"):
        SMConfig.from_dict({"parallel": {"band_slice": "nope"}})


def test_variant_estimator(fixture_ds):
    """_variant_for picks by padded-capacity cost: narrow bands -> band,
    tiny keeps with wide bands -> compact, near-full batches -> plain;
    'on' modes force their variant."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    ds, truth = fixture_ds
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})

    def mk(band="auto", compaction="auto"):
        sm = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 8, "band_slice": band,
                          "peak_compaction": compaction}})
        return JaxBackend(ds, ds_config, sm)

    be = mk()
    n = int(be._mz_host.size)
    runs_tiny = (None, None, 1000, None)       # keep ~1k -> 64k capacity
    band_narrow = (0, 100)                     # bucket = _BAND_MIN
    band_wide = (0, n)                         # bucket >= n -> no band est
    if be._BAND_MIN < n:
        assert be._variant_for(None, band_narrow) == "band"
    assert be._variant_for(None, band_wide) == "plain"
    # compact charged at padded 64k-rounded capacity (37 ns/slot): wins
    # over plain only when 37*cap < 14*n
    want = "compact" if 37.0 * (1 << 16) < 14.0 * n else "plain"
    assert be._variant_for(runs_tiny, None) == want
    assert mk(band="on")._variant_for(None, band_wide) == "band"
    assert mk(band="off", compaction="on")._variant_for(
        runs_tiny, band_narrow) == "compact"
    assert mk(band="off", compaction="off")._variant_for(
        None, None) == "plain"


@pytest.mark.parametrize("resident, n_keep, union, band_width, want", [
    # hmdb-section128-reannotate (PERF.md section 4, PR 41): 17.45 M
    # resident peaks, the band at its 2,097,152-slot floor, the compact
    # capacity sticky at 786,432: 37 x 786,432 < 14 x 2,097,152 by 0.9%
    (17_450_000, 786_432, 700_000, 1_500_000, "compact"),
    # ... and a window union one 64k step above it is a `band` batch
    (17_450_000, 786_432, 800_000, 1_500_000, "band"),
    # hmdb-section64-reannotate (ledger, PR 43: `compact` on all 62
    # batches): under the band floor ANY union up to 786,432 is compact
    (4_370_000, 0, 786_432, 1_000_000, "compact"),
    (4_370_000, 0, 786_433, 1_000_000, "band"),
    # a band past the floor pays its own ladder point
    (58_720_000, 0, 3_000_000, 9_000_000, "compact"),
    (58_720_000, 0, 4_000_000, 9_000_000, "band"),
    # a one-batch table over the whole range of a small section: plain
    (3_670_000, 0, 3_000_000, 3_670_000, "plain")])
def test_variant_choice_at_the_cells_sizes(resident, n_keep, union,
                                           band_width, want):
    """``_variant_for`` under ``auto``, as a table, at the sizes the
    benchmark's cells hand it: the host decision that picks the program
    every batch runs."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    backend = object.__new__(JaxBackend)   # the decision reads host fields
    backend._band_mode = backend._compaction = "auto"
    backend._mz_host = np.empty(resident, np.int8)
    backend._n_keep = n_keep
    runs, band = (None, None, union, None), (0, band_width)
    assert backend._variant_for(runs, band) == want


def test_batch_peak_runs_plan_exact():
    """Host compaction plan: kept runs and re-based bound ranks agree with a
    brute-force recomputation on random windows over a random peak list."""
    from sm_distributed_tpu.ops.imager_jax import (
        batch_peak_runs, flat_bound_ranks, merged_window_bounds,
        window_union_member, window_rank_grid,
    )

    rng = np.random.default_rng(11)
    for trial in range(20):
        mz = np.sort(rng.integers(0, 10_000, size=400)).astype(np.int32)
        lo = rng.integers(0, 9_900, size=30).astype(np.int32)
        hi = lo + rng.integers(0, 50, size=30).astype(np.int32)  # some empty
        grid, r_lo, r_hi = window_rank_grid(lo, hi)
        pos = flat_bound_ranks(mz, grid)
        run_pos, run_delta, n_b, pos_b = batch_peak_runs(mz, lo, hi, pos)

        member = window_union_member(mz, merged_window_bounds(lo, hi))
        kept = mz[member]
        assert n_b == kept.size
        # reconstruct the kept array through the run mapping
        if n_b:
            off = np.zeros(n_b, np.int64)
            np.add.at(off, run_pos[run_pos < n_b], run_delta[run_pos < n_b])
            src = np.arange(n_b) + np.cumsum(off)
            np.testing.assert_array_equal(mz[src], kept)
        # re-based ranks count kept peaks strictly below each bound
        want = np.searchsorted(kept, grid, side="left")
        np.testing.assert_array_equal(pos_b, want)


def test_flat_scratch_guard_names_live_remedies(fixture_ds):
    """A histogram scratch past 8 GiB fails at construction, before any
    device allocation, and the message names remedies that exist."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    ds, _truth = fixture_ds
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})
    # 4 * (144 + 1) * (2 * B * 4 + 1) bytes at B = 2**28: ~1.2 TiB
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 300_000_000}})
    with pytest.raises(ValueError, match="histogram scratch") as err:
        JaxBackend(ds, ds_config, sm)
    msg = str(err.value)
    assert "parallel.formula_batch" in msg and "parallel.pixels_axis" in msg
    assert "mz_chunk" not in msg


@pytest.mark.parametrize("parallel, names", [
    ({"mz_chunk": 0}, ("mz_chunk",)),
    ({"cube_dtype": "int8"}, ("cube_dtype", "'f32', 'bf16'")),
    ({"fused_metrics": "on"}, (
        "parallel.fused_metrics must be one of ('auto', 'off'), got 'on': "
        "the fused Pallas scoring variant was removed in PR 44",))],
    ids=["mz_chunk", "cube_dtype-int8", "fused_metrics-on"])
def test_removed_parallel_values_fail_at_load(parallel, names):
    """A configuration file written for the cube path, the int8 cube or the
    fused Pallas variant is refused by name at load, not ignored."""
    with pytest.raises(ValueError) as err:
        SMConfig.from_dict({"backend": "jax_tpu", "parallel": parallel})
    for name in names:
        assert name in str(err.value)


_BENCH_CONFIGS = sorted(
    (Path(__file__).resolve().parent.parent / "benchmarks" / "configs")
    .glob("*.json"))


@pytest.mark.parametrize("path", _BENCH_CONFIGS, ids=lambda p: p.stem)
def test_every_benchmark_configuration_loads(path):
    """``from_dict`` rejects unknown keys and values, and no PR but a
    ``benchmark`` one may edit these files: a field or a value they spell out
    (``parallel.fused_metrics: auto``) cannot go before they drop it."""
    block = json.loads(path.read_text())["sm_config"]
    loaded = SMConfig.from_dict(block)
    for key, value in block.get("parallel", {}).items():
        assert getattr(loaded.parallel, key) == value
    assert len(_BENCH_CONFIGS) >= 7


@pytest.mark.parametrize("fused_metrics", ["auto", "off"])
def test_fused_metrics_values_that_load_build_the_three_programs(
        fixture_ds, fused_metrics):
    """The vestigial knob (benchmarks/configs/*.json spell it out) selects
    nothing: either value that loads binds the geometry's three XLA programs
    and scores what a configuration without the key scores."""
    from sm_distributed_tpu.models.msm_jax import (
        _VARIANTS,
        JaxBackend,
        make_flat_jits,
    )
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    table = IsocalcWrapper(
        IsotopeGenerationConfig(adducts=("+H",))).pattern_table(
        [(sf, "+H") for sf in truth.formulas[:20]])
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})

    def backend(parallel):
        return JaxBackend(ds, dc, SMConfig.from_dict(
            {"backend": "jax_tpu",
             "parallel": {"formula_batch": 32, **parallel}}))

    knob, bare = backend({"fused_metrics": fused_metrics}), backend({})
    jits = make_flat_jits(knob._common)
    assert set(jits) == set(_VARIANTS) == {"plain", "band", "compact"}
    for name, (attr, *_rest) in _VARIANTS.items():
        assert getattr(knob, attr) is jits[name] is getattr(bare, attr)
    assert knob._flat_call(table)[0] == bare._flat_call(table)[0]
    np.testing.assert_array_equal(
        knob.score_batch(table), bare.score_batch(table))


def test_one_batch_table_at_shipped_defaults_runs_the_plain_chain(fixture_ds):
    """The deployment that used to route to the fused kernel on a TPU: a
    table of ONE batch over the dataset's whole m/z range with ``parallel.*``
    at shipped defaults dispatches ``plain``, scores inside the component
    contracts of ``numpy_ref`` and ranks FDR identically."""
    from sm_distributed_tpu.analysis.numerics import component_report
    from sm_distributed_tpu.models.msm_basic import NumpyBackend
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.fdr import FDR
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    formulas = truth.formulas
    fdr = FDR(decoy_sample_size=4, target_adducts=("+H",), seed=1)
    assignment = fdr.decoy_adduct_selection(formulas)
    pairs, flags = assignment.all_ion_tuples(formulas, ("+H",))
    table = IsocalcWrapper(
        IsotopeGenerationConfig(adducts=("+H",))).pattern_table(pairs, flags)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    backend = JaxBackend(ds, dc, SMConfig.from_dict({"backend": "jax_tpu"}))
    assert table.n_ions <= backend.batch                  # one batch
    assert table.mzs[table.mzs > 0].min() < ds.mzs_flat.mean() \
        < table.mzs.max()
    variant, _args, statics = backend._flat_call(table)
    assert variant == "plain" and statics["b"] == backend._batch_for(
        table.n_ions)
    got = backend.score_batches([table])[0]
    want = NumpyBackend(ds, dc).score_batch(table)
    report = component_report(got, want)
    assert {c: r["outside"] for c, r in report.items()} == dict.fromkeys(
        report, 0), report
    assert (got[:, 3] > 0).sum() >= len(truth.present) // 2   # real scores

    def ranks(metrics):
        df = pd.DataFrame({"sf": table.sfs, "adduct": table.adducts,
                           "msm": metrics[:, 3]})
        return fdr.estimate_fdr(df, assignment).sort_values(
            ["msm", "sf"], ascending=False)

    r_got, r_want = ranks(got), ranks(want)
    assert list(zip(r_got.sf, r_got.adduct)) == list(
        zip(r_want.sf, r_want.adduct))
    np.testing.assert_array_equal(r_got.fdr.to_numpy(), r_want.fdr.to_numpy())


@pytest.mark.parametrize("cube_dtype", ["f32", "bf16"])
def test_probe_phases_run_the_dispatched_program(fixture_ds, cube_dtype):
    """``probe_phases`` (bench.py, scripts/roofline_probe.py) hands back
    the call ``score_batch`` makes and its sub-phases on f32 intensities,
    whatever the resident dtype."""
    from sm_distributed_tpu.models.msm_jax import _VARIANTS, JaxBackend
    from sm_distributed_tpu.ops.imager_np import extract_ion_images
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    table = calc.pattern_table([(sf, "+H") for sf in truth.formulas[:20]])
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict({"backend": "jax_tpu", "parallel": {
        "formula_batch": 32, "cube_dtype": cube_dtype}})
    backend = JaxBackend(ds, dc, sm)
    phases, info = backend.probe_phases(table)
    assert set(phases) == {"fused_full", "extract", "moments", "chaos",
                           "correlation", "pattern"}
    assert info["variant"] in _VARIANTS
    np.testing.assert_array_equal(
        np.asarray(phases["fused_full"]())[: table.n_ions].astype(np.float64),
        backend.score_batch(table))
    imgs = np.asarray(phases["extract"]())
    assert imgs.dtype == np.float32
    assert imgs.shape == (32 * table.max_peaks, backend._n_pix_b)
    if cube_dtype == "f32":
        # the plan sorts ions, so compare per-window totals as multisets:
        # exact, every sum is an integer below 2**24 times a power of two
        want = extract_ion_images(ds, table, ppm=3.0).reshape(
            -1, ds.n_pixels).sum(axis=1, dtype=np.float64)
        got = imgs.sum(axis=1, dtype=np.float64) / backend.int_scale
        np.testing.assert_array_equal(
            np.sort(got)[-want.size:], np.sort(want))


def test_window_chunks_plan_covers_all_windows():
    from sm_distributed_tpu.ops.imager_jax import window_chunks

    rng = np.random.default_rng(0)
    r_lo = rng.integers(0, 500, 77).astype(np.int32)
    r_hi = (r_lo + rng.integers(1, 5, 77)).astype(np.int32)
    starts, r_lo_loc, r_hi_loc, inv, gc_width = window_chunks(r_lo, r_hi, 16)
    c, wc = r_lo_loc.shape
    assert c * wc >= 77 and wc == 16
    # every real window recoverable: local + start == global, inv is a perm
    order = np.argsort(r_lo, kind="stable")
    flat_lo = (r_lo_loc + starts[:, None]).ravel()[:77]
    np.testing.assert_array_equal(flat_lo, r_lo[order])
    assert sorted(inv.tolist()) == list(range(77))
    assert r_hi_loc.max() <= gc_width
    # padded tail windows are empty (lo == hi)
    tail = (r_lo_loc == r_hi_loc).ravel()[77:]
    assert tail.all()


def test_window_chunks_empty_windows_do_not_blow_band():
    """Empty windows (lo == hi, e.g. batch padding at rank 0) must sort LAST:
    chunked together with high-rank real windows they'd stretch a chunk's
    span to the whole grid (measured 8x gc_width growth -> ~10x slowdown on
    partially-padded batches)."""
    from sm_distributed_tpu.ops.imager_jax import window_chunks

    rng = np.random.default_rng(1)
    # a mostly-padded batch: 48 real windows at HIGH ranks, 464 empties at 0
    n_real = 48
    r_lo = np.zeros(512, dtype=np.int32)
    r_hi = np.zeros(512, dtype=np.int32)
    r_lo[:n_real] = rng.integers(7000, 8100, n_real)
    r_hi[:n_real] = r_lo[:n_real] + rng.integers(1, 5, n_real)
    starts, r_lo_loc, r_hi_loc, inv, gc_width = window_chunks(r_lo, r_hi, 16)
    # band stays proportional to the REAL windows' local spread, not the
    # empty-to-real rank gap (the old argsort gave gc_width >= 4096 here)
    assert gc_width <= 2048
    # reconstruction still exact for every real window
    flat_lo = (r_lo_loc + starts[:, None]).ravel()[:512]
    srt = np.lexsort((r_lo, (r_lo == r_hi).astype(np.int8)))
    np.testing.assert_array_equal(flat_lo, r_lo[srt])
    assert sorted(inv.tolist()) == list(range(512))


def test_tail_batch_executable_matches(fixture_ds):
    """A stream's small final slice runs through the 256-wide tail
    executable (full-size padding would pay ~8x its cost); results must be
    identical to full-size padding and to the numpy oracle."""
    from sm_distributed_tpu.models.msm_basic import NumpyBackend, _slice_table
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = fixture_ds
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H", "+Na")))
    table = calc.pattern_table(
        [(sf, ad) for sf in truth.formulas[:20] for ad in ("+H", "+Na")])
    assert table.n_ions > 8
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 300}})
    backend = JaxBackend(ds, ds_config, sm)
    # the shape-bucket lattice snaps the pad-to batch DOWN to a lattice
    # point (ops/buckets.batch_bucket_down: 300 -> 256), so an arbitrary
    # configured size cannot mint a one-off executable
    assert backend.batch == 256
    # default threshold routing (batch == tail width -> one executable)
    assert backend._batch_for(8) == 256
    assert backend._batch_for(2048) == 256
    # a MIXED-size stream through both executables: shrink the tail
    # threshold so the head takes the full-size (b=256) variant while the
    # tail (8 ions) takes the small one — this exercises the b_eff
    # plumbing on both, in one warmed backend
    backend._TAIL_BATCH = 8
    head = _slice_table(table, 0, table.n_ions - 8)
    tail = _slice_table(table, table.n_ions - 8, table.n_ions)
    assert backend._batch_for(head.n_ions) == 256
    assert backend._batch_for(tail.n_ions) == 8
    outs = backend.score_batches([head, tail])
    np_b = NumpyBackend(ds, ds_config)
    np.testing.assert_array_equal(outs[0][:, 0], np_b.score_batch(head)[:, 0])
    np.testing.assert_array_equal(outs[1][:, 0], np_b.score_batch(tail)[:, 0])
    np.testing.assert_allclose(outs[0], np_b.score_batch(head), atol=1e-6)
    np.testing.assert_allclose(outs[1], np_b.score_batch(tail), atol=1e-6)
    # single-batch entry point takes the tail path too
    np.testing.assert_array_equal(backend.score_batch(tail), outs[1])
    # padding-size invariance: the same tail through a small-batch config
    # (single full-size executable) gives identical metric bits
    sm_small = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 40}})
    b_small = JaxBackend(ds, ds_config, sm_small)
    np.testing.assert_array_equal(
        b_small.score_batch(tail)[:, 0], outs[1][:, 0])


# ------------------------------------------------- export row bucket (PR 27)
# The store's re-extraction pads to the lattice bucket of the KEPT count
# (floor 64, never above the batch), not to the scoring batch.
_EXPORT_BATCH = 128


@pytest.fixture(scope="module", params=["f32", "bf16"])
def export_backend(request, offgrid_ds):
    """One backend per resident dtype on the off-lattice 9x11 fixture
    (99 px in a 110-px bucket), with a 150-ion table whose every third
    ion has ``n_valid`` < k (the mask must zero images the windows fill)."""
    import dataclasses

    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = offgrid_ds
    adducts = ("+H", "+Na", "+K")
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=adducts))
    table = calc.pattern_table(
        [(sf, ad) for sf in truth.formulas for ad in adducts])
    n_valid = table.n_valid.copy()
    n_valid[::3] = 2
    table = dataclasses.replace(table, n_valid=n_valid)
    assert table.n_ions > _EXPORT_BATCH
    sm = SMConfig.from_dict({"backend": "jax_tpu", "parallel": {
        "formula_batch": _EXPORT_BATCH, "cube_dtype": request.param}})
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    backend = JaxBackend(ds, dc, sm)
    assert backend._in_s.dtype == {"f32": "float32",
                                   "bf16": "bfloat16"}[request.param]
    assert backend._n_pix_b == 110 > ds.n_pixels == 99  # off the lattice
    return backend, table


def _full_batch_export(backend, table):
    """The export as it was before PR 27: every call padded to the scoring
    batch, the whole padded block copied to the host, divided in place."""
    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.ops.imager_jax import (
        extract_images_flat, flat_bound_ranks,
    )

    b, k = backend.batch, table.max_peaks
    out = []
    for s in range(0, table.n_ions, b):
        t = _slice_table(table, s, min(s + b, table.n_ions))
        grid, r_lo, r_hi, _ints, _nv = backend._padded_windows(t, b)
        imgs = extract_images_flat(
            backend._px_s, backend._in_f32(),
            flat_bound_ranks(backend._mz_host, grid), r_lo, r_hi,
            n_pixels=backend._n_pix_b)
        imgs = np.array(imgs).reshape(b, k, -1)[
            : t.n_ions, :, : backend.ds.n_pixels]
        imgs /= np.float32(backend.int_scale)
        imgs[~(np.arange(k)[None, :] < t.n_valid[:, None])] = 0.0
        out.append(imgs)
    return np.concatenate(out)


def _traced_export(backend, table, tmp_path):
    """(images, attrs the export left on the span open around it)."""
    from sm_distributed_tpu.utils import tracing

    ctx = tracing.new_trace("export", trace_dir=tmp_path)
    with tracing.span("store_extract_images", ctx=ctx):
        images = backend.extract_ion_images(table)
    (rec,) = [r for r in tracing.read_trace(
        tracing.trace_path(tmp_path, ctx.trace_id))
        if r["name"] == "store_extract_images"]
    return images, rec["attrs"]


# n: one ion, the floor, floor + 1, a non-lattice count (301 of 2048 scaled
# to 128), the batch, batch + 1 (the loop: one full call and a tail of one)
@pytest.mark.parametrize("n, rows, calls", [
    (1, 64, 1), (64, 64, 1), (65, 80, 1), (100, 112, 1),
    (_EXPORT_BATCH, 128, 1), (_EXPORT_BATCH + 1, 128 + 64, 2)])
def test_export_row_bucket_bit_identical(export_backend, tmp_path, n, rows,
                                         calls):
    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.ops.imager_np import extract_ion_images

    backend, table = export_backend
    sub = _slice_table(table, 0, n)
    got, attrs = _traced_export(backend, sub, tmp_path)
    assert got.dtype == np.float32
    assert got.shape == (n, table.max_peaks, backend.ds.n_pixels)
    wants = [_full_batch_export(backend, sub)]
    if backend._cube_dtype == "f32":
        # bf16 residents are a coarser grid than the oracle's: there the
        # bucketed export is held to the full-batch one on the same backend
        wants.append(extract_ion_images(backend.ds, sub, ppm=3.0))
    for want in wants:
        np.testing.assert_array_equal(
            got.view(np.uint32), want.view(np.uint32))
    assert got[0, 2:].max() == 0.0                      # n_valid = 2 of k
    # pixels fetched per window: the row-bucketed grid
    assert attrs == {"rows": rows, "calls": calls,
                     "fetched_bytes":
                         rows * table.max_peaks * backend._n_pix_b * 4}


def _export_traces():
    """Executables the export site has traced so far: real compiles plus
    loads from the session's persistent cache (which of the two a trace is
    depends on what earlier tests compiled)."""
    from sm_distributed_tpu.analysis import retrace

    return sum(e["events"] + e["cache_hits"]
               for s, e in retrace.snapshot()["sites"].items()
               if s.endswith("models/msm_jax.py:_export_images"))


def test_export_traces_once_per_bucket(offgrid_ds, tmp_path):
    """Two subsets in one bucket share the executable; a subset in another
    bucket traces one more; after an OOM shrink the bucket never exceeds
    the batch."""
    from sm_distributed_tpu.analysis import retrace
    from sm_distributed_tpu.models import msm_jax
    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    ds, truth = offgrid_ds
    adducts = ("+H", "+Na")
    table = IsocalcWrapper(
        IsotopeGenerationConfig(adducts=adducts)).pattern_table(
        [(sf, ad) for sf in truth.formulas for ad in adducts])
    sm = SMConfig.from_dict({"backend": "jax_tpu",
                             "parallel": {"formula_batch": 96}})
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                             "image_generation": {"ppm": 3.0}})
    # the export's jit is shared by every backend of one pixel count
    # (msm_jax.make_extract_jit): start from a registry that has not seen
    # this one, whatever earlier tests of the process exported
    with msm_jax._SHARED_JITS_LOCK:
        msm_jax._SHARED_JITS.clear()
    backend = JaxBackend(ds, dc, sm)
    assert backend.batch == 96
    retrace.enable()
    retrace.reset()
    try:
        backend.extract_ion_images(_slice_table(table, 0, 3))     # 64
        assert _export_traces() == 1
        backend.extract_ion_images(_slice_table(table, 5, 64))    # 64 again
        assert _export_traces() == 1
        backend.extract_ion_images(_slice_table(table, 0, 70))    # 80
        assert _export_traces() == 2
        backend.extract_ion_images(_slice_table(table, 0, 90))    # 96 = batch
        assert _export_traces() == 3
        # a second backend of the geometry calls the same jit object: the
        # three buckets are traced, and it traces none of them again
        again = JaxBackend(ds, dc, sm)
        for n in (3, 70, 90):
            again.extract_ion_images(_slice_table(table, 0, n))
        assert again._extract_fn is backend._extract_fn
        assert _export_traces() == 3
    finally:
        retrace.disable()
        retrace.reset()
    want = backend.extract_ion_images(table)
    backend.shrink_batch(50)                  # snaps down to 48
    assert backend.batch == 48
    got, attrs = _traced_export(backend, table, tmp_path)
    # 100 ions at batch 48: two full calls and a tail of 4, which pads to
    # the batch and not to the floor of 64 above it
    assert table.n_ions == 100
    assert attrs["calls"] == 3 and attrs["rows"] == 3 * 48
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
