"""The membership product of the flat-banded extraction (PR 42): the chunk
plan that feeds it.  Every variant runs the WINDOW-MAJOR plan, whose band
stays near 2 x 512 rows on a DENSE table (the HMDB shape at toy size: ions
0.01 Da apart), and gives the unchunked extraction's bits."""

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.models.msm_basic import NumpyBackend
from sm_distributed_tpu.models.msm_jax import JaxBackend
from sm_distributed_tpu.ops.isocalc import IsotopePatternTable
from sm_distributed_tpu.utils.config import DSConfig, SMConfig

N_IONS, K = 1024, 4
DS_CONFIG = DSConfig.from_dict(
    {"isotope_generation": {"adducts": ["+H"]},
     "image_generation": {"ppm": 3.0}})


@pytest.fixture(scope="module")
def dense(tmp_path_factory):
    """(dataset, a table of 1,024 ions inside 10 Da whose windows sit on the
    dataset's own peaks).  Ions share peaks, as isomers do."""
    out = tmp_path_factory.mktemp("dense")
    path, _truth = generate_synthetic_dataset(
        out, nrows=12, ncols=12, formulas=None, present_fraction=0.5,
        noise_peaks=60, seed=23)
    ds = SpectralDataset.from_imzml(path)
    rng = np.random.default_rng(5)
    peaks = np.unique(ds.mzs_flat)
    inside = peaks[(peaks > 200.0) & (peaks < 210.0)]
    base = np.sort(rng.choice(inside, N_IONS)) * (
        1.0 + rng.uniform(-1e-6, 1e-6, N_IONS))
    mzs = base[:, None] + 1.00336 * np.arange(K)[None, :]
    # later peaks snap to a real peak where one is near: their images
    # are not all empty
    near = peaks[np.clip(np.searchsorted(peaks, mzs[:, 1:]), 0,
                         peaks.size - 1)]
    mzs[:, 1:] = np.where(np.abs(near - mzs[:, 1:]) < 0.05, near, mzs[:, 1:])
    ints = np.tile(np.array([100.0, 40.0, 12.0, 3.0]), (N_IONS, 1))
    n_valid = np.full(N_IONS, K, np.int32)
    n_valid[::7] = 3
    pad = np.arange(K)[None, :] >= n_valid[:, None]
    mzs[pad], ints[pad] = 0.0, 0.0
    table = IsotopePatternTable(
        sfs=[f"X{i}" for i in range(N_IONS)], adducts=["+H"] * N_IONS,
        mzs=mzs, ints=ints, n_valid=n_valid,
        targets=np.ones(N_IONS, bool))
    return ds, table


def _backend(ds, **parallel):
    return JaxBackend(ds, DS_CONFIG, SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": N_IONS, **parallel}}))


def _inv_of(variant, args):
    from sm_distributed_tpu.models.msm_jax import _VARIANTS

    return args[_VARIANTS[variant][3] + 4]


def _unchunked_scores(backend, table):
    """The scoring program by hand and without a chunk plan: the dense
    ``extract_images_flat`` over the padded windows, then the metrics of
    that image block."""
    import jax
    import jax.numpy as jnp

    from sm_distributed_tpu.models.msm_jax import named_partial
    from sm_distributed_tpu.ops.imager_jax import extract_images_flat
    from sm_distributed_tpu.ops.metrics_jax import batch_metrics

    _grid, r_lo, r_hi, ints_p, nv_p, _chunks, pos, _runs, b_eff, _band = \
        backend._flat_plan(table)
    common = backend._common
    imgs = extract_images_flat(
        backend._px_s, backend._in_f32(), jnp.asarray(pos),
        jnp.asarray(r_lo), jnp.asarray(r_hi),
        n_pixels=common["nrows"] * common["ncols"])
    out, _programs = jax.jit(named_partial(batch_metrics, **common))(
        imgs.reshape(b_eff, table.max_peaks, -1),
        jnp.asarray(ints_p), jnp.asarray(nv_p), n_real=backend._n_real)
    return np.asarray(out)[: table.n_ions].astype(np.float64)


# -- the plan ------------------------------------------------------------------

@pytest.mark.parametrize("fused_metrics", ["auto", "off"])
def test_a_plan_holds_one_window_major_band(dense, fused_metrics):
    """Ten fields, none of them ion-major, and one band width, under every
    value of the vestigial knob that loads; the band of a dense table stays
    narrow (512 neighbours span their own bounds)."""
    ds, table = dense
    backend = _backend(ds, fused_metrics=fused_metrics)
    plan = backend._flat_plan(table)
    assert len(plan) == 10
    starts, r_lo_loc, _r_hi_loc, inv, gc_width = plan[5]
    assert gc_width <= 1536
    assert inv.shape == (N_IONS * K,)
    assert r_lo_loc.shape == (starts.size, 512)
    backend._flat_call(table, plan)
    assert backend._band_width(plan[8]) == backend._gc_width == gc_width


def test_banded_extraction_on_a_dense_plan_is_the_oracles(dense):
    """``extract_images_flat_banded`` under the window-major plan against
    the dense ``extract_images_flat`` and the numpy extraction, bit for
    bit."""
    import jax.numpy as jnp

    from sm_distributed_tpu.ops.imager_jax import (
        extract_images_flat,
        extract_images_flat_banded,
    )
    from sm_distributed_tpu.ops.imager_np import extract_ion_images

    ds, table = dense
    backend = _backend(ds)
    _grid, r_lo, r_hi, _ints, _nv = backend._padded_windows(table, N_IONS)
    plan = backend._flat_plan(table)
    pos, (starts, rlo, rhi, inv, gc) = plan[6], plan[5]
    px, ints = backend._px_s, backend._in_f32()
    want = np.asarray(extract_images_flat(
        px, ints, jnp.asarray(pos), jnp.asarray(r_lo), jnp.asarray(r_hi),
        n_pixels=ds.n_pixels))
    got = np.asarray(extract_images_flat_banded(
        px, ints, jnp.asarray(pos), jnp.asarray(starts),
        jnp.asarray(rlo), jnp.asarray(rhi), jnp.asarray(inv),
        gc_width=gc, n_pixels=ds.n_pixels))
    np.testing.assert_array_equal(got, want)
    oracle = extract_ion_images(ds, table, ppm=3.0).reshape(N_IONS * K, -1)
    np.testing.assert_array_equal(
        got / np.float32(backend.int_scale), oracle.astype(np.float32))
    assert (got != 0).any(axis=1).sum() > N_IONS      # not a test of zeros


# -- the backend ---------------------------------------------------------------

VARIANT_KNOBS = {
    "plain": {"band_slice": "off", "peak_compaction": "off"},
    "band": {"band_slice": "on", "peak_compaction": "off"},
    "compact": {"band_slice": "off", "peak_compaction": "on"},
}


@pytest.mark.parametrize("variant", sorted(VARIANT_KNOBS))
def test_backend_scores_a_dense_table_window_major_bit_exact(dense, variant):
    """Each banded variant runs the window-major plan on the dense table,
    and its metrics are the unchunked program's bits and ``numpy_ref``'s
    within the f32 contracts."""
    ds, table = dense
    backend = _backend(ds, **VARIANT_KNOBS[variant])
    chosen, args, statics = backend._flat_call(table)
    assert chosen == variant and statics["gc_width"] <= 1536
    assert _inv_of(chosen, args).shape == (N_IONS * K,)     # the rows'
    got = backend.score_batch(table)
    np.testing.assert_array_equal(got, _unchunked_scores(backend, table))
    want = NumpyBackend(ds, DS_CONFIG).score_batch(table)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert (got[:, 2] > 0).sum() > N_IONS // 2        # spectral: real images


@pytest.mark.parametrize("variant", sorted(VARIANT_KNOBS))
def test_probe_phases_follow_the_window_major_plan(dense, variant):
    """``probe_phases`` hands each variant's extraction the row inverse, so
    its image block and side inputs are in the table's order, and the full
    phase is what ``score_batch`` dispatches."""
    ds, table = dense
    backend = _backend(ds, **VARIANT_KNOBS[variant])
    phases, info = backend.probe_phases(table)
    assert info["variant"] == variant and info["gc_width"] <= 1536
    np.testing.assert_array_equal(
        np.asarray(phases["fused_full"]())[:N_IONS],
        backend.score_batch(table).astype(np.float32))
    imgs = np.asarray(phases["extract"]()).reshape(N_IONS, K, -1)
    want = backend.extract_ion_images(table) * np.float32(backend.int_scale)
    np.testing.assert_array_equal(imgs[:, :, : ds.n_pixels], want)


@pytest.mark.parametrize("n_ions,k", [(1024, 1), (1000, 4), (100, 4), (7, 2)])
def test_short_and_padded_batches_gather_their_own_rows(dense, n_ions, k):
    """One window an ion, a batch short of its static size (empty windows
    sort last and leave the band narrow), a tail batch, and fewer windows
    than one chunk holds (the scan's rows outnumber ``inv``'s): each scores
    as ``numpy_ref`` does and as the unchunked program."""
    from sm_distributed_tpu.models.msm_basic import _slice_table

    ds, table = dense
    part = _slice_table(table, 0, n_ions)
    part = IsotopePatternTable(
        sfs=part.sfs, adducts=part.adducts, mzs=part.mzs[:, :k],
        ints=part.ints[:, :k], n_valid=np.minimum(part.n_valid, k),
        targets=part.targets)
    backend = _backend(ds)
    variant, args, statics = backend._flat_call(part)
    assert _inv_of(variant, args).shape == (statics["b"] * k,)
    assert statics["gc_width"] <= 1536
    got = backend.score_batch(part)
    np.testing.assert_array_equal(got, _unchunked_scores(backend, part))
    want = NumpyBackend(ds, DS_CONFIG).score_batch(part)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert (got[:, 1:3] > 0).any()                    # not a test of zeros


def _traced_presize(ds, table, tmp_path):
    """(backend, tables, records) of a stream of two 512-ion batches
    planned under a ``score`` phase, as a served job's is."""
    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.utils import tracing

    backend = _backend(ds, formula_batch=512)
    tables = [_slice_table(table, s, s + 512) for s in range(0, N_IONS, 512)]
    ctx = tracing.new_trace(job_id="j42", trace_dir=tmp_path)
    with tracing.attach(ctx):
        with tracing.span("score", phase=True):
            with tracing.span("presize", batches=len(tables)):
                backend.presize(tables)
    tracing.close_file(ctx.file)
    return backend, tables, tracing.read_trace(ctx.file)


def test_a_planned_stream_shares_one_narrow_band(dense, tmp_path):
    """``presize`` grows the sticky width over the stream, every batch
    dispatches at it, and the span says how wide."""
    ds, table = dense
    backend, tables, recs = _traced_presize(ds, table, tmp_path)
    attrs = next(r for r in recs if r["name"] == "presize")["attrs"]
    assert attrs["executables"] == 1
    assert attrs["gc_width"] == backend._gc_width <= 1536
    assert {backend._flat_call(t)[2]["gc_width"] for t in tables} \
        == {backend._gc_width}


# -- the program, not the numbers --------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANT_KNOBS))
def test_scoring_program_multiplies_the_narrow_band(dense, variant):
    """The lowered scoring jit of a window-major batch holds ONE membership
    dot, an f32 one at HIGHEST whose contraction is the window-major band
    (gc_width + 2 rows), and the gather of the image rows."""
    ds, table = dense
    backend = _backend(ds, **VARIANT_KNOBS[variant])
    chosen, args, statics = backend._flat_call(table)
    fn = getattr(backend, {"plain": "_fn", "band": "_fn_bs",
                           "compact": "_fn_c"}[chosen])
    text = fn.lower(backend._px_s, backend._in_s, *args, **statics).as_text(
        debug_info=True)
    # the scan body is a function of its own in the text, outside the
    # scopes' names: the membership product is the program's ONE dot at
    # HIGHEST (the moments' einsum runs at the default precision)
    dots = [ln for ln in text.splitlines()
            if "stablehlo.dot_general" in ln and "HIGHEST" in ln]
    assert len(dots) == 1, dots
    rows, n_pix = statics["gc_width"] + 2, ds.n_pixels
    assert (f"(tensor<512x{rows}xf32>, tensor<{rows}x{n_pix}xf32>)"
            in dots[0]), dots[0]
    assert any("stablehlo.gather" in ln
               and f"-> tensor<{N_IONS * K}x{n_pix}xf32>" in ln
               for ln in text.splitlines())


@pytest.mark.parametrize("variant", sorted(VARIANT_KNOBS))
def test_primer_rebuilds_the_window_major_call(dense, variant):
    """The recorded BucketSpec carries ``w``, the rows ``inv`` permutes,
    and the AOT primer lowers the same signature from it."""
    from sm_distributed_tpu.service.primer import _flat_lower_call

    ds, table = dense
    backend = _backend(ds, **VARIANT_KNOBS[variant])
    chosen, args, statics = backend._flat_call(table)
    spec = backend._bucket_spec(chosen, args, statics)
    assert chosen == variant and spec["w"] == N_IONS * K
    fn, avals, kw = _flat_lower_call(spec)
    assert kw == statics
    assert [tuple(a.shape) for a in avals[2:]] == [
        tuple(np.shape(a)) for a in args]
    fn.lower(*avals, **kw)


# -- what a trace says -----------------------------------------------------------

def test_trace_report_prints_the_band_width(dense, tmp_path):
    from scripts import trace_report

    ds, table = dense
    backend, _tables, recs = _traced_presize(ds, table, tmp_path)
    text = trace_report.render(trace_report.summarize(recs))
    assert f"gc_width={backend._gc_width}" in text
