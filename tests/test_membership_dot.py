"""The membership product of the flat-banded extraction (PR 42): the chunk
plan that feeds it.  The XLA variants run the WINDOW-MAJOR plan, whose band
stays near 2 x 512 rows on a DENSE table (the HMDB shape at toy size: ions
0.01 Da apart, so an ion-major chunk's band is several times as wide), and
give the ion-major plan's bits; the fused kernel alone keeps ion-major
chunks."""

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


def _plans(backend, table):
    """(grid ranks, the ion-major plan, the window-major plan) of one batch."""
    from sm_distributed_tpu.ops.imager_jax import (
        BAND_WINDOWS,
        ion_window_chunks,
        ions_per_chunk_for,
    )

    plan = backend._flat_plan(table)
    b, k = plan[8], table.max_peaks
    ion = ion_window_chunks(plan[1], plan[2], b, k,
                            ions_per_chunk_for(b, k, BAND_WINDOWS))
    return plan[6], ion, plan[5]


def _inv_of(variant, args):
    from sm_distributed_tpu.models.msm_jax import _VARIANTS

    return args[_VARIANTS[variant][3] + 4]


def _ion_major_scores(backend, table):
    """The parent's program by hand: ion-major extraction, the metrics of
    the ion-sorted block, the metric rows un-permuted."""
    import jax
    import jax.numpy as jnp

    from sm_distributed_tpu.models.msm_jax import named_partial
    from sm_distributed_tpu.ops.imager_jax import extract_images_flat_banded
    from sm_distributed_tpu.ops.metrics_jax import batch_metrics

    plan = backend._flat_plan(table)
    pos, ion, _win = _plans(backend, table)
    starts, rlo, rhi, inv, gc, order = ion
    common = backend._common
    imgs = extract_images_flat_banded(
        backend._px_s, backend._in_f32(), jnp.asarray(pos),
        jnp.asarray(starts), jnp.asarray(rlo), jnp.asarray(rhi), None,
        gc_width=gc, n_pixels=common["nrows"] * common["ncols"])
    out = jax.jit(named_partial(batch_metrics, **common))(
        imgs.reshape(plan[8], table.max_peaks, -1),
        jnp.asarray(plan[3][order]), jnp.asarray(plan[4][order]),
        n_real=backend._n_real)
    return np.asarray(out)[inv][: table.n_ions].astype(np.float64)


# -- the plans -----------------------------------------------------------------

def test_dense_table_widens_the_ion_major_band_only(dense):
    ds, table = dense
    _pos, ion, win = _plans(_backend(ds), table)
    assert ion[4] >= 3072           # an ion's windows reach 3 Da up
    assert win[4] <= 1536           # 512 neighbours span their own bounds
    assert win[3].shape == (N_IONS * K,) and ion[3].shape == (N_IONS,)


@pytest.mark.parametrize("plan_kind", ["ion_major", "window_major"])
def test_banded_extraction_on_a_dense_plan_is_the_oracles(dense, plan_kind):
    """``extract_images_flat_banded`` under each plan against the dense
    ``extract_images_flat`` and the numpy extraction, bit for bit."""
    import jax.numpy as jnp

    from sm_distributed_tpu.ops.imager_jax import (
        extract_images_flat,
        extract_images_flat_banded,
    )
    from sm_distributed_tpu.ops.imager_np import extract_ion_images

    ds, table = dense
    backend = _backend(ds)
    grid, r_lo, r_hi, _ints, _nv = backend._padded_windows(table, N_IONS)
    pos, ion, win = _plans(backend, table)
    px, ints = backend._px_s, backend._in_f32()
    want = np.asarray(extract_images_flat(
        px, ints, jnp.asarray(pos), jnp.asarray(r_lo), jnp.asarray(r_hi),
        n_pixels=ds.n_pixels))
    if plan_kind == "window_major":
        starts, rlo, rhi, inv, gc = win
        got = np.asarray(extract_images_flat_banded(
            px, ints, jnp.asarray(pos), jnp.asarray(starts),
            jnp.asarray(rlo), jnp.asarray(rhi), jnp.asarray(inv),
            gc_width=gc, n_pixels=ds.n_pixels))
    else:
        starts, rlo, rhi, _inv, gc, order = ion
        rows = np.asarray(extract_images_flat_banded(
            px, ints, jnp.asarray(pos), jnp.asarray(starts),
            jnp.asarray(rlo), jnp.asarray(rhi), None,
            gc_width=gc, n_pixels=ds.n_pixels))
        got = np.empty_like(rows).reshape(N_IONS, K, -1)
        got[order] = rows.reshape(N_IONS, K, -1)      # ion-sorted -> table
        got = got.reshape(N_IONS * K, -1)
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
    and its metrics are the ion-major program's bits and ``numpy_ref``'s
    within the f32 contracts."""
    ds, table = dense
    backend = _backend(ds, **VARIANT_KNOBS[variant])
    chosen, args, statics = backend._flat_call(table)
    assert chosen == variant and statics["gc_width"] <= 1536
    assert _inv_of(chosen, args).shape == (N_IONS * K,)     # the rows'
    got = backend.score_batch(table)
    np.testing.assert_array_equal(got, _ion_major_scores(backend, table))
    want = NumpyBackend(ds, DS_CONFIG).score_batch(table)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert (got[:, 2] > 0).sum() > N_IONS // 2        # spectral: real images


def test_probe_phases_follow_the_window_major_plan(dense):
    """``probe_phases`` hands extraction the row inverse, so its image block
    and side inputs are in the table's order, and the full phase is what
    ``score_batch`` dispatches."""
    ds, table = dense
    backend = _backend(ds)
    phases, info = backend.probe_phases(table)
    assert info["gc_width"] <= 1536
    np.testing.assert_array_equal(
        np.asarray(phases["fused_full"]())[:N_IONS],
        backend.score_batch(table).astype(np.float32))
    imgs = np.asarray(phases["extract"]()).reshape(N_IONS, K, -1)
    want = backend.extract_ion_images(table) * np.float32(backend.int_scale)
    np.testing.assert_array_equal(imgs[:, :, : ds.n_pixels], want)


def test_fused_kernel_keeps_its_ion_major_chunks(dense):
    """Only a backend that can route to the fused kernel plans ion-major
    chunks, and only the fused call takes them."""
    ds, table = dense
    assert _backend(ds)._flat_plan(table)[10] is None     # CPU, auto
    backend = _backend(ds, fused_metrics="on")
    variant, args, statics = backend._flat_call(table)
    assert variant == "fused" and statics["gc_width"] >= 3072
    assert _inv_of(variant, args).shape == (N_IONS,)      # the ions'
    assert backend._bucket_spec(variant, args, statics)["w"] == N_IONS
    assert backend._gc_width <= 1536 < backend._gf_width


@pytest.mark.parametrize("n_ions,k", [(1024, 1), (1000, 4), (100, 4), (7, 2)])
def test_short_and_padded_batches_gather_their_own_rows(dense, n_ions, k):
    """One window an ion, a batch short of its static size (empty windows
    sort last and leave the band narrow), a tail batch, and fewer windows
    than one chunk holds (the scan's rows outnumber ``inv``'s): each scores
    as ``numpy_ref`` does and as the ion-major program did."""
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
    np.testing.assert_array_equal(got, _ion_major_scores(backend, part))
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
