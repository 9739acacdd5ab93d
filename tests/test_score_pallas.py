"""Fused scoring kernel + resident-cube compaction tests (ISSUE 18).

Proves the declared NUMERICS contracts of the perf tentpole:

- ``ops/score_pallas.fused_window_moments`` (interpret mode) against a
  direct dense reference over the same histogram scratch — principal
  images, pixel sums, maxima and positive counts BIT-EXACT (integer-grid
  sums), centered norm/dot partials within the ulp ceiling — and
  pad-invariant across shape-bucket lattice pixel paddings.
- ``ops/metrics_jax.batch_metrics_from_partials`` — the fused kernel's
  epilogue — bit-identical to ``batch_metrics`` on materialized images.
- ``ops/quantize.compact_cube`` / ``expand_cube_jnp`` — exact roundtrip
  (the bf16 cast), and FDR-rank identity of
  bf16-compacted scoring on the off-lattice 9x11 spheroid.
- The end-to-end ``fused`` variant vs the plain dispatch chain through
  ``JaxBackend``: chaos bit-equal, components within the declared
  contracts, FDR ranks identical — including OOM-shrunk batches and
  checkpoint-grouped search resume.
"""

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.ops import buckets
from sm_distributed_tpu.ops import score_pallas as sp
from sm_distributed_tpu.utils.config import DSConfig, SMConfig


@pytest.fixture(scope="module")
def offgrid_ds(tmp_path_factory):
    """Same off-lattice spheroid as test_buckets: 9 rows bucket to 10,
    peaks sit under the 4096 resident floor — real padding everywhere."""
    out = tmp_path_factory.mktemp("dsp")
    path, truth = generate_synthetic_dataset(
        out, nrows=9, ncols=11, formulas=None, present_fraction=0.5,
        noise_peaks=12, seed=41,
    )
    return SpectralDataset.from_imzml(path), truth


def _table(truth, n=14):
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    return calc.pattern_table([(sf, "+H") for sf in truth.formulas[:n]])


def _table_with_decoys(truth, n=10):
    from sm_distributed_tpu.ops.fdr import FDR
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    formulas = truth.formulas[:n]
    fdr = FDR(decoy_sample_size=2, target_adducts=("+H",), seed=1)
    assignment = fdr.decoy_adduct_selection(formulas)
    pairs, flags = assignment.all_ion_tuples(formulas, ("+H",))
    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    return calc.pattern_table(pairs, flags), fdr, assignment


def _fdr_ranks(table, metrics, fdr, assignment):
    import pandas as pd

    df = pd.DataFrame({"sf": table.sfs, "adduct": table.adducts,
                       "msm": metrics[:, 3]})
    ann = fdr.estimate_fdr(df, assignment)
    return ann.sort_values(["msm", "sf"], ascending=False)


def _score_all(backend, table, batch):
    from sm_distributed_tpu.models.msm_basic import _slice_table

    outs = backend.score_batches(
        [_slice_table(table, s, min(s + batch, table.n_ions))
         for s in range(0, table.n_ions, batch)])
    return np.concatenate(outs)


def _backend(ds, extra):
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    p = {"formula_batch": 16}
    p.update(extra)
    sm = SMConfig.from_dict({"backend": "jax_tpu", "parallel": p})
    return JaxBackend(ds, dc, sm)


# --------------------------------------------------- kernel-level parity
def _plan_case(seed=0, C=3, ipc=4, k=3, gc_width=11, g=40, n_pix=128):
    """A synthetic histogram scratch + window chunk plan shaped like the
    real ``ion_window_chunks`` output: integer-valued intensities (the
    quantized grid), chunk grid offsets, local window rank bounds."""
    rng = np.random.default_rng(seed)
    wc = ipc * k
    cols_p = sp.cols_padded(g, gc_width)
    whp = np.zeros((cols_p, n_pix), np.float32)
    # integer-grid intensities on real grid rows only (pads stay zero)
    whp[:g + 1] = (rng.integers(0, 50, size=(g + 1, n_pix))
                   * (rng.random((g + 1, n_pix)) < 0.4)).astype(np.float32)
    starts = rng.integers(0, g - gc_width, size=C).astype(np.int32)
    r_lo = rng.integers(-1, gc_width - 2, size=(C, wc)).astype(np.int32)
    r_hi = (r_lo + rng.integers(1, 3, size=(C, wc))).astype(np.int32)
    return whp, starts, r_lo, r_hi


def _reference(whp, starts, r_lo, r_hi, n_real, k):
    """Dense f64 reference: global membership matmul + masked moments."""
    C, wc = r_lo.shape
    ipc = wc // k
    rows = np.arange(whp.shape[0])
    glo = starts[:, None] + r_lo
    ghi = starts[:, None] + r_hi
    d = ((rows[None, None, :] > glo[..., None])
         & (rows[None, None, :] <= ghi[..., None]))
    imgs = np.einsum("cwr,rp->cwp", d.astype(np.float64),
                     whp.astype(np.float64))
    principal = imgs.reshape(C, ipc, k, -1)[:, :, 0, :]
    sums = imgs.sum(axis=2)
    vmax = imgs.max(axis=2)
    nn = (imgs > 0).sum(axis=2).astype(np.float64)
    col = np.arange(imgs.shape[2])
    mean = sums / n_real
    cent = np.where(col[None, None, :] < n_real, imgs - mean[..., None], 0.0)
    c3 = cent.reshape(C, ipc, k, -1)
    dots = np.einsum("cikp,cikp->cik", c3, c3[:, :, 0:1, :]).reshape(C, wc)
    normsq = np.einsum("cwp,cwp->cw", cent, cent)
    return dict(principal=principal, sums=sums, vmax=vmax, nn=nn,
                dots=dots, normsq=normsq)


def test_fused_matches_unfused():
    """The declared contract (ops/score_pallas.py NUMERICS): principal
    rows, sums, vmax and positive counts bit-exact vs the dense
    reference (integer-grid sums in any order); centered normsq/dots
    within the ulp(16) ceiling."""
    import jax.numpy as jnp

    gc_width, k = 11, 3
    whp, starts, r_lo, r_hi = _plan_case(gc_width=gc_width, k=k)
    n_real = whp.shape[1]
    partials, principal = sp.fused_window_moments(
        jnp.asarray(whp), jnp.asarray(starts), jnp.asarray(r_lo),
        jnp.asarray(r_hi), jnp.int32(n_real),
        gc_width=gc_width, k=k, interpret=True)
    partials = np.asarray(partials)
    ref = _reference(whp, starts, r_lo, r_hi, n_real, k)
    # integer-grid outputs: exact
    np.testing.assert_array_equal(np.asarray(principal),
                                  ref["principal"].astype(np.float32))
    np.testing.assert_array_equal(partials[..., 0],
                                  ref["sums"].astype(np.float32))
    np.testing.assert_array_equal(partials[..., 3],
                                  ref["vmax"].astype(np.float32))
    np.testing.assert_array_equal(partials[..., 4],
                                  ref["nn"].astype(np.float32))
    # centered reductions: f32 vs the f64 oracle, ulp-class tolerance
    np.testing.assert_allclose(partials[..., 1], ref["normsq"],
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(partials[..., 2], ref["dots"],
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("pad_to", [160, 256])
def test_fused_pad_invariant_across_lattice(pad_to):
    """Zero pixel padding to a larger lattice point + traced n_real
    leaves every partial unchanged: sums/vmax/nn/principal bit-equal,
    centered reductions too (pads are masked to exact zeros)."""
    import jax.numpy as jnp

    gc_width, k, n_pix = 11, 3, 128
    whp, starts, r_lo, r_hi = _plan_case(gc_width=gc_width, k=k,
                                         n_pix=n_pix)
    base_p, base_pr = sp.fused_window_moments(
        jnp.asarray(whp), jnp.asarray(starts), jnp.asarray(r_lo),
        jnp.asarray(r_hi), jnp.int32(n_pix),
        gc_width=gc_width, k=k, interpret=True)
    padded = np.zeros((whp.shape[0], pad_to), np.float32)
    padded[:, :n_pix] = whp
    pad_p, pad_pr = sp.fused_window_moments(
        jnp.asarray(padded), jnp.asarray(starts), jnp.asarray(r_lo),
        jnp.asarray(r_hi), jnp.int32(n_pix),
        gc_width=gc_width, k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(pad_pr)[..., :n_pix],
                                  np.asarray(base_pr))
    assert not np.any(np.asarray(pad_pr)[..., n_pix:])
    np.testing.assert_array_equal(np.asarray(pad_p), np.asarray(base_p))


def test_fused_fit_and_tile_ladder():
    """Dispatch gating: off-lane-lattice pixel counts refuse a compiled
    tile; lattice shapes pick the largest dividing tile in budget; the
    scratch geometry covers any start offset in whole super-rows."""
    assert sp.pick_tile(110, 48, 16, 11) is None          # 9x11 spheroid
    assert sp.pick_tile(0, 48, 16, 11) is None
    pt = sp.pick_tile(4096, 48, 16, 11)
    assert pt is not None and 4096 % pt == 0 and pt % 128 == 0
    assert sp.fused_fit(48, 16, 4096, 11)
    assert not sp.fused_fit(48, 16, 110, 11)
    for g, gc in ((40, 11), (100, 3), (7, 30)):
        cols = sp.cols_padded(g, gc)
        nsb = sp.n_super_blocks(gc)
        assert cols % sp.SC == 0
        # any start <= g leaves the fetched nsb super-rows in bounds
        assert (g // sp.SC) + nsb <= cols // sp.SC
        # the fetched band always covers gc + 2 rows past any shift
        assert nsb * sp.SC >= gc + 2 + (sp.SC - 1)


# ------------------------------------------------------------- epilogue
def test_epilogue_matches_batch_metrics():
    """batch_metrics_from_partials (the fused exit) is bit-identical to
    batch_metrics on the materialized image block — including invalid
    window rows and all-empty ions."""
    import jax.numpy as jnp

    from sm_distributed_tpu.ops.metrics_jax import (
        batch_metrics,
        batch_metrics_from_partials,
    )
    from sm_distributed_tpu.ops.moments_pallas import batch_moments_jnp

    rng = np.random.default_rng(3)
    n, k, nrows, ncols = 6, 4, 8, 16
    n_pix = nrows * ncols
    imgs = (rng.integers(0, 50, size=(n, k, n_pix))
            * (rng.random((n, k, n_pix)) < 0.4)).astype(np.float32)
    n_valid = np.array([4, 3, 1, 0, 4, 2], np.int32)
    imgs[3] = 0.0                                  # dead ion
    theor = rng.random((n, k)).astype(np.float32)

    want = np.asarray(batch_metrics(
        jnp.asarray(imgs), jnp.asarray(theor), jnp.asarray(n_valid),
        nrows, ncols))
    # the fused kernel's moments are UNMASKED (the epilogue masks the
    # moment columns instead) — build partials the same way
    sums, normsq, dots, vmax, nn = batch_moments_jnp(jnp.asarray(imgs))
    partials = jnp.stack(
        [sums, normsq, dots,
         jnp.broadcast_to(vmax[:, None], (n, k)),
         jnp.broadcast_to(nn[:, None], (n, k))], axis=-1)
    got = np.asarray(batch_metrics_from_partials(
        partials, jnp.asarray(imgs[:, 0, :]), jnp.asarray(theor),
        jnp.asarray(n_valid), nrows, ncols))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- cube compaction
def test_compact_expand_roundtrip():
    """expand_cube_jnp inverts the stored representation exactly: the f32
    passthrough is the identity, the bf16 cast is value-preserving."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from sm_distributed_tpu.ops.quantize import compact_cube, expand_cube_jnp

    rng = np.random.default_rng(9)
    x = (rng.integers(0, 3000, size=2048)
         * (rng.random(2048) < 0.7)).astype(np.float32)

    codes = compact_cube(x, "f32")
    assert codes.dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(jax.jit(expand_cube_jnp)(jnp.asarray(codes))), x)

    codes = compact_cube(x, "bf16")
    assert codes.dtype == ml_dtypes.bfloat16
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(expand_cube_jnp(jnp.asarray(codes))), want)
    # integer-preservation: the bf16 grid still holds exact integers
    assert np.array_equal(want, np.rint(want))
    with pytest.raises(ValueError, match="f32.*bf16"):
        compact_cube(x, "int8")


def test_quantized_cube_rank_identity(offgrid_ds):
    """The compact_cube acceptance bar: bf16-compacted scoring keeps FDR
    ranks identical to the f32 cube on the off-lattice spheroid.  The
    bf16-vs-f32 drift is DATA-level (a coarser intensity grid), bounded
    by compact_cube's wide declared ceiling; fused-vs-plain ON the bf16
    cube is same-data and must sit inside the tight component contracts."""
    from sm_distributed_tpu.analysis.numerics import (
        COMPONENT_CONTRACTS,
        component_drift,
        contract_ulps,
        parse_policy,
    )
    from sm_distributed_tpu.ops.quantize import NUMERICS as QN

    cube_ulps = contract_ulps(parse_policy(QN["compact_cube"])["contract"])
    ds, truth = offgrid_ds
    table, fdr, assignment = _table_with_decoys(truth)
    base = _score_all(_backend(ds, {"fused_metrics": "off"}), table, 8)
    r_base = _fdr_ranks(table, base, fdr, assignment)
    bf16 = {}
    for fused in ("off", "on"):
        got = _score_all(
            _backend(ds, {"fused_metrics": fused, "cube_dtype": "bf16"}),
            table, 8)
        bf16[fused] = got
        drift = component_drift(base, got)
        assert max(drift.values()) <= cube_ulps, (fused, drift)
        # the HARD acceptance: identical FDR ranks and levels
        r_got = _fdr_ranks(table, got, fdr, assignment)
        assert list(r_base.sf) == list(r_got.sf), fused
        np.testing.assert_array_equal(r_base.fdr.to_numpy(),
                                      r_got.fdr.to_numpy())
    # same-data comparison: the fused kernel on the bf16 cube vs the
    # plain chain on the bf16 cube rides the tight reduction-order
    # ceilings, exactly like the f32 pair
    drift = component_drift(bf16["off"], bf16["on"])
    for comp, ulps in drift.items():
        assert ulps <= COMPONENT_CONTRACTS[comp], (comp, drift)


# --------------------------------------------------- end-to-end variant
def test_fused_variant_matches_plain(offgrid_ds):
    """Forcing the fused kernel through JaxBackend reproduces the plain
    chain: chaos bit-equal, every component inside its declared contract,
    msm ranks identical — lattice on AND off."""
    from sm_distributed_tpu.analysis.numerics import (
        COMPONENT_CONTRACTS,
        component_drift,
    )

    ds, truth = offgrid_ds
    table = _table(truth)
    for lattice in ({}, {"shape_buckets": "off"}):
        plain = _score_all(_backend(ds, {"fused_metrics": "off", **lattice}),
                           table, 16)
        fused = _score_all(_backend(ds, {"fused_metrics": "on", **lattice}),
                           table, 16)
        np.testing.assert_array_equal(fused[:, 0], plain[:, 0])  # chaos
        drift = component_drift(plain, fused)
        for comp, ulps in drift.items():
            assert ulps <= COMPONENT_CONTRACTS[comp], (lattice, comp, drift)
        assert np.array_equal(
            np.argsort(-plain[:, 3], kind="stable"),
            np.argsort(-fused[:, 3], kind="stable")), lattice


def test_fused_oom_shrink_lands_on_lattice(offgrid_ds):
    """An OOM-shrunk batch through the FUSED variant snaps down to a
    lattice point and rescores within contract (same guarantee the plain
    chain proves in test_buckets)."""
    ds, truth = offgrid_ds
    table = _table(truth)
    b = _backend(ds, {"fused_metrics": "on", "formula_batch": 8})
    want = _score_all(b, table, 8)
    b.shrink_batch(3)                  # OOM backoff: 3 snaps down to 2
    assert b.batch == 2
    got = _score_all(b, table, 2)
    np.testing.assert_array_equal(got[:, 0], want[:, 0])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.argsort(-got[:, 3], kind="stable"),
                          np.argsort(-want[:, 3], kind="stable"))


def test_fused_checkpointed_search_matches_plain(offgrid_ds, tmp_path):
    """Checkpoint-grouped search through the fused variant produces the
    same annotations as one ungrouped fused stream."""
    import pandas.testing as pdt

    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch

    ds, truth = offgrid_ds
    formulas = truth.formulas[:10]
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]}})

    def run(extra):
        sm_config = SMConfig.from_dict(
            {"backend": "jax_tpu",
             "fdr": {"decoy_sample_size": 4, "seed": 3},
             "parallel": {"formula_batch": 16, "fused_metrics": "on",
                          "cube_dtype": "bf16", **extra}})
        return MSMBasicSearch(
            ds, formulas, ds_config, sm_config,
            checkpoint_dir=str(tmp_path) if extra else None,
        ).search().annotations

    plain = run({})
    grouped = run({"checkpoint_every": 1})
    pdt.assert_frame_equal(grouped, plain)
