"""Shape-bucket lattice + AOT cache primer tests (ISSUE 13).

The tentpole's correctness contract: scoring through the lattice (padded
pixel rows, padded resident peaks, snapped batches, traced real-pixel
count) is BIT-IDENTICAL to unpadded scoring — FDR ranks and chaos bits
exactly equal — on both backends; and the primer's ahead-of-time compiles
are the byte-identical executables real jobs look up (idempotent, resume-
able, and never running while real work is in flight)."""

import json

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.ops import buckets
from sm_distributed_tpu.utils.config import DSConfig, SMConfig


def _table(truth, n=14):
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.utils.config import IsotopeGenerationConfig

    calc = IsocalcWrapper(IsotopeGenerationConfig(adducts=("+H",)))
    return calc.pattern_table([(sf, "+H") for sf in truth.formulas[:n]])


# ------------------------------------------------------------------ lattice
def test_lattice_points_round_trip():
    for n in (1, 2, 3, 5, 7, 8, 9, 12, 40, 56, 60, 64, 100, 300, 2048,
              5000, 123457):
        up = buckets.pow2ish(n)
        dn = buckets.pow2ish_down(n)
        assert dn <= n <= up
        # lattice points are fixpoints in both directions
        assert buckets.pow2ish(up) == up
        assert buckets.pow2ish_down(dn) == dn
    # bounded waste: a quarter ladder never pads more than 25%
    for n in range(8, 4096):
        assert buckets.pow2ish(n) < 1.25 * n + 1


@pytest.mark.parametrize("n, batch, rows", [
    (1, 2048, 64), (64, 2048, 64), (65, 2048, 80), (301, 2048, 320),
    (2047, 2048, 2048), (2048, 2048, 2048), (4, 48, 48), (301, 256, 256)])
def test_export_bucket_follows_the_kept_count(n, batch, rows):
    """The store's image export pads to the lattice point of the kept ion
    count (floor 64) and never above the scoring batch."""
    assert buckets.export_bucket(n, batch) == rows
    assert n <= rows <= batch or batch < n


def test_lattice_floors_and_batch_snap():
    assert buckets.row_bucket(6) == 8          # floor
    assert buckets.row_bucket(9) == 10
    assert buckets.row_bucket(64) == 64        # lattice sizes unchanged
    assert buckets.peak_bucket(100) == 4096    # floor
    assert buckets.batch_bucket_down(2048) == 2048
    assert buckets.batch_bucket_down(300) == 256
    assert buckets.batch_bucket_down(1) == 1
    # effective_batch: slicer (msm_basic) and padder (backends) agree
    from sm_distributed_tpu.utils.config import ParallelConfig

    assert buckets.effective_batch(ParallelConfig(formula_batch=300)) == 256
    assert buckets.effective_batch(
        ParallelConfig(formula_batch=300, shape_buckets="off")) == 300


def test_oom_shape_key_buckets_pixels():
    from sm_distributed_tpu.models import oom

    # two dataset sizes in one pixel bucket share the safe-batch key
    assert oom.shape_key(130, "jax_tpu") == oom.shape_key(150, "jax_tpu")
    assert oom.shape_key(130, "jax_tpu") != oom.shape_key(700, "jax_tpu")
    assert oom.shape_key(130, "jax_tpu", (0, 1)) != \
        oom.shape_key(130, "jax_tpu", (2, 3))


# ------------------------------------------- bucketed == unpadded, bit-exact
def _score_all(backend, table, batch):
    from sm_distributed_tpu.models.msm_basic import _slice_table

    outs = backend.score_batches(
        [_slice_table(table, s, min(s + batch, table.n_ions))
         for s in range(0, table.n_ions, batch)])
    return np.concatenate(outs)


def _table_with_decoys(truth, n=10):
    """A real search table: targets + sampled decoys, plus the FDR state
    needed to rank it (mirrors MSMBasicSearch.search)."""
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


def test_bucketed_scoring_bit_identical_fdr(offgrid_ds):
    """The acceptance criterion: FDR ranks (and chaos bits) identical
    between lattice-bucketed and unpadded scoring, jax backend vs the
    numpy oracle, on the off-grid spheroid fixture."""
    from sm_distributed_tpu.models.msm_basic import NumpyBackend
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    ds, truth = offgrid_ds
    table, fdr, assignment = _table_with_decoys(truth)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm_on = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 8}})
    sm_off = SMConfig.from_dict(
        {"backend": "jax_tpu",
         "parallel": {"formula_batch": 8, "shape_buckets": "off"}})
    b_on = JaxBackend(ds, dc, sm_on)
    b_off = JaxBackend(ds, dc, sm_off)
    # the lattice actually engaged: padded rows, lattice-point residents
    assert b_on._nrows_b == 10 and ds.nrows == 9
    n_res = int(b_on._px_s.shape[0])
    assert buckets.pow2ish(n_res, buckets.PEAK_FLOOR) == n_res
    assert n_res >= int(b_off._px_s.shape[0])
    assert b_off._nrows_b == 9
    got_on = _score_all(b_on, table, 8)
    got_off = _score_all(b_off, table, 8)
    oracle = _score_all(NumpyBackend(ds, dc), table, 8)
    # chaos is exactly integer-derived: bit-equal across all three (zero
    # pads join no component and move no max/count)
    np.testing.assert_array_equal(got_on[:, 0], oracle[:, 0])
    np.testing.assert_array_equal(got_off[:, 0], oracle[:, 0])
    # spatial/spectral: the padded and unpadded programs reduce over
    # different pixel lengths, so XLA may associate the f32 sums
    # differently — the documented cross-variant contract (ulps), same as
    # chunked-vs-unchunked and TPU-vs-CPU
    np.testing.assert_allclose(got_on, got_off, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_on, oracle, rtol=1e-6, atol=1e-6)
    # the ACCEPTANCE bar: FDR ranks bit-identical across bucketed /
    # unpadded / numpy-oracle scoring
    r_on, r_off, r_np = (_fdr_ranks(table, m, fdr, assignment)
                         for m in (got_on, got_off, oracle))
    assert list(r_on.sf) == list(r_off.sf) == list(r_np.sf)
    np.testing.assert_array_equal(r_on.fdr.to_numpy(), r_off.fdr.to_numpy())
    np.testing.assert_array_equal(r_on.fdr.to_numpy(), r_np.fdr.to_numpy())
    np.testing.assert_array_equal(r_on.fdr_level.to_numpy(),
                                  r_np.fdr_level.to_numpy())


def test_oom_shrunk_batch_lands_on_lattice(offgrid_ds):
    """An OOM-shrunk batch snaps DOWN to a lattice point and rescores
    bit-identically (the smaller-bucket executable is one the primer
    enumerates)."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    ds, truth = offgrid_ds
    table = _table(truth)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "parallel": {"formula_batch": 8}})
    b = JaxBackend(ds, dc, sm)
    want = _score_all(b, table, 8)
    b.shrink_batch(3)                  # OOM backoff: 3 snaps down to 2
    assert b.batch == 2
    got = _score_all(b, table, 2)
    np.testing.assert_array_equal(got, want)


def test_masked_moments_match_unpadded():
    """batch_moments with trailing zero padding + traced n_real returns
    the unpadded moments: bit-for-bit on the jnp fallback, and inside the
    declared ulp contract on the masked Pallas kernel (interpret mode)."""
    import jax.numpy as jnp

    from sm_distributed_tpu.analysis.numerics import (
        contract_ulps,
        max_ulp,
        parse_policy,
    )
    from sm_distributed_tpu.ops.moments_pallas import (
        NUMERICS,
        batch_moments_jnp,
        batch_moments_pallas,
        batch_moments_pallas_masked,
    )

    rng = np.random.default_rng(7)
    imgs = (rng.integers(0, 50, size=(3, 4, 128)) *
            (rng.random((3, 4, 128)) < 0.4)).astype(np.float32)
    padded = np.concatenate(
        [imgs, np.zeros((3, 4, 128), np.float32)], axis=-1)
    want = batch_moments_jnp(jnp.asarray(imgs))
    got = batch_moments_jnp(jnp.asarray(padded), n_real=jnp.int32(128))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the masked kernel against ITS unpadded program (the unmasked kernel
    # on the unpadded block), inside the contract the kernel declares
    ceiling = contract_ulps(
        parse_policy(NUMERICS["batch_moments_pallas_masked"])["contract"])
    want_pl = batch_moments_pallas(jnp.asarray(imgs), interpret=True)
    got_pl = batch_moments_pallas_masked(
        jnp.asarray(padded), jnp.int32(128), interpret=True)
    for a, b in zip(want_pl, got_pl):
        assert max_ulp(np.asarray(a), np.asarray(b)) <= ceiling


# ------------------------------------------------------------------- primer
@pytest.fixture()
def recorded_backend(offgrid_ds, tmp_path, isolated_compile_cache):
    """A scored backend with an isolated cache dir, so the bucket
    manifest + prime manifest live under tmp_path."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    buckets.reset()
    ds, truth = offgrid_ds
    table = _table(truth)
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "work_dir": str(tmp_path / "work"),
         "parallel": {"formula_batch": 8}})
    b = JaxBackend(ds, dc, sm)
    _score_all(b, table, 8)
    yield sm, tmp_path
    buckets.reset()


def test_primer_idempotent_and_resumable(recorded_backend):
    """One prime pass compiles every recorded bucket; an interrupted pass
    (max_specs=1) resumes from the persisted prime manifest; a repeat
    pass is a no-op (all skipped)."""
    from sm_distributed_tpu.service.primer import CachePrimer

    sm, tmp = recorded_backend
    specs = buckets.recorded_specs()
    assert specs, "backend recorded no bucket specs"
    p1 = CachePrimer(sm, busy=lambda: False)
    res1 = p1.prime_once(max_specs=1)
    assert res1["compiled"] == 1
    # a NEW primer (fresh process analog) resumes: the first spec is
    # already marked primed in prime_manifest.json
    p2 = CachePrimer(sm, busy=lambda: False)
    res2 = p2.prime_once()
    assert res2["errors"] == 0
    assert res2["compiled"] + res2["skipped"] >= len(specs)
    snap = p2.snapshot()
    flat = [s for s in specs if s["kind"] == "flat"]
    assert snap["primed"] >= len(flat) >= 1
    # idempotence: everything already primed
    res3 = p2.prime_once()
    assert res3["compiled"] == 0 and res3["errors"] == 0
    # the manifest survived on disk
    manifest = json.loads((tmp / "xla" / "prime_manifest.json").read_text())
    assert len(manifest["primed"]) >= len(flat)


@pytest.mark.parametrize("field, value, reason", [
    ("cube_dtype", "int8", "cube_dtype"),
    ("variant", "fused", "legacy_spec")])
def test_primer_skips_entries_of_removed_programs(recorded_backend, caplog,
                                                  field, value, reason):
    """A manifest entry recorded under a resident dtype (int8) or a scoring
    variant (the fused Pallas one) this program no longer has is skipped
    with one log line, never an error; the manifest's other specs still
    prime."""
    import logging

    from sm_distributed_tpu.service.primer import CachePrimer
    from sm_distributed_tpu.utils.logger import LOGGER_NAME

    sm, _tmp = recorded_backend
    specs = buckets.recorded_specs()
    stale = dict(specs[0], **{field: value})
    assert buckets.record_spec(stale)
    primer = CachePrimer(sm, busy=lambda: False)
    with caplog.at_level(logging.INFO, logger=LOGGER_NAME):
        res = primer.prime_once()
        again = primer.prime_once()
    assert res == {"compiled": len(specs), "skipped": 1, "errors": 0,
                   "aborted": False}
    assert again["compiled"] == 0 and again["errors"] == 0
    lines = [r.getMessage() for r in caplog.records
             if f"skipped:{reason}" in r.getMessage()]
    assert len(lines) == 1 and f"{field}={value}" in lines[0]


def test_primer_yields_to_real_work(recorded_backend):
    """A busy service aborts the cycle at the next spec boundary without
    compiling — priming never delays a real job (and touches no
    device-pool lease by construction: it only lowers on host)."""
    from sm_distributed_tpu.service.primer import CachePrimer

    sm, _tmp = recorded_backend
    p = CachePrimer(sm, busy=lambda: True)
    res = p.prime_once()               # abort_when_busy defaults True
    assert res["aborted"] is True
    assert res["compiled"] == 0


def test_warmup_manifest_rekeyed_on_buckets(offgrid_ds, tmp_path,
                                            isolated_compile_cache):
    """ISSUE 13 satellite: the warmup manifest keys on BUCKET ids, so a
    cache warmed by one dataset size is recognized as warm for another
    size in the same bucket — no redundant representative executions."""
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    from sm_distributed_tpu.models.msm_basic import _slice_table

    # two SMALL fixtures whose peak counts both sit under the 4096-slot
    # floor and whose rows share the 8-row bucket (8x8 and 6x8) — the
    # same bucket pair the compile census uses
    path1, truth = generate_synthetic_dataset(
        tmp_path / "ds1", nrows=8, ncols=8, formulas=None,
        present_fraction=0.3, noise_peaks=5, seed=41)
    ds = SpectralDataset.from_imzml(path1)
    table = _table(truth)
    batches = [_slice_table(table, s0, min(s0 + 8, table.n_ions))
               for s0 in range(0, table.n_ions, 8)]
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "work_dir": str(tmp_path / "work"),
         "parallel": {"formula_batch": 8}})
    b1 = JaxBackend(ds, dc, sm)
    b1.warmup(batches)
    assert not b1.last_warmup_skipped
    path2, _truth2 = generate_synthetic_dataset(
        tmp_path / "ds2", nrows=6, ncols=8, formulas=None,
        present_fraction=0.3, noise_peaks=5, seed=42)
    ds2 = SpectralDataset.from_imzml(path2)
    b2 = JaxBackend(ds2, dc, sm)
    assert b2._nrows_b == b1._nrows_b == 8
    assert b2._px_s.shape == b1._px_s.shape
    b2.warmup(batches)
    assert b2.last_warmup_skipped, \
        "same-bucket dataset re-ran warmup executions despite the manifest"


def test_warmup_manifest_of_the_parent_misses_once(offgrid_ds, tmp_path,
                                                   isolated_compile_cache):
    """A manifest written before PR 44 keyed on the fused kernel's band
    widths and on ``fused_metrics`` too: the first warmup against it runs
    its representative batches and rewrites it, the next backend hits."""
    import hashlib

    import jax

    from sm_distributed_tpu.models.msm_basic import _slice_table
    from sm_distributed_tpu.models.msm_jax import JaxBackend

    ds, truth = offgrid_ds
    table = _table(truth)
    batches = [_slice_table(table, s0, min(s0 + 8, table.n_ions))
               for s0 in range(0, table.n_ions, 8)]
    dc = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]}})
    sm = SMConfig.from_dict(
        {"backend": "jax_tpu", "work_dir": str(tmp_path / "work"),
         "parallel": {"formula_batch": 8}})
    b0 = JaxBackend(ds, dc, sm)
    plans = [b0._flat_plan(t) for t in batches]
    b0._grow_for_stream(plans)
    kinds = sorted({b0._plan_kind(plan) for plan in plans})
    dev = jax.devices()[0]
    img = dc.image_generation
    # the parent's _warmup_manifest_key, on a platform that could not fuse
    parent_key = hashlib.sha256(repr((
        kinds,
        (b0._gc_width, b0._gc_tail, 0, 0, b0._n_keep, b0._r_pad),
        (b0._nrows_b, ds.ncols, int(b0._mz_host.size), b0.batch, True),
        (img.nlevels, img.do_preprocessing),
        ("f32", "auto"),
        (jax.__version__, dev.platform, str(dev.device_kind)),
    )).encode()).hexdigest()
    path = b0._manifest_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(
        {"keys": [parent_key], "entries": {parent_key: 0}}))

    b1 = JaxBackend(ds, dc, sm)
    b1.warmup(batches)
    assert not b1.last_warmup_skipped
    keys = json.loads(path.read_text())["keys"]
    assert len(keys) == 2 and keys[0] == parent_key
    b2 = JaxBackend(ds, dc, sm)
    b2.warmup(batches)
    assert b2.last_warmup_skipped
    assert json.loads(path.read_text())["keys"] == keys
