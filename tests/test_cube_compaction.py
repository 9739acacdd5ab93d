"""Resident-cube compaction tests (parallel.cube_dtype, ops/quantize.py).

Proves the declared NUMERICS contracts of ``ops/quantize.compact_cube`` /
``expand_cube_jnp``: an exact roundtrip (the bf16 cast), and FDR-rank
identity of bf16-compacted scoring on the off-lattice 9x11 spheroid.
"""

import numpy as np
import pytest

from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
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
    by compact_cube's wide declared ceiling."""
    from sm_distributed_tpu.analysis.numerics import (
        component_drift,
        contract_ulps,
        parse_policy,
    )
    from sm_distributed_tpu.ops.quantize import NUMERICS as QN

    cube_ulps = contract_ulps(parse_policy(QN["compact_cube"])["contract"])
    ds, truth = offgrid_ds
    table, fdr, assignment = _table_with_decoys(truth)
    base = _score_all(_backend(ds, {}), table, 8)
    r_base = _fdr_ranks(table, base, fdr, assignment)
    got = _score_all(_backend(ds, {"cube_dtype": "bf16"}), table, 8)
    drift = component_drift(base, got)
    assert max(drift.values()) <= cube_ulps, drift
    # the HARD acceptance: identical FDR ranks and levels
    r_got = _fdr_ranks(table, got, fdr, assignment)
    assert list(r_base.sf) == list(r_got.sf)
    np.testing.assert_array_equal(r_base.fdr.to_numpy(),
                                  r_got.fdr.to_numpy())
