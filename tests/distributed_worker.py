"""Worker process for the REAL 2-process distributed test (no mocks).

Launched by tests/test_distributed.py with SM_COORDINATOR / SM_NUM_PROCESSES /
SM_PROCESS_ID in the environment (the production launch contract,
parallel/distributed.py).  Each process owns 4 virtual CPU devices, so the
("pixels", "formulas") mesh spans 8 devices across 2 OS processes — the
reference actually executes its "distributed" code across Spark executors
(SURVEY.md §5.8); this is the JAX-runtime equivalent.

Steps:
1. jax.distributed.initialize via the real config resolution path.
2. Build the same synthetic dataset + ion table in both processes (seeded).
3. ShardedJaxBackend.score_batch over the cross-process mesh; save metrics.
4. Run a checkpointed search, delete the LAST checkpoint shard in process 1
   only (divergent `done` counts), and verify _agree_resume_point lowers
   both processes to the common minimum before re-searching to completion.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# 4 virtual CPU devices per process — must be set before jax imports
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    out_dir = Path(sys.argv[1])
    pid = int(os.environ["SM_PROCESS_ID"])

    sys.path.insert(0, str(Path(__file__).parent.parent))
    from sm_distributed_tpu.io.dataset import SpectralDataset
    from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
    from sm_distributed_tpu.models.msm_basic import MSMBasicSearch
    from sm_distributed_tpu.ops.fdr import FDR
    from sm_distributed_tpu.ops.isocalc import IsocalcWrapper
    from sm_distributed_tpu.parallel.distributed import (
        maybe_initialize_distributed,
    )
    from sm_distributed_tpu.parallel.sharded import ShardedJaxBackend
    from sm_distributed_tpu.utils.config import DSConfig, SMConfig

    sm_config = SMConfig.from_dict({
        "backend": "jax_tpu",
        "fdr": {"decoy_sample_size": 3, "seed": 5},
        "parallel": {"formula_batch": 8, "pixels_axis": 4,
                     "formulas_axis": 2, "checkpoint_every": 1},
    })
    SMConfig.set(sm_config)
    assert maybe_initialize_distributed(sm_config.parallel) is True
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    # identical dataset/table in both processes (same seed, private dirs)
    path, truth = generate_synthetic_dataset(
        out_dir / f"ds_p{pid}", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=30, seed=17)
    ds = SpectralDataset.from_imzml(path)
    ds_config = DSConfig.from_dict(
        {"isotope_generation": {"adducts": ["+H"]},
         "image_generation": {"ppm": 3.0}})
    formulas = list(truth.formulas)[:8]

    fdr = FDR(decoy_sample_size=3, target_adducts=("+H",), seed=5)
    assignment = fdr.decoy_adduct_selection(formulas)
    pairs, flags_ = assignment.all_ion_tuples(formulas, ("+H",))
    calc = IsocalcWrapper(ds_config.isotope_generation)
    table = calc.pattern_table(pairs, flags_)

    # --- step 3: sharded scoring across both processes ------------------
    backend = ShardedJaxBackend(ds, ds_config, sm_config)
    from sm_distributed_tpu.models.msm_basic import NumpyBackend, _slice_table

    sub = _slice_table(table, 0, min(8, table.n_ions))
    out = backend.score_batch(sub)
    np.save(out_dir / f"metrics_p{pid}.npy", out)
    # vs the numpy oracle: chaos is bit-exact (integer component counts on
    # integer images); spatial/spectral may differ by f32 ulps because the
    # multi-process SPMD lowering fuses reductions differently than the
    # single-process program (same caveat as fused_score_fn_chunked)
    want = NumpyBackend(ds, ds_config).score_batch(sub)
    np.testing.assert_array_equal(out[:, 0], want[:, 0])
    np.testing.assert_allclose(out, want, atol=1e-6)

    # --- step 4: checkpoint resume with divergent done counts -----------
    ckpt_dir = out_dir / "ckpt"
    search = MSMBasicSearch(ds, formulas, ds_config, sm_config,
                            checkpoint_dir=str(ckpt_dir))
    first = search.search()
    ckpt = search.last_checkpoint
    assert ckpt is not None
    shards = sorted(ckpt_dir.glob(f"msm_search.p{pid}.g*.ckpt.npz"))
    n_groups = len(shards)
    assert n_groups >= 2, f"need >=2 checkpoint groups, got {n_groups}"
    if pid == 1:
        shards[-1].unlink()          # process 1 lost its last group

    # both processes must agree on min(done) or the SPMD program deadlocks
    metrics = np.zeros((table.n_ions, 4))
    row_ranges = []
    batch = sm_config.parallel.formula_batch
    slices = [(s, min(s + batch, table.n_ions))
              for s in range(0, table.n_ions, batch)]
    row_ranges = [(s, e) for s, e in slices]     # checkpoint_every=1
    done_local = ckpt.load(metrics, n_groups, row_ranges)
    agreed = search._agree_resume_point(done_local)
    assert done_local == (n_groups if pid == 0 else n_groups - 1), done_local
    assert agreed == n_groups - 1, (pid, done_local, agreed)

    # resume to completion: annotations identical to the first run
    second = MSMBasicSearch(ds, formulas, ds_config, sm_config,
                            checkpoint_dir=str(ckpt_dir)).search()
    import pandas.testing as pdt

    pdt.assert_frame_equal(second.annotations, first.annotations)

    (out_dir / f"ok_p{pid}.json").write_text(json.dumps({
        "pid": pid, "n_groups": n_groups, "agreed": agreed,
        "n_ions": int(sub.n_ions)}))
    print(f"worker {pid} OK", flush=True)


if __name__ == "__main__":
    main()
