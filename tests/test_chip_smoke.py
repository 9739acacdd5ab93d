"""chip_smoke.py's legs at 16x16 px, called as functions on the CPU mesh.

The only thing not exercised is the platform assertion: the script itself
always demands ``tpu`` (no flag, no environment variable), the tests pass
``cpu``.  Everything else is the script's own code — the ``serve`` child
driven over HTTP, the per-job trace checks, the metric checks, the numpy
oracle, the kernel table (in interpret mode here, Mosaic on the chip).
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke

REPO_ROOT = Path(__file__).resolve().parent.parent


def _datasets(work):
    # the deployments' fixture, cut to test size: 16x16 / 8x8 px, 6 formulas
    return {
        "scale": chip_smoke.make_dataset(work, "scale", side=16,
                                         n_formulas=6, noise_peaks=30),
        "headline": chip_smoke.make_dataset(work, "headline", side=8,
                                            n_formulas=6, noise_peaks=30),
    }


# formula_batch 32: several batches per job and an off-size tail, so the
# per-batch trace checks see more than one batch
SMALL = {"parallel": {"formula_batch": 32}}


def test_serve_and_oracle_legs(tmp_path):
    data = _datasets(tmp_path)
    ident = chip_smoke.serve_leg(tmp_path, "cpu", sm_overrides=SMALL,
                                 job_timeout=300.0, datasets=data)
    assert ident["platform"] == "cpu" and ident["device_count"] >= 1
    serve = tmp_path / "serve"
    for name, ds_ids, sample in (("scale", ["scale_cold", "scale_again"], 40),
                                 ("headline", ["headline",
                                               "headline_small_db"], None)):
        path, formulas, present = data[name]
        chip_smoke.oracle_check(serve / "results", serve / "work", ds_ids,
                                path, formulas, present, sample)


def test_serve_leg_refuses_another_platform(tmp_path):
    """What the script does off the chip: names the platform and fails
    before any job is submitted."""
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu'"):
        chip_smoke.serve_leg(tmp_path, "tpu", datasets=_datasets(tmp_path))
    assert not list((tmp_path / "serve" / "queue").rglob("*.json"))


def test_four_chip_leg(tmp_path):
    path, formulas, present = chip_smoke.make_dataset(
        tmp_path, "headline", side=16, n_formulas=6, noise_peaks=30)
    chip_smoke.four_chip_leg(tmp_path, "cpu", job_timeout=300.0,
                             sm_overrides=SMALL, dataset=(path, formulas))
    serve = tmp_path / "serve_four"
    chip_smoke.oracle_check(
        serve / "results", serve / "work",
        chip_smoke.FOUR_CHIP_PLAN["ds_ids"], path, formulas, present, None)


def test_kernels_leg_interpret():
    chip_smoke.kernels_leg("cpu", interpret=True)


def test_script_fails_off_chip_and_prints_no_result():
    """The contract's negative: ``python chip_smoke.py`` on a CPU platform
    exits non-zero, names the platform, prints no result line."""
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=str(REPO_ROOT))
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
