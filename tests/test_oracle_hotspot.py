"""The benchmark's own cases of the reference and the oracle under hot-spot
removal (``benchmarks/tests/test_oracle_hotspot.py``, ISSUE 50) in tier-1:
imported from where they live, path-relative, as
``tests/test_oracle_target_adducts.py`` does for its file.  This file takes
the twelve that judge the CLIP: one in-process service scores a 60-formula
section clipped at the 99th percentile, not clipped and clipped at the 95th
(the program's ``hotspot_clip_batch`` under XLA:CPU), and the plain
reference reads the sound job inside every limit with chaos at 0 and each
control outside; the f32 sequence by its cases.  The other six (the cell
through ``run.run_cell``, the manifest, the counts) are in
``tests/test_hotspot_deployment.py``."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

_CASES = (Path(__file__).resolve().parent.parent / "benchmarks" / "tests"
          / "test_oracle_hotspot.py")
_spec = importlib.util.spec_from_file_location("bench_oracle_hotspot", _CASES)
cases = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = cases
_spec.loader.exec_module(cases)

HERE = (
    "served",
    "test_a_sound_job_is_inside_every_limit",
    "test_control_reads_outside",
    "test_the_oracle_reads_the_two_keys_and_their_defaults",
    "test_hotspot_clip_by_its_cases",
    "test_the_f32_sequence_against_numpys_percentile",
    "test_the_clip_is_the_programs_numpy_backends_to_the_bit",
    "test_without_the_flag_score_ions_computes_the_same_bits",
)
globals().update({name: getattr(cases, name) for name in HERE})
