"""``maldi-section-128-hotspot`` as a deployment, at 8x8 px: the six cases of
``benchmarks/tests/test_oracle_hotspot.py`` (ISSUE 50; imported from where
they live, path-relative) that run the CELL and read its files.  The whole
command through ``run.run_cell`` is ``correct`` as configured and not when
the program is told to leave the clip out, under ``cube_dtype: bf16``, or
judged at another ``q``; the configuration is its sibling's but for the
clip; the manifest names the cell; ``counts.py`` charges the clip's passes.
The twelve that judge the clip itself are in ``tests/test_oracle_hotspot.py``;
one case here says that the two files leave none of the eighteen out."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

_CASES = (Path(__file__).resolve().parent.parent / "benchmarks" / "tests"
          / "test_oracle_hotspot.py")
_spec = importlib.util.spec_from_file_location(
    "bench_hotspot_deployment", _CASES)
cases = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = cases
_spec.loader.exec_module(cases)

HERE = (
    "test_the_cell_through_run_cell_sound_and_with_the_clip_left_out",
    "test_the_lower_precision_control_is_not_correct_under_the_clip",
    "test_the_control_script_judges_as_configured",
    "test_the_configuration_is_the_siblings_but_for_the_clip",
    "test_the_manifest_names_the_cell_beside_its_sibling",
    "test_counts_charge_the_clip_where_it_is_asked_for",
)
globals().update({name: getattr(cases, name) for name in HERE})


def test_the_two_thin_files_hold_every_case_of_the_benchmarks_file():
    # by path: the benchmark's file has the other thin file's name
    spec = importlib.util.spec_from_file_location(
        "tier1_oracle_hotspot", Path(__file__).with_name(
            "test_oracle_hotspot.py"))
    clip_half = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(clip_half)

    theirs = {n for n in vars(cases) if n.startswith("test_")}
    assert theirs == (set(HERE) | set(clip_half.HERE)) - {"served"}
    assert not set(HERE) & set(clip_half.HERE)
