"""The decoy assignment of a job, written out as the contract's
``target_decoy_add.parquet`` (``benchmarks/README.md``) BY THE TESTS, for as
long as the program does not store it itself: the program's own seeded draw
(``ops/fdr.py::FDR.decoy_adduct_selection``, a function of the formula list,
the target adducts, ``fdr.decoy_sample_size`` and ``fdr.seed`` alone), so it
is the assignment the job ranked by.  Imports numpy and pandas only: the
benchmark's process stays off jax."""

from __future__ import annotations

import shutil
import sys
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import oracle  # noqa: E402
import traffic  # noqa: E402


def seeded_assignment(formulas: list[str], config: dict):
    """Rows (sf, target_adduct, decoy_adduct) of the draw a job of ``config``
    makes over ``formulas``."""
    import pandas as pd
    from sm_distributed_tpu.ops.fdr import FDR

    fdr = FDR(config["sm_config"]["fdr"]["decoy_sample_size"],
              tuple(config["ds_config"]["isotope_generation"]["adducts"]),
              config["sm_config"]["fdr"]["seed"])
    sample = fdr.decoy_adduct_selection(list(formulas)).sample
    return pd.DataFrame(
        [(sf, ta, da) for (sf, ta), das in sample.items() for da in das],
        columns=oracle.ASSIGNMENT_COLUMNS)


def old_rule(draw, targets: list[str]):
    """``draw`` as ``oracle.py`` read a table before ISSUE 46: every decoy of
    a formula assigned to each of its target adducts."""
    import pandas as pd

    union = draw[["sf", "decoy_adduct"]].drop_duplicates()
    return pd.concat([union.assign(target_adduct=ta) for ta in targets])[
        oracle.ASSIGNMENT_COLUMNS].reset_index(drop=True)


@contextmanager
def stored_with_every_answer(config: dict):
    """While open, ``traffic.Driver.wait`` finds the assignment beside every
    answer it keeps, as it will once the program stores it: drawn once (every
    job of a catalogue sends the same formula list), copied after."""
    kept_wait = traffic.Driver.wait
    made: dict[tuple, Path] = {}

    def wait(self, job, until):
        ok = kept_wait(self, job, until)
        kept = self.answers / job["msg_id"]
        if ok and kept.is_dir() and not (kept / oracle.ASSIGNMENT).exists():
            formulas = tuple(job["dataset"]["formulas"])
            if formulas not in made:
                made[formulas] = self.answers / f"draw{len(made)}.parquet"
                seeded_assignment(formulas, config).to_parquet(made[formulas])
            shutil.copy(made[formulas], kept / oracle.ASSIGNMENT)
        return ok

    traffic.Driver.wait = wait
    try:
        yield
    finally:
        traffic.Driver.wait = kept_wait
