"""Target/decoy FDR engine.

Reference: ``sm/engine/fdr.py::FDR`` [U] (SURVEY.md #10): for every
(formula, target adduct), sample ``decoy_sample_size`` implausible elemental
adducts from ``DECOY_ADDUCTS``; score decoy ions with the same MSM pipeline;
rank targets against decoys per target adduct; report each annotation at the
minimal passing FDR level in {0.05, 0.1, 0.2, 0.5}.

Decoy sampling is explicitly seeded (SURVEY.md §7 hard part 3): the reference
uses an unseeded RNG, which makes runs irreproducible — here the seed lives
in config (``fdr.seed``) so numpy_ref and jax_tpu backends rank identically.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property

import numpy as np
import pandas as pd

from ..utils import tracing

# The reference's implausible-adduct list (sm/engine/fdr.py::DECOY_ADDUCTS [U]).
DECOY_ADDUCTS: tuple[str, ...] = tuple(
    "+" + el
    for el in (
        "He Li Be B C N O F Ne Mg Al Si P S Cl Ar Ca Sc Ti V Cr Mn Fe Co Ni Cu Zn "
        "Ga Ge As Se Br Kr Rb Sr Y Zr Nb Mo Ru Rh Pd Ag Cd In Sn Sb Te I Xe Cs Ba "
        "La Ce Pr Nd Sm Eu Gd Tb Dy Ho Ir Th Pt Os Yb Lu Tm Er Pb Tl Hg Au W Ta Hf Re"
    ).split()
)

FDR_LEVELS: tuple[float, ...] = (0.05, 0.1, 0.2, 0.5)

# columns of the stored assignment (upstream's ``target_decoy_add`` [U]): one
# row a sampled triple
ASSIGNMENT_COLUMNS: tuple[str, ...] = ("sf", "target_adduct", "decoy_adduct")


@dataclass(eq=False)
class DecoyAssignment:
    """Sampled decoys, columnar: ``decoys[f, t]`` holds the
    ``decoy_sample_size`` decoy adducts drawn for ``(sfs[f],
    target_adducts[t])``.  Everything derived is made ONCE here, with the
    draw, and travels with it (the resident ion-table entry keeps the
    object): ``frame``, the triples as the three aligned columns a job
    stores (``target_decoy_add.parquet``) and ``estimate_fdr`` ranks by,
    and the distinct decoy ions, which ``all_ion_tuples`` scores once."""

    sfs: list[str]
    target_adducts: tuple[str, ...]
    decoys: np.ndarray              # (formulas, target adducts, sample) str
    decoy_sample_size: int
    frame: pd.DataFrame = field(init=False, repr=False)
    n_distinct_decoys: int = field(init=False)

    def __post_init__(self):
        n_f, n_t, k = self.decoys.shape
        self.frame = pd.DataFrame(dict(zip(ASSIGNMENT_COLUMNS, (
            np.repeat(np.array(self.sfs, dtype=object), n_t * k),
            np.tile(np.repeat(np.array(self.target_adducts, dtype=object), k),
                    n_f),
            self.decoys.ravel()))))
        # a decoy ion two target adducts sampled is ONE ion: the first
        # triple of each distinct (sf, decoy adduct), in draw order
        self._first = np.flatnonzero(
            ~self.frame.duplicated(["sf", "decoy_adduct"]).to_numpy())
        self.n_distinct_decoys = int(self._first.size)
        self._formula_index = pd.Index(self.sfs)

    @property
    def n_triples(self) -> int:
        return int(self.decoys.size)

    @cached_property
    def sample(self) -> dict[tuple[str, str], tuple[str, ...]]:
        """The draw as ``{(sf, target_adduct): decoy adducts}``: for callers
        that look one pair up (tests, ``benchmarks/tests/assignment.py``);
        the engine reads the columns."""
        return {(sf, ta): tuple(self.decoys[f, t].tolist())
                for f, sf in enumerate(self.sfs)
                for t, ta in enumerate(self.target_adducts)}

    def decoys_of(self, sfs: np.ndarray, target_adduct: str
                  ) -> tuple[np.ndarray, np.ndarray]:
        """(which of ``sfs`` were sampled under ``target_adduct``, their
        decoy adducts as ``(n, decoy_sample_size)``), rows in ``sfs`` order."""
        if target_adduct not in self.target_adducts:
            return np.zeros(len(sfs), dtype=bool), self.decoys[:0, 0]
        at = self._formula_index.get_indexer(sfs)
        has = at >= 0
        return has, self.decoys[at[has],
                                self.target_adducts.index(target_adduct)]

    def all_ion_tuples(
        self, sfs: list[str], target_adducts: tuple[str, ...]
    ) -> tuple[list[tuple[str, str]], list[bool]]:
        """Deduplicated (sf, adduct) list to score + per-ion target flag.
        A decoy ion sampled under several target adducts is scored once
        (reference dedups the same way before theor-peak generation [U])."""
        pairs = list(dict.fromkeys(
            (sf, ta) for sf in sfs for ta in target_adducts))
        n_targets = len(pairs)
        targets = set(pairs)
        first = self.frame.iloc[self._first]
        pairs.extend(
            p for p in zip(first.sf.tolist(), first.decoy_adduct.tolist())
            if p not in targets)
        return pairs, [True] * n_targets + [False] * (len(pairs) - n_targets)


class FDR:
    """Reference-compatible FDR engine (class name kept, SURVEY.md #10)."""

    def __init__(
        self,
        decoy_sample_size: int = 20,
        target_adducts: tuple[str, ...] = ("+H", "+Na", "+K"),
        seed: int = 42,
    ):
        if decoy_sample_size < 1:
            raise ValueError("decoy_sample_size must be >= 1")
        self.decoy_sample_size = decoy_sample_size
        self.target_adducts = tuple(target_adducts)
        self.seed = seed
        candidates = [a for a in DECOY_ADDUCTS if a not in self.target_adducts]
        if decoy_sample_size > len(candidates):
            raise ValueError(
                f"decoy_sample_size {decoy_sample_size} exceeds the "
                f"{len(candidates)} available decoy adducts"
            )
        self._candidates = candidates

    def decoy_adduct_selection(self, sfs: list[str]) -> DecoyAssignment:
        """Sample decoy adducts per (formula, target adduct) — reference:
        ``FDR.decoy_adduct_selection`` storing ``target_decoy_add`` [U]."""
        rng = np.random.default_rng(self.seed)
        cand = np.array(self._candidates)
        # a formula listed twice keeps its place and its LAST draw, as a
        # dict keyed by (sf, target adduct) did
        row_of = {sf: i for i, sf in enumerate(sfs)}
        picks = np.empty((len(sfs), len(self.target_adducts),
                          self.decoy_sample_size), dtype=np.intp)
        for row in picks.reshape(-1, self.decoy_sample_size):
            row[:] = rng.choice(cand.size, size=self.decoy_sample_size,
                                replace=False)
        return DecoyAssignment(
            sfs=list(row_of), target_adducts=self.target_adducts,
            decoys=cand[picks[list(row_of.values())]],
            decoy_sample_size=self.decoy_sample_size)

    @staticmethod
    def _qvalues(target_msm: np.ndarray, decoy_msm: np.ndarray, decoy_sample_size: int
                 ) -> np.ndarray:
        """q-value per target: FDR(t) = (#decoys>=t / decoy_sample_size) /
        #targets>=t, monotonized by the reverse running minimum.  Ties count
        the decoy first (conservative)."""
        n_t = target_msm.size
        if n_t == 0:
            return np.zeros(0)
        scores = np.concatenate([target_msm, decoy_msm])
        is_target = np.concatenate([
            np.ones(n_t, dtype=bool), np.zeros(decoy_msm.size, dtype=bool)
        ])
        # sort by score desc; on ties decoys come first (is_target False < True)
        order = np.lexsort((is_target, -scores))
        s_target = is_target[order]
        cum_t = np.cumsum(s_target)
        cum_d = np.cumsum(~s_target)
        fdr = (cum_d / decoy_sample_size) / np.maximum(cum_t, 1)
        q = np.minimum.accumulate(fdr[::-1])[::-1]
        # map back to each target's position in the sorted array
        q_target_sorted = q[s_target]
        target_order = order[s_target]  # original target indices, by score desc
        out = np.empty(n_t)
        out[target_order] = q_target_sorted
        return out

    def estimate_fdr(self, msm_df: pd.DataFrame, assignment: DecoyAssignment
                     ) -> pd.DataFrame:
        """Annotate target ions with q-values + snapped FDR levels.

        ``msm_df`` columns: sf, adduct, msm — one row per scored ion (targets
        and decoys).  Returns the target rows with added ``fdr`` (continuous
        q-value) and ``fdr_level`` (smallest passing level from FDR_LEVELS, or
        1.0) — reference: ``FDR.estimate_fdr`` [U].
        """
        # Vectorized ranking (VERDICT r1 weak #8: the per-ion dict loops cost
        # ~5M dict.gets at 80k-formula scale).  Decoy scores resolve through
        # ONE left merge per target adduct on the assignment's columns;
        # ordering is fixed (targets in msm_df row order, decoys in
        # (target-row, sampled-decoy) order), so q-values are reproducible
        # bit for bit.
        frames = []
        k = assignment.decoy_sample_size
        for ta in self.target_adducts:
            t = msm_df[msm_df.adduct == ta]
            if t.empty:
                continue
            sfs_arr = t.sf.to_numpy()
            target_msm = t.msm.to_numpy(dtype=np.float64)
            has, dec = assignment.decoys_of(sfs_arr, ta)
            with tracing.span("fdr_rank", adduct=ta, targets=int(sfs_arr.size),
                              decoy_entries=int(dec.size)):
                pairs = pd.DataFrame({
                    "sf": np.repeat(sfs_arr[has], k), "adduct": dec.ravel()})
                merged = pairs.merge(msm_df[["sf", "adduct", "msm"]],
                                     on=["sf", "adduct"], how="left")
                decoy_msm = merged.msm.fillna(0.0).to_numpy(dtype=np.float64)
                q = self._qvalues(target_msm, decoy_msm,
                                  self.decoy_sample_size)
            level = np.select([q <= lv for lv in FDR_LEVELS],
                              FDR_LEVELS, default=1.0)
            frames.append(pd.DataFrame({
                "sf": sfs_arr, "adduct": ta, "msm": target_msm,
                "fdr": q, "fdr_level": level,
            }))
        if not frames:
            return pd.DataFrame(
                columns=["sf", "adduct", "msm", "fdr", "fdr_level"])
        out = pd.concat(frames, ignore_index=True)
        # "sf" as the final key makes the row order a TOTAL order: without
        # it, exact-MSM ties kept the incoming table order, which depends
        # on the internal parallel.order_ions batching knob
        return out.sort_values(
            ["adduct", "msm", "sf"], ascending=[True, False, True]
        ).reset_index(drop=True)


# -- metrics hook (mirrors ops/isocalc.attach_metrics) -----------------------

_metrics_registry = None
_TRIPLES = ("sm_fdr_decoy_triples_total",
            "Sampled (sf, target adduct, decoy adduct) triples of the jobs "
            "that reached the fdr phase")
_DECOY_IONS = ("sm_fdr_decoy_ions_total",
               "Distinct decoy ions of the jobs that reached the fdr phase "
               "(a decoy two target adducts sampled is one ion)")
_RANKINGS = ("sm_fdr_rankings_total",
             "Final target/decoy rankings made, by target adduct",
             ("adduct",))


def attach_metrics(registry) -> None:
    """Export the ``sm_fdr_*`` family through a service ``MetricsRegistry``."""
    global _metrics_registry
    _metrics_registry = registry
    for family in (_TRIPLES, _DECOY_IONS, _RANKINGS):
        registry.counter(*family)


def count_ranked(assignment: DecoyAssignment, adducts) -> None:
    """A job's final FDR ran: its assignment's triples and distinct decoy
    ions, and one ranking for each of ``adducts``."""
    reg = _metrics_registry
    if reg is None:
        return
    reg.counter(*_TRIPLES).inc(assignment.n_triples)
    reg.counter(*_DECOY_IONS).inc(assignment.n_distinct_decoys)
    for adduct in adducts:
        reg.counter(*_RANKINGS).labels(adduct=adduct).inc()
