"""Isotope patterns: the share of sampled decoys that another target adduct
of the same formula sampled too, in percent: 100 x (1 - window delta of
counter ``sm_fdr_decoy_ions_total`` (distinct decoy ions of the jobs that
reached ``fdr``) / that of ``sm_fdr_decoy_triples_total`` (their sampled
triples), ``ops/fdr.py``).  0 with one target adduct; were a shared decoy
scored once per sample it would read 0 with three.  None where the program
has no such counters or no job ranked inside the window."""
from layers.counters import window_delta


def read(run):
    triples = window_delta(run, "sm_fdr_decoy_triples_total")
    ions = window_delta(run, "sm_fdr_decoy_ions_total")
    if not triples or ions is None:
        return None
    return 100.0 * (1.0 - ions / triples)
