"""Numerics contracts + ULP instrumentation (ISSUE 15 "numlint").

Fused Pallas scoring and bf16 intensity compaction are gated on one
invariant: FDR ranks stay bit-identical — or within a
*declared* tolerance — to the fp32/numpy oracle.  This module is the
declarative half of that gate, mirroring ``analysis/surface.py``:

- every jitting module declares a module-level ``NUMERICS =
  numerics_surface(__name__, {...})`` mapping each site (its
  ``COMPILE_SURFACE`` sites, plus any public numeric function the module
  wants covered) to a **contract string** in the grammar::

      "contract=bit_exact|ulp(N); test=tests/<file>.py::<test_name>
       [; padded=<param,param>]"

  ``contract=`` is the declared drift bound versus the site's reference
  (the numpy oracle, the unpadded program, or the sibling variant —
  whichever the named test asserts): ``bit_exact`` means every bit, and
  ``ulp(N)`` means at most N float32 units-in-the-last-place.
  ``test=`` names the committed test that PROVES the contract — the
  ``ulp-contract`` smlint rule statically cross-checks that the file
  exists and defines that test, so a contract can never outlive its
  proof.  ``padded=`` names the parameters that receive lattice-padded
  blocks (ops/buckets, ISSUE 13): the ``masked-reduction`` rule seeds
  its taint from them, so a raw reduction over a padded axis that skips
  the ``n_real`` masked helpers is a lint error, not a silent metric
  corruption;

- the runtime half is ``scripts/ulp_sentinel.py``: it scores the
  spheroid fixture on both backends, measures per-MSM-component max-ULP
  drift with the helpers below, hard-gates FDR-rank identity, enforces
  the per-component ceilings in :data:`COMPONENT_CONTRACTS`, and bands
  the drift against the committed ``NUMERICS_r*.json`` history
  (perf_sentinel-style: rising drift regresses).

The registry is import-time write-once state like the compile surface;
one leaf lock guards the map and the class carries a ``_GUARDED_BY``
registry for the smlint ``guarded-by`` rule.  Only numpy is imported —
jitting modules pull ``numerics_surface`` at import time, before any
backend initialization.
"""

from __future__ import annotations

import re
import threading

import numpy as np

# contract grammar (keep in lockstep with the ulp-contract rule's static
# validation in rules.py — same regexes, one checked at import, one in lint)
CONTRACT_RE = re.compile(r"^(bit_exact|ulp\((\d+)\))$")
TEST_RE = re.compile(r"^[\w./-]+\.py::\w+$")
PADDED_RE = re.compile(r"^\w+(,\w+)*$")
POLICY_KEYS = ("contract", "test")          # mandatory clauses
OPTIONAL_KEYS = ("padded",)

# The per-MSM-component drift ceilings the runtime sentinel enforces on
# the spheroid fixture (jax lattice-bucketed scoring vs the numpy
# oracle, float32 ULPs).  chaos is integer-derived (component counts /
# exact maxima) => bit-exact by construction; spatial (image
# correlation) and spectral (pattern match) reduce f32 in a different
# association order than numpy, so they carry a small declared budget;
# msm is their product.  Measured on the committed fixture
# (NUMERICS_r01.json, XLA-CPU): chaos 0 / spatial 2 / spectral 1 / msm 2
# ULPs — the integer-grid intensity quantization (ops/quantize.py) makes
# the image sums exact, and the residual drift is reduction-order in the
# metric epilogues.  The budgets below are the DECLARED cross-backend
# ceilings (the same 1e-6-grade bound tests assert on TPU); the
# committed-history banding in ulp_sentinel catches drift long before a
# ceiling is reached.
COMPONENTS = ("chaos", "spatial", "spectral", "msm")
COMPONENT_CONTRACTS = {"chaos": 0, "spatial": 16, "spectral": 16, "msm": 32}


def parse_policy(policy: str) -> dict[str, str]:
    """Parse one contract policy string; raises ``ValueError`` on any
    grammar violation (missing clause, bad contract form, malformed test
    reference or padded list)."""
    if not isinstance(policy, str):
        raise ValueError(f"policy must be a string, got {policy!r}")
    out: dict[str, str] = {}
    for part in policy.split(";"):
        part = part.strip()
        if not part:
            continue
        key, eq, value = part.partition("=")
        key, value = key.strip(), value.strip()
        if not eq or key not in POLICY_KEYS + OPTIONAL_KEYS:
            raise ValueError(f"unknown policy clause {part!r}")
        out[key] = value
    for key in POLICY_KEYS:
        if key not in out:
            raise ValueError(f"policy lacks the {key}= clause: {policy!r}")
    if not CONTRACT_RE.match(out["contract"]):
        raise ValueError(
            f"contract must be bit_exact or ulp(N), got {out['contract']!r}")
    if not TEST_RE.match(out["test"]):
        raise ValueError(
            f"test must be <path>.py::<test_name>, got {out['test']!r}")
    if "padded" in out and not PADDED_RE.match(out["padded"]):
        raise ValueError(
            f"padded must be a comma list of parameter names, got "
            f"{out['padded']!r}")
    return out


def contract_ulps(contract: str) -> int:
    """Declared float32 ULP budget: 0 for ``bit_exact``, N for ``ulp(N)``."""
    m = CONTRACT_RE.match(contract)
    if not m:
        raise ValueError(f"not a contract: {contract!r}")
    return int(m.group(2)) if m.group(2) is not None else 0


class _NumericsRegistry:
    """Process-global {module: {site: policy}} map (import-time
    write-once, reader-iterated — same protocol as the compile surface)."""

    _GUARDED_BY = {"_surfaces": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._surfaces: dict[str, dict[str, str]] = {}

    def declare(self, module: str, entries: dict[str, str]) -> None:
        with self._lock:
            self._surfaces[module] = dict(entries)

    def registered(self) -> dict[str, dict[str, str]]:
        with self._lock:
            return {m: dict(e) for m, e in self._surfaces.items()}


_registry = _NumericsRegistry()


def numerics_surface(module: str, entries: dict[str, str]) -> dict[str, str]:
    """Declare ``module``'s numerics contracts and return ``entries`` (the
    declaration doubles as the module-level ``NUMERICS`` constant).
    Malformed policies raise at import time — a bad contract must not
    wait for the lint run."""
    for site, policy in entries.items():
        try:
            parse_policy(policy)
        except ValueError as exc:
            raise ValueError(
                f"numerics_surface({module!r}): entry {site!r}: {exc}"
            ) from exc
    _registry.declare(module, entries)
    return dict(entries)


def registered() -> dict[str, dict[str, str]]:
    """{module name: {site: policy}} of every imported declaration."""
    return _registry.registered()


# --------------------------------------------------------- ULP measurement
def _lex_f32(x: np.ndarray) -> np.ndarray:
    """Monotone int64 image of float32 values: consecutive floats map to
    consecutive integers (the ULP number line), with -0.0 == +0.0."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.int32)
    bits = bits.astype(np.int64)
    return np.where(bits >= 0, bits, np.int64(-(2**31)) - bits)


def ulp_distance(a, b) -> np.ndarray:
    """Elementwise float32 ULP distance (int64).  Inputs are cast to f32
    first — the engine's device dtype — so a float64 oracle value and
    its f32 rounding compare at distance 0 when they share the f32 bit
    pattern.  NaNs (none expected from the metric epilogues, which clip
    to [0, 1]) compare as +inf-like: any NaN pairing maps to 2**62."""
    fa = np.asarray(a, dtype=np.float32)
    fb = np.asarray(b, dtype=np.float32)
    dist = np.abs(_lex_f32(fa) - _lex_f32(fb))
    nan = np.isnan(fa) | np.isnan(fb)
    both = np.isnan(fa) & np.isnan(fb)
    return np.where(both, 0, np.where(nan, np.int64(2**62), dist))


def max_ulp(a, b) -> int:
    """Max elementwise float32 ULP distance between two arrays."""
    d = ulp_distance(a, b)
    return int(d.max()) if d.size else 0


def component_drift(got: np.ndarray, want: np.ndarray) -> dict[str, int]:
    """Per-MSM-component max-ULP drift between two (N, 4) metric blocks
    ordered (chaos, spatial, spectral, msm) — the sentinel's unit of
    record."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape or got.ndim != 2 or got.shape[1] != 4:
        raise ValueError(
            f"metric blocks must share an (N, 4) shape, got {got.shape} "
            f"vs {want.shape}")
    return {comp: max_ulp(got[:, i], want[:, i])
            for i, comp in enumerate(COMPONENTS)}


def component_report(got: np.ndarray, want: np.ndarray) -> dict[str, dict]:
    """Per-MSM-component comparison of two (N, 4) metric blocks against
    :data:`COMPONENT_CONTRACTS`, at sizes where relative ulps alone mislead:
    ``{comp: {max_ulp, max_abs, outside}}``.

    A component is a quantity in [0, 1].  Near 1 its ulp(N) ceiling is an
    absolute ``N * 2**-24`` (the "1e-6-grade" bound); near 0 a correlation
    is a cancelling sum over every pixel, so its *relative* error grows
    without bound while its absolute error — what the MSM product and an
    FDR rank see — stays at the f32 floor (on the v5e at 65,536 px:
    hundreds of ulps on values ~1e-4, absolute error ~1e-9).  An ion is
    ``outside`` when it exceeds the ceiling in ulps AND in absolute terms;
    a ``bit_exact`` (0) component has no absolute allowance at all."""
    got = np.asarray(got)
    want = np.asarray(want)
    out = {}
    for i, comp in enumerate(COMPONENTS):
        ulps = ulp_distance(got[:, i], want[:, i])
        err = np.abs(got[:, i].astype(np.float32).astype(np.float64)
                     - want[:, i].astype(np.float32).astype(np.float64))
        ceiling = COMPONENT_CONTRACTS[comp]
        outside = (ulps > ceiling) & (err > ceiling * 2.0 ** -24)
        out[comp] = {"max_ulp": int(ulps.max()) if ulps.size else 0,
                     "max_abs": float(err.max()) if err.size else 0.0,
                     "outside": int(outside.sum())}
    return out
