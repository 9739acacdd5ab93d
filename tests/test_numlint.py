"""numlint tests (ISSUE 15): dtype-flow / masked-reduction / ulp-contract
rules, the numerics contract registry + ULP helpers, and the runtime
sentinel's honest-pass/degraded-fail proof.

The generic fire/pass fixture replay rides tests/test_smlint.py's
parametrization over RULES; here are the targeted mechanics plus the
acceptance checks: every committed NUMERICS contract cross-references a
real test, the tree is clean for the three rules against the committed
baseline, and the committed NUMERICS_r01.json history passes its own
gate while a synthetic contract bust fails it.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from sm_distributed_tpu.analysis import numerics
from sm_distributed_tpu.analysis import rules as rules_mod  # noqa: F401
from sm_distributed_tpu.analysis.core import (
    RULES,
    Project,
    load_baseline,
    run_lint,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
_NUMLINT_RULES = {"dtype-flow", "masked-reduction", "ulp-contract"}


# ----------------------------------------------------------- registry/grammar
def test_parse_policy_grammar():
    p = numerics.parse_policy(
        "contract=ulp(16); test=tests/test_x.py::test_y; padded=a,b")
    assert p == {"contract": "ulp(16)", "test": "tests/test_x.py::test_y",
                 "padded": "a,b"}
    assert numerics.contract_ulps("bit_exact") == 0
    assert numerics.contract_ulps("ulp(128)") == 128
    for bad in ("contract=maybe; test=tests/t.py::x",
                "contract=bit_exact",
                "test=tests/t.py::x",
                "contract=bit_exact; test=nodoublecolon",
                "contract=bit_exact; test=tests/t.py::x; padded=a b",
                "contract=bit_exact; test=tests/t.py::x; bogus=1"):
        with pytest.raises(ValueError):
            numerics.parse_policy(bad)


def test_numerics_surface_validates_at_import_time():
    with pytest.raises(ValueError, match="entry 'bad'"):
        numerics.numerics_surface("m", {"bad": "contract=whenever"})
    out = numerics.numerics_surface(
        "tests.synthetic", {"ok": "contract=bit_exact; "
                                  "test=tests/t.py::test_ok"})
    assert out == {"ok": "contract=bit_exact; test=tests/t.py::test_ok"}
    assert numerics.registered()["tests.synthetic"] == out
    assert numerics._NumericsRegistry._GUARDED_BY == {"_surfaces": "_lock"}


# -------------------------------------------------------------- ULP helpers
def test_ulp_distance_basics():
    one = np.float32(1.0)
    nxt = np.nextafter(one, np.float32(2.0), dtype=np.float32)
    assert numerics.max_ulp([1.0], [1.0]) == 0
    assert numerics.max_ulp([one], [nxt]) == 1
    assert numerics.max_ulp([0.0], [-0.0]) == 0
    tiny = np.nextafter(np.float32(0.0), np.float32(1.0), dtype=np.float32)
    # crossing zero: one step up from +0 and one step down from -0
    assert numerics.max_ulp([tiny], [-float(tiny)]) == 2
    # f64 oracle value that rounds to the same f32 bits is distance 0
    assert numerics.max_ulp([float(np.float32(0.1))], [0.1]) == 0
    nan = numerics.ulp_distance([np.nan], [1.0])
    assert nan[0] == 2**62
    assert numerics.ulp_distance([np.nan], [np.nan])[0] == 0


def test_component_drift_shape_and_order():
    a = np.zeros((3, 4), np.float32)
    b = a.copy()
    b[1, 2] = np.nextafter(np.float32(0.0), np.float32(1.0),
                           dtype=np.float32)
    d = numerics.component_drift(a, b)
    assert list(d) == ["chaos", "spatial", "spectral", "msm"]
    assert d["spectral"] == 1 and d["chaos"] == 0
    with pytest.raises(ValueError):
        numerics.component_drift(np.zeros((3, 3)), np.zeros((3, 3)))


# ------------------------------------------------------- dtype-flow details
def _run(rule_name: str, modules: dict, aux: dict | None = None):
    return RULES[rule_name].run(Project(modules=modules, aux=aux or {}))


_NUM_HEADER = (
    "import jax.numpy as jnp\n"
    "import numpy as np\n"
    "from ..analysis.numerics import numerics_surface\n"
    "NUMERICS = numerics_surface(__name__, {\n"
    "    'f': 'contract=bit_exact; test=tests/t.py::test_f',\n"
    "})\n"
)


def test_dtype_flow_positional_dtype_is_fine():
    src = _NUM_HEADER + (
        "def f(x):\n"
        "    return jnp.zeros((4, 4), jnp.float32) + "
        "jnp.full((2,), 0.5, jnp.float32)\n"
    )
    assert not _run("dtype-flow", {"sm_distributed_tpu/ops/x_jax.py": src})


def test_dtype_flow_scoped_to_numerics_modules():
    src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.zeros(4)\n"
    assert not _run("dtype-flow", {"sm_distributed_tpu/ops/x_jax.py": src})


def test_dtype_flow_empty_annotation_reason_still_fires():
    src = _NUM_HEADER + (
        "def f(x):\n"
        "    # smlint: dtype-ok[]\n"
        "    return jnp.zeros(4)\n"
    )
    got = _run("dtype-flow", {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "empty" in got[0].message


def test_dtype_flow_f64_through_single_level_summary():
    src = _NUM_HEADER + (
        "def scale(v):\n"
        "    return v * 2\n"
        "def f(x):\n"
        "    w = np.float64(0.5)\n"
        "    return jnp.multiply(x, scale(w))\n"
    )
    got = _run("dtype-flow", {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "float64" in got[0].message


def test_dtype_flow_astype_f64_and_dtype_kwarg():
    src = _NUM_HEADER + (
        "def f(x, host):\n"
        "    w = host.astype(np.float64)\n"
        "    y = jnp.add(x, w)\n"
        "    z = jnp.zeros((4,), dtype=np.float64)\n"
        "    return y, z\n"
    )
    msgs = " | ".join(f.message for f in _run(
        "dtype-flow", {"sm_distributed_tpu/ops/x_jax.py": src}))
    assert msgs.count("float64") >= 2


# -------------------------------------------------- masked-reduction details
def test_masked_reduction_function_form_and_bucket_helper_seed():
    src = (
        "import jax.numpy as jnp\n"
        "from ..analysis.numerics import numerics_surface\n"
        "from ..ops.buckets import row_bucket\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'f': 'contract=bit_exact; test=tests/t.py::test_f',\n"
        "})\n"
        "def f(imgs, nrows):\n"
        "    p = row_bucket(nrows)\n"
        "    block = imgs.reshape(4, p)\n"
        "    return jnp.sum(block, axis=-1)\n"
    )
    got = _run("masked-reduction", {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "jnp.sum()" in got[0].message


def test_masked_reduction_cleared_by_n_real_helper():
    src = (
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'f': 'contract=bit_exact; test=tests/t.py::test_f; "
        "padded=images',\n"
        "})\n"
        "def f(images, n_real):\n"
        "    sums, normsq = batch_moments(images, n_real=n_real)\n"
        "    return sums.sum(axis=0)\n"   # post-helper values are clean
    )
    assert not _run("masked-reduction",
                    {"sm_distributed_tpu/ops/x_jax.py": src})


# ------------------------------------------------------ ulp-contract details
def test_ulp_contract_surface_without_numerics_fires():
    src = (
        "from ..analysis.surface import compile_surface\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'score': 'statics=none; buckets=one shape',\n"
        "})\n"
        "def score(x):\n"
        "    return x\n"
    )
    got = _run("ulp-contract", {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "no NUMERICS" in got[0].message


def test_ulp_contract_missing_test_file_and_bad_padded():
    src = (
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'f': 'contract=bit_exact; test=tests/test_gone.py::test_x; "
        "padded=ghost',\n"
        "})\n"
        "def f(images):\n"
        "    return images\n"
    )
    msgs = " | ".join(f.message for f in _run(
        "ulp-contract", {"sm_distributed_tpu/ops/x_jax.py": src}))
    assert "does not exist" in msgs
    assert "not a parameter" in msgs


def test_ulp_contract_grammar_violation_is_a_finding():
    src = (
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'f': 'contract=roughly; test=tests/t.py::test_x',\n"
        "})\n"
        "def f(x):\n"
        "    return x\n"
    )
    got = _run("ulp-contract", {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "contract must be" in got[0].message


# ------------------------------------------------------------- whole repo
def _repo_project() -> Project:
    return Project.load(REPO_ROOT, ["sm_distributed_tpu", "scripts",
                                    "bench.py"])


def test_every_committed_contract_cross_references_a_real_test():
    """The acceptance bar: every COMPILE_SURFACE site carries a declared
    contract and every NUMERICS test= reference resolves to a committed
    test — zero ulp-contract findings on the tree."""
    res = run_lint(_repo_project(), only={"ulp-contract"})
    assert not res.new, "\n".join(f.render() for f in res.new)


def test_repo_clean_for_numlint_rules_against_baseline():
    baseline = load_baseline(REPO_ROOT / "conf" / "smlint_baseline.json")
    res = run_lint(_repo_project(), baseline, only=_NUMLINT_RULES)
    assert not res.new, "\n".join(f.render() for f in res.new)
    # ... and with nothing baselined: the one argued entry went with the
    # legacy correlation it kept firing on (PR 44)
    assert not res.suppressed


def test_jitting_modules_declare_numerics_registries():
    from sm_distributed_tpu.analysis.rules import numerics_census

    census = numerics_census(_repo_project())
    assert census["modules"] >= 8
    assert census["contracts"] >= 25


def test_smlint_json_emits_numerics_totals(capsys):
    from scripts.smlint import main

    rc = main(["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["sm_numerics_contracts_total"] >= 25
    assert out["sm_numerics_modules_total"] >= 8
    assert out["sm_numerics_violations_total"] == 0


# --------------------------------------------------------------- sentinel
def test_ulp_sentinel_honest_pass_and_degraded_fail():
    """The committed NUMERICS_r01.json passes its own gate; a synthetic
    ceiling-busting copy fails every layer (rank identity, component
    contracts, history banding)."""
    from scripts import ulp_sentinel

    history = sorted(str(p) for p in REPO_ROOT.glob("NUMERICS_r*.json"))
    assert history, "no committed NUMERICS history"
    honest = json.loads(Path(history[-1]).read_text())
    assert honest["fdr_ranks_identical"] is True
    assert honest["sm_numerics_max_ulp"]["chaos"] == 0
    rc = ulp_sentinel.gate(honest, history, tolerance=0.5, min_history=1,
                           label="test honest")
    assert rc == 0
    bad = ulp_sentinel.degrade(honest)
    rc_bad = ulp_sentinel.gate(bad, history, tolerance=0.5, min_history=1,
                               label="test degraded")
    assert rc_bad == 1


def test_ulp_sentinel_cli_self_check():
    from scripts import ulp_sentinel

    assert ulp_sentinel.main(["--self-check"]) == 0


def test_committed_drift_within_component_contracts():
    """The committed history honors the declared per-component ceilings
    (chaos bit_exact, spatial/spectral/msm within budget)."""
    for p in sorted(REPO_ROOT.glob("NUMERICS_r*.json")):
        art = json.loads(p.read_text())
        for comp, ulps in art["sm_numerics_max_ulp"].items():
            assert ulps <= numerics.COMPONENT_CONTRACTS[comp], (p, comp)
