"""smlint framework + rule tests (ISSUE 9).

Per-rule coverage uses the fixtures the rules SHIP (each rule declares a
firing and a passing snippet — ``--self-check`` replays them in
production, these tests replay them in CI), plus targeted cases for the
framework mechanics: inline suppressions, baseline matching + minimality,
anchor stability under line drift, guard DOMINATION (a fence after the
seam does not count), and the real repo staying clean against the
committed baseline.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from sm_distributed_tpu.analysis import rules as rules_mod  # noqa: F401
from sm_distributed_tpu.analysis.core import (
    RULES,
    Finding,
    Project,
    load_baseline,
    run_lint,
    self_check,
)

REPO_ROOT = Path(__file__).resolve().parent.parent


# ------------------------------------------------------- per-rule fixtures
@pytest.mark.parametrize("rule_name", sorted(RULES))
def test_rule_fires_on_its_fixture(rule_name):
    r = RULES[rule_name]
    assert r.fixture_fail, f"rule {rule_name} ships no firing fixture"
    findings = r.run_fixture(r.fixture_fail)
    assert findings, f"rule {rule_name} did not fire on its firing fixture"
    assert all(f.rule == rule_name for f in findings)
    assert all(f.severity == r.severity for f in findings)


@pytest.mark.parametrize("rule_name", sorted(RULES))
def test_rule_passes_on_its_fixture(rule_name):
    r = RULES[rule_name]
    assert r.fixture_pass, f"rule {rule_name} ships no passing fixture"
    got = r.run_fixture(r.fixture_pass)
    assert not got, [f.render() for f in got]


# ----------------------------------------------------------- rule details
def test_broad_except_counts_by_fixture_shape():
    r = RULES["broad-except"]
    # the firing fixture has exactly two silent handlers
    assert len(r.run_fixture(r.fixture_fail)) == 2


def test_fence_guard_must_dominate_not_merely_exist():
    src = (
        "from u import register_failpoint, failpoint\n"
        "FP_C = register_failpoint('spool.complete', 'seam')\n"
        "class S:\n"
        "    def _finish(self, claimed, rec):\n"
        "        failpoint(FP_C, path=claimed)\n"     # seam first...
        "        self._fence_ok(rec, 'late')\n"       # ...guard after: FAIL
    )
    got = RULES["fence-gate"].run_fixture(
        {"sm_distributed_tpu/service/x.py": src})
    assert len(got) == 1 and "fence guard" in got[0].message


def test_fence_gate_ignores_scripts_and_storage_layer():
    src = RULES["fence-gate"].fixture_fail[
        "sm_distributed_tpu/service/x.py"]
    assert not RULES["fence-gate"].run_fixture({"scripts/x.py": src})
    assert not RULES["fence-gate"].run_fixture(
        {"sm_distributed_tpu/engine/storage.py": src})


def test_guarded_by_subscript_and_augassign_and_del():
    src = (
        "class C:\n"
        "    _GUARDED_BY = {'_m': '_lock'}\n"
        "    def bad1(self, k):\n"
        "        self._m[k] = 1\n"
        "    def bad2(self):\n"
        "        self._m.update({})\n"
        "    def bad3(self, k):\n"
        "        del self._m[k]\n"
        "    def ok(self, k):\n"
        "        with self._lock:\n"
        "            self._m[k] = 1\n"
    )
    got = RULES["guarded-by"].run_fixture({"sm_distributed_tpu/x.py": src})
    assert sorted(f.anchor.split(".")[-1] for f in got) == \
        ["bad1", "bad2", "bad3"]


def test_guarded_by_wrong_lock_is_a_violation():
    src = (
        "class C:\n"
        "    _GUARDED_BY = {'_m': '_lock'}\n"
        "    def bad(self, k):\n"
        "        with self._other:\n"
        "            self._m[k] = 1\n"
    )
    assert RULES["guarded-by"].run_fixture({"sm_distributed_tpu/x.py": src})


def test_metrics_kind_conflict_and_prefix():
    r = RULES["metrics-conventions"]
    msgs = " | ".join(f.message for f in r.run_fixture(r.fixture_fail))
    assert "naming convention" in msgs
    assert "one name, one kind" in msgs
    assert "not documented" in msgs


def test_failpoint_registry_finds_all_three_failure_modes():
    r = RULES["failpoint-registry"]
    msgs = " | ".join(f.message for f in r.run_fixture(r.fixture_fail))
    assert "dead entry" in msgs
    assert "not documented" in msgs
    assert "no chaos_sweep scenario" in msgs
    assert "does not resolve" in msgs


def test_config_drift_both_directions():
    r = RULES["config-drift"]
    msgs = " | ".join(f.message for f in r.run_fixture(r.fixture_fail))
    assert "missing from" in msgs          # knob absent from template
    assert "not a SMConfig knob" in msgs   # template key absent from config


def test_jit_compile_surface_statics_drift_and_dead_entry():
    src = (
        "import jax\n"
        "from ..analysis.surface import compile_surface\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'score': 'statics=b; buckets=padded',\n"
        "    'ghost': 'statics=none; buckets=nothing calls this',\n"
        "})\n"
        "def score(x, *, b, k):\n"
        "    return x\n"
        "fn = jax.jit(score, static_argnames=('b', 'k'))\n"
    )
    msgs = " | ".join(
        f.message for f in RULES["jit-compile-surface"].run_fixture(
            {"sm_distributed_tpu/ops/x_jax.py": src}))
    assert "statics drift" in msgs
    assert "dead entry" in msgs


def test_jit_compile_surface_policy_grammar():
    # missing buckets= clause fires
    src = (
        "import jax\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'plain': 'statics=none',\n"
        "})\n"
        "def plain(x):\n"
        "    return x\n"
        "fn = jax.jit(plain)\n"
    )
    msgs = " | ".join(
        f.message for f in RULES["jit-compile-surface"].run_fixture(
            {"sm_distributed_tpu/ops/x_jax.py": src}))
    assert "buckets=" in msgs


def test_retrace_hazard_taints_through_locals_and_dict_sinks():
    src = (
        "import jax\n"
        "fn = jax.jit(score, static_argnames=('b',))\n"
        "def go(x):\n"
        "    n = x.shape[0]\n"
        "    statics = dict(b=n)\n"
        "    return fn(x, **statics)\n"
    )
    got = RULES["retrace-hazard"].run_fixture(
        {"sm_distributed_tpu/ops/x_jax.py": src})
    assert len(got) == 1 and "retrace hazard" in got[0].message
    # the same flow through a bucketing helper passes
    ok = src.replace("n = x.shape[0]", "n = band_bucket(x.shape[0])")
    assert not RULES["retrace-hazard"].run_fixture(
        {"sm_distributed_tpu/ops/x_jax.py": ok})


def test_host_sync_empty_reason_is_a_finding():
    src = (
        "import numpy as np\n"
        "def f(out):\n"
        "    # smlint: host-sync-ok[]\n"
        "    return np.asarray(out)\n"
    )
    got = RULES["host-sync"].run_fixture(
        {"sm_distributed_tpu/models/msm_jax.py": src})
    assert len(got) == 1 and "empty" in got[0].message


def test_host_sync_scoped_to_hot_modules():
    src = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
    assert not RULES["host-sync"].run_fixture(
        {"sm_distributed_tpu/engine/storage.py": src})
    assert RULES["host-sync"].run_fixture(
        {"sm_distributed_tpu/ops/x_jax.py": src})


def test_cli_scopes_tests_to_broad_except_only():
    from scripts.smlint import _scope_tests

    res = run_lint(Project(modules={
        "tests/test_x.py": (
            "def f(m):\n"
            "    m.counter('badname_total', 'x').inc()\n"   # conventions
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n"                                 # broad-except
        ),
    }), only={"metrics-conventions", "broad-except"})
    assert {f.rule for f in res.new} == {"metrics-conventions",
                                         "broad-except"}
    scoped = _scope_tests(res)
    assert [f.rule for f in scoped.new] == ["broad-except"]


# -------------------------------------------------------------- framework
def test_inline_ignore_suppresses_only_that_rule():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # smlint: ignore[broad-except]\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    proj = Project(modules={"sm_distributed_tpu/x.py": src})
    res = run_lint(proj, only={"broad-except"})
    assert len(res.new) == 1 and res.new[0].line == 8


def test_baseline_matches_by_anchor_and_reports_unused():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    proj = Project(modules={"sm_distributed_tpu/x.py": src})
    baseline = [
        {"rule": "broad-except", "path": "sm_distributed_tpu/x.py",
         "anchor": "f", "justification": "test"},
        {"rule": "broad-except", "path": "sm_distributed_tpu/x.py",
         "anchor": "gone_function", "justification": "stale"},
    ]
    res = run_lint(proj, baseline, only={"broad-except"})
    assert not res.new and len(res.suppressed) == 1
    assert [e["anchor"] for e in res.unused_suppressions] == ["gone_function"]
    errs = self_check(proj, baseline)
    assert any("gone_function" in e for e in errs)


def test_anchor_stable_under_line_drift():
    body = (
        "class C:\n"
        "    def f(self):\n"
        "        try:\n"
        "            g()\n"
        "        except Exception:\n"
        "            pass\n"
    )
    a1 = run_lint(Project(modules={"sm_distributed_tpu/x.py": body}),
                  only={"broad-except"}).new[0]
    a2 = run_lint(Project(
        modules={"sm_distributed_tpu/x.py": "import os\n\n" + body}),
        only={"broad-except"}).new[0]
    assert a1.anchor == a2.anchor == "C.f"
    assert a1.line != a2.line              # the line moved; the key did not


def test_baseline_rejects_entries_without_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"rule": "x", "path": "y", "anchor": "z"}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(p)


def test_syntax_error_is_a_parse_finding():
    proj = Project(modules={"sm_distributed_tpu/x.py": "def broken(:\n"})
    res = run_lint(proj, only=set())
    assert [f.rule for f in res.new] == ["parse-error"]


# ------------------------------------------------------------- whole repo
def test_repo_is_clean_against_committed_baseline():
    """The acceptance gate, in-process: zero NEW findings over the tree,
    and the committed baseline is minimal (every suppression matches)."""
    proj = Project.load(REPO_ROOT, ["sm_distributed_tpu", "scripts",
                                    "bench.py"])
    baseline = load_baseline(REPO_ROOT / "conf" / "smlint_baseline.json")
    res = run_lint(proj, baseline)
    assert not res.new, "\n".join(f.render() for f in res.new)
    assert not res.unused_suppressions, res.unused_suppressions
    # every committed suppression is a justified one
    assert all(len(e["justification"]) > 40 for e in baseline)


def test_cli_json_summary(tmp_path, capsys):
    from scripts.smlint import main

    rc = main(["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["sm_analysis_new_findings_total"] == {}
    # the committed fence-gate exemptions are visible as history, not muted
    assert out["sm_analysis_findings_total"].get("fence-gate", 0) >= 1
    assert out["files"] > 50


def test_cli_self_check_passes():
    from scripts.smlint import main

    assert main(["--self-check"]) == 0
