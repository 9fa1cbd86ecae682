"""Tests for the repro.analysis static-analysis suite.

Three layers: fixture tests (every rule has >=1 fire and >=1 no-fire case
under ``tests/analysis_fixtures/``), CLI contract tests (exit codes, JSON
mode), and meta-tests (the live tree is clean modulo suppressions, the
protocol symmetry table is two-sided and matches the real engines).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (
    Options,
    all_rules,
    analyze_file,
    analyze_paths,
    analyze_source,
    rules_protocol,
)

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
REPO = Path(__file__).resolve().parents[1]

# fixture file -> the one rule it must fire (and nothing else may fire)
FIRE_CASES = {
    "pl01_fire.py": "PL01",
    "pl01_prefetch_fire.py": "PL01",
    "pl02_fire.py": "PL02",
    "pl03_fire.py": "PL03",
    "pl04_fire.py": "PL04",
    "pl05_fire.py": "PL05",
    "pl05_prefetch_fire.py": "PL05",
    "jx01_fire.py": "JX01",
    "jx02_fire.py": "JX02",
    "jx03_fire.py": "JX03",
    "jx04_fire.py": "JX04",
    "jx05_fire.py": "JX05",
    "pr01_fire.py": "PR01",
    "pr02_fire.py": "PR02",
    "pr03_fire.py": "PR03",
    "pr04_fire.py": "PR04",
}

OK_CASES = [
    "pallas_ok.py",
    "jax_ok.py",
    "protocol_ok.py",
    "noqa_ok.py",
    "fl/vectorized.py",
]


def _rules(findings):
    return {f.rule for f in findings}


@pytest.mark.parametrize("name,rule", sorted(FIRE_CASES.items()))
def test_rule_fires_on_known_bad_fixture(name, rule):
    findings = analyze_file(FIXTURES / name)
    assert rule in _rules(findings), f"{name}: expected {rule} to fire"
    assert _rules(findings) == {rule}, (
        f"{name}: unexpected extra findings {findings}"
    )


@pytest.mark.parametrize("name", OK_CASES)
def test_no_fire_on_known_good_fixture(name):
    findings = analyze_file(FIXTURES / name)
    assert findings == [], f"{name}: expected clean, got {findings}"


def test_every_rule_has_a_fire_fixture():
    assert set(FIRE_CASES.values()) == set(all_rules().keys())


def test_every_pack_has_fire_and_no_fire_coverage():
    packs = {r.pack for r in all_rules().values()}
    assert packs == {"pallas", "jax", "protocol"}
    # each pack's ok twin exists alongside its fire fixtures
    for prefix, ok in [("pl", "pallas_ok.py"), ("jx", "jax_ok.py"), ("pr", "protocol_ok.py")]:
        assert any(n.startswith(prefix) for n in FIRE_CASES)
        assert (FIXTURES / ok).exists()


def test_live_tree_clean_modulo_suppressions():
    findings = analyze_paths(
        [REPO / "src", REPO / "tests", REPO / "benchmarks"]
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_noqa_requires_matching_rule_id():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return int(x)  # repro: noqa[JX02] wrong id does not suppress\n"
    )
    assert _rules(analyze_source("f.py", src)) == {"JX01"}
    src_ok = src.replace("noqa[JX02]", "noqa[JX01]")
    assert analyze_source("f.py", src_ok) == []


def test_select_option_filters_rules():
    findings = analyze_file(FIXTURES / "pl03_fire.py", Options(select={"PL04"}))
    assert findings == []


def test_syntax_error_is_a_finding():
    findings = analyze_source("broken.py", "def f(:\n")
    assert _rules(findings) == {"SYNTAX"}


def test_symmetry_table_is_two_sided():
    sides = rules_protocol.symmetry_is_balanced()
    assert sides["scalar"], "scalar engine has no declared accounting sites"
    assert sides["scalar"] == sides["vectorized"], (
        "every counter family needs a site in BOTH engines: "
        f"{sides}"
    )


def test_symmetry_table_matches_real_engines():
    # the declared files exist and declared functions are present — a rename
    # would silently turn declarations stale without this
    for suffix, funcs in rules_protocol.SYMMETRY.items():
        path = REPO / "src" / "repro" / suffix
        assert path.exists(), f"SYMMETRY references missing file {suffix}"
        text = path.read_text()
        for fn in funcs:
            assert f"def {fn}(" in text, f"{suffix}: declared '{fn}' not found"
    for suffix, fn in rules_protocol.EMITTER_FUNCS.items():
        path = REPO / "src" / "repro" / suffix
        assert path.exists(), f"EMITTER_FUNCS references missing file {suffix}"
        assert f"def {fn}(" in path.read_text(), (
            f"{suffix}: declared emitter '{fn}' not found"
        )


def test_pr04_schema_mirror_matches_live_schema():
    # PR04 carries a hardcoded copy of the telemetry schema so the analyzer
    # stays importable without the telemetry package; this pins the mirror
    from repro.telemetry import schema

    assert rules_protocol.METRIC_FINISH_KEYS == schema.FINISH_KEYS
    assert rules_protocol.METRIC_CHANNELS == schema.CHANNELS


def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
    )


def test_cli_exits_nonzero_on_known_bad_fixture():
    proc = _run_cli(str(FIXTURES / "pl02_fire.py"))
    assert proc.returncode == 1
    assert "PL02" in proc.stdout


def test_cli_exits_zero_on_clean_tree():
    proc = _run_cli("src", "tests", "benchmarks")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_output_is_machine_readable():
    proc = _run_cli(str(FIXTURES / "jx01_fire.py"), "--json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload and payload[0]["rule"] == "JX01"
    assert {"rule", "path", "line", "message"} <= set(payload[0])
