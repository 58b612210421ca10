"""Tier-1 gate + unit tests for the repro.analysis static-analysis pass.

Three layers:

* fixture tests — every rule fires on its bad fixture (exact lines,
  marked ``# FIRE``) and stays quiet on the good one; stripping the
  ``# repro: ignore[...]`` comments resurfaces exactly the suppressed
  findings.
* framework tests — suppressions, baselines, path normalization, CLI.
* the gate — the full pass over ``src/repro`` must report zero
  non-baselined findings, and stay fast enough to run in tier-1.
"""

import io
import json
import pathlib
import re
import time

import pytest

from repro.analysis import (Finding, all_checkers, analyze_paths,
                            analyze_source, load_baseline, module_path,
                            split_baselined, write_baseline)
from repro.analysis.cli import main

HERE = pathlib.Path(__file__).resolve().parent
FIXTURES = HERE / "analysis_fixtures"
REPO = HERE.parent
SRC_REPRO = REPO / "src" / "repro"
BASELINE = REPO / "analysis_baseline.json"

#: rule -> (bad fixture, good fixture, virtual module path)
CASES = {
    "RPA001": ("rpa001_bad.py", "rpa001_good.py",
               "repro/core/codecs_fixture.py"),
    "RPA002": ("rpa002_bad.py", "rpa002_good.py",
               "repro/shard/service_fixture.py"),
    "RPA003": ("rpa003_bad.py", "rpa003_good.py",
               "repro/core/container.py"),
    "RPA004": ("rpa004_bad.py", "rpa004_good.py",
               "repro/ann/pack_fixture.py"),
    "RPA005": ("rpa005_bad.py", "rpa005_good.py",
               "repro/kernels/fixture.py"),
    "RPA006": ("rpa006_bad.py", "rpa006_good.py",
               "repro/shard/router_fixture.py"),
}


def _read(name):
    return (FIXTURES / name).read_text()


def _fire_lines(source):
    return [i for i, line in enumerate(source.splitlines(), 1)
            if "# FIRE" in line]


# ---------------------------------------------------------------------------
# per-rule fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_fires_on_bad_fixture(rule):
    bad, _, vpath = CASES[rule]
    source = _read(bad)
    findings = analyze_source(source, vpath, rules=[rule])
    assert [f.line for f in findings] == _fire_lines(source)
    assert {f.rule for f in findings} == {rule}
    assert all(f.path == vpath for f in findings)


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_quiet_on_good_fixture(rule):
    _, good, vpath = CASES[rule]
    findings = analyze_source(_read(good), vpath, rules=[rule])
    assert findings == []


@pytest.mark.parametrize("rule", sorted(CASES))
def test_suppression_comment_suppresses(rule):
    """Stripping `# repro: ignore[...]` resurfaces exactly those lines."""
    bad, _, vpath = CASES[rule]
    source = _read(bad)
    suppressed_lines = [i for i, line in enumerate(source.splitlines(), 1)
                        if "repro: ignore" in line]
    assert suppressed_lines, f"{bad} must exercise suppression"
    stripped = re.sub(r"#\s*repro:\s*ignore\[[^\]]*\]", "", source)
    before = {f.line for f in analyze_source(source, vpath, rules=[rule])}
    after = {f.line for f in analyze_source(stripped, vpath, rules=[rule])}
    assert after - before == set(suppressed_lines)


def test_bare_ignore_suppresses_every_rule():
    src = "def route(ix):\n    return hasattr(ix, 'ivf')  # repro: ignore\n"
    assert analyze_source(src, "repro/api/fixture.py", rules=["RPA001"]) == []


# ---------------------------------------------------------------------------
# rule scoping
# ---------------------------------------------------------------------------

def test_rpa003_scopes_to_writer_functions():
    src = ("import uuid\n"
           "def pack_header(m):\n    return uuid.uuid4()\n"
           "def unrelated(m):\n    return uuid.uuid4()\n")
    findings = analyze_source(src, "repro/ann/other.py", rules=["RPA003"])
    assert [f.line for f in findings] == [3]   # only inside pack_header


def test_rpa005_only_applies_under_kernels_and_scan():
    src = "import jax\n@jax.jit\ndef f(x):\n    return float(x[0])\n"
    hot = analyze_source(src, "repro/kernels/x.py", rules=["RPA005"])
    cold = analyze_source(src, "repro/serve/x.py", rules=["RPA005"])
    assert [f.line for f in hot] == [4]
    assert cold == []


def test_rpa001_hasattr_only_on_hot_paths():
    src = "def f(ix):\n    return hasattr(ix, 'ivf')\n"
    hot = analyze_source(src, "repro/serve/x.py", rules=["RPA001"])
    cold = analyze_source(src, "repro/data/x.py", rules=["RPA001"])
    assert [f.line for f in hot] == [2]
    assert cold == []


# ---------------------------------------------------------------------------
# RPA007 — spec-grammar / docs drift (tmp-tree fixtures: the rule reads
# docs/architecture.md relative to the analyzed file)
# ---------------------------------------------------------------------------

_GRAMMAR_DOC = """# Architecture

```text spec-grammar
spec := struct ("," key "=" value)*

{keys}
```
"""


def _spec_tree(tmp_path, code_keys, doc_keys=None, doc=True,
               keys_line="KNOWN_OPTION_KEYS = ({keys},)"):
    """tmp/src/repro/api/spec.py + tmp/docs/architecture.md; returns the
    spec path to analyze."""
    spec = tmp_path / "src" / "repro" / "api" / "spec.py"
    spec.parent.mkdir(parents=True)
    keys = ", ".join(repr(k) for k in code_keys)
    spec.write_text('"""fixture."""\n' + keys_line.format(keys=keys) + "\n")
    if doc:
        doc_path = tmp_path / "docs" / "architecture.md"
        doc_path.parent.mkdir()
        lines = "\n".join(f"{k} = <value>" for k in (doc_keys or []))
        doc_path.write_text(_GRAMMAR_DOC.format(keys=lines))
    return str(spec)


def _rpa007(path):
    from repro.analysis import analyze_file

    return analyze_file(path, rules=["RPA007"])


def test_rpa007_in_sync_is_quiet(tmp_path):
    keys = ["ids", "engine"]
    assert _rpa007(_spec_tree(tmp_path, keys, keys)) == []


def test_rpa007_parsed_but_undocumented(tmp_path):
    f = _rpa007(_spec_tree(tmp_path, ["ids", "engine"], ["ids"]))
    assert len(f) == 1 and "'engine'" in f[0].message
    assert "missing from the spec-grammar" in f[0].message


def test_rpa007_documented_but_not_parsed(tmp_path):
    f = _rpa007(_spec_tree(tmp_path, ["ids"], ["ids", "bogus"]))
    assert len(f) == 1 and "'bogus'" in f[0].message
    assert "not parsed" in f[0].message


def test_rpa007_missing_grammar_block(tmp_path):
    spec = _spec_tree(tmp_path, ["ids"], doc=False)
    doc = tmp_path / "docs" / "architecture.md"
    doc.parent.mkdir()
    doc.write_text("# Architecture\n\nno fenced grammar here\n")
    f = _rpa007(spec)
    assert len(f) == 1 and "spec-grammar fenced block" in f[0].message


def test_rpa007_missing_doc_file(tmp_path):
    f = _rpa007(_spec_tree(tmp_path, ["ids"], doc=False))
    assert len(f) == 1 and "cannot locate" in f[0].message


def test_rpa007_keys_must_be_literal_tuple(tmp_path):
    spec = _spec_tree(tmp_path, ["ids"], ["ids"],
                      keys_line="KNOWN_OPTION_KEYS = tuple({keys},)")
    f = _rpa007(spec)
    assert len(f) == 1 and "module-level tuple" in f[0].message


def test_rpa007_scoped_to_spec_module(tmp_path):
    other = tmp_path / "src" / "repro" / "api" / "other.py"
    other.parent.mkdir(parents=True)
    other.write_text("KNOWN_OPTION_KEYS = ('ids',)\n")
    assert _rpa007(str(other)) == []


def test_rpa007_real_repo_in_sync():
    # the committed grammar block in docs/architecture.md matches what
    # parse_spec accepts — the live version of the drift the rule guards
    from repro.analysis import analyze_file
    from repro.api.spec import KNOWN_OPTION_KEYS, parse_spec

    spec_py = SRC_REPRO / "api" / "spec.py"
    assert analyze_file(str(spec_py), rules=["RPA007"]) == []
    # KNOWN_OPTION_KEYS is itself in sync with the parser
    for key in KNOWN_OPTION_KEYS:
        with pytest.raises(ValueError) as e:
            parse_spec(f"IVF8,{key}=@@bad@@")
        assert "unknown spec option" not in str(e.value)
    with pytest.raises(ValueError, match="unknown spec option"):
        parse_spec("IVF8,nope=1")


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

def test_finding_str_and_fingerprint():
    f = Finding(path="repro/a.py", line=3, rule="RPA001", message="m")
    assert str(f) == "repro/a.py:3: RPA001: m"
    assert f.fingerprint == "repro/a.py::RPA001::m"
    assert f.to_dict()["line"] == 3


def test_module_path_normalization():
    assert module_path("/x/y/src/repro/ann/scan.py") == "repro/ann/scan.py"
    assert module_path("repro/core/codecs.py") == "repro/core/codecs.py"
    assert module_path("./tests/foo.py") == "tests/foo.py"


def test_syntax_error_becomes_rpa000():
    findings = analyze_source("def broken(:\n", "repro/x.py")
    assert len(findings) == 1
    assert findings[0].rule == "RPA000"


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="RPA999"):
        analyze_source("x = 1\n", "repro/x.py", rules=["RPA999"])


def test_registry_has_all_rules():
    rules = {c.rule for c in all_checkers()}
    # RPA007 checks against a docs artifact, so its fixtures are tmp
    # trees (below) rather than CASES entries
    assert rules == set(CASES) | {"RPA007"}


def test_baseline_round_trip(tmp_path):
    f1 = Finding(path="repro/a.py", line=3, rule="RPA001", message="m1")
    f2 = Finding(path="repro/b.py", line=9, rule="RPA006", message="m2")
    path = tmp_path / "base.json"
    write_baseline(str(path), [f1, f2, f1])          # dedup on write
    base = load_baseline(str(path))
    assert base == {f1.fingerprint, f2.fingerprint}
    # fingerprints are line-independent: a drifted copy still matches
    drifted = Finding(path="repro/a.py", line=99, rule="RPA001",
                      message="m1")
    new, old = split_baselined([drifted, f2], base)
    assert new == [] and len(old) == 2
    assert load_baseline(str(tmp_path / "missing.json")) == frozenset()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_flags_bad_fixture_and_json(tmp_path):
    bad = FIXTURES / "rpa004_bad.py"
    out = io.StringIO()
    rc = main([str(bad), "--rules", "RPA004", "--format", "json",
               "--baseline", str(tmp_path / "none.json")], out=out)
    assert rc == 1
    payload = json.loads(out.getvalue())
    assert {e["rule"] for e in payload["findings"]} == {"RPA004"}
    assert payload["baselined"] == []


def test_cli_baseline_workflow(tmp_path):
    bad = FIXTURES / "rpa004_bad.py"
    base = tmp_path / "base.json"
    out = io.StringIO()
    rc = main([str(bad), "--rules", "RPA004", "--write-baseline",
               "--baseline", str(base)], out=out)
    assert rc == 0 and base.exists()
    rc = main([str(bad), "--rules", "RPA004", "--baseline", str(base)],
              out=out)
    assert rc == 0          # everything grandfathered
    rc = main([str(bad), "--rules", "RPA004",
               "--baseline", str(tmp_path / "empty.json")], out=out)
    assert rc == 1


def test_cli_list_rules():
    out = io.StringIO()
    assert main(["--list-rules"], out=out) == 0
    assert "RPA001" in out.getvalue()


# ---------------------------------------------------------------------------
# the tier-1 gate: full pass over src/repro
# ---------------------------------------------------------------------------

def test_full_repo_pass_is_clean_and_fast():
    t0 = time.perf_counter()
    findings = analyze_paths([str(SRC_REPRO)])
    elapsed = time.perf_counter() - t0
    baseline = load_baseline(str(BASELINE) if BASELINE.exists() else None)
    new, _ = split_baselined(findings, baseline)
    assert new == [], "new static-analysis findings:\n" + "\n".join(
        str(f) for f in new)
    # lint must stay cheap enough to live in tier-1 (ISSUE 9: ~5s budget)
    assert elapsed < 5.0, f"full-repo analysis took {elapsed:.2f}s"


def test_committed_baseline_is_minimal():
    # the committed baseline grandfathers nothing: findings got fixed,
    # not buried (ISSUE 9 acceptance criterion)
    assert BASELINE.exists()
    data = json.loads(BASELINE.read_text())
    assert data["findings"] == []
