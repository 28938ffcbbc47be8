"""jaxpr-tier self-tests: per-JX fixture checks, registry coverage, and the
end-to-end "the traced repo is clean against its baseline" contract.

The fixtures in ``tests/jaxlint_fixtures/jaxpr_bad.py`` are a registry of
deliberately broken entries — one per JX rule (two for JX102/JX106's two
sub-checks). Each must keep producing its finding; the full built-in
registry must keep tracing clean. Mirrors tests/test_jaxlint.py for the
AST tier.
"""
import os
import re

import pytest

from repro.analysis.engine import find_repo_root
from repro.analysis.findings import Baseline
from repro.analysis.jaxpr.registry import build_registry
from repro.analysis.jaxpr.rules import JAXPR_RULE_SUMMARIES
from repro.analysis.jaxpr.runner import load_registry_file, run_jaxpr_tier

REPO = find_repo_root(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE_REGISTRY = os.path.join(REPO, "tests", "jaxlint_fixtures",
                                "jaxpr_bad.py")
# an inline JX pragma comment (not a docstring that mentions the syntax)
_JX_PRAGMA = re.compile(r"(?:^|\s)#\s*jaxlint:\s*allow=JX\d")


@pytest.fixture(scope="module")
def fixture_report():
    registry = load_registry_file(FIXTURE_REGISTRY)
    return run_jaxpr_tier(root=REPO, registry=registry, baseline="none")


# ------------------------------------------------------------ fixture bites


def test_fixture_registry_loads_every_rule():
    registry = load_registry_file(FIXTURE_REGISTRY)
    assert len(registry) >= len(JAXPR_RULE_SUMMARIES)


def test_fixture_registry_has_no_trace_crashes(fixture_report):
    # broken CONTRACTS must surface as findings, not analyzer crashes
    assert fixture_report.parse_errors == []


@pytest.mark.parametrize("rule", sorted(JAXPR_RULE_SUMMARIES))
def test_every_jx_rule_fires_on_its_fixture(fixture_report, rule):
    hits = [f for f in fixture_report.findings if f.rule == rule]
    assert hits, f"{rule} no longer fires on its broken fixture entry"
    # ...and on the entry built to trip it, not by accident elsewhere
    tag = rule.lower()
    assert any(tag in f.message or tag in f.snippet.lower() or
               f"fixture.{tag}" in f.snippet or f.line > 0 for f in hits)


def test_jx106_broken_adjoint_demonstrably_fails(fixture_report):
    """Acceptance criterion: the deliberately broken operator fails the
    adjoint-contract check with a shape-duality finding."""
    msgs = [f.message for f in fixture_report.findings if f.rule == "JX106"]
    assert any("rmv" in m and "contract requires" in m for m in msgs), msgs
    assert any("dtype" in m for m in msgs), msgs  # the narrowing-mv operator


def test_site_anchored_findings_point_into_the_fixture(fixture_report):
    sited = [f for f in fixture_report.findings
             if f.rule in ("JX101", "JX104")]
    assert sited
    for f in sited:
        assert f.path == "tests/jaxlint_fixtures/jaxpr_bad.py"
        assert f.line > 1
        assert f.snippet  # stripped source line, AST-tier-compatible identity


# ------------------------------------------------------------ registry shape


def test_registry_names_are_unique_and_cover_the_surfaces():
    names = [e.name for e in build_registry()]
    assert len(names) == len(set(names))
    for required in ("qniht.packed.per_tensor", "qniht.packed.per_block",
                     "qniht_batch.dense.early_exit", "solver_segment.dense",
                     "qmm_fused.batch_canonical", "op.composed.mri",
                     "op.fourier", "batch_server.chunk_fn"):
        assert required in names, f"registry lost {required}"


# ---------------------------------------------------------------- repo e2e


@pytest.fixture(scope="module")
def repo_report():
    return run_jaxpr_tier(root=REPO)


def test_full_registry_traces_clean_against_baseline(repo_report):
    """The blocking CI contract: every entry point traces, and the IR rules
    find nothing unsuppressed."""
    assert repo_report.parse_errors == [], repo_report.parse_errors
    assert repo_report.files == len(build_registry())
    assert repo_report.findings == [], \
        "\n".join(f.format() for f in repo_report.findings)


def test_repo_jx_baseline_entries_are_not_stale(repo_report):
    matched = {(f.rule, f.path, f.snippet)
               for f, how in repo_report.suppressed if how == "baseline"}
    bl = Baseline.load(os.path.join(REPO, ".jaxlint-baseline.json"))
    stale = [e for e in bl.entries if e["rule"].startswith("JX")
             and (e["rule"], e["path"], e["snippet"]) not in matched]
    assert stale == [], f"stale JX baseline entries: {stale}"


def test_known_suppressions_are_exercised(repo_report):
    """Every inline JX pragma under ``src/`` suppresses a live finding: a
    pragma whose finding stopped firing must be removed."""
    pragma_sites = set()
    for dirpath, _, files in os.walk(os.path.join(REPO, "src")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8") as f:
                if any(_JX_PRAGMA.search(line) for line in f):
                    pragma_sites.add(os.path.relpath(path, REPO))
    live = {f.path for f, how in repo_report.suppressed if how == "pragma"}
    assert pragma_sites <= live, f"stale JX pragmas in {pragma_sites - live}"
