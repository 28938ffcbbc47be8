"""Analysis CLI contract tests: exit codes, output formats, and the
``--update-baseline`` round-trip (update → clean run → stale rejection).

These drive ``repro.analysis.cli.main`` in-process. The AST-only paths stay
jax-free (millisecond runs); the two jaxpr-tier tests use a tiny/empty
registry file so they pay jax import but no real tracing.
"""
import json
import os
import textwrap

import pytest

from repro.analysis.cli import main as cli_main
from repro.analysis.engine import BASELINE_NAME

BAD_SRC = textwrap.dedent("""\
    import jax.numpy as jnp

    def demote(x):
        return x.astype(jnp.complex64)   # JL001: literal narrowing cast
""")


@pytest.fixture()
def tmp_repo(tmp_path):
    """A minimal repo root: pyproject.toml marker + src/ with one finding."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='t'\n")
    src = tmp_path / "src"
    src.mkdir()
    (src / "m.py").write_text(BAD_SRC)
    return tmp_path


def _run(tmp_repo, *argv):
    return cli_main(["--root", str(tmp_repo), *argv])


# ---------------------------------------------------------------- exit codes


def test_findings_exit_1(tmp_repo):
    assert _run(tmp_repo, "--baseline", "none") == 1


def test_clean_repo_exits_0(tmp_repo):
    (tmp_repo / "src" / "m.py").write_text("x = 1\n")
    assert _run(tmp_repo, "--baseline", "none") == 0


def test_unknown_rule_id_is_a_usage_error(tmp_repo):
    with pytest.raises(SystemExit) as e:
        _run(tmp_repo, "--rules", "JL999")
    assert e.value.code == 2


def test_rule_filter_crossing_tiers_is_a_usage_error(tmp_repo):
    # a JX-only filter with --tier ast selects nothing runnable
    with pytest.raises(SystemExit) as e:
        _run(tmp_repo, "--tier", "ast", "--rules", "JX104")
    assert e.value.code == 2


def test_rule_filter_limits_findings(tmp_repo):
    # JL002 never fires on the JL001 fixture source
    assert _run(tmp_repo, "--baseline", "none", "--rules", "JL002") == 0
    assert _run(tmp_repo, "--baseline", "none", "--rules", "JL001,JL002") == 1


# ------------------------------------------------------------------ formats


def test_github_format_emits_error_annotations(tmp_repo, capsys):
    assert _run(tmp_repo, "--baseline", "none", "--format", "github") == 1
    out = capsys.readouterr().out
    assert "::error file=src/m.py,line=4,title=JL001::" in out


def test_json_format_structure(tmp_repo, capsys):
    _run(tmp_repo, "--baseline", "none", "--format", "json")
    data = json.loads(capsys.readouterr().out)
    assert data["tiers"] == ["jaxlint"]
    assert data["findings"] and data["findings"][0]["rule"] == "JL001"
    assert data["stale_baseline_entries"] == []


# --------------------------------------------------- baseline round-trip


def test_update_baseline_round_trip_then_stale_rejection(tmp_repo, capsys):
    bl = tmp_repo / BASELINE_NAME
    # 1. update: findings land in the baseline with a placeholder reason
    assert _run(tmp_repo, "--update-baseline") == 0
    entries = json.loads(bl.read_text())["entries"]
    assert len(entries) == 1 and entries[0]["rule"] == "JL001"
    assert "TODO" in entries[0]["reason"]
    # 2. clean run: the same finding is now suppressed
    assert _run(tmp_repo) == 0
    # 3. justified reasons survive a re-update
    entries[0]["reason"] = "vetted: fixture demotion is the point here"
    bl.write_text(json.dumps({"version": 1, "entries": entries}, indent=2))
    assert _run(tmp_repo, "--update-baseline") == 0
    kept = json.loads(bl.read_text())["entries"]
    assert kept[0]["reason"].startswith("vetted:")
    # 4. the flagged code changes -> the entry is stale -> blocking rejection
    (tmp_repo / "src" / "m.py").write_text("x = 1\n")
    capsys.readouterr()
    assert _run(tmp_repo) == 1
    assert "stale baseline entry" in capsys.readouterr().out


def test_update_baseline_preserves_other_tiers_entries(tmp_repo):
    bl = tmp_repo / BASELINE_NAME
    jx_entry = {"rule": "JX104", "path": "src/other.py",
                "snippet": "while_loop(...)",
                "reason": "vetted: schema uniformity"}
    bl.write_text(json.dumps({"version": 1, "entries": [jx_entry]}, indent=2))
    # an AST-tier update must not drop the jaxpr tier's vetted entries
    assert _run(tmp_repo, "--update-baseline", "--tier", "ast") == 0
    entries = json.loads(bl.read_text())["entries"]
    rules = sorted(e["rule"] for e in entries)
    assert rules == ["JL001", "JX104"]
    assert [e for e in entries if e["rule"] == "JX104"][0] == jx_entry


def test_stale_check_skipped_for_explicit_paths(tmp_repo):
    bl = tmp_repo / BASELINE_NAME
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "JL001", "path": "src/gone.py", "snippet": "nope",
         "reason": "vetted: entry for a file being linted elsewhere"}]},
        indent=2))
    (tmp_repo / "src" / "m.py").write_text("x = 1\n")
    # naming a path narrows the run — staleness is only judged on full runs
    assert _run(tmp_repo, "src/m.py") == 0
    assert _run(tmp_repo) == 1


# ------------------------------------------------------------- jaxpr tier


def test_jaxpr_budget_blows_on_tiny_budget(tmp_repo, capsys):
    reg = tmp_repo / "empty_registry.py"
    reg.write_text("ENTRIES = []\n")
    assert _run(tmp_repo, "--tier", "jaxpr", "--registry", str(reg),
                "--baseline", "none") == 0
    assert _run(tmp_repo, "--tier", "jaxpr", "--registry", str(reg),
                "--baseline", "none", "--budget", "0.0000001") == 1
    assert "BUDGET EXCEEDED" in capsys.readouterr().out


def test_list_entries_prints_registry(capsys):
    assert cli_main(["--list-entries"]) == 0
    out = capsys.readouterr().out
    assert "qniht.packed.per_tensor" in out
    assert "batch_server.chunk_fn" in out
