"""jaxpr-tier driver: trace the registry, run IR rules, emit Findings.

Findings reuse the AST tier's :class:`~repro.analysis.findings.Finding` and
its suppression machinery unchanged. Identity works the same way — keyed on
``(rule, path, snippet)`` — with one twist: an issue that carries a concrete
trace site (``source_info`` of the offending eqn) anchors at that file/line
with the stripped source line as snippet, exactly like an AST finding, so
inline ``# jaxlint: allow=JX...`` pragmas work at the real site. Issues
without a site (contract violations, weak outputs, baked consts) anchor at
the entry point's ``def`` line with a ``"<entry> :: <detail>"`` snippet that
is stable across unrelated edits.
"""
from __future__ import annotations

import os

from repro.analysis.engine import Report, find_repo_root
from repro.analysis.findings import Baseline, Finding, pragma_suppresses
from repro.analysis.jaxpr import rules as _jx
from repro.analysis.jaxpr.registry import (EntryPoint, OperatorSpec,
                                           TraceSpec, build_registry)

_HINTS = {
    "JX101": "keep the iteration algebra in the operator dtype; narrow only "
             "at explicit quantization points (repro.quant), or pragma at "
             "the converting line with why the demotion is intended",
    "JX102": "return strongly-typed arrays (jnp.asarray(..., dtype=...)); "
             "hoist shape-dependent Python branches into static dispatch "
             "documented as separate compile units",
    "JX104": "move the callback/transfer outside the loop and batch it, or "
             "pragma with why a per-iteration host hop is unavoidable",
    "JX105": "thread the array through the entry's signature so it ships as "
             "an argument, not a compile-time constant",
    "JX106": "make mv/rmv shapes and dtypes mutually dual (see "
             "docs/operator-protocol semantics in core/operators.py); the "
             "solver's adjoint identity depends on it",
}


def _trace_entry(spec: TraceSpec):
    import jax

    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    alt = jax.make_jaxpr(spec.fn)(*spec.alt_args) if spec.alt_args else None
    return closed, alt


def _issues_for(entry: EntryPoint, spec, rule_ids):
    def want(rid):
        return rule_ids is None or rid in rule_ids

    issues = []
    if isinstance(spec, TraceSpec):
        closed, alt = _trace_entry(spec)
        for rid, rule in _jx.IR_RULES.items():
            if want(rid):
                issues += rule(entry.name, closed)
        if want("JX102"):
            issues += _jx.check_jx102_recompile(entry.name, closed, alt)
    elif isinstance(spec, OperatorSpec):
        import jax
        import numpy as np

        for i, op in enumerate(spec.ops):
            sub = entry.name if len(spec.ops) == 1 else f"{entry.name}[{i}]"
            if want("JX106"):
                issues += _jx.check_jx106_adjoint_contract(sub, op)
            if spec.trace_mv:
                try:
                    n = op.shape[1]
                    dt = np.dtype(op.dtype)
                    closed = jax.make_jaxpr(op.mv)(
                        jax.ShapeDtypeStruct((n,), dt))
                    for rid, rule in _jx.IR_RULES.items():
                        if want(rid):
                            issues += rule(f"{sub}.mv", closed)
                except Exception:  # noqa: BLE001 - JX106 already reported it
                    pass
    else:  # pragma: no cover - registry bug
        raise TypeError(f"entry {entry.name}: unknown spec {type(spec)}")
    return issues


def _finding_from(issue: _jx.Issue, anchor, root, src_cache) -> Finding:
    path, line = anchor
    snippet = issue.detail
    if issue.site is not None:
        site_file, site_line = issue.site
        # only re-anchor at sites inside the repo — an eqn traced from jax
        # internals stays attributed to the registry entry
        if os.path.isfile(site_file) and \
                os.path.abspath(site_file).startswith(root + os.sep):
            path, line = site_file, site_line
            lines = _source_lines(site_file, src_cache)
            if 1 <= line <= len(lines):
                snippet = lines[line - 1].strip()
    rel = os.path.relpath(os.path.abspath(path), root).replace(os.sep, "/")
    return Finding(rule=issue.rule, path=rel, line=line,
                   message=issue.message, hint=_HINTS[issue.rule],
                   snippet=snippet)


def _source_lines(abspath, cache):
    if abspath not in cache:
        try:
            with open(abspath, encoding="utf-8") as f:
                cache[abspath] = f.read().splitlines()
        except OSError:
            cache[abspath] = []
    return cache[abspath]


def run_jaxpr_tier(root=None, registry=None, baseline=None,
                   rule_ids=None, respect_pragmas=True) -> Report:
    """Trace every registry entry and run the JX rules. Returns the same
    :class:`Report` shape as the AST tier (``files`` counts entries traced;
    an entry whose trace itself crashes lands in ``parse_errors``)."""
    root = find_repo_root(root)
    bl = Baseline()
    if baseline != "none":
        from repro.analysis.engine import BASELINE_NAME

        bl_path = baseline or os.path.join(root, BASELINE_NAME)
        if os.path.isfile(bl_path):
            bl = Baseline.load(bl_path)

    entries = registry if registry is not None else build_registry()
    findings, suppressed, parse_errors = [], [], []
    src_cache: dict = {}
    seen_keys = set()
    for entry in entries:
        try:
            spec = entry.make()
            issues = _issues_for(entry, spec, rule_ids)
        except Exception as e:  # noqa: BLE001 - a crashing trace must fail CI
            parse_errors.append(
                (entry.name, f"{entry.name}: trace failed: "
                             f"{type(e).__name__}: {e}"))
            continue
        for issue in issues:
            f = _finding_from(issue, spec.anchor, root, src_cache)
            key = (f.rule, f.path, f.snippet)
            if key in seen_keys:
                continue  # same site reached via several registry entries
            seen_keys.add(key)
            lines = _source_lines(os.path.join(root, f.path), src_cache)
            if respect_pragmas and pragma_suppresses(lines, f):
                suppressed.append((f, "pragma"))
            elif bl.matches(f):
                suppressed.append((f, "baseline"))
            else:
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(findings=findings, suppressed=suppressed,
                  files=len(entries), parse_errors=parse_errors)


def load_registry_file(path) -> list:
    """Load a registry module by file path; it must define ``ENTRIES``.

    This is how CI proves the tier still bites: a fixtures module of
    deliberately broken entries must keep producing findings.
    """
    import importlib.util

    spec = importlib.util.spec_from_file_location("jaxpr_fixture_registry",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return list(mod.ENTRIES)
