"""The `python -m paddle_tpu.analysis` entry: lint the package (or
given paths) with graftlint + locklint against the committed baseline.

Baseline contract (`analysis/baseline.json`): findings the repo
ACCEPTS, each with a one-line justification. Keys are
(rule, path, func) with a count — never line numbers, so unrelated
edits don't churn the file. `--check` fails (exit 1) on any finding
not covered by the baseline; a stale baseline entry (code fixed,
entry left behind) is a warning, and `--update-baseline` rewrites
the file from the current findings, preserving reasons for keys
that survive.

Usage:
    python -m paddle_tpu.analysis              # report all findings
    python -m paddle_tpu.analysis --check      # CI gate: unbaselined -> exit 1
    python -m paddle_tpu.analysis --update-baseline --reason "..."
    python -m paddle_tpu.analysis path/to/file.py --rules GL001,GL004
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from paddle_tpu.analysis import graftlint, locklint
from paddle_tpu.analysis.graftlint import Finding, RULES, lint_source
from paddle_tpu.analysis.locklint import (lint_lock_graph,
                                          lint_locks_source,
                                          scan_module)

#: rules owned by the locklint pass (LK002 additionally needs the
#: cross-module graph — see collect_findings)
_LK_RULES = tuple(r for r in RULES if r.startswith("LK"))

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)
DEFAULT_BASELINE = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "baseline.json")

Key = Tuple[str, str, str]


def _default_paths() -> List[str]:
    """The whole repo: the package plus every sibling python tree
    (tests included — discipline is repo-wide; a sloppy test is how
    the next engineer learns the sloppy idiom)."""
    out = [_PKG_ROOT]
    for name in ("tests", "examples", "benchmarks", "scripts"):
        p = os.path.join(_REPO_ROOT, name)
        if os.path.exists(p):
            out.append(p)
    return out


def _iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def _rel(path: str) -> str:
    """Repo-relative forward-slash path — the baseline's path key must
    be stable across machines and cwd."""
    ap = os.path.abspath(path)
    if ap.startswith(_REPO_ROOT + os.sep):
        ap = ap[len(_REPO_ROOT) + 1:]
    return ap.replace(os.sep, "/")


def collect_findings(paths: Sequence[str],
                     rules: Optional[Sequence[str]] = None,
                     locklint: bool = True) -> List[Finding]:
    """graftlint + locklint over every .py under `paths`, with
    repo-relative paths (baseline-key form)."""
    findings: List[Finding] = []
    lk_on = locklint and (
        rules is None or any(r in rules for r in _LK_RULES))
    lk_scans = []
    for f in _iter_py_files(paths):
        rel = _rel(f)
        try:
            with open(f, encoding="utf-8") as fh:
                src = fh.read()
        except OSError:
            continue
        findings.extend(lint_source(src, rel, rules=rules))
        if lk_on:
            # ONE parse+scan per file, shared by the per-file LK
            # rules and the project-wide LK002 graph pass
            scan = scan_module(src, rel)
            findings.extend(lint_locks_source(src, rel, rules=rules,
                                              scan=scan))
            if rules is None or "LK002" in rules:
                lk_scans.append(scan)
    # LK002 runs over ALL scanned files at once: a lock-order cycle
    # closing across modules only exists in the merged graph
    if lk_on and lk_scans:
        findings.extend(lint_lock_graph(scans=lk_scans))
    findings.sort(key=lambda x: (x.path, x.line, x.col, x.rule))
    return findings


# -- baseline -------------------------------------------------------------


def load_baseline(path: str) -> Dict[Key, dict]:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    out: Dict[Key, dict] = {}
    for e in data.get("entries", []):
        out[(e["rule"], e["path"], e["func"])] = e
    return out


def save_baseline(path: str, entries: List[dict]) -> None:
    data = {
        "_comment": (
            "graftlint/locklint accepted findings. Keyed by "
            "(rule, path, func) + count — line-number free, so "
            "unrelated edits don't churn this file. Every entry "
            "needs a one-line `reason`. Regenerate with "
            "`python -m paddle_tpu.analysis --update-baseline` "
            "(reasons for surviving keys are preserved). See "
            "docs/ANALYSIS.md."),
        "version": 1,
        "entries": sorted(
            entries,
            key=lambda e: (e["path"], e["func"], e["rule"])),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")


def apply_baseline(findings: Sequence[Finding],
                   baseline: Dict[Key, dict],
                   scope_paths: Optional[Sequence[str]] = None,
                   scope_rules: Optional[Sequence[str]] = None,
                   ) -> Tuple[List[Finding], List[Key]]:
    """(unbaselined findings, stale baseline keys). A baseline entry
    covers up to `count` findings of its key; extras are
    unbaselined. Stale detection only considers entries inside the
    linted scope (files actually scanned, rules actually run) — a
    path- or rule-restricted invocation must not declare the rest of
    the baseline dead."""
    grouped: Dict[Key, List[Finding]] = collections.defaultdict(list)
    for fd in findings:
        grouped[fd.key()].append(fd)
    unbaselined: List[Finding] = []
    for key, fds in grouped.items():
        allowed = baseline.get(key, {}).get("count", 0)
        if len(fds) > allowed:
            unbaselined.extend(
                sorted(fds, key=lambda x: x.line)[allowed:])
    in_scope = lambda k: (
        (scope_paths is None or k[1] in scope_paths)
        and (scope_rules is None or k[0] in scope_rules))
    stale = [k for k in baseline
             if k not in grouped and in_scope(k)]
    unbaselined.sort(key=lambda x: (x.path, x.line, x.col, x.rule))
    return unbaselined, sorted(stale)


def make_baseline_entries(findings: Sequence[Finding],
                          old: Dict[Key, dict],
                          default_reason: str) -> List[dict]:
    grouped: Dict[Key, List[Finding]] = collections.defaultdict(list)
    for fd in findings:
        grouped[fd.key()].append(fd)
    entries = []
    for (rule, path, func), fds in grouped.items():
        reason = old.get((rule, path, func), {}).get(
            "reason", default_reason)
        entries.append({
            "rule": rule, "path": path, "func": func,
            "count": len(fds), "reason": reason,
            "message": fds[0].message,
        })
    return entries


# -- CLI ------------------------------------------------------------------


def run_cli(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="graftlint + locklint: trace-safety, recompile "
                    "discipline and lock discipline "
                    "(docs/ANALYSIS.md)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the paddle_tpu "
                        "package)")
    p.add_argument("--check", action="store_true",
                   help="CI gate: exit 1 on any finding not covered "
                        "by the baseline")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline json (default: "
                        "paddle_tpu/analysis/baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline (report everything)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from current findings "
                        "(reasons preserved for surviving keys)")
    p.add_argument("--reason", default="TODO: justify",
                   help="reason recorded for NEW entries with "
                        "--update-baseline")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids to run "
                        f"(default: all of {', '.join(RULES)})")
    p.add_argument("--no-locklint", action="store_true",
                   help="skip the LK001-LK005 lock-discipline pass")
    p.add_argument("--explain", default=None, metavar="ID",
                   help="print the rule's catalog entry (bad/good "
                        "example) and exit — so disables stop citing "
                        "rules by number only")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(argv)

    if args.explain is not None:
        rid = args.explain.upper()
        catalog = {**graftlint.CATALOG, **locklint.CATALOG}
        if rid not in catalog:
            p.error(f"unknown rule {args.explain!r}; valid: "
                    f"{', '.join(sorted(catalog))}")
        print(f"{rid} — {catalog[rid]}")
        return 0

    rules = args.rules.split(",") if args.rules else None
    if rules:
        unknown = [r for r in rules if r not in RULES]
        if unknown:
            p.error(f"unknown rules {unknown}; valid: "
                    f"{', '.join(RULES)}")
    paths = args.paths or _default_paths()
    findings = collect_findings(paths, rules=rules,
                                locklint=not args.no_locklint)

    if args.update_baseline:
        old = load_baseline(args.baseline)
        entries = make_baseline_entries(findings, old, args.reason)
        save_baseline(args.baseline, entries)
        print(f"baseline: wrote {len(entries)} entries covering "
              f"{len(findings)} findings to {args.baseline}")
        return 0

    baseline = ({} if args.no_baseline
                else load_baseline(args.baseline))
    linted = [_rel(f) for f in _iter_py_files(paths)]
    unbaselined, stale = apply_baseline(
        findings, baseline, scope_paths=linted, scope_rules=rules)

    if args.as_json:
        print(json.dumps({
            "findings": [vars(f) for f in findings],
            "unbaselined": [vars(f) for f in unbaselined],
            "stale_baseline_keys": [list(k) for k in stale],
        }, indent=1))
    else:
        report = unbaselined if (args.check and baseline) else findings
        for fd in report:
            print(fd)
        if stale:
            # prune report, grouped per rule: stale entries are the
            # baseline outliving the code — name what to delete
            by_rule: Dict[str, List[Key]] = collections.defaultdict(
                list)
            for k in stale:
                by_rule[k[0]].append(k)
            print(f"stale baseline entries to prune ({len(stale)} — "
                  f"the findings are gone; run --update-baseline):")
            for rule in sorted(by_rule):
                ks = by_rule[rule]
                print(f"  {rule} ({RULES.get(rule, '?')}): "
                      f"{len(ks)} entr{'y' if len(ks) == 1 else 'ies'}")
                for k in ks:
                    print(f"    - {k[1]} [{k[2]}]")
        n_base = len(findings) - len(unbaselined)
        print(f"graftlint: {len(findings)} finding(s), "
              f"{n_base} baselined, {len(unbaselined)} unbaselined"
              + (f", {len(stale)} stale baseline entr"
                 f"{'y' if len(stale) == 1 else 'ies'}"
                 if stale else ""))
    if args.check:
        return 1 if unbaselined else 0
    return 0
