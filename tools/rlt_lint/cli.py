"""rlt-lint CLI: file scoping, baseline semantics, fixture self-test.

Usage (mirrors ``format.sh``'s scoping)::

    python -m tools.rlt_lint             # changed files vs origin/main
    python -m tools.rlt_lint --all       # the whole scanned tree
    python -m tools.rlt_lint --baseline tools/rlt_lint/baseline.json
    python -m tools.rlt_lint --selftest  # fixture matrix (format.sh)
    python -m tools.rlt_lint path.py ... # explicit paths

Baseline semantics: entries are keyed ``(path, rule, stripped source
text)`` with a ``count`` — line numbers drift, the flagged text does
not.  A finding matching an entry is suppressed (up to ``count``
times); an entry whose file was scanned but matched fewer findings
than its count (including none) is stale and reported as RLT000 so
the baseline only ever shrinks — leftover count budget must never
suppress a future same-text finding without review.  The
committed baseline must stay enumerated in docs/STATIC_ANALYSIS.md.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from tools.rlt_lint.core import (
    Config, Finding, check_source, load_env_registry, load_schema_keys,
    repo_config,
)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_BASELINE = os.path.join(
    "tools", "rlt_lint", "baseline.json"
)

#: Scanned universe: the package, tooling, the entry point and examples.
#: Tests are exempt (they deliberately poke invariants), and the
#: fixture corpus is lint-bait by construction.
_SCAN_PREFIXES = ("ray_lightning_tpu/", "tools/", "examples/")
_SCAN_ROOT_FILES = re.compile(r"^__graft_entry__\.py$")
_EXCLUDE_PREFIXES = ("tools/rlt_lint/fixtures/",)


def in_scope(relpath: str) -> bool:
    relpath = relpath.replace(os.sep, "/")
    if any(relpath.startswith(p) for p in _EXCLUDE_PREFIXES):
        return False
    if any(relpath.startswith(p) for p in _SCAN_PREFIXES):
        return relpath.endswith(".py")
    return bool(_SCAN_ROOT_FILES.match(relpath))


def _git_files(all_files: bool, cwd: Optional[str] = None) -> List[str]:
    cwd = cwd or _REPO_ROOT

    def git_lines(*cmd):
        out = subprocess.run(
            ["git", *cmd], capture_output=True, text=True, cwd=cwd
        ).stdout
        return [line for line in out.splitlines() if line.strip()]

    # Untracked files are invisible to both ls-files (default) and
    # diff — without this a brand-new in-scope file ships unlinted and
    # breaks the NEXT committer's run once tracked.
    untracked = git_lines(
        "ls-files", "--others", "--exclude-standard", "*.py"
    )
    if all_files:
        files = git_lines("ls-files", "*.py") + untracked
    else:
        try:
            base = subprocess.run(
                ["git", "merge-base", "HEAD", "origin/main"],
                capture_output=True, text=True, cwd=cwd,
            ).stdout.strip() or "HEAD"
        except OSError:
            base = "HEAD"
        # ACMR: a renamed-and-edited file is still changed (git shows
        # status R under default rename detection; plain ACM drops it).
        files = git_lines(
            "diff", "--name-only", "--diff-filter=ACMR", base,
            "--", "*.py"
        ) + untracked
    seen, out = set(), []
    for f in files:
        if f not in seen:
            seen.add(f)
            out.append(f)
    return out


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> List[Dict]:
    with open(path) as f:
        doc = json.load(f)
    entries = doc.get("entries", [])
    for e in entries:
        for key in ("path", "rule", "text"):
            if key not in e:
                raise ValueError(f"baseline entry missing {key!r}: {e}")
        e.setdefault("count", 1)
    return entries


def apply_baseline(
    findings: List[Finding], entries: List[Dict], scanned: List[str]
) -> Tuple[List[Finding], List[str]]:
    """Returns (unsuppressed findings, stale-entry messages)."""
    budget: Dict[Tuple[str, str, str], int] = {}
    for e in entries:
        key = (e["path"], e["rule"], e["text"])
        budget[key] = budget.get(key, 0) + int(e["count"])
    used: Dict[Tuple[str, str, str], int] = {}
    kept: List[Finding] = []
    for f in findings:
        key = (f.path, f.rule, f.text)
        if used.get(key, 0) < budget.get(key, 0):
            used[key] = used.get(key, 0) + 1
        else:
            kept.append(f)
    stale: List[str] = []
    scanned_set = set(scanned)
    for key, n in budget.items():
        path, rule, text = key
        if path not in scanned_set:
            continue
        u = used.get(key, 0)
        if u == 0:
            stale.append(
                f"{path}: RLT000 stale baseline entry ({rule}: {text!r}) "
                f"— the finding is gone; prune it from the baseline"
            )
        elif u < n:
            # A partially-consumed count is stale too: the leftover
            # budget would silently suppress a FUTURE same-text finding
            # without noqa or review, breaking the only-ever-shrinks
            # invariant.
            stale.append(
                f"{path}: RLT000 stale baseline entry ({rule}: {text!r}) "
                f"— count is {n} but only {u} matched; shrink the count"
            )
    return kept, stale


# ---------------------------------------------------------------------------
# Fixture self-test
# ---------------------------------------------------------------------------

_FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures")
_DIRECTIVE_RE = re.compile(r"#\s*rlt-fixture:\s*(\S+)\s*(.*)$")
_EXPECT_RE = re.compile(r"#\s*expect\[([A-Z0-9]+)\]")


def _fixture_config(src: str, relname: str) -> Config:
    """Build a per-fixture Config from ``# rlt-fixture:`` directives."""
    hot_jit: Dict[str, frozenset] = {}
    hot_sync: Dict[str, frozenset] = {}
    wall, perf, envl = set(), set(), set()
    ledger_paths: List[str] = []
    producers: Dict[str, Dict[str, str]] = {}
    schema_keys: Dict[str, Tuple[frozenset, frozenset]] = {}
    env_registry = {"RLT_KNOWN"}
    for line in src.splitlines():
        m = _DIRECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        rest = []
        for tok in m.group(2).split():
            if tok.startswith("#"):
                break  # trailing comment (e.g. an expect marker)
            rest.append(tok)
        if kind == "hot-jit":
            hot_jit[relname] = frozenset(rest)
        elif kind == "hot-sync":
            hot_sync[relname] = frozenset(rest)
        elif kind == "wall-clock-tracer":
            wall.add(relname)
        elif kind == "perf-timing":
            perf.add(relname)
        elif kind == "trace-envelope":
            envl.add(relname)
        elif kind == "producer":
            producers.setdefault(relname, {})[rest[0]] = rest[1]
        elif kind == "schema-keys":
            prefix = rest[0]
            req: frozenset = frozenset()
            opt: frozenset = frozenset()
            for tok in rest[1:]:
                side, _, csv = tok.partition("=")
                vals = frozenset(v for v in csv.split(",") if v)
                if side == "required":
                    req = vals
                elif side == "optional":
                    opt = vals
            schema_keys[prefix] = (req, opt)
        elif kind == "env-registry":
            env_registry.update(rest)
        elif kind == "ledger-scope":
            ledger_paths.append(relname)
        else:
            raise ValueError(f"unknown fixture directive {kind!r}")
    return Config(
        hot_jit=hot_jit, hot_sync=hot_sync,
        wall_clock_tracer_files=frozenset(wall),
        perf_timing_files=frozenset(perf),
        trace_envelope_files=frozenset(envl),
        schema_producers=producers, schema_keys=schema_keys,
        env_registry=frozenset(env_registry),
        ledger_paths=tuple(ledger_paths),
    )


def run_fixture(path: str) -> Tuple[List[str], int]:
    """Check one fixture file: every ``# expect[RLTxxx]`` line must be
    flagged with exactly that rule, and nothing else may fire.
    Returns (mismatch messages, expectation count)."""
    with open(path) as f:
        src = f.read()
    relname = os.path.basename(path)
    config = _fixture_config(src, relname)
    expected = set()
    for i, line in enumerate(src.splitlines(), 1):
        for m in _EXPECT_RE.finditer(line):
            expected.add((i, m.group(1)))
    got = {
        (f.line, f.rule)
        for f in check_source(relname, src, config)
    }
    problems = []
    for line, rule in sorted(expected - got):
        problems.append(
            f"{relname}:{line}: expected {rule} but the rule did not fire"
        )
    for line, rule in sorted(got - expected):
        problems.append(
            f"{relname}:{line}: unexpected {rule} finding"
        )
    return problems, len(expected)


def selftest() -> int:
    """Drive the committed fixture corpus.  Each rule ships flagged AND
    clean snippets; a rule change that breaks either fails format.sh."""
    names = sorted(
        n for n in os.listdir(_FIXTURE_DIR) if n.endswith(".py")
    )
    if not names:
        print("rlt_lint selftest: no fixtures found", file=sys.stderr)
        return 1
    rules_seen = set()
    total = 0
    failed = False
    for name in names:
        problems, n_expected = run_fixture(
            os.path.join(_FIXTURE_DIR, name)
        )
        total += n_expected
        m = re.match(r"(rlt\d{3})", name)
        if m:
            rules_seen.add(m.group(1).upper())
        for p in problems:
            print(f"rlt_lint selftest: {p}", file=sys.stderr)
            failed = True
    missing = {f"RLT{i:03d}" for i in range(9)} - rules_seen
    if missing:
        print(
            f"rlt_lint selftest: no fixture exercises "
            f"{', '.join(sorted(missing))}", file=sys.stderr,
        )
        failed = True
    if failed:
        return 1
    print(
        f"rlt_lint selftest OK: {len(names)} fixtures, "
        f"{total} expectations, rules "
        f"{', '.join(sorted(rules_seen))}"
    )
    return 0


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------

def run_lint(paths: List[str], baseline_path: Optional[str],
             config: Optional[Config] = None) -> int:
    config = config or repo_config(_REPO_ROOT)
    findings: List[Finding] = []
    scanned: List[str] = []
    for rel in sorted(paths):
        # Normalize to the repo-relative forward-slash form every
        # path-keyed registry (hot paths, tracers, producers, the
        # baseline) is keyed on — an absolute or ./-prefixed path
        # would otherwise silently match NO rules and report a false
        # clean.
        rel = os.path.relpath(os.path.abspath(
            rel if os.path.isabs(rel)
            else os.path.join(_REPO_ROOT, rel)
        ), _REPO_ROOT)
        rel = rel.replace(os.sep, "/")
        abspath = os.path.join(_REPO_ROOT, rel)
        try:
            with open(abspath) as f:
                src = f.read()
        except OSError as e:
            print(f"rlt_lint: cannot read {rel}: {e}", file=sys.stderr)
            return 2
        scanned.append(rel)
        findings.extend(check_source(rel, src, config))
    stale: List[str] = []
    if baseline_path:
        try:
            entries = load_baseline(os.path.join(_REPO_ROOT, baseline_path))
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"rlt_lint: bad baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2
        findings, stale = apply_baseline(findings, entries, scanned)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    for f in findings:
        print(f.render())
    for msg in stale:
        print(msg)
    n = len(findings) + len(stale)
    if n:
        print(
            f"rlt_lint: {n} finding(s) in {len(scanned)} file(s) — fix, "
            f"'# rlt: noqa[RLT00x] reason', or baseline "
            f"(docs/STATIC_ANALYSIS.md)"
        )
        return 1
    print(f"rlt_lint: OK ({len(scanned)} file(s))")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="rlt_lint",
        description="AST invariant checker (docs/STATIC_ANALYSIS.md)",
    )
    ap.add_argument("--all", action="store_true",
                    help="scan the whole tree (default: changed files)")
    ap.add_argument("--changed", action="store_true",
                    help="scan files changed vs origin/main (default)")
    ap.add_argument("--baseline", default=None,
                    help="findings baseline JSON (grandfathered sites)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the committed baseline")
    ap.add_argument("--selftest", action="store_true",
                    help="run the per-rule fixture matrix and exit")
    ap.add_argument("paths", nargs="*",
                    help="explicit repo-relative files (overrides scope)")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.paths:
        paths = [p for p in args.paths]
    else:
        paths = [p for p in _git_files(args.all) if in_scope(p)]
    if not paths:
        print("rlt_lint: no python files in scope")
        return 0
    baseline = args.baseline
    if baseline is None and not args.no_baseline:
        if os.path.exists(os.path.join(_REPO_ROOT, DEFAULT_BASELINE)):
            baseline = DEFAULT_BASELINE
    return run_lint(paths, baseline)


if __name__ == "__main__":
    sys.exit(main())
