#!/usr/bin/env bash
# Format / lint entry point (≙ reference format.sh:1-150 + .style.yapf).
#
# Usage:
#   ./format.sh            # check changed files (vs origin/main or HEAD)
#   ./format.sh --all      # check the whole tree
#   ./format.sh --fix      # apply fixes (yapf, when installed) instead of
#                          # just checking
#
# Tool layering (the dev image may have no lint tools at all):
#   1. builtin checks (always run, zero deps): line length <= 88, no tabs
#      in indentation, no trailing whitespace, LF endings;
#   2. flake8 (pinned below, when importable) — the CI lint gate;
#   3. yapf --diff/--in-place (pinned below, when importable) with the
#      repo .style.yapf;
#   4. wire-schema gate (tests/test_wire_schemas.py, also part of
#      tier-1) — every real producer of an item that crosses a process
#      or machine boundary against its validator in telemetry/schema.py;
#   5. chaos-plane smoke (tools/chaos_sweep.py --selftest, no
#      subprocesses/fits) — the RLT_FAULT grammar, deterministic
#      matching, exactly-once markers and the file corruptors vs the
#      checkpoint verifier.  The full fault matrix lives in
#      "python tools/chaos_sweep.py" / "pytest -m chaos"; the serving
#      sibling (tools/chaos_serve_sweep.py --selftest) gates the serve
#      fault templates, brownout ladder and retry/hedge maths;
#   6. rlt-lint (tools/rlt_lint, stdlib-ast only) — the repo's own
#      invariants as machine checks: hot-path jit/host-sync bans,
#      guarded-by lock discipline, clock discipline, the RLT_* env-bus
#      registry, telemetry schema-key drift, thread hygiene.  Fixture
#      self-test first, then changed-scope lint (--all honored) against
#      the committed baseline.  Catalog: docs/STATIC_ANALYSIS.md.
# Missing optional tools are reported and skipped; the builtin layer
# still gates, so "./format.sh --all" is meaningful everywhere.
set -euo pipefail

FLAKE8_VERSION=7.1.1
YAPF_VERSION=0.40.2
FLAKE8_ARGS=(--max-line-length 88 --extend-ignore E203,W503,E731)

cd "$(dirname "$0")"

MODE=check
SCOPE=changed
for arg in "$@"; do
  case "$arg" in
    --all) SCOPE=all ;;
    --fix) MODE=fix ;;
    --check) MODE=check ;;
    *) echo "unknown arg: $arg" >&2; exit 2 ;;
  esac
done

# Untracked files are invisible to both ls-files (default) and diff —
# without the union a brand-new file ships past layers 1-3 unchecked
# until after commit.  ACMR keeps renamed-and-edited files (status R)
# in the changed scope; plain ACM drops them.
if [ "$SCOPE" = all ]; then
  mapfile -t FILES < <(
    { git ls-files '*.py'
      git ls-files --others --exclude-standard '*.py'; } | sort -u)
else
  base=$(git merge-base HEAD origin/main 2>/dev/null || echo HEAD)
  mapfile -t FILES < <(
    { git diff --name-only --diff-filter=ACMR "$base" -- '*.py'
      git ls-files --others --exclude-standard '*.py'; } | sort -u)
fi
[ ${#FILES[@]} -eq 0 ] && { echo "format.sh: no python files in scope"; exit 0; }

fail=0

# -- layer 1: builtin checks (no dependencies) -------------------------------
builtin_ok=1
python - "$MODE" "${FILES[@]}" <<'PYEOF' || builtin_ok=0
import sys

mode, files = sys.argv[1], sys.argv[2:]
bad = 0
for path in files:
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        continue
    if b"\r\n" in raw:
        print(f"{path}: CRLF line endings")
        bad += 1
    for lineno, line in enumerate(raw.decode("utf-8").splitlines(), 1):
        if len(line) > 88:
            print(f"{path}:{lineno}: line too long ({len(line)} > 88)")
            bad += 1
        if line != line.rstrip():
            print(f"{path}:{lineno}: trailing whitespace")
            bad += 1
        stripped = line.lstrip(" ")
        if stripped.startswith("\t"):
            print(f"{path}:{lineno}: tab indentation")
            bad += 1
sys.exit(1 if bad else 0)
PYEOF
[ "$builtin_ok" = 1 ] || fail=1

# -- layer 2: flake8 (pinned; the CI gate) -----------------------------------
if python -c "import flake8" 2>/dev/null; then
  python -m flake8 "${FLAKE8_ARGS[@]}" "${FILES[@]}" || fail=1
else
  echo "format.sh: flake8 not installed (pip install flake8==${FLAKE8_VERSION}) — skipped"
fi

# -- layer 3: yapf (pinned; auto-format) -------------------------------------
if python -c "import yapf" 2>/dev/null; then
  if [ "$MODE" = fix ]; then
    python -m yapf --in-place "${FILES[@]}"
  else
    # Advisory (non-gating) in check mode: the dev image ships no yapf,
    # so the tree cannot be guaranteed yapf-clean offline; flake8 and the
    # builtin layer are the enforced gates.
    python -m yapf --diff "${FILES[@]}" \
      || echo "format.sh: yapf would reformat (advisory) — run ./format.sh --fix"
  fi
else
  echo "format.sh: yapf not installed (pip install yapf==${YAPF_VERSION}) — skipped"
fi

# -- layer 4: wire schemas (zero extra deps) ----------------------------------
# Gates producer/schema drift: spans, Chrome traces, heartbeat/event/log
# stream items, crash flight bundles (and the committed fixture
# tests/data/flight_bundle.json), program-ledger rows, the serving
# plane's frames and the MPMD transfer frames, each built by its real
# producer and held to its validator.
python -m pytest tests/test_wire_schemas.py -q -p no:cacheprovider || fail=1

# -- layer 5: chaos-plane smoke (zero extra deps, no subprocess fits) --------
# Gates the fault-injection grammar + deterministic matching + the
# corruptor/verifier pair, so a drifted RLT_FAULT parser can't silently
# turn the recovery acceptance suite into a no-op.
python tools/chaos_sweep.py --selftest || fail=1
# Serving-plane sibling (tools/chaos_serve_sweep.py --selftest): the
# serve fault templates, the brownout ladder's hysteresis/probe logic
# and client retry backoff maths.
# The full serving matrix lives in "python tools/chaos_serve_sweep.py".
python tools/chaos_serve_sweep.py --selftest || fail=1

# -- layer 6: rlt-lint invariant checks (stdlib-ast, zero extra deps) --------
# The fixture matrix self-tests every rule (a rule edit that stops
# flagging its own positive fixtures fails here), then the lint runs at
# the same scope as the rest of this script: changed files by default,
# the whole tree under --all, gating either way.  Suppressions need a
# reason; grandfathered sites live in tools/rlt_lint/baseline.json and
# are enumerated in docs/STATIC_ANALYSIS.md.
python -m tools.rlt_lint --selftest || fail=1
if [ "$SCOPE" = all ]; then
  python -m tools.rlt_lint --all || fail=1
else
  python -m tools.rlt_lint --changed || fail=1
fi

if [ $fail -ne 0 ]; then
  echo "format.sh: FAILED (run ./format.sh --fix after installing tools)"
  exit 1
fi
echo "format.sh: OK"
