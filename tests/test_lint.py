"""rlt-lint: rule matrix, suppression policy, baseline semantics,
scoping, and the tree-wide acceptance gate (ISSUE 14).

The fixture corpus under ``tools/rlt_lint/fixtures/`` is the per-rule
positive/negative matrix (each rule ships flagged AND clean snippets,
asserted line-exactly by the selftest).  These tests drive that corpus
plus the pieces fixtures cannot cover: the committed baseline, git
scoping, the repo-config registries, and the two ISSUE-pinned negative
self-tests — deleting a distributed tracer's ``clock=`` or moving a
``jax.jit`` construction into ``ServeEngine.step`` must fail
``./format.sh``.
"""

from __future__ import annotations

import os
import subprocess
import threading
import time

import pytest

from tools.rlt_lint.cli import (
    _FIXTURE_DIR, apply_baseline, in_scope, load_baseline, run_fixture,
    run_lint, selftest, _git_files,
)
from tools.rlt_lint.core import (
    Config, check_source, load_env_registry, load_schema_keys,
    repo_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Fixture matrix
# ---------------------------------------------------------------------------

def test_fixture_matrix_selftest_passes():
    assert selftest() == 0


def test_every_rule_has_flagged_and_clean_fixtures():
    """Each rule's fixture file must carry >= 2 expected findings AND
    >= 2 'clean' markers (negative snippets the rule must NOT flag)."""
    import re

    by_rule = {}
    for name in sorted(os.listdir(_FIXTURE_DIR)):
        if not name.endswith(".py"):
            continue
        src = open(os.path.join(_FIXTURE_DIR, name)).read()
        m = re.match(r"(rlt\d{3})", name)
        assert m, f"fixture {name} must be named rltNNN_*.py"
        rule = m.group(1).upper()
        rec = by_rule.setdefault(rule, {"expect": 0, "clean": 0})
        rec["expect"] += len(re.findall(r"#\s*expect\[", src))
        rec["clean"] += len(re.findall(r"#\s*clean", src, re.I))
    for rule in [f"RLT{i:03d}" for i in range(8)]:
        assert rule in by_rule, f"no fixture file for {rule}"
        assert by_rule[rule]["expect"] >= 2, f"{rule}: <2 flagged snippets"
        assert by_rule[rule]["clean"] >= 2, f"{rule}: <2 clean snippets"


def test_fixture_runner_catches_a_broken_rule(tmp_path):
    """The selftest fails BOTH ways: a finding that stops firing and a
    finding that fires unexpectedly."""
    p = tmp_path / "rlt007_broken.py"
    p.write_text(
        "import threading\n"
        "t = threading.Thread(target=print, daemon=True)  # expect[RLT007]\n"
    )
    problems, n = run_fixture(str(p))
    assert n == 1
    assert any("did not fire" in x for x in problems)
    p.write_text(
        "import threading\n"
        "t = threading.Thread(target=print)\n"
    )
    problems, _ = run_fixture(str(p))
    assert any("unexpected RLT007" in x for x in problems)


# ---------------------------------------------------------------------------
# Suppression policy
# ---------------------------------------------------------------------------

def test_noqa_with_reason_suppresses():
    src = (
        "import threading\n"
        "t = threading.Thread(target=print)"
        "  # rlt: noqa[RLT007] joined in caller\n"
    )
    assert check_source("x.py", src, Config()) == []


def test_noqa_without_reason_is_a_finding_and_does_not_suppress():
    src = (
        "import threading\n"
        "t = threading.Thread(target=print)  # rlt: noqa[RLT007]\n"
    )
    findings = check_source("x.py", src, Config())
    assert _rules_of(findings) == {"RLT000", "RLT007"}


def test_noqa_unknown_rule_is_a_finding():
    src = "x = 1  # rlt: noqa[RLT999] not a rule\n"
    findings = check_source("x.py", src, Config())
    assert [f.rule for f in findings] == ["RLT000"]


def test_noqa_only_suppresses_the_named_rule():
    src = (
        "import threading\n"
        "t = threading.Thread(target=print)  # rlt: noqa[RLT001] wrong\n"
    )
    findings = check_source("x.py", src, Config())
    assert _rules_of(findings) == {"RLT007"}


# ---------------------------------------------------------------------------
# Baseline semantics
# ---------------------------------------------------------------------------

def _finding(path="a.py", rule="RLT007", text="t = Thread()"):
    from tools.rlt_lint.core import Finding

    return Finding(path, 10, rule, "msg", text)


def test_baseline_suppresses_matching_findings_up_to_count():
    entries = [{"path": "a.py", "rule": "RLT007",
                "text": "t = Thread()", "count": 2}]
    findings = [_finding(), _finding(), _finding()]
    kept, stale = apply_baseline(findings, entries, ["a.py"])
    assert len(kept) == 1 and not stale


def test_baseline_matches_on_text_not_line():
    """Line drift must not churn the baseline: the same source text at
    a different line still matches its entry."""
    entries = [{"path": "a.py", "rule": "RLT007",
                "text": "t = Thread()", "count": 1}]
    moved = _finding()._replace(line=999)
    kept, stale = apply_baseline([moved], entries, ["a.py"])
    assert kept == [] and stale == []


def test_stale_baseline_entry_is_reported_for_scanned_files():
    entries = [{"path": "a.py", "rule": "RLT007",
                "text": "gone = Thread()", "count": 1}]
    kept, stale = apply_baseline([], entries, ["a.py"])
    assert stale and "stale baseline entry" in stale[0]
    # ...but NOT when the file was out of scope this run (--changed).
    kept, stale = apply_baseline([], entries, ["b.py"])
    assert stale == []


def test_partially_consumed_baseline_count_is_stale():
    """Fixing SOME of an entry's sites must flag the leftover count:
    otherwise the unused budget silently suppresses a future same-text
    finding without noqa or review (the baseline must only shrink)."""
    entries = [{"path": "a.py", "rule": "RLT007",
                "text": "t = Thread()", "count": 3}]
    kept, stale = apply_baseline([_finding()], entries, ["a.py"])
    assert kept == []
    assert stale and "only 1 matched" in stale[0]
    # An exactly-consumed count is NOT stale.
    kept, stale = apply_baseline(
        [_finding(), _finding(), _finding()], entries, ["a.py"]
    )
    assert kept == [] and stale == []


def test_committed_baseline_is_well_formed_and_documented():
    entries = load_baseline(
        os.path.join(REPO, "tools", "rlt_lint", "baseline.json")
    )
    assert entries, "committed baseline unexpectedly empty"
    # Only the grandfathered MPMD instruction-loop syncs are allowed in
    # the shipped baseline; anything else must be fixed or noqa'd.
    assert {e["path"] for e in entries} == {
        "ray_lightning_tpu/mpmd/stage.py"
    }
    assert {e["rule"] for e in entries} == {"RLT002"}
    docs = _read("docs/STATIC_ANALYSIS.md")
    assert "mpmd/stage.py" in docs and "baseline" in docs.lower()


# ---------------------------------------------------------------------------
# Scoping
# ---------------------------------------------------------------------------

def test_in_scope_covers_package_tools_entry_not_tests():
    assert in_scope("ray_lightning_tpu/serve/engine.py")
    assert in_scope("tools/rlt_top.py")
    assert in_scope("__graft_entry__.py")
    assert in_scope("examples/tpu_serve_example.py")
    assert not in_scope("tests/test_lint.py")
    assert not in_scope("tools/rlt_lint/fixtures/rlt007_threads.py")
    assert not in_scope("README.md")


def test_changed_scoping_against_synthetic_git_diff(tmp_path):
    """--changed lints exactly the files git reports as changed."""
    repo = tmp_path / "r"
    os.makedirs(repo / "ray_lightning_tpu")
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*args):
        subprocess.run(["git", *args], cwd=repo, env=env,
                       check=True, capture_output=True)

    git("init", "-q")
    (repo / "ray_lightning_tpu" / "old.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "base")
    # one modified, one added, one untouched
    (repo / "ray_lightning_tpu" / "old.py").write_text("x = 2\n")
    (repo / "ray_lightning_tpu" / "new.py").write_text("y = 1\n")
    git("add", "ray_lightning_tpu/new.py")
    changed = sorted(p for p in _git_files(False, cwd=str(repo))
                     if in_scope(p))
    assert changed == [
        "ray_lightning_tpu/new.py", "ray_lightning_tpu/old.py",
    ]


def test_changed_scope_includes_renames_and_untracked(tmp_path):
    """A renamed-and-edited file (git status R — dropped by plain
    --diff-filter=ACM) and a brand-new untracked file (invisible to
    both ls-files and diff) must both land in the lint scope; either
    slipping through ships an unlinted hot-path edit."""
    repo = tmp_path / "r"
    os.makedirs(repo / "ray_lightning_tpu")
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*args):
        subprocess.run(["git", *args], cwd=repo, env=env,
                       check=True, capture_output=True)

    git("init", "-q")
    body = "".join(f"x{i} = {i}\n" for i in range(40))
    (repo / "ray_lightning_tpu" / "engine_old.py").write_text(body)
    git("add", "-A")
    git("commit", "-qm", "base")
    # rename + a small edit: similar enough for rename detection.
    git("mv", "ray_lightning_tpu/engine_old.py",
        "ray_lightning_tpu/engine_new.py")
    (repo / "ray_lightning_tpu" / "engine_new.py").write_text(
        body + "y = 1\n"
    )
    # brand-new file, never git-added.
    (repo / "ray_lightning_tpu" / "untracked.py").write_text("z = 1\n")
    changed = sorted(p for p in _git_files(False, cwd=str(repo))
                     if in_scope(p))
    assert "ray_lightning_tpu/engine_new.py" in changed
    assert "ray_lightning_tpu/untracked.py" in changed
    # --all picks up the untracked file too.
    everything = sorted(p for p in _git_files(True, cwd=str(repo))
                        if in_scope(p))
    assert "ray_lightning_tpu/untracked.py" in everything


# ---------------------------------------------------------------------------
# Repo config registries
# ---------------------------------------------------------------------------

def test_repo_config_loads_env_registry_and_schema_keys():
    cfg = repo_config(REPO)
    assert "RLT_GRAD_COMM" in cfg.env_registry
    assert "RLT_FAULT" in cfg.env_registry
    req, opt = cfg.schema_keys["HEARTBEAT"]
    assert "global_step" in req and "open_span" in opt


def test_env_bus_registry_matches_runtime_module():
    """The linter's AST parse of env_bus.py and the runtime module
    agree — strategies forward exactly the forward-marked subset."""
    from ray_lightning_tpu.parallel import env_bus

    parsed = load_env_registry(
        _read("ray_lightning_tpu/parallel/env_bus.py")
    )
    assert parsed == frozenset(env_bus.registered_names())
    assert set(env_bus.forwarded_vars()) <= parsed
    # the forwarding bridge the strategies actually use
    assert "RLT_GRAD_COMM" in env_bus.forwarded_vars()
    assert "RLT_AGENT_TOKEN" not in env_bus.forwarded_vars()


def test_registry_drift_is_a_finding():
    """A registered hot-path qualname that no longer exists fails the
    lint, so the protection moves with refactors instead of silently
    evaporating."""
    cfg = Config(hot_sync={"m.py": frozenset({"Engine.gone"})})
    findings = check_source("m.py", "class Engine:\n    pass\n", cfg)
    assert [f.rule for f in findings] == ["RLT000"]
    assert "Engine.gone" in findings[0].message


def test_schema_key_loader_reads_required_and_optional():
    keys = load_schema_keys(
        "_BEAT_REQUIRED = {'a': int}\n_BEAT_OPTIONAL = {'b': str}\n"
    )
    assert keys == {"BEAT": (frozenset({"a"}), frozenset({"b"}))}


# ---------------------------------------------------------------------------
# ISSUE-pinned negative self-tests (format.sh must fail on these edits)
# ---------------------------------------------------------------------------

def test_deleting_spantracer_clock_fails_lint():
    """Removing ``clock=time.time`` from a distributed tracer's
    SpanTracer construction is the PR-13 stitching bug — RLT004 pins
    it in every registered wall-clock-tracer module."""
    cfg = repo_config(REPO)
    for rel in sorted(cfg.wall_clock_tracer_files):
        src = _read(rel)
        clean = check_source(rel, src, cfg)
        assert "RLT004" not in _rules_of(clean), rel
        # stage.py aliases `import time as _time`
        mutated = src.replace("clock=time.time,", "") \
                     .replace("clock=_time.time,", "")
        assert mutated != src, rel
        findings = check_source(rel, mutated, cfg)
        assert "RLT004" in _rules_of(findings), rel


def test_moving_jit_into_engine_step_fails_lint():
    """A fresh ``jax.jit`` per serve iteration is the PR-12 recompile
    footgun ('zero steady-state recompiles' dies under cache pressure)
    — RLT001 pins ServeEngine.step."""
    rel = "ray_lightning_tpu/serve/engine.py"
    cfg = repo_config(REPO)
    src = _read(rel)
    anchor = "    def step(self) -> bool:\n"
    assert anchor in src
    mutated = src.replace(
        anchor,
        anchor + "        _oops = jax.jit(lambda z: z)\n",
    )
    assert "RLT001" not in _rules_of(check_source(rel, src, cfg))
    findings = check_source(rel, mutated, cfg)
    assert "RLT001" in _rules_of(findings)


def test_partial_jit_nested_def_in_hot_path_fails_lint():
    """Review fix: ``@partial(jax.jit, ...)`` — the required form for
    static/donated args — constructs a fresh jit object per enclosing
    call exactly like ``@jax.jit``; the nested-def check must unwrap
    partial or the most common decorator idiom evades RLT001."""
    rel = "ray_lightning_tpu/serve/engine.py"
    cfg = repo_config(REPO)
    anchor = "    def step(self) -> bool:\n"
    injected = anchor + (
        "        @functools.partial(jax.jit, donate_argnums=0)\n"
        "        def _oops(z):\n"
        "            return z\n"
    )
    mutated = _read(rel).replace(anchor, injected)
    findings = check_source(rel, mutated, cfg)
    assert "RLT001" in _rules_of(findings)


def test_unregistered_env_knob_fails_lint():
    """A new RLT_* knob read anywhere without an env_bus entry fails —
    the class of bug where a knob silently never reaches workers."""
    rel = "ray_lightning_tpu/core/loop.py"
    cfg = repo_config(REPO)
    src = _read(rel) + (
        "\n\ndef _sneaky():\n"
        "    import os\n"
        "    return os.environ.get('RLT_BRAND_NEW_KNOB')\n"
    )
    findings = check_source(rel, src, cfg)
    assert any(f.rule == "RLT005"
               and "RLT_BRAND_NEW_KNOB" in f.message for f in findings)


def test_schema_producer_key_drift_fails_lint():
    """A key added to make_beat without a schema entry fails RLT006
    (the static complement to tests/test_wire_schemas.py)."""
    rel = "ray_lightning_tpu/telemetry/heartbeat.py"
    cfg = repo_config(REPO)
    src = _read(rel)
    mutated = src.replace(
        '"phase": str(getattr(ctx, "phase", "init")),',
        '"phase": str(getattr(ctx, "phase", "init")),\n'
        '        "phse_typo": 0,',
    )
    assert mutated != src
    assert "RLT006" not in _rules_of(check_source(rel, src, cfg))
    assert "RLT006" in _rules_of(check_source(rel, mutated, cfg))


# ---------------------------------------------------------------------------
# Acceptance: the shipped tree is clean
# ---------------------------------------------------------------------------

def test_shipped_tree_is_lint_clean_modulo_baseline(capsys):
    paths = [p for p in _git_files(True) if in_scope(p)]
    assert len(paths) > 80, "scan scope suspiciously small"
    rc = run_lint(paths, os.path.join("tools", "rlt_lint",
                                      "baseline.json"))
    out = capsys.readouterr().out
    assert rc == 0, f"tree has unsuppressed findings:\n{out}"


def test_guard_comment_on_use_site_is_not_a_suppression():
    """Review fix: only the annotated DECLARATION assignment is exempt
    from RLT003 — pasting '# guarded by ...' on a use site must not
    bypass the lock check without a reasoned noqa."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._state = []  # guarded by self._lock\n"
        "    def bad(self):\n"
        "        return len(self._state)  # guarded by self._lock\n"
    )
    findings = check_source("c.py", src, Config())
    assert [f.rule for f in findings] == ["RLT003"]
    assert findings[0].line == 7


def test_explicit_absolute_path_is_normalized(tmp_path, capsys,
                                              monkeypatch):
    """Review fix: an absolute path to a registered file must hit the
    same path-keyed rules as the repo-relative form (no false clean).
    The mutated file is a copy under ``tmp_path`` taken as the root:
    other workers import the real one while this runs."""
    from tools.rlt_lint import cli

    rel = "ray_lightning_tpu/serve/engine.py"
    anchor = "    def step(self) -> bool:\n"
    mutated = _read(rel).replace(
        anchor, anchor + "        _oops = jax.jit(lambda z: z)\n"
    )
    baseline = os.path.join("tools", "rlt_lint", "baseline.json")
    for path, text in ((rel, mutated), (baseline, _read(baseline))):
        os.makedirs(tmp_path / os.path.dirname(path))
        (tmp_path / path).write_text(text)
    monkeypatch.setattr(cli, "_REPO_ROOT", str(tmp_path))
    rc = run_lint([str(tmp_path / rel)], baseline, repo_config(REPO))
    out = capsys.readouterr().out
    assert rc == 1 and "RLT001" in out, out


def test_heartbeat_stop_does_not_hang_on_never_released_sink():
    """Review fix: with the publisher wedged inside a sink put holding
    the publish lock, stop() must return within its timeout budget
    (skipping the final beat) instead of blocking unboundedly."""
    from ray_lightning_tpu.telemetry.heartbeat import HeartbeatPublisher

    class Ctx:
        global_step = micro_step = current_epoch = progress = 0
        phase = "train"

    class WedgedSink:
        def __init__(self):
            self.first = threading.Event()

        def put(self, beat):
            self.first.set()
            time.sleep(3600)  # never returns within the test

    sink = WedgedSink()
    pub = HeartbeatPublisher(0, Ctx(), sink, interval_s=0.01)
    pub.start()
    assert sink.first.wait(5.0)
    t0 = time.monotonic()
    pub.stop(final=True, timeout_s=0.2)
    assert time.monotonic() - t0 < 5.0, "stop() hung on a wedged sink"


def test_guarded_by_annotations_are_live():
    """The lock discipline the sweep added is actually enforced: strip
    one 'with self._feed_lock' from PrefillRunner and RLT003 fires."""
    rel = "ray_lightning_tpu/serve/dist/prefill.py"
    cfg = repo_config(REPO)
    src = _read(rel)
    mutated = src.replace(
        "        with self._feed_lock:\n"
        "            done, self._done = self._done, []\n",
        "        if True:\n"
        "            done, self._done = self._done, []\n",
    )
    assert mutated != src
    assert "RLT003" not in _rules_of(check_source(rel, src, cfg))
    assert "RLT003" in _rules_of(check_source(rel, mutated, cfg))


# ---------------------------------------------------------------------------
# Sweep regressions (the genuine fixes the tree-wide run surfaced)
# ---------------------------------------------------------------------------

class _BlockingSink:
    """Sink whose put() can be held open — and which records overlap."""

    def __init__(self):
        self.release = threading.Event()
        self.release.set()
        self.beats = []
        self._inside = 0
        self.max_inside = 0
        self._mu = threading.Lock()

    def put(self, beat):
        with self._mu:
            self._inside += 1
            self.max_inside = max(self.max_inside, self._inside)
        try:
            self.release.wait(5.0)
            self.beats.append(beat)
        finally:
            with self._mu:
                self._inside -= 1


def test_heartbeat_stop_final_beat_serializes_with_wedged_publisher():
    """Sweep fix: stop() joins the publisher with a timeout; a wedged
    sink used to leave BOTH threads inside _publish (duplicate seq,
    interleaved file writes).  The publish lock serializes them: with
    the publisher wedged mid-put, stop() either lands the final beat
    AFTER the put completes or (lock unavailable within budget) skips
    it — never overlaps.  Either way stop() stays bounded."""
    from ray_lightning_tpu.telemetry.heartbeat import HeartbeatPublisher

    class Ctx:
        global_step = micro_step = current_epoch = progress = 0
        phase = "train"

    sink = _BlockingSink()
    pub = HeartbeatPublisher(0, Ctx(), sink, interval_s=0.01)
    pub.start()
    deadline = time.monotonic() + 5
    while not sink.beats and time.monotonic() < deadline:
        time.sleep(0.005)
    sink.release.clear()          # wedge the NEXT publish mid-put
    time.sleep(0.05)              # let the publisher enter the wedge

    done = threading.Event()

    def stopper():
        pub.stop(final=True, timeout_s=0.05)  # join times out
        done.set()

    t = threading.Thread(target=stopper, daemon=True)
    t.start()
    # Pre-fix, the stopper thread would now be INSIDE _publish
    # concurrently with the wedged publisher (max_inside == 2).
    time.sleep(0.1)
    assert done.wait(5.0), "stop() not bounded while sink wedged"
    sink.release.set()
    t.join(5.0)
    deadline = time.monotonic() + 5
    while sink._inside and time.monotonic() < deadline:
        time.sleep(0.005)
    assert sink.max_inside == 1, "concurrent _publish detected"
    seqs = [b["seq"] for b in sink.beats]
    assert len(seqs) == len(set(seqs)), f"duplicate seq: {seqs}"


def test_engine_reply_handle_cache_is_lock_guarded():
    """Sweep fix: ServeEngine._reply_handles is mutated by the serve
    thread and cleared by stop() after a join that can time out — the
    annotation (and RLT003) now pin it under self._lock."""
    rel = "ray_lightning_tpu/serve/engine.py"
    src = _read(rel)
    assert "# guarded by self._lock\n" \
           "        self._reply_handles" in src
    cfg = repo_config(REPO)
    assert "RLT003" not in _rules_of(check_source(rel, src, cfg))


def test_inproc_pipeline_threads_are_daemonized():
    src = _read("ray_lightning_tpu/mpmd/inproc.py")
    assert 'name=f"rlt-mpmd-w{r.worker}",\n            daemon=True' in src, \
        "inproc drive threads must pass explicit daemon="


@pytest.mark.parametrize("rule", [f"RLT{i:03d}" for i in range(8)])
def test_rule_catalog_documented(rule):
    docs = _read("docs/STATIC_ANALYSIS.md")
    assert rule in docs, f"{rule} missing from docs/STATIC_ANALYSIS.md"
