"""The harness's own limits (``tests/conftest.py``): a test that waits
past the soft limit fails with every thread's stack and the next test
runs; a main thread no handler can reach ends its worker at the hard
limit and xdist goes on without it.

Each case is a child ``pytest`` over a temporary directory whose
``conftest.py`` runs the repo's from its file and then shortens the two
constants for that run, on one test that waits and one after it.
"""

import os
import subprocess
import sys
import textwrap

REPO_CONFTEST = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "conftest.py")

CONFTEST = """
with open({conftest!r}) as f:
    exec(compile(f.read(), {conftest!r}, "exec"))
SOFT_LIMIT_S = {soft}
HARD_LIMIT_S = {hard}
"""


def _child_pytest(tmp_path, soft, hard, tests, *args):
    (tmp_path / "conftest.py").write_text(CONFTEST.format(
        conftest=REPO_CONFTEST, soft=soft, hard=hard))
    (tmp_path / "test_waits.py").write_text(
        textwrap.dedent(tests) + "\ndef test_the_one_after_it():\n    pass\n")
    return subprocess.run(
        [sys.executable, "-m", "pytest", "test_waits.py", "-v",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         "--rootdir", str(tmp_path), "-c", os.devnull, *args],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)


def test_a_test_past_the_soft_limit_fails_with_its_stacks(tmp_path):
    done = _child_pytest(tmp_path, 0.5, 30.0, """
        import threading
        import time

        def test_sleeps_past_the_limit():
            waiter = threading.Thread(
                target=time.sleep, args=(3,), name="a-second-thread")
            waiter.start()
            time.sleep(20)
        """, "-p", "no:xdist")
    out = done.stdout + done.stderr
    assert done.returncode == 1, out
    assert "test_sleeps_past_the_limit FAILED" in out, out
    assert "test_the_one_after_it PASSED" in out, out
    assert ("test_waits.py::test_sleeps_past_the_limit is over the "
            "limit of 0.5 s") in out, out
    # both threads' stacks, the test's own sleeping line among them
    assert out.count("most recent call first") >= 2, out
    assert "in test_sleeps_past_the_limit" in out, out
    assert "1 failed, 1 passed" in out, out


def test_a_main_thread_no_handler_reaches_costs_its_worker_only(tmp_path):
    """The alarm blocked stands for a main thread held below the
    interpreter: the soft limit cannot run, the hard one ends the worker,
    xdist reports the test failed and runs the next on a new worker."""
    done = _child_pytest(tmp_path, 0.3, 1.0, """
        import signal
        import time

        def test_held_where_no_handler_runs():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(30)
        """, "-p", "xdist", "-n", "1")
    out = done.stdout + done.stderr
    assert done.returncode == 1, out
    assert "Timeout (0:00:01)!" in out, out
    assert "in test_held_where_no_handler_runs" in out, out
    assert "test_the_one_after_it" in out and "1 passed" in out, out
    assert "crashed while running" in out, out
    assert "1 failed, 1 passed" in out, out
